"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.

  PYTHONPATH=src python -m benchmarks.run [--fast] [--only loc_table,...]
"""
import argparse
import sys
import traceback


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    from benchmarks import tables
    from benchmarks.kernel_bench import kernel_bench
    from benchmarks.multihost_bench import bench_rows as multihost_rows
    from benchmarks.roofline import roofline_rows
    from benchmarks.serve_bench import serving_throughput
    from benchmarks.tune_bench import tune_rows

    benches = {
        "loc_table": tables.loc_table,                 # paper Table II
        "collect_overhead": tables.collect_overhead,   # paper Table III
        "speedup_error": tables.speedup_error,         # paper Fig 5
        "runtime_breakdown": tables.runtime_breakdown, # paper Fig 6
        "pareto_sweep": tables.pareto_sweep,           # paper Fig 7/8
        "interleave": tables.interleave,               # paper Fig 9d
        "kernel_bench": kernel_bench,                  # Pallas kernels
        "roofline": roofline_rows,                     # §Roofline (dry-run)
        "serve_throughput": serving_throughput,        # repro.serve coalescing
        "tune": tune_rows,                             # repro.tune autotuning
        "multihost": multihost_rows,                   # pod serving (2 procs)
    }
    only = set(args.only.split(",")) if args.only else None
    print("name,us_per_call,derived")
    ok = True
    for name, fn in benches.items():
        if only and name not in only:
            continue
        try:
            for row in fn(fast=args.fast):
                n, us, derived = row
                print(f"{n},{us:.2f},{derived}", flush=True)
        except Exception as e:
            ok = False
            print(f"{name},0,ERROR:{type(e).__name__}:{e}", flush=True)
            traceback.print_exc(file=sys.stderr)
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
