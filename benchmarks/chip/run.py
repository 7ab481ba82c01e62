"""Run one benchmark cell once on the chips of this machine.

    python3 benchmarks/chip/run.py --workload binomial-ranks --seed 7 \\
        --seconds 51 --trace 0

Sets the cell up from the seed, warms up its shapes, measures for
``--seconds`` seconds, checks the rows served against the benchmark's own
reference, and prints one JSON object as the last line of standard
output: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics read from
a traced window of at most a few seconds), ``device``, and last the
numbers compared beside their limits, which also end standard error.
With no TPU, or fewer chips than the cell asks for, it exits non-zero and
prints no result.  JAX's compile cache lives at a fixed path inside the
checkout, so only the first run of a cell there compiles.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CACHE_DIR = ROOT / "artifacts" / "jax-cache"


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the traced window's .xplane.pb here")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    # the program takes its compile cache from this variable; it must be
    # set before JAX is imported
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path[:0] = [str(HERE), str(ROOT / "src")]
    import harness
    from repro.launch.compile_cache import enable_compile_cache

    t_imported = time.perf_counter()
    cell = harness.find_cell(args.workload, harness.load_benchmark(ROOT))
    try:
        devices = harness.require_devices(int(cell["workload"]["chips"]))
    except harness.NoChip as e:
        print(f"no result: {e}", file=sys.stderr, flush=True)
        return 2
    print(f"start: imports {t_imported - T_START:.3f} s, devices "
          f"{time.perf_counter() - t_imported:.3f} s", file=sys.stderr,
          flush=True)
    enable_compile_cache()
    out = harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                           trace=bool(args.trace), t_start=T_START,
                           devices=devices, keep_trace=args.keep_trace)
    w = out["window"]
    phases = ", ".join(f"{k} {v:.3f}" for k, v in w["setup_phases"].items())
    print(f"window: {w['steps']} steps in {w['seconds']:.3f} s (longest "
          f"{w['step_ms_max']:.1f} ms, loop CPU {w['loop_cpu_s']:.3f} s), "
          f"set-up {w['setup_s']:.3f} s ({phases}), compiles in the window "
          f"{w['compiles']}, dispatches {w['dispatch']}, breaker fallbacks "
          f"{w['breaker_fallbacks']}, comparison {w['compare_s']:.3f} s",
          file=sys.stderr, flush=True)
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    # a check that reads no finite number (a served row that is not
    # finite) prints null there; ``correct`` already says false
    for c in out["checks"].values():
        if not math.isfinite(c["value"]):
            c["value"] = None
    print(json.dumps(out, allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
