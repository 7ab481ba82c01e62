"""Multi-process pod serving benchmark: NAS-retrain-under-load.

The end-to-end scenario the ROADMAP's "serve hardening at pod scale"
item asks for: N real ``jax.distributed`` processes (spawn_local_pod)
serve a stream of cross-host mega-batches for one surrogate bundle while
the bundle is *retrained between batches* — host 0 rewrites
``params.npz`` exactly like the NAS loop does, and every host's
``InferenceEngine.get`` must pick the new weights up through mtime
staleness before the next pod batch.

Checked invariants (``--check``):

  * every round's results are bit-identical to single-process (eager,
    mesh-less) serving of the same rows under the same weights, on every
    host;
  * after each retrain, every host's outputs actually change (bundle
    invalidation propagated cross-process — nobody served stale weights);
  * every dispatched batch spans the pod axis (remote rows > 0).

Usage:
  PYTHONPATH=src python -m benchmarks.multihost_bench --check [--fast]
  PYTHONPATH=src python -m benchmarks.multihost_bench --markdown
"""
import argparse
import os
import tempfile
import time


def _pod_worker(tmp: str, rounds: int, callers_per_host: int,
                rows_per_caller: int):
    """One pod process of the retrain-under-load loop."""
    import jax
    import numpy as np

    from repro.core.engine import InferenceEngine
    from repro.dist.sharding import use_mesh
    from repro.launch.mesh import make_pod_mesh
    from repro.launch.multihost import barrier
    from repro.nn import MLP
    from repro.nn.serialize import save_model
    from repro.serve import FlushPolicy, ServeQueue

    pid, nproc = jax.process_index(), jax.process_count()
    bundle = os.path.join(tmp, "surrogate")
    net = MLP((1, 5), [32, 32], 1)

    def retrain(round_no: int):
        # the NAS loop's bundle rewrite: fresh params, same architecture
        params = net.init(jax.random.PRNGKey(100 + round_no))
        save_model(bundle, net, params)

    if pid == 0:
        retrain(0)
    barrier("bundle-ready")

    rng = np.random.default_rng(42)
    full = rng.standard_normal(
        (nproc * callers_per_host * rows_per_caller, 5)).astype(np.float32)
    mine = full.reshape(nproc, callers_per_host, rows_per_caller, 5)[pid]

    mesh = make_pod_mesh()
    queue = ServeQueue(FlushPolicy(max_batch_rows=1 << 30))
    rows_local = callers_per_host * rows_per_caller

    results = []
    prev = None
    t_serve = 0.0
    for rnd in range(rounds):
        t0 = time.monotonic()
        with use_mesh(mesh, multi_pod=True):
            futs = [queue.submit(bundle, mine[c])
                    for c in range(callers_per_host)]
            queue.pod_flush(bundle)
        got = np.concatenate(
            [np.asarray(f.result(timeout=120)) for f in futs])
        t_serve += time.monotonic() - t0
        # reference under the *current* weights, eager and mesh-less
        eng = InferenceEngine.get(bundle)
        ref = np.concatenate(
            [np.asarray(eng(mine[c])) for c in range(callers_per_host)])
        results.append({
            "round": rnd,
            "equal": bool(np.array_equal(got, ref)),
            "changed": bool(prev is None or not np.array_equal(got, prev)),
        })
        prev = got
        # retrain between batches: host 0 rewrites, everyone syncs so no
        # host races the rewrite with its next engine fingerprint check
        barrier(f"round-{rnd}-served")
        if pid == 0 and rnd + 1 < rounds:
            retrain(rnd + 1)
        barrier(f"round-{rnd}-retrained")

    snap = queue.stats(bundle).snapshot()
    return {
        "pid": pid,
        "nproc": nproc,
        "rounds": results,
        "rows_local": rows_local,
        "rows_per_s": rounds * rows_local / t_serve if t_serve else 0.0,
        "pod_batches": int(snap["pod_batches"]),
        "remote_rows": int(snap["remote_rows"]),
        "bucket_rows": int(snap["bucket_rows"]),
        "occupancy": float(snap["batch_occupancy"]),
    }


def run_bench(fast: bool = False, processes: int = 2,
              devices_per_host: int = 2):
    from repro.launch.multihost import spawn_local_pod
    rounds = 3 if fast else 5
    tmp = tempfile.mkdtemp(prefix="repro_mh_bench_")
    res = spawn_local_pod(
        processes, "benchmarks.multihost_bench:_pod_worker",
        (tmp, rounds, 4, 8), devices_per_host=devices_per_host,
        timeout_s=600.0)
    failures = []
    for r in res:
        for rec in r["rounds"]:
            if not rec["equal"]:
                failures.append(f"p{r['pid']} round {rec['round']}: diverged "
                                f"from single-process serving")
            if not rec["changed"]:
                failures.append(f"p{r['pid']} round {rec['round']}: outputs "
                                f"unchanged after retrain — served a stale "
                                f"bundle")
        if processes > 1 and r["remote_rows"] <= 0:
            failures.append(f"p{r['pid']}: no remote rows — batches did not "
                            f"span the pod axis")
        if r["pod_batches"] != rounds:
            failures.append(f"p{r['pid']}: {r['pod_batches']} pod batches, "
                            f"expected {rounds}")
    return res, failures


def bench_rows(fast: bool = False):
    """benchmarks.run entry: CSV rows."""
    res, failures = run_bench(fast=fast)
    total_rows_s = sum(r["rows_per_s"] for r in res)
    rounds = len(res[0]["rounds"])
    derived = (f"processes={len(res)};rounds={rounds};"
               f"rows_per_s={total_rows_s:.0f};"
               f"occupancy={res[0]['occupancy']:.2f};"
               f"remote_rows={res[0]['remote_rows']};"
               f"all_equal={not failures}")
    us = (1e6 / total_rows_s) if total_rows_s else 0.0
    return [("multihost/nas_retrain_under_load", us, derived)]


def _markdown(res):
    rounds = len(res[0]["rounds"])
    out = ["### Pod serving: NAS-retrain-under-load "
           f"({len(res)} processes, {rounds} retrain rounds)", "",
           "| host | rows/s | pod batches | remote rows | occupancy | "
           "bit-identical | invalidation seen |",
           "|---:|---:|---:|---:|---:|---|---|"]
    for r in res:
        eq = all(rec["equal"] for rec in r["rounds"])
        ch = all(rec["changed"] for rec in r["rounds"])
        out.append(f"| p{r['pid']} | {r['rows_per_s']:.0f} | "
                   f"{r['pod_batches']} | {r['remote_rows']} | "
                   f"{r['occupancy']:.2f} | {eq} | {ch} |")
    return "\n".join(out)


def main():
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true",
                    help="fail unless every host serves bit-identically and "
                         "sees every retrain")
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--processes", type=int, default=2)
    ap.add_argument("--devices-per-host", type=int, default=2)
    ap.add_argument("--markdown", action="store_true")
    args = ap.parse_args()
    res, failures = run_bench(fast=args.fast, processes=args.processes,
                              devices_per_host=args.devices_per_host)
    if args.markdown:
        print(_markdown(res))
    else:
        print("name,us_per_call,derived")
        total = sum(r["rows_per_s"] for r in res)
        print(f"multihost/nas_retrain_under_load,"
              f"{(1e6 / total) if total else 0.0:.2f},"
              f"rows_per_s={total:.0f};all_equal={not failures}")
    if args.check:
        if failures:
            raise SystemExit("multihost bench FAILED:\n" + "\n".join(failures))
        print(f"[multihost bench] OK: {len(res)} hosts, "
              f"{len(res[0]['rounds'])} retrain rounds, bit-identical, "
              f"invalidation propagated", flush=True)


if __name__ == "__main__":
    main()
