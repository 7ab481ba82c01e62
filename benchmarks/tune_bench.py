"""Autotuning benchmark: tuned-vs-default kernel configs across the
registry, adaptive-vs-static flush policies, measured-vs-open-loop
latency control.

Three measurements, three gate families (``--check``, the CI autotune
smoke):

  1. **Kernels**: sweep every tunable registered kernel — ``fused_mlp``
     batch tiles, ``flash_attention`` block_q/block_kv,
     ``stencil_gather`` row/column tiles — via ``repro.tune.sweep``
     (persisted per kernel in ``artifacts/tune/<kernel>.json``).  Gate:
     the tuned config must be >= 1.0x the spec default (structural: the
     default is always swept, the winner is the measured argmin) and
     every winner validated against the jitted ref oracle
     (bit-identical where the spec demands it; flash attention to its
     declared f32 tolerance — the online-softmax block order
     legitimately changes rounding).
  2. **Serving**: drive a surrogate region queue under a fast burst
     (throughput regime) and a slow trickle (latency regime) for each
     static deadline and for the adaptive controller.  Gate: adaptive
     achieves >= ``CHECK_RATIO`` x the best static deadline's burst
     rows/s AND a trickle p99 no worse than that same best-throughput
     static's — the adaptive policy must win the latency regime without
     giving up the throughput regime.
  3. **Measured loop**: the closed-loop controller (ServeStats batch
     latencies blended into the deadline model) vs the same controller
     open-loop (`use_measured=False`).  Gate: closing the loop must not
     regress either regime beyond measurement noise
     (>= ``MEASURED_BURST_RATIO`` x burst rows/s, trickle p99 within
     ``MEASURED_P99_SLACK``).

``--markdown`` renders the result sets as tables (the EXPERIMENTS.md
"Autotuning" section is regenerated from this).

  PYTHONPATH=src python -m benchmarks.tune_bench --check [--fast]
"""
import argparse
import time

import jax
import numpy as np

from benchmarks.common import write_bench_json
from repro.launch.compile_cache import enable_compile_cache

CHECK_RATIO = 0.9        # adaptive rows/s vs best static
MEASURED_BURST_RATIO = 0.85   # closed-loop rows/s vs open-loop (median)
MEASURED_P99_SLACK = 1.5      # closed-loop p99 <= slack x open-loop (median)
STATIC_DEADLINES_S = (0.005, 0.02, 0.05)
BURST_REQUESTS, TRICKLE_REQUESTS = 96, 24
ROWS_PER_REQUEST = 8
TRICKLE_GAP_S = 0.005

# NAS-representative pure-MLP surrogate shapes: (widths, serve bucket)
KERNEL_SHAPES = (
    ((5, 128, 128, 1), 256),    # binomial/bonds-like scalar regressor
    ((16, 256, 256, 4), 512),   # wider multi-output head
)

# registered-kernel problems swept alongside fused_mlp (kept small: the
# sweep runs Pallas interpret mode on CPU; winners persist in
# artifacts/tune so CI only re-sweeps on kernel/tuner changes)
REGISTRY_PROBLEMS = (
    ("flash_attention",
     {"b": 1, "sq": 128, "skv": 128, "h": 4, "kv": 2, "hd": 32,
      "causal": True, "q_offset": 0, "dtype": "float32"},
     {"b": 1, "sq": 64, "skv": 64, "h": 2, "kv": 1, "hd": 16,
      "causal": True, "q_offset": 0, "dtype": "float32"}),
    ("stencil_gather",
     {"h": 256, "w": 288, "out_h": 252, "out_w": 284,
      "offsets": ((0, 1), (2, 0), (1, 1), (0, 0), (1, 2)),
      "origin": (1, 1), "dtype": "float32"},
     {"h": 128, "w": 160, "out_h": 124, "out_w": 156,
      "offsets": ((0, 1), (2, 0), (1, 1), (0, 0), (1, 2)),
      "origin": (1, 1), "dtype": "float32"}),
)


# ------------------------------------------------------------- kernel ------
def _fmt_params(params):
    return "/".join(f"{k}={v}" for k, v in sorted(params.items()))


def kernel_rows(fast=False, force=False):
    """Sweep fused_mlp + every other tunable registered kernel."""
    from repro.kernels import registry
    from repro.tune import sweep, sweep_fused_mlp
    reps = 3 if fast else 5
    rows = []
    shapes = KERNEL_SHAPES[:1] if fast else KERNEL_SHAPES
    for widths, bucket in shapes:
        rec = sweep_fused_mlp(list(widths), bucket, force=force, reps=reps)
        name = "tune/fused_mlp_" + "-".join(map(str, widths)) + f"_b{bucket}"
        derived = (f"kernel=fused_mlp;params={_fmt_params(rec['params'])};"
                   f"default=batch_tile=128;"
                   f"tuned_us={rec['us']};default_us={rec['default_us']};"
                   f"speedup_x={rec['speedup_x']};exact={rec['exact']};"
                   f"backend={rec['backend']}")
        rows.append((name, rec["us"] or 0.0, derived))
    for kernel, full, small in REGISTRY_PROBLEMS:
        spec = registry.get_spec(kernel)
        problem = small if fast else full
        rec = sweep(spec, problem, force=force, reps=reps)
        tag = spec.cache_key(dict(problem), rec["backend"]).split("|")[0]
        derived = (f"kernel={kernel};params={_fmt_params(rec['params'])};"
                   f"default={_fmt_params(spec.defaults())};"
                   f"tuned_us={rec['us']};default_us={rec['default_us']};"
                   f"speedup_x={rec['speedup_x']};exact={rec['exact']};"
                   f"backend={rec['backend']}")
        rows.append((f"tune/{kernel}_{tag}", rec["us"] or 0.0, derived))
    return rows


# ------------------------------------------------------------ serving ------
def _bundle(path):
    from repro.nn import MLP
    from repro.nn.serialize import save_model
    net = MLP((1, 5), [128, 128], 1)
    params = net.init(jax.random.PRNGKey(0))
    return save_model(path, net, params)


def _prewarm(mp):
    """Compile every bucket shape (donated + caller-owned applies) the
    scenarios can dispatch, so the timed runs compare flush policies —
    not which config happened to hit a fresh jit shape first."""
    import jax.numpy as jnp

    from repro.core.engine import InferenceEngine
    eng = InferenceEngine.get(mp)
    b = 8
    while b <= 1024:
        eng.apply_batched(jnp.zeros((b, 5), np.float32))
        eng.apply_batched(jnp.zeros((b, 5), np.float32), donate=True,
                          prepadded=True)
        b *= 2


def _drive(mp, make_queue, n_requests, gap_s, seed=0):
    """Run one serving scenario; returns (wall_s, stats snapshot)."""
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    blocks = [jnp.asarray(rng.normal(size=(ROWS_PER_REQUEST, 5))
                          .astype(np.float32)) for _ in range(n_requests)]
    q = make_queue()
    with q:
        t0 = time.perf_counter()
        futs = []
        for b in blocks:
            futs.append(q.submit(mp, b))
            if gap_s:
                time.sleep(gap_s)
        for f in futs:
            f.result(30)
        wall = time.perf_counter() - t0
    return wall, q.stats(mp).snapshot()


def _scenarios(mp, make_queue, fast=False):
    """(burst rows/s, trickle p50/p99 ms) for one queue configuration.

    Both regimes take the best of several short runs: a trickle p99 over
    a couple dozen requests is a max-of-N statistic, and on a shared CI
    machine a single draw is dominated by scheduler noise — best-of
    measures what the policy can do, which is what the gates compare."""
    n_burst = BURST_REQUESTS // (2 if fast else 1)
    n_trickle = TRICKLE_REQUESTS // (2 if fast else 1)
    # warmup: compile every bucket shape this config will serve, so the
    # timed runs compare policies, not jit cache luck
    _drive(mp, make_queue, n_burst, 0.0, seed=99)
    burst_rows_s = 0.0
    for i in range(4):
        wall, _ = _drive(mp, make_queue, n_burst, 0.0, seed=i)
        burst_rows_s = max(burst_rows_s, n_burst * ROWS_PER_REQUEST / wall)
    p50 = p99 = float("inf")
    for i in range(4):
        _, st = _drive(mp, make_queue, n_trickle, TRICKLE_GAP_S, seed=i)
        p50 = min(p50, st["latency_p50_ms"])
        p99 = min(p99, st["latency_p99_ms"])
    return {"burst_rows_s": burst_rows_s,
            "trickle_p50_ms": p50,
            "trickle_p99_ms": p99}


def _paired_ratios(mp, make_a, make_b, fast=False, pairs=4):
    """Median per-pair (B / A) metric ratios, runs interleaved.

    Two scenario blocks measured seconds apart on a shared machine see
    different background load; comparing their absolutes turns drift
    into false regressions.  Back-to-back pairs share the drift, so the
    per-pair ratio isolates the *policy* difference, and the median of
    a few pairs shrugs off one noisy draw."""
    n_burst = BURST_REQUESTS // (2 if fast else 1)
    n_trickle = TRICKLE_REQUESTS // (2 if fast else 1)
    burst, p99 = [], []
    for i in range(pairs):
        wa, _ = _drive(mp, make_a, n_burst, 0.0, seed=10 + i)
        wb, _ = _drive(mp, make_b, n_burst, 0.0, seed=10 + i)
        burst.append(wa / wb)  # rows/s ratio = inverse wall ratio
    for i in range(pairs):
        _, sa = _drive(mp, make_a, n_trickle, TRICKLE_GAP_S, seed=20 + i)
        _, sb = _drive(mp, make_b, n_trickle, TRICKLE_GAP_S, seed=20 + i)
        p99.append(sb["latency_p99_ms"] / max(sa["latency_p99_ms"], 1e-9))
    return {"burst_ratio": float(np.median(burst)),
            "p99_ratio": float(np.median(p99))}


def serving_rows(fast=False):
    """Adaptive controller (closed- and open-loop) vs each static
    deadline, both regimes."""
    import pathlib
    import tempfile

    from repro.serve import FlushPolicy, ServeQueue
    from repro.tune import AdaptiveFlushController

    tmp = pathlib.Path(tempfile.mkdtemp(prefix="tune_bench_"))
    mp = _bundle(tmp / "surrogate")
    _prewarm(mp)
    results = {}
    for d in STATIC_DEADLINES_S:
        pol = FlushPolicy(max_batch_rows=4096, max_pending_rows=1 << 16,
                          max_delay_s=d)
        results[f"static_{d * 1e3:g}ms"] = _scenarios(
            mp, lambda p=pol: ServeQueue(p), fast=fast)
    ctrl_pol = FlushPolicy(max_batch_rows=4096, max_pending_rows=1 << 16,
                           max_delay_s=max(STATIC_DEADLINES_S))

    def adaptive_queue(use_measured=True):
        return ServeQueue(ctrl_pol, controller=AdaptiveFlushController(
            ctrl_pol, warmup_requests=4, use_measured=use_measured))

    # open-loop first so the closed-loop run cannot ride its jit warmth
    results["adaptive_openloop"] = _scenarios(
        mp, lambda: adaptive_queue(use_measured=False), fast=fast)
    results["adaptive"] = _scenarios(mp, adaptive_queue, fast=fast)
    # closed-vs-open gate metrics come from interleaved pairs (drift-
    # immune), not from the absolute scenario blocks above
    measured = _paired_ratios(mp, lambda: adaptive_queue(use_measured=False),
                              adaptive_queue, fast=fast)

    rows = []
    for name, r in results.items():
        derived = (f"burst_rows_s={r['burst_rows_s']:.0f};"
                   f"trickle_p50_ms={r['trickle_p50_ms']:.2f};"
                   f"trickle_p99_ms={r['trickle_p99_ms']:.2f}")
        rows.append((f"tune/serve_{name}", 0.0, derived))
    rows.append(("tune/serve_measured_vs_openloop", 0.0,
                 f"burst_ratio={measured['burst_ratio']:.3f};"
                 f"p99_ratio={measured['p99_ratio']:.3f}"))
    results["measured_vs_openloop"] = measured
    return rows, results


def tune_rows(fast=False):
    """benchmarks.run entry: kernel + serving CSV rows."""
    rows = kernel_rows(fast=fast)
    srows, _ = serving_rows(fast=fast)
    return rows + srows


# ------------------------------------------------------------- output ------
def _markdown(krows, results):
    out = ["### Autotuned kernel configs", "",
           "| kernel | problem | tuned params | tuned us | default us | "
           "speedup | validated |",
           "|---|---|---|---|---|---|---|"]
    for name, _, derived in krows:
        kv = dict(item.split("=", 1) for item in derived.split(";"))
        problem = name.split("/", 1)[1].split(kv["kernel"] + "_", 1)[-1]
        out.append(f"| {kv['kernel']} | {problem} | {kv['params']} | "
                   f"{kv['tuned_us']} | {kv['default_us']} | "
                   f"{kv['speedup_x']}x | {kv['exact']} |")
    out += ["", "### Adaptive vs static flush policies", "",
            "| policy | burst rows/s | trickle p50 ms | trickle p99 ms |",
            "|---|---|---|---|"]
    for name, r in results.items():
        if "burst_rows_s" not in r:
            continue
        out.append(f"| {name} | {r['burst_rows_s']:.0f} | "
                   f"{r['trickle_p50_ms']:.2f} | {r['trickle_p99_ms']:.2f} |")
    m = results.get("measured_vs_openloop")
    if m:
        out += ["", "Closed- vs open-loop controller (interleaved pairs, "
                     "median ratios): "
                     f"burst {m['burst_ratio']:.2f}x rows/s, "
                     f"trickle p99 {m['p99_ratio']:.2f}x."]
    return "\n".join(out)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true",
                    help="fail unless every tuned kernel >= 1.0x default, "
                         f"adaptive >= {CHECK_RATIO}x best-static rows/s "
                         "with no worse trickle p99, and the measured-"
                         "latency loop does not regress the open-loop "
                         "controller")
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--force", action="store_true",
                    help="re-sweep even if the tune cache has entries")
    ap.add_argument("--markdown", action="store_true",
                    help="print markdown tables (for EXPERIMENTS.md)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="run with tracing on and write the Chrome trace "
                         "+ metrics snapshots to PATH(.metrics.json/.prom) "
                         "— controller decisions, tune-cache hit/miss and "
                         "kernel-dispatch provenance all land in the "
                         "metrics dump")
    args = ap.parse_args()
    if args.trace:
        from repro.obs import enable_tracing
        enable_tracing()

    krows = kernel_rows(fast=args.fast, force=args.force)
    srows, results = serving_rows(fast=args.fast)
    if args.trace:
        import json
        import pathlib

        from repro.obs import TRACER, default_registry
        path = pathlib.Path(args.trace)
        events = TRACER.export_chrome_trace(path)
        path.with_suffix(".metrics.json").write_text(
            json.dumps(default_registry().collect(), indent=1))
        path.with_suffix(".prom").write_text(default_registry().dump())
        print(f"[tune trace] {len(events)} events -> {path}", flush=True)
    if args.markdown:
        print(_markdown(krows, results))
    else:
        print("name,us_per_call,derived")
        for n, us, derived in krows + srows:
            print(f"{n},{us:.2f},{derived}", flush=True)

    def _num(v):
        try:
            return float(v)
        except (TypeError, ValueError):
            return v

    bench_json = {
        "kernels": {
            name.split("/", 1)[1]: {
                k: (_num(v) if k in ("tuned_us", "default_us", "speedup_x")
                    else v)
                for k, v in (item.split("=", 1)
                             for item in derived.split(";"))}
            for name, _, derived in krows},
        "policies": {name: {"burst_rows_s": r["burst_rows_s"],
                            "trickle_p50_ms": r["trickle_p50_ms"],
                            "trickle_p99_ms": r["trickle_p99_ms"]}
                     for name, r in results.items()
                     if "burst_rows_s" in r},
        "gate": {"adaptive_min_ratio": CHECK_RATIO,
                 "measured_burst_min_ratio": MEASURED_BURST_RATIO,
                 "measured_p99_max_ratio": MEASURED_P99_SLACK,
                 **results["measured_vs_openloop"]},
    }
    write_bench_json("tune", bench_json)
    if args.check:
        failures = []
        for name, _, derived in krows:
            kv = dict(item.split("=", 1) for item in derived.split(";"))
            if kv["exact"] != "True":
                failures.append(f"{name}: tuned config not validated "
                                "against the ref oracle")
            if float(kv["speedup_x"]) < 1.0:
                failures.append(f"{name}: tuned {kv['speedup_x']}x < 1.0x "
                                "default")
        statics = {k: v for k, v in results.items()
                   if k.startswith("static_")}
        best_name = max(statics, key=lambda k: statics[k]["burst_rows_s"])
        best = statics[best_name]
        ad = results["adaptive"]
        if ad["burst_rows_s"] < CHECK_RATIO * best["burst_rows_s"]:
            failures.append(
                f"adaptive burst {ad['burst_rows_s']:.0f} rows/s < "
                f"{CHECK_RATIO}x best static {best_name} "
                f"({best['burst_rows_s']:.0f})")
        if ad["trickle_p99_ms"] > best["trickle_p99_ms"]:
            failures.append(
                f"adaptive trickle p99 {ad['trickle_p99_ms']:.2f}ms worse "
                f"than best-throughput static {best_name} "
                f"({best['trickle_p99_ms']:.2f}ms)")
        m = results["measured_vs_openloop"]
        if m["burst_ratio"] < MEASURED_BURST_RATIO:
            failures.append(
                f"measured-latency burst ratio {m['burst_ratio']:.3f} < "
                f"{MEASURED_BURST_RATIO}x open-loop (median of interleaved "
                "pairs)")
        if m["p99_ratio"] > MEASURED_P99_SLACK:
            failures.append(
                f"measured-latency trickle p99 ratio {m['p99_ratio']:.3f} > "
                f"{MEASURED_P99_SLACK}x open-loop (median of interleaved "
                "pairs)")
        if failures:
            raise SystemExit("tune smoke FAILED:\n  " + "\n  ".join(failures))
        print(f"[tune smoke] OK: kernels tuned, adaptive "
              f"{ad['burst_rows_s']:.0f} rows/s vs best static "
              f"{best['burst_rows_s']:.0f} ({best_name}), trickle p99 "
              f"{ad['trickle_p99_ms']:.2f}ms vs {best['trickle_p99_ms']:.2f}"
              f"ms; measured loop vs open-loop (paired medians) "
              f"burst {m['burst_ratio']:.2f}x, p99 {m['p99_ratio']:.2f}x")


if __name__ == "__main__":
    main()
