"""Kernel micro-benchmarks + the quantized-tier acceptance gate.

On this CPU container the Pallas kernels run in interpret mode (Python
emulation — not a performance measurement), so wall-clock rows are taken
from the jnp reference paths; the kernels' TPU value is argued in the
roofline analysis.  Rows still record interpret-mode validation deltas.

``--quant-check`` gates the int8 serving tier end to end (see
:func:`quant_check`): per-bundle gate RMSE within budget on real
calibration rows, the engine actually serving the gated int8 path under
``REPRO_QUANT=force``, a >= :data:`QUANT_MIN_SPEEDUP` rows/s win on at
least one bandwidth-bound served shape, and — the part that matters
most — a deliberately mis-calibrated bundle *failing* the gate and
serving f32 bit-identically, with the fail counter incremented.  The
speedup leg follows this file's standing rule: measured wall-clock on
TPU, roofline-priced off-TPU (XLA's CPU int8 dot is slower than its
f32 one, so CPU wall-clock would gate nothing about the TPU tier).
"""
from __future__ import annotations

import os
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import timeit, write_bench_json
from repro.launch.compile_cache import enable_compile_cache

#: gated int8 fused_mlp must beat f32 rows/s by at least this factor on
#: >= 1 served shape (HBM-bound regime: weights quarter, io unchanged)
QUANT_MIN_SPEEDUP = 1.5
#: per-bundle RMSE budget as a fraction of the f32 output RMS — the
#: relative form keeps one constant meaningful across apps whose output
#: scales differ by orders of magnitude (option prices vs BUDE energies)
QUANT_BUDGET_REL = 0.03
#: deliberately wrong calibration for the fail-path drill: scales
#: inflated 64x crush every weight into a couple of int8 steps
QUANT_BAD_SCALE = 64.0

#: (in_dim, hidden, hidden, out_dim) per app — the NAS-winner shapes the
#: serving benchmarks use for these bundles
QUANT_APP_SHAPES = (
    ("binomial", (5, 256, 256, 1)),
    ("bonds", (4, 512, 512, 2)),
    ("minibude", (6, 1024, 1024, 1)),
)
#: the bucket the speedup leg prices.  256 rows is the bandwidth-bound
#: serving regime for these nets — the weight stream dominates the
#: roofline (at 1024 rows the f32 compute term takes over and
#: quantizing the weights moves nothing, on the model *or* the chip)
QUANT_BUCKET = 256


def kernel_bench(fast=False):
    rows = []
    rng = np.random.default_rng(0)

    # stencil gather (data bridge hot path)
    from repro.kernels.stencil_gather.ref import stencil_gather_ref
    x = jnp.asarray(rng.normal(size=(512, 512)).astype(np.float32))
    offs = ((0, 1), (2, 0), (1, 1), (0, 0), (1, 2))
    f = jax.jit(lambda x: stencil_gather_ref(x, offs, 508, 508, origin=(1, 1)))
    t = timeit(f, x, reps=5)
    bytes_moved = 508 * 508 * 5 * 4 * 2
    rows.append(("kernel/stencil_gather_ref_512", t * 1e6,
                 f"gb_s={bytes_moved/t/1e9:.2f}"))

    # fused MLP surrogate inference
    from repro.kernels.fused_mlp.ref import fused_mlp_ref
    ws = [jnp.asarray(rng.normal(size=(64, 256)).astype(np.float32)),
          jnp.asarray(rng.normal(size=(256, 256)).astype(np.float32)),
          jnp.asarray(rng.normal(size=(256, 1)).astype(np.float32))]
    bs = [jnp.zeros(256), jnp.zeros(256), jnp.zeros(1)]
    xx = jnp.asarray(rng.normal(size=(4096, 64)).astype(np.float32))
    f = jax.jit(lambda x: fused_mlp_ref(x, ws, bs, ("relu", "relu", "identity")))
    t = timeit(f, xx, reps=5)
    flops = 2 * 4096 * (64 * 256 + 256 * 256 + 256)
    rows.append(("kernel/fused_mlp_ref_b4096", t * 1e6,
                 f"gflops_s={flops/t/1e9:.2f}"))

    # flash attention
    from repro.kernels.flash_attention.ref import flash_attention_ref
    S = 256 if fast else 512
    q = jnp.asarray(rng.normal(size=(1, S, 8, 64)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, S, 2, 64)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(1, S, 2, 64)).astype(np.float32))
    f = jax.jit(lambda q, k, v: flash_attention_ref(q, k, v, causal=True))
    t = timeit(f, q, k, v, reps=3)
    flops = 4 * S * S * 8 * 64
    rows.append((f"kernel/flash_attention_ref_s{S}", t * 1e6,
                 f"gflops_s={flops/t/1e9:.2f}"))

    # rwkv6 chunk
    from repro.kernels.rwkv6_chunk.ref import rwkv6_chunk_ref
    B, T, H, hd = 2, 128, 8, 64
    r, kk, vv = (jnp.asarray(rng.normal(size=(B, T, H, hd)).astype(np.float32))
                 for _ in range(3))
    w = jnp.asarray(rng.uniform(0.9, 0.999, (B, T, H, hd)).astype(np.float32))
    u = jnp.asarray(rng.normal(size=(H, hd)).astype(np.float32))
    s0 = jnp.zeros((B, H, hd, hd), jnp.float32)
    f = jax.jit(lambda *a: rwkv6_chunk_ref(*a)[0])
    t = timeit(f, r, kk, vv, w, u, s0, reps=3)
    flops = B * T * H * hd * hd * 6
    rows.append((f"kernel/rwkv6_chunk_ref_t{T}", t * 1e6,
                 f"gflops_s={flops/t/1e9:.2f}"))

    # interpret-mode validation deltas (correctness, not speed)
    from repro.kernels.flash_attention.flash_attention import flash_attention
    a = flash_attention(q[:, :64], k[:, :64], v[:, :64], causal=True,
                        block_q=32, block_k=32, interpret=True)
    b = flash_attention_ref(q[:, :64], k[:, :64], v[:, :64], causal=True)
    rows.append(("kernel/flash_interpret_maxerr", 0.0,
                 f"err={float(jnp.abs(a-b).max()):.2e}"))

    # int8 variants vs their int8-simulating oracles (interpret mode)
    from repro.kernels.fused_mlp import int8 as q_mlp
    prob = dict(q_mlp.SPEC.default_problems[0])
    arrs = q_mlp._make(prob, rng)
    d = jnp.abs(q_mlp._run(prob, arrs, {"batch_tile": 64}, interpret=True)
                - q_mlp._ref(prob, arrs))
    rows.append(("kernel/fused_mlp_int8_interpret_maxerr", 0.0,
                 f"err={float(d.max()):.2e}"))
    from repro.kernels.flash_attention import int8 as q_fa
    prob = dict(q_fa.SPEC.default_problems[0])
    arrs = q_fa._make(prob, rng)
    d = jnp.abs(q_fa._run(prob, arrs, {"block_q": 32, "block_kv": 128},
                          interpret=True) - q_fa._ref(prob, arrs))
    rows.append(("kernel/flash_attention_int8_interpret_maxerr", 0.0,
                 f"err={float(d.max()):.2e}"))
    return rows


# ======================================================== quant gate ========
def _quant_bundle(path, shape, app_name, seed=0):
    """An app-shaped MLP bundle plus a SurrogateDB holding assimilation
    rows for it: inputs from the app's own sampler (real input
    distributions, not gaussians), outputs from the bundle's f32
    forward — so the held-out split isolates quantization error
    exactly."""
    import importlib

    from repro.core.database import SurrogateDB
    from repro.nn import MLP
    from repro.nn.serialize import save_model

    in_dim, h1, h2, out_dim = shape
    net = MLP((1, in_dim), [h1, h2], out_dim)
    params = net.init(jax.random.PRNGKey(seed))
    mp = save_model(pathlib.Path(path) / "surrogate", net, params)

    app = importlib.import_module(f"repro.apps.{app_name}")
    x = np.asarray(app.make_inputs(1024), np.float32).reshape(1024, -1)
    y = np.asarray(jax.jit(net.apply)(params, jnp.asarray(x)))
    db = SurrogateDB(pathlib.Path(path) / "db")
    db.group(app_name).append(x, y, 0.0)
    db.flush()
    return mp, db


def _quant_speedup(widths, bucket):
    """(f32_rows_s, int8_rows_s) for one served shape.

    On TPU: measured wall-clock through the engine's two tiers.  Off
    TPU: roofline-priced (weight stream at 1 byte vs 4) — the module
    docstring's standing rule, because XLA's CPU int8 dot_general is
    *slower* than f32 and would invert the comparison the gate is
    about."""
    from repro.tune.controller import predict_batch_latency_s
    if jax.default_backend() == "tpu":
        from repro.kernels.fused_mlp.fused_mlp import fused_mlp
        from repro.kernels.fused_mlp.int8 import fused_mlp_int8
        from repro.quant.quantize import quantize_params
        rng = np.random.default_rng(0)
        ws = [rng.normal(size=(a, b)).astype(np.float32) * 0.3
              for a, b in zip(widths[:-1], widths[1:])]
        bs = [rng.normal(size=(b,)).astype(np.float32) * 0.1
              for b in widths[1:]]
        acts = ("relu",) * (len(widths) - 2) + ("identity",)
        x = jnp.asarray(rng.normal(size=(bucket, widths[0])), jnp.float32)
        qlayers = quantize_params(ws, bs)
        wj = [jnp.asarray(w) for w in ws]
        bj = [jnp.asarray(b) for b in bs]
        f32 = jax.jit(lambda x: fused_mlp(x, wj, bj, acts, interpret=False))
        i8 = jax.jit(lambda x: fused_mlp_int8(x, qlayers, acts,
                                              interpret=False))
        return (bucket / timeit(f32, x, reps=10),
                bucket / timeit(i8, x, reps=10))
    # overhead_s is the fixed dispatch floor — identical for both tiers,
    # so it is excluded: the gate is about the memory-bound kernel term
    t32 = predict_batch_latency_s(widths, bucket, overhead_s=0.0)
    t8 = predict_batch_latency_s(widths, bucket, overhead_s=0.0,
                                 weight_dtype_bytes=1)
    return bucket / t32, bucket / t8


def quant_check(fast=False, markdown=False):
    """The quantized-tier acceptance gate (CI: ``--quant-check``).

    Per app bundle: harvest held-out calibration rows, register the
    per-bundle RMSE budget in the shared registry, run the accuracy
    gate, then serve the bundle under ``REPRO_QUANT=force`` and check
    the engine resolved the int8 tier, produced all-finite outputs
    within budget of its f32 serving, and counted the served rows.
    Then the fail path: re-gate the first bundle with a deliberately
    wrong calibration (``scale_mult=QUANT_BAD_SCALE``), and require the
    gate to FAIL, the fail counter to increment, and the engine to fall
    back to bit-identical f32 serving.  Finally the speedup leg:
    >= :data:`QUANT_MIN_SPEEDUP` int8-vs-f32 rows/s on at least one
    served shape.
    """
    import tempfile

    from repro.core.engine import InferenceEngine
    from repro.obs import metrics as _m
    from repro.quant.budgets import set_rmse_budget
    from repro.quant.calibrate import calibration_rows
    from repro.quant.gate import gate_bundle, gate_passed

    n_cal = 512 if fast else 2048
    prev_env = os.environ.get("REPRO_QUANT")
    served = _m.counter("repro_quant_served_rows_total",
                        "rows served by the gated int8 tier", ("bundle",))
    fails = _m.counter("repro_quant_gate_fail_total",
                       "quant gate evaluations that failed the RMSE budget",
                       ("bundle",))
    results = []
    try:
        for app_name, shape in QUANT_APP_SHAPES:
            tmp = tempfile.mkdtemp(prefix=f"quant_bench_{app_name}_")
            mp, db = _quant_bundle(tmp, shape, app_name)
            rows = calibration_rows(db, app_name, max_rows=n_cal)

            # budget: relative to this bundle's f32 output scale, then
            # registered where BOTH the gate and the shadow scorer look
            from repro.nn.serialize import load_model
            net, params, _ = load_model(mp)
            y32 = np.asarray(jax.jit(net.apply)(params, jnp.asarray(rows)))
            budget = QUANT_BUDGET_REL * float(
                np.sqrt(np.mean(np.square(y32))) or 1.0)
            set_rmse_budget(mp, budget)

            rec = gate_bundle(mp, rows)
            if not rec["exact"] or rec["rmse"] > budget:
                raise SystemExit(
                    f"quant check FAILED: {app_name} gate rmse "
                    f"{rec['rmse']:.4g} vs budget {budget:.4g} "
                    f"(exact={rec['exact']})")
            if not gate_passed(mp):
                raise SystemExit(f"quant check FAILED: {app_name} verdict "
                                 f"did not persist/bind to the bundle")

            # serve the gated tier for real (off-TPU this runs the int8
            # simulation oracle — same numbers the gate certified)
            x = jnp.asarray(rows[:256])
            os.environ["REPRO_QUANT"] = "never"
            InferenceEngine.invalidate(mp)
            y_f32 = np.asarray(InferenceEngine.get(mp).apply_batched(x))
            os.environ["REPRO_QUANT"] = "force"
            InferenceEngine.invalidate(mp)
            eng = InferenceEngine.get(mp)
            before = served.value(bundle=mp)
            y_q = np.asarray(eng.apply_batched(x))
            if eng.tier != "int8":
                raise SystemExit(f"quant check FAILED: {app_name} engine "
                                 f"resolved tier {eng.tier!r} under force "
                                 f"with a passing gate")
            if not np.isfinite(y_q).all():
                raise SystemExit(f"quant check FAILED: {app_name} int8 "
                                 f"serving produced non-finite outputs")
            if served.value(bundle=mp) - before < x.shape[0]:
                raise SystemExit(f"quant check FAILED: {app_name} served "
                                 f"rows not counted")
            serve_rmse = float(np.sqrt(np.mean((y_q - y_f32) ** 2)))
            if serve_rmse > budget:
                raise SystemExit(
                    f"quant check FAILED: {app_name} served int8-vs-f32 "
                    f"rmse {serve_rmse:.4g} exceeds budget {budget:.4g}")

            f32_rs, i8_rs = _quant_speedup(shape, QUANT_BUCKET)
            results.append({"app": app_name, "widths": shape,
                            "rmse": rec["rmse"], "budget": budget,
                            "serve_rmse": serve_rmse, "f32_rows_s": f32_rs,
                            "int8_rows_s": i8_rs,
                            "speedup": i8_rs / f32_rs, "mp": mp,
                            "x": np.asarray(x), "y_f32": y_f32})
            print(f"[quant] {app_name}: gate rmse={rec['rmse']:.3g} "
                  f"budget={budget:.3g} serve rmse={serve_rmse:.3g} "
                  f"speedup={i8_rs / f32_rs:.2f}x "
                  f"({'measured' if jax.default_backend() == 'tpu' else 'roofline'})",
                  flush=True)

        # ---- fail path: a mis-calibrated bundle must NOT serve int8 ----
        r0 = results[0]
        mp = r0["mp"]
        rows = r0["x"]
        fails_before = fails.value(bundle=mp)
        rec = gate_bundle(mp, rows, scale_mult=QUANT_BAD_SCALE)
        if rec["exact"] or gate_passed(mp):
            raise SystemExit(
                f"quant check FAILED: mis-calibrated (scale_mult="
                f"{QUANT_BAD_SCALE}) bundle PASSED the gate "
                f"(rmse={rec['rmse']:.4g} vs budget {rec['budget']:.4g})")
        if fails.value(bundle=mp) - fails_before < 1:
            raise SystemExit("quant check FAILED: gate-fail counter did "
                             "not increment")
        os.environ["REPRO_QUANT"] = "force"
        InferenceEngine.invalidate(mp)
        eng = InferenceEngine.get(mp)
        y_after = np.asarray(eng.apply_batched(jnp.asarray(rows)))
        if eng.tier != "f32":
            raise SystemExit(f"quant check FAILED: engine serves tier "
                             f"{eng.tier!r} after a gate fail")
        if not np.array_equal(y_after, r0["y_f32"]):
            raise SystemExit("quant check FAILED: post-gate-fail serving "
                             "is not bit-identical to the f32 path")
        # the fail-record must never be resolvable as a tuned winner
        from repro.tune.cache import best_params
        from repro.quant.gate import GATE_NAMESPACE, _key
        if best_params(GATE_NAMESPACE, [_key(mp)]) is not None:
            raise SystemExit("quant check FAILED: gate-fail record "
                             "resolvable via best_params")
        print(f"[quant] fail path OK: scale_mult={QUANT_BAD_SCALE} gate "
              f"rmse={rec['rmse']:.3g} > budget {rec['budget']:.3g}; "
              f"engine fell back to bit-identical f32", flush=True)

        best = max(results, key=lambda r: r["speedup"])
        if best["speedup"] < QUANT_MIN_SPEEDUP:
            raise SystemExit(
                f"quant check FAILED: best int8 speedup "
                f"{best['speedup']:.2f}x ({best['app']}) < "
                f"{QUANT_MIN_SPEEDUP}x")
        print(f"[quant] OK: best speedup {best['speedup']:.2f}x "
              f"({best['app']}), all gates within budget", flush=True)
    finally:
        if prev_env is None:
            os.environ.pop("REPRO_QUANT", None)
        else:
            os.environ["REPRO_QUANT"] = prev_env
        InferenceEngine.invalidate()

    if markdown:
        basis = ("measured" if jax.default_backend() == "tpu"
                 else "roofline")
        print("\n## Quantization gate (int8 tier vs f32, "
              f"rows/s {basis})\n")
        print("| app | widths | f32 rows/s | int8 rows/s | speedup | "
              "gate RMSE | budget | gated |")
        print("|---|---|---|---|---|---|---|---|")
        for r in results:
            w = "-".join(str(v) for v in r["widths"])
            print(f"| {r['app']} | {w} | {r['f32_rows_s']:,.0f} | "
                  f"{r['int8_rows_s']:,.0f} | {r['speedup']:.2f}x | "
                  f"{r['rmse']:.3g} | {r['budget']:.3g} | yes |")
        print()
    return results


def main(argv=None):
    enable_compile_cache()
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--markdown", action="store_true")
    ap.add_argument("--quant-check", action="store_true",
                    help="run the int8-tier acceptance gate")
    args = ap.parse_args(argv)
    if args.quant_check:
        results = quant_check(fast=args.fast, markdown=args.markdown)
        write_bench_json("quant", {
            "apps": [{k: v for k, v in r.items()
                      if k not in ("mp", "x", "y_f32", "widths")}
                     | {"widths": list(r["widths"])}
                     for r in results],
            "gate": {"min_speedup_x": QUANT_MIN_SPEEDUP,
                     "budget_rel": QUANT_BUDGET_REL,
                     "best_speedup_x": max(r["speedup"] for r in results)},
        })
        return 0
    for name, us, note in kernel_bench(fast=args.fast):
        print(f"{name:45s} {us:10.1f}us  {note}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
