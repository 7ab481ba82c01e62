"""Smoke run of the served surrogate path on a TPU — a smoke, not a benchmark.

Drives the binomial-options region through the entry points a user calls:

1. device check: the platform must be ``tpu``; there is no CPU fallback;
2. collect with the accurate path (256-step lattice) into a fresh
   ``SurrogateDB``, then train the widest net of the app's search space
   (5-512-512-1) for a few epochs;
3. serve 64 callers x 512 options through a ``ServeQueue`` on the f32
   tier, and compare with a plain f32 reference of the same bundle at
   ``highest`` matmul precision;
4. gate the bundle for int8 and serve again with ``REPRO_QUANT`` unset:
   the engine must pick the int8 tier and stay within the gate's budget;
5. read the metrics registry: no breaker fallback, no ``ref`` dispatch
   and no ``vmem-fallback`` dispatch may have happened.

Any failed check raises, so the exit code is non-zero.  The last line of
standard output is one JSON object naming the device.

    python chip_smoke.py [--seed N]
    python chip_smoke.py --four-chips   # 1x4 data-sharded serving only
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import shutil
import sys
import tempfile
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.apps import binomial  # noqa: E402
from repro.core.engine import InferenceEngine, bundle_norm  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.nas.train_surrogate import fit  # noqa: E402
from repro.nn.serialize import load_model, save_model  # noqa: E402
from repro.obs import TRACER, default_registry  # noqa: E402
from repro.serve import FlushPolicy, ServeQueue  # noqa: E402
from repro.serve.batcher import Batcher  # noqa: E402

CALLERS = 64           # region invocations per sweep
CHUNK = 512            # options per invocation
SWEEPS = 3             # timed sweeps after the compiling one
MIN_OPTIONS = 32768    # collected options (at least one full sweep)
EPOCHS = 3
#: f32 tier vs the ``highest`` reference, relative to max|reference|.
#: The f32 kernel asks Mosaic for ``HIGHEST`` precision, so the two
#: differ by summation order only (~1e-6 relative).  Mosaic's default
#: contracts f32 operands in one bf16 pass, ~6e-3 relative off at
#: 5-512-512-1.  1e-4 sits between the two.
F32_REL_TOL = 1e-4
#: int8 gate budget, as a fraction of the surrogate's own validation
#: RMSE: quantization may add at most half the error training left, so
#: the served error grows by at most ~12% (in quadrature)
INT8_BUDGET_OF_VAL_RMSE = 0.5


class SmokeFailure(AssertionError):
    """A smoke check failed."""


def require(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


# ------------------------------------------------------------- phases ---
def device_check() -> dict:
    """Print and return the device JAX found; fail unless it is a TPU."""
    devs = jax.devices()
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}
    print(f"device: platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}", flush=True)
    require(d.platform == "tpu",
            f"no TPU: JAX runs on {d.platform!r}; this smoke has no CPU "
            f"fallback")
    return info


def widest_net():
    """The widest MLP of the binomial search space (5-512-512-1)."""
    from repro.nas.space import build_net
    space = binomial.surrogate_space()
    return build_net(space, {"hidden1": space["hidden1"][1],
                             "hidden2": space["hidden2"][1]})


def collect_and_train(workdir: pathlib.Path, *, seed: int, n_options: int,
                      net=None, epochs: int = EPOCHS):
    """Collect ``n_options`` accurate prices into a fresh SurrogateDB and
    train ``net`` (default: :func:`widest_net`) on them.  Returns
    ``(bundle_path, db_path, val_rmse)``."""
    net = net if net is not None else widest_net()
    db_path = workdir / "db"
    region = binomial.make_region(n_options, mode="collect",
                                  database=str(db_path))
    opts = binomial.make_inputs(n_options, seed=seed)
    t0 = time.perf_counter()
    region(opts=opts)
    region.db.flush()
    t_collect = time.perf_counter() - t0
    data = region.db.group("binomial").load()
    t0 = time.perf_counter()
    params, val_rmse, stats = fit(net, data["inputs"].reshape(-1, 5),
                                  data["outputs"].reshape(-1, 1),
                                  epochs=epochs, seed=seed)
    t_train = time.perf_counter() - t0
    bundle = save_model(workdir / "bundle", net, params, extra=stats)
    widths = "-".join(str(w) for w in mlp_widths(net))
    print(f"collect: {n_options} options x {binomial.N_STEPS} steps in "
          f"{t_collect:.3f}s; train {widths}: {epochs} epochs in "
          f"{t_train:.3f}s, val RMSE {val_rmse:.6g}", flush=True)
    return pathlib.Path(bundle), db_path, val_rmse


def mlp_widths(net) -> tuple:
    spec = net.spec()
    dense = [l["features"] for l in spec["layers"] if l["kind"] == "dense"]
    return (spec["in_shape"][-1],) + tuple(dense)


def reference(bundle, x) -> np.ndarray:
    """Plain f32 forward of the bundle (normalization included) at
    ``highest`` matmul precision — the served tiers' yardstick."""
    net, params, spec = load_model(str(bundle))
    norm = bundle_norm(spec, net)

    def f(params, x):
        if norm is not None:
            x = (x - norm[0]) / norm[1]
        y = net.apply(params, x)
        return y if norm is None else y * norm[3] + norm[2]

    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(f)(params, jnp.asarray(x, jnp.float32)))


def serve_sweeps(bundle, opts, *, callers: int = CALLERS,
                 chunk: int = CHUNK, sweeps: int = SWEEPS) -> dict:
    """Serve ``sweeps + 1`` sweeps of ``callers`` region calls through one
    ServeQueue; the first sweep compiles and is timed as set-up.  Every
    sweep must return the same rows."""
    n = callers * chunk
    opts = opts[:n]
    queue = ServeQueue(FlushPolicy(max_batch_rows=n, max_pending_rows=n))
    region = binomial.make_region(chunk, mode="infer_async",
                                  model=str(bundle), serving=queue)
    rows = region._rows_in(region.engine(), {"opts": opts[:chunk]})
    require(Batcher._device_resident(rows),
            "bridged rows are not device-resident: the batcher would "
            "gather on the host instead of concatenating on device")
    outs, times = [], []
    try:
        for _ in range(sweeps + 1):
            t0 = time.perf_counter()
            y = binomial.price_chunks_async(opts, region, queue, chunk)
            outs.append(np.asarray(y))
            times.append(time.perf_counter() - t0)
    finally:
        queue.close()
    for o in outs[1:]:
        require(np.array_equal(o, outs[0]),
                "served rows differ between sweeps of the same inputs")
    eng = InferenceEngine.get(str(bundle))
    return {"rows": outs[0], "tier": eng.tier, "setup_s": times[0],
            "sweep_s": times[1:]}


def report_sweeps(name: str, res: dict, rows: int) -> None:
    ts = res["sweep_s"]
    print(f"smoke timing (not a benchmark) {name}: set-up (first sweep, "
          f"compiles) {res['setup_s']:.3f}s; served sweeps of {rows} rows "
          f"{', '.join(f'{t:.4f}s' for t in ts)}", flush=True)


def check_f32(name: str, served, ref,
              ref_name: str = "highest-precision f32 reference") -> float:
    err = float(np.abs(np.asarray(served) - ref).max())
    tol = F32_REL_TOL * float(np.abs(ref).max())
    print(f"{name}: max |served - {ref_name}| = {err:.6g} (tolerance "
          f"{tol:.6g} = {F32_REL_TOL:g} x max|{ref_name}|)", flush=True)
    require(np.all(np.isfinite(served)), f"{name}: non-finite rows")
    require(err <= tol, f"{name}: error {err:.6g} exceeds {tol:.6g}")
    return err


def gate_int8(bundle, db_path, val_rmse: float) -> dict:
    """Gate the bundle for int8 on held-out rows of its own DB."""
    from repro.quant.calibrate import calibration_rows
    from repro.quant.gate import gate_bundle
    rows = calibration_rows(db_path, "binomial")
    rec = gate_bundle(bundle, rows,
                      budget=INT8_BUDGET_OF_VAL_RMSE * val_rmse)
    print(f"int8 gate: RMSE {rec['rmse']:.6g} on {rec['rows']} calibration "
          f"rows, budget {rec['budget']:.6g} "
          f"({INT8_BUDGET_OF_VAL_RMSE:g} x val RMSE): "
          f"{'pass' if rec['exact'] else 'FAIL'}", flush=True)
    require(rec["exact"], "the int8 gate failed")
    return rec


def check_int8(served, ref, budget: float) -> float:
    served = np.asarray(served, np.float64)
    rmse = float(np.sqrt(np.mean((served - ref) ** 2)))
    err = float(np.abs(served - ref).max())
    print(f"served int8: RMSE vs highest-precision f32 reference {rmse:.6g}"
          f" (gate budget {budget:.6g}), max abs {err:.6g}", flush=True)
    require(np.all(np.isfinite(served)), "served int8: non-finite rows")
    require(rmse <= budget, f"served int8: RMSE {rmse:.6g} exceeds the "
                            f"gate budget {budget:.6g}")
    return rmse


def fallback_counts(bundle) -> dict:
    """The counters that would show a hidden fallback, from the metrics
    registry: breaker fallbacks for this bundle, and kernel dispatches
    that served the oracle (``ref``) or abandoned their tuned tile for
    VMEM (``default:vmem-fallback``)."""
    snap = default_registry().collect()

    def total(metric, **want):
        vals = snap.get(metric, {"values": []})["values"]
        return sum(v["value"] for v in vals
                   if all(v["labels"].get(k) == w for k, w in want.items()))

    return {
        "breaker_fallback": total("repro_resilience_fallback_total",
                                  key=str(bundle)),
        "ref_dispatch": total("repro_kernel_dispatch_total",
                              provenance="ref"),
        "vmem_fallback_dispatch": total("repro_kernel_dispatch_total",
                                        provenance="default:vmem-fallback"),
    }


def check_no_fallback(counts: dict) -> None:
    print("fallback counters: " + ", ".join(
        f"{k}={v:g}" for k, v in counts.items()), flush=True)
    bad = {k: v for k, v in counts.items() if v}
    require(not bad, f"hidden fallbacks happened: {bad}")


def dispatches() -> list:
    """Kernel dispatches the tracer saw (one per compiled shape), then
    forget them."""
    ds = [dict(e.args) for e in TRACER.events()
          if e.name == "kernel.dispatch"]
    TRACER.clear()
    return ds


def check_dispatches(name: str, ds: list, kernel: str) -> None:
    for d in ds:
        print(f"{name} dispatch: kernel={d.get('kernel')} "
              f"tier={d.get('tier')} provenance={d.get('provenance')} "
              f"interpret={d.get('interpret')} params={d.get('params')}",
              flush=True)
    require(any(d.get("kernel") == kernel for d in ds),
            f"{name}: no {kernel} kernel was dispatched")
    require(all(d.get("interpret") is False for d in ds),
            f"{name}: a kernel ran in interpret mode")


# --------------------------------------------------------------- runs ---
def run_one_chip(workdir: pathlib.Path, seed: int) -> None:
    n = CALLERS * CHUNK
    bundle, db_path, val_rmse = collect_and_train(
        workdir, seed=seed, n_options=max(MIN_OPTIONS, n))
    opts = binomial.make_inputs(n, seed=seed + 1)
    ref = reference(bundle, opts)

    f32 = serve_sweeps(bundle, opts)
    require(f32["tier"] == "f32", f"ungated bundle served {f32['tier']}")
    report_sweeps("f32", f32, n)
    check_f32("served f32", f32["rows"], ref)
    check_dispatches("f32", dispatches(), "fused_mlp")

    rec = gate_int8(bundle, db_path, val_rmse)
    i8 = serve_sweeps(bundle, opts)
    print(f"engine tier with REPRO_QUANT unset: {i8['tier']}", flush=True)
    require(i8["tier"] == "int8", f"gated bundle served {i8['tier']}, "
                                  f"not int8")
    report_sweeps("int8", i8, n)
    check_int8(i8["rows"], ref, rec["budget"])
    check_dispatches("int8", dispatches(), "fused_mlp_int8")

    check_no_fallback(fallback_counts(bundle))
    print(f"smoke timing (not a benchmark): set-up total "
          f"{f32['setup_s'] + i8['setup_s']:.3f}s", flush=True)


def run_four_chips(workdir: pathlib.Path, seed: int) -> None:
    """The phase-3 sweep served on a 1x4 ``data`` mesh, against the same
    sweep on device 0 alone, with seeded random weights."""
    from repro.dist.sharding import use_mesh
    from repro.launch.mesh import make_pod_mesh
    n = CALLERS * CHUNK
    mesh = make_pod_mesh()
    print(f"mesh: {dict(mesh.shape)}", flush=True)
    require(mesh.shape["data"] == 4, "the data axis does not span 4 chips")
    net = widest_net()
    bundle = save_model(workdir / "bundle", net,
                        net.init(jax.random.PRNGKey(seed)))
    opts = binomial.make_inputs(n, seed=seed + 1)
    ref = reference(bundle, opts)

    one = serve_sweeps(bundle, opts)
    report_sweeps("device 0", one, n)
    check_f32("served on device 0", one["rows"], ref)
    check_dispatches("device 0", dispatches(), "fused_mlp")
    with use_mesh(mesh):
        four = serve_sweeps(bundle, opts)
        # device 0's program is cached, so a dispatch here means the
        # served sweep traced a program of its own for the mesh
        check_dispatches("1x4 mesh", dispatches(), "fused_mlp")
        y = InferenceEngine.get(str(bundle)).apply_batched(
            jnp.asarray(opts))
    report_sweeps("1x4 mesh", four, n)
    check_f32("served on the 1x4 mesh", four["rows"], ref)
    check_f32("served on the 1x4 mesh", four["rows"], one["rows"],
              ref_name="rows served on device 0")
    shards = {s.device: s.data.shape for s in y.addressable_shards}
    print(f"output shards: {len(shards)} devices, shapes "
          f"{sorted(set(shards.values()))}", flush=True)
    require(len(shards) == 4 and all(s[0] == n // 4
                                     for s in shards.values()),
            f"output is not split over 4 devices: {shards}")
    check_no_fallback(fallback_counts(bundle))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="serve on a 1x4 data mesh and compare with one "
                         "chip; runs nothing else")
    args = ap.parse_args(argv)
    cache = pathlib.Path(enable_compile_cache())
    info = device_check()
    n_cached = len(list(cache.glob("*"))) if cache.is_dir() else 0
    print(f"compile cache: {cache} ({n_cached} entries at start)",
          flush=True)
    # the engine must resolve its tier as users get it: REPRO_QUANT=auto
    os.environ.pop("REPRO_QUANT", None)
    TRACER.enable()
    workdir = pathlib.Path(tempfile.mkdtemp(prefix="chip-smoke-"))
    t0 = time.perf_counter()
    try:
        if args.four_chips:
            run_four_chips(workdir, args.seed)
        else:
            run_one_chip(workdir, args.seed)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    print(f"smoke wall time {time.perf_counter() - t0:.3f}s", flush=True)
    print(json.dumps({"ok": True, "device": info}))


if __name__ == "__main__":
    main()
