"""Serving-throughput benchmark: per-call vs coalesced mesh-wide batching.

Models the paper-at-scale regime: many independent callers (solver
instances / ensemble members / sweep chunks), each invoking the same
surrogate region with a small row block per sweep step.

  * per-call   — every caller runs ``MLRegion._infer`` synchronously:
                 one bridge + placement + jit dispatch per caller;
  * coalesced  — callers enqueue on a ``ServeQueue``; one flush serves
                 the whole sweep as a single padded mega-batch placed
                 over the mesh ``data`` axis.

Standalone (the CI smoke) forces an 8-device host platform so placement
really spans a mesh:

  PYTHONPATH=src python -m benchmarks.serve_bench --check

``--check`` exits non-zero unless coalesced achieves >= CHECK_SPEEDUP x
the per-call rows/s — the serving-regression gate.
"""
import os

if __name__ == "__main__":  # must precede the first jax import
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse
import json
import pathlib
import time

import jax
import numpy as np

from benchmarks.common import write_bench_json
from repro.launch.compile_cache import enable_compile_cache

CHECK_SPEEDUP = 3.0
#: instrumentation gate: tracing ON must keep >= this fraction of the
#: tracing-OFF rows/s (interleaved-pair median ratio, drift-immune)
OVERHEAD_MIN_RATIO = 0.98
#: a sampled request's spans must cover >= this much of its measured
#: enqueue->resolve window (no unaccounted gaps)
TRACE_MIN_COVERAGE = 0.95
#: shadow-quality gate: sampling ON must keep >= this fraction of the
#: unsampled rows/s (same interleaved-pair minimum as the tracing gate)
SHADOW_MIN_RATIO = 0.98
#: shadow sampling fraction under test (overridable for sweeps)
SHADOW_RATE = float(os.environ.get("REPRO_SHADOW_RATE", "") or 0.05)
#: injected weight corruption must flip the drift alert to CRITICAL
#: within this many shadow samples
SHADOW_ALERT_SAMPLES = 20
#: drift-alert budget for the corruption drill.  Registered in the
#: shared per-bundle registry (``repro.quant.budgets``) rather than set
#: directly on the scorer: the check exercises the same resolution path
#: the quant gate certifies int8 eligibility through, so this bench
#: fails if the two accuracy gates ever stop reading the same numbers.
SHADOW_RMSE_BUDGET = float(
    os.environ.get("REPRO_SHADOW_RMSE_BUDGET", "") or 0.05)
#: resilience gate: the breaker board enabled (idle, CLOSED) must keep
#: >= this fraction of the board-disabled rows/s on the coalesced path
FAULT_IDLE_MIN_RATIO = 0.98
#: injected dispatch faults must trip the breaker OPEN within this many
#: failing batches
FAULT_OPEN_BATCHES = 8
#: tenancy gate: the hot tenant submits this many times the traffic of
#: each latency tenant in the skewed run
TENANT_SKEW = 10
#: tenancy gate: no tenant's p99 may degrade more than this factor vs
#: the unskewed baseline (per-tenant, measured on the same scheduler)
TENANT_P99_MAX_RATIO = 2.0
#: tenancy gate: p99s below this floor compare as equal — at sub-ms
#: latencies the ratio is scheduler noise, not starvation
TENANT_P99_FLOOR_MS = 2.0
#: residency gate: byte budget in units of one bundle's params, chosen
#: so 3 served bundles never fit resident at once
TENANT_RESIDENCY_FIT = 2.5


def _bundle(path):
    """A NAS-shaped MLP surrogate bundle (weights need not be trained:
    throughput is architecture- and batch-shaped, not accuracy-shaped)."""
    from repro.nn import MLP
    from repro.nn.serialize import save_model
    net = MLP((1, 5), [128, 128], 1)
    params = net.init(jax.random.PRNGKey(0))
    return save_model(path, net, params)


def _measure(fn, reps=5, warmup=2):
    for _ in range(warmup):
        fn()
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def serving_throughput(fast=False, *, n_callers=None, rows_per_call=8):
    """benchmarks.run entry: CSV rows only (drops the latency table)."""
    rows, _ = serving_throughput_full(fast=fast, n_callers=n_callers,
                                      rows_per_call=rows_per_call)
    return rows


def serving_throughput_full(fast=False, *, n_callers=None, rows_per_call=8):
    """CSV rows comparing per-call vs coalesced serving on the host mesh,
    plus the per-bucket measured-vs-roofline latency table."""
    import pathlib
    import tempfile

    import jax.numpy as jnp

    from repro.apps import binomial
    from repro.dist.sharding import use_mesh
    from repro.launch.mesh import make_local_mesh
    from repro.serve import FlushPolicy, ServeQueue

    n_callers = n_callers or (16 if fast else 64)
    total = n_callers * rows_per_call
    tmp = pathlib.Path(tempfile.mkdtemp(prefix="serve_bench_"))
    mp = _bundle(tmp / "surrogate")

    ndev = len(jax.devices())
    mesh_shape = (ndev, 1)
    mesh = make_local_mesh(mesh_shape)
    opts = binomial.make_inputs(total, seed=7)
    chunks = [opts[i:i + rows_per_call] for i in range(0, total,
                                                      rows_per_call)]

    from repro.tune import AdaptiveFlushController
    queue = ServeQueue(FlushPolicy(max_batch_rows=total,
                                   max_pending_rows=4 * total))
    ad_policy = FlushPolicy(max_batch_rows=total, max_pending_rows=4 * total,
                            max_delay_s=0.05)
    ad_queue = ServeQueue(ad_policy,
                          controller=AdaptiveFlushController(ad_policy))
    r_sync = binomial.make_region(rows_per_call, mode="infer", model=mp)
    r_async = binomial.make_region(rows_per_call, mode="infer_async",
                                   model=mp, serving=queue)
    r_adapt = binomial.make_region(rows_per_call, mode="infer_async",
                                   model=mp, serving=ad_queue)

    with use_mesh(mesh):
        def per_call():
            outs = [r_sync(opts=c)["out"] for c in chunks]
            jax.block_until_ready(outs)
            return outs

        def coalesced():
            handles = [r_async(opts=c) for c in chunks]
            queue.flush(mp, reason="sweep_step")
            outs = [h.result()["out"] for h in handles]
            jax.block_until_ready(outs)
            return outs

        def adaptive():
            # no explicit flush: the controller's deadline/batch trigger
            # decides when the mega-batches go out
            handles = [r_adapt(opts=c) for c in chunks]
            outs = [h.result(30)["out"] for h in handles]
            jax.block_until_ready(outs)
            return outs

        t_call = _measure(per_call)
        t_coal = _measure(coalesced)
        with ad_queue:  # dispatcher thread enforces the adaptive deadline
            t_adapt = _measure(adaptive)
        # exactness: coalesced rows must match per-call rows bit-for-bit
        same = all(
            bool((np.asarray(a) == np.asarray(b)).all())
            for a, b in zip(per_call(), coalesced()))

    st = queue.stats(mp).snapshot()
    ast = ad_queue.stats(mp).snapshot()
    pool = ad_queue._batcher.scratch.stats()
    rows_s_call = total / t_call
    rows_s_coal = total / t_coal
    rows_s_adapt = total / t_adapt
    speedup = rows_s_coal / rows_s_call
    model_err = latency_model_rows(ad_queue, mp)
    worst_err = max((abs(r["err_pct"]) for r in model_err), default=0.0)
    derived = (f"devices={ndev};callers={n_callers};"
               f"rows_per_call={rows_per_call};"
               f"percall_rows_s={rows_s_call:.0f};"
               f"coalesced_rows_s={rows_s_coal:.0f};"
               f"speedup_x={speedup:.2f};bitwise_equal={same};"
               f"occupancy={st['batch_occupancy']:.2f};"
               f"p50_ms={st['latency_p50_ms']:.2f};"
               f"p99_ms={st['latency_p99_ms']:.2f};"
               f"adaptive_rows_s={rows_s_adapt:.0f};"
               f"adaptive_p50_ms={ast['latency_p50_ms']:.2f};"
               f"adaptive_p99_ms={ast['latency_p99_ms']:.2f};"
               f"scratch_hit_rate={pool['hits'] / max(1, pool['hits'] + pool['misses']):.2f};"
               f"roofline_worst_err_pct={worst_err:.0f}")
    return ([("serve_throughput/binomial", t_coal / n_callers * 1e6,
              derived)], model_err)


def latency_model_rows(ad_queue, mp):
    """Per-bucket measured-vs-roofline batch latency error.

    The adaptive controller's deadline model starts from the roofline
    prediction and converges on measured ``ServeStats`` latencies; this
    table makes the model's drift visible (a large error means the
    open-loop prior was badly miscalibrated for this backend — exactly
    what the measured loop corrects, and what EXPERIMENTS.md should
    show).
    """
    ctrl = ad_queue.controller
    st = ad_queue.stats(mp)
    widths = ctrl._widths_cached(mp) if ctrl is not None else None
    rows = []
    if not widths:
        return rows
    for bucket, (ewma_s, n) in sorted(st.batch_latencies().items()):
        pred_s = ctrl.predict_latency_s(widths, bucket)
        err = (pred_s - ewma_s) / ewma_s * 100.0 if ewma_s > 0 else 0.0
        rows.append({"bucket": bucket, "batches": n,
                     "measured_ms": ewma_s * 1e3,
                     "roofline_ms": pred_s * 1e3, "err_pct": err})
    return rows


def export_trace(path) -> None:
    """Write the Chrome trace + metrics artifacts and gate span coverage.

    The trace must account for each sampled request's whole
    enqueue->resolve window: queue.submit + serve.request tile it by
    construction, so any request whose union coverage drops below
    :data:`TRACE_MIN_COVERAGE` means an instrumentation gap crept into
    the serve path.  Coverage is taken over those two spans alone: the
    region spans sharing the id also hold the caller's time around the
    queue (its other calls, its flush) before ``result()``.
    """
    from repro.obs import TRACER, default_registry, request_coverage
    path = pathlib.Path(path)
    events = TRACER.export_chrome_trace(path)
    # sampled = requests whose span set is complete in the ring (the ring
    # evicts oldest-first, so early-warmup requests may be partial)
    full = {t for t in
            ( (e.get("args") or {}).get("trace") for e in events
              if e["name"] == "queue.submit" )
            if t is not None}
    served = [e for e in events
              if e["name"] in ("queue.submit", "serve.request")]
    cov = {t: c for t, c in request_coverage(served).items()
           if t in full and c["spans"] >= 2}
    if not cov:
        raise SystemExit("--trace: no fully-sampled request in the trace "
                         "(ring too small for this workload?)")
    worst = min(cov.values(), key=lambda c: c["coverage"])
    metrics = default_registry()
    path.with_suffix(".metrics.json").write_text(
        json.dumps(metrics.collect(), indent=1))
    path.with_suffix(".prom").write_text(metrics.dump())
    print(f"[serve trace] {len(events)} events -> {path}; "
          f"{len(cov)} sampled requests, worst coverage "
          f"{worst['coverage']:.3f} over {worst['window_us']:.0f}us",
          flush=True)
    if worst["coverage"] < TRACE_MIN_COVERAGE:
        raise SystemExit(
            f"--trace FAILED: worst request coverage {worst['coverage']:.3f}"
            f" < {TRACE_MIN_COVERAGE} (unaccounted gap in the serve path)")


def overhead_check(fast=False, pairs=50):
    """Gate instrumentation cost: tracing on vs off, interleaved pairs.

    Runs the coalesced serve path (the instrumented hot path) with the
    tracer toggled every other run; the gate compares the *minimum* off
    time against the minimum on time.  Scheduler noise only ever adds
    time, so each minimum estimates that path's true cost; the tight
    interleave guarantees both sets sample the same machine conditions
    (a sequential off-block/on-block comparison is dominated by drift —
    measured, the drift between two such blocks exceeds the effect being
    gated); and the within-pair order alternates each pair because the
    second run of a pair measures systematically slower than the first
    (also larger than the effect under test).  GC is paused during
    timing, as ``timeit`` does.  Fails below :data:`OVERHEAD_MIN_RATIO`.
    """
    import gc
    import tempfile

    from repro.dist.sharding import use_mesh
    from repro.launch.mesh import make_local_mesh
    from repro.obs import TRACER, disable_tracing, enable_tracing
    from repro.serve import FlushPolicy, ServeQueue

    n_callers = 16 if fast else 32
    rows_per_call = 8
    total = n_callers * rows_per_call
    tmp = pathlib.Path(tempfile.mkdtemp(prefix="serve_obs_bench_"))
    mp = _bundle(tmp / "surrogate")
    mesh = make_local_mesh((len(jax.devices()), 1))
    queue = ServeQueue(FlushPolicy(max_batch_rows=total,
                                   max_pending_rows=4 * total))
    rng = np.random.default_rng(3)
    chunks = [rng.standard_normal((rows_per_call, 5)).astype(np.float32)
              for _ in range(n_callers)]

    def run_once():
        futs = [queue.submit(mp, c) for c in chunks]
        queue.flush(mp, reason="bench")
        for f in futs:
            f.result(30)

    was_enabled = TRACER.enabled
    offs, ons = [], []
    try:
        with use_mesh(mesh):
            disable_tracing()
            _measure(run_once, reps=1, warmup=3)  # compile outside timing
            gc.disable()
            try:
                for i in range(pairs):
                    halves = [(False, offs), (True, ons)]
                    if i % 2:
                        halves.reverse()
                    for on, times in halves:
                        enable_tracing() if on else disable_tracing()
                        t0 = time.perf_counter()
                        run_once()
                        times.append(time.perf_counter() - t0)
                    if i % 10 == 9:  # bound ring/heap growth, untimed
                        TRACER.clear()
                        gc.collect()
            finally:
                gc.enable()
            TRACER.clear()
    finally:
        TRACER.enabled = was_enabled
    ratio = min(offs) / min(ons)
    print(f"[serve obs overhead] traced serving retains "
          f"{ratio * 100:.1f}% of untraced rows/s over {pairs} "
          f"interleaved pairs (off {min(offs) * 1e3:.3f}ms / on "
          f"{min(ons) * 1e3:.3f}ms)", flush=True)
    if ratio < OVERHEAD_MIN_RATIO:
        raise SystemExit(
            f"obs overhead gate FAILED: traced/untraced rows/s "
            f"ratio {ratio:.3f} < {OVERHEAD_MIN_RATIO} (instrumentation "
            f"costs more than {100 * (1 - OVERHEAD_MIN_RATIO):.0f}%)")
    return ratio


def shadow_overhead_check(fast=False, pairs=50):
    """Gate shadow-sampling cost on the serving hot path.

    The coalesced region path (``MLRegion._infer_async`` — where the
    sampling hook lives) runs with shadow sampling toggled every other
    run at :data:`SHADOW_RATE`, tracing off on both sides, and the gate
    compares minimum unsampled time against minimum sampled time — the
    same interleaved-pair methodology as :func:`overhead_check` (see
    there for why min/min + alternating within-pair order + paused GC).
    The accurate-path replay cost lands on the scorer's background
    thread by design; what this gates is the hot-path hook (an attribute
    check + Bernoulli draw) plus any GIL pressure the replays leak into
    the serving threads.
    """
    import gc
    import tempfile

    from repro.apps import binomial
    from repro.dist.sharding import use_mesh
    from repro.launch.mesh import make_local_mesh
    from repro.obs import SHADOW, TRACER, disable_tracing
    from repro.serve import FlushPolicy, ServeQueue

    n_callers = 16 if fast else 32
    rows_per_call = 8
    total = n_callers * rows_per_call
    tmp = pathlib.Path(tempfile.mkdtemp(prefix="serve_shadow_bench_"))
    mp = _bundle(tmp / "surrogate")
    mesh = make_local_mesh((len(jax.devices()), 1))
    queue = ServeQueue(FlushPolicy(max_batch_rows=total,
                                   max_pending_rows=4 * total))
    region = binomial.make_region(rows_per_call, mode="infer_async",
                                  model=mp, serving=queue)
    opts = binomial.make_inputs(total, seed=11)
    chunks = [opts[i:i + rows_per_call]
              for i in range(0, total, rows_per_call)]

    def run_once():
        handles = [region(opts=c) for c in chunks]
        queue.flush(mp, reason="bench")
        for h in handles:
            h.result(30)

    was_traced, was_shadow = TRACER.enabled, SHADOW.enabled
    prev_rate = SHADOW.rate
    offs, ons = [], []
    try:
        with use_mesh(mesh):
            disable_tracing()
            # warmup at rate 1.0: compiles the surrogate path AND the
            # accurate replay (binomial's 256-step scan) and spins up
            # the scorer thread, all outside timing
            SHADOW.enable(rate=1.0)
            _measure(run_once, reps=1, warmup=3)
            SHADOW.flush(60)
            SHADOW.disable()
            gc.disable()
            try:
                for i in range(pairs):
                    halves = [(False, offs), (True, ons)]
                    if i % 2:
                        halves.reverse()
                    for on, times in halves:
                        if on:
                            SHADOW.enable(rate=SHADOW_RATE)
                        else:
                            SHADOW.disable()
                        t0 = time.perf_counter()
                        run_once()
                        times.append(time.perf_counter() - t0)
                        # drain the scorer after every half, untimed:
                        # residual replays must not bleed GIL time into
                        # the next timed run (that is backlog cost, not
                        # the hot-path hook cost this gates)
                        SHADOW.disable()
                        SHADOW.flush(30)
                    if i % 10 == 9:
                        gc.collect()
            finally:
                gc.enable()
            SHADOW.disable()
            SHADOW.flush(30)
    finally:
        TRACER.enabled = was_traced
        SHADOW.rate = prev_rate
        SHADOW.enabled = was_shadow
    ratio = min(offs) / min(ons)
    print(f"[shadow overhead] sampling at {SHADOW_RATE:.0%} retains "
          f"{ratio * 100:.1f}% of unsampled rows/s over {pairs} "
          f"interleaved pairs (off {min(offs) * 1e3:.3f}ms / on "
          f"{min(ons) * 1e3:.3f}ms)", flush=True)
    if ratio < SHADOW_MIN_RATIO:
        raise SystemExit(
            f"shadow overhead gate FAILED: sampled/unsampled rows/s "
            f"ratio {ratio:.3f} < {SHADOW_MIN_RATIO} (shadow sampling "
            f"costs more than {100 * (1 - SHADOW_MIN_RATIO):.0f}%)")
    return ratio


def shadow_alert_check():
    """Injected weight corruption must actually fire the drift alert.

    A region whose accurate function *is* the surrogate's own original
    forward serves through the queue with shadow sampling at 100%: the
    clean run scores RMSE ~0 and must stay OK.  Then the bundle is
    rewritten with corrupted weights — the engine's mtime-staleness
    reload picks them up on the next batch — and the RMSE EWMA must
    cross the budget and latch CRITICAL within
    :data:`SHADOW_ALERT_SAMPLES` shadow samples, visibly: ``/healthz``
    flips 200 -> 503, ``/metrics`` carries ``repro_quality_rmse`` (and
    validates as Prometheus text), and the pod snapshot reports the
    CRITICAL state.
    """
    import tempfile
    import urllib.error
    import urllib.request

    from repro.core import approx_ml, tensor_functor
    from repro.nn.serialize import load_model, save_model
    from repro.obs import (MONITOR, SHADOW, SLO, ObsServer, pod_snapshot,
                           validate_exposition)
    from repro.serve import FlushPolicy, ServeQueue

    rows_per_call, n_callers = 8, 8
    tmp = pathlib.Path(tempfile.mkdtemp(prefix="serve_shadow_alert_"))
    mp = _bundle(tmp / "surrogate")
    net, params0, _ = load_model(mp)
    ref_apply = jax.jit(net.apply)

    def fn(x):
        return {"out": ref_apply(params0, x)}

    rngs = {"i": (0, rows_per_call)}
    qin = tensor_functor("qin: [i, 0:5] = ([i, 0:5])")
    qout = tensor_functor("qout: [i, 0:1] = ([i, 0:1])")
    queue = ServeQueue(FlushPolicy(max_batch_rows=1024))
    region = approx_ml(fn, name="shadow_probe",
                       inputs={"x": (qin, rngs)},
                       outputs={"out": (qout, rngs)},
                       mode="infer_async", model=mp, serving=queue)
    rng = np.random.default_rng(5)
    chunks = [rng.standard_normal((rows_per_call, 5)).astype(np.float32)
              for _ in range(n_callers)]

    was_shadow, prev_rate = SHADOW.enabled, SHADOW.rate
    SHADOW.enable(rate=1.0)
    # through the shared registry, NOT SHADOW.set_budget: the scorer's
    # fallback chain (explicit > quant.budgets > default) must resolve it
    from repro.quant.budgets import set_rmse_budget
    set_rmse_budget(mp, SHADOW_RMSE_BUDGET)
    MONITOR.track(mp, queue.stats(mp),
                  SLO(latency_threshold_s=5.0, windows_s=(30.0, 120.0),
                      min_events=1))
    server = ObsServer().start().watch_queue("serve", queue)

    def run_batch():
        handles = [region(x=c) for c in chunks]
        queue.flush(mp, reason="bench")
        for h in handles:
            h.result(30)

    def healthz_code():
        try:
            with urllib.request.urlopen(server.url("/healthz"),
                                        timeout=10) as r:
                return r.status
        except urllib.error.HTTPError as e:
            return e.code

    try:
        # clean phase: surrogate == accurate fn, alert must stay OK
        for _ in range(3):
            run_batch()
        if not SHADOW.flush(60):
            raise SystemExit("shadow alert check: scorer backlog did not "
                             "drain on the clean run")
        clean = SHADOW.snapshot()["keys"][mp]
        code = healthz_code()
        print(f"[shadow alert] clean: rmse_ewma="
              f"{clean['rmse_ewma']:.3g} state={clean['state']} "
              f"healthz={code}", flush=True)
        if clean["state"] != "OK" or code != 200:
            raise SystemExit(
                f"shadow alert check FAILED: clean run reports "
                f"{clean['state']}/HTTP {code} (expected OK/200)")

        # corrupt the bundle in place; the engine's mtime fingerprint
        # reloads it on the next batch while fn keeps the true params
        bad = jax.tree_util.tree_map(lambda p: p + 0.5, params0)
        save_model(mp, net, bad)
        fired_at = None
        for batch in range(SHADOW_ALERT_SAMPLES):
            run_batch()
            SHADOW.flush(60)
            if SHADOW.state(mp) == "CRITICAL":
                fired_at = batch + 1
                break
        snap = SHADOW.snapshot()["keys"][mp]
        code = healthz_code()
        print(f"[shadow alert] corrupted: rmse_ewma="
              f"{snap['rmse_ewma']:.3g} state={snap['state']} "
              f"fired_after={fired_at} batches healthz={code}", flush=True)
        if fired_at is None:
            raise SystemExit(
                f"shadow alert check FAILED: drift alert never reached "
                f"CRITICAL within {SHADOW_ALERT_SAMPLES} corrupted "
                f"batches (rmse_ewma={snap['rmse_ewma']:.3g}, budget "
                f"{SHADOW_RMSE_BUDGET})")
        if code != 503:
            raise SystemExit(
                f"shadow alert check FAILED: /healthz returned {code} "
                f"with a CRITICAL drift alert (expected 503)")
        with urllib.request.urlopen(server.url("/metrics"),
                                    timeout=10) as r:
            text = r.read().decode("utf-8")
        validate_exposition(text)
        if "repro_quality_rmse{" not in text:
            raise SystemExit("shadow alert check FAILED: /metrics has no "
                             "repro_quality_rmse samples")
        pod_q = pod_snapshot()[0]["quality"]["keys"].get(mp, {})
        if pod_q.get("state") != "CRITICAL":
            raise SystemExit(
                f"shadow alert check FAILED: pod snapshot reports "
                f"{pod_q.get('state')!r}, expected CRITICAL")
        print(f"[shadow alert] OK: corruption fired CRITICAL after "
              f"{fired_at} batches; healthz 503; exposition valid; pod "
              f"snapshot agrees", flush=True)
    finally:
        server.stop()
        MONITOR.untrack(mp)
        SHADOW.rate = prev_rate
        SHADOW.enabled = was_shadow


def fault_overhead_check(fast=False, pairs=50):
    """Gate the breaker's idle cost on the serving hot path.

    A CLOSED breaker is pure overhead: one ``allow()`` per request
    (a lock acquire + two branches) in ``MLRegion._infer_async`` plus
    one ``record_success`` per dispatched batch in the batcher.  The
    gate runs the coalesced region path with the :data:`BREAKERS` board
    toggled every other run — the same interleaved-pair min/min
    methodology as :func:`overhead_check` (see there for why min/min +
    alternating within-pair order + paused GC) — and fails if the
    enabled side retains less than :data:`FAULT_IDLE_MIN_RATIO` of the
    disabled side's rows/s.
    """
    import gc
    import tempfile

    from repro.apps import binomial
    from repro.dist.sharding import use_mesh
    from repro.launch.mesh import make_local_mesh
    from repro.obs import SHADOW, TRACER, disable_tracing
    from repro.resilience import BREAKERS
    from repro.serve import FlushPolicy, ServeQueue

    n_callers = 16 if fast else 32
    rows_per_call = 8
    total = n_callers * rows_per_call
    tmp = pathlib.Path(tempfile.mkdtemp(prefix="serve_fault_bench_"))
    mp = _bundle(tmp / "surrogate")
    mesh = make_local_mesh((len(jax.devices()), 1))
    queue = ServeQueue(FlushPolicy(max_batch_rows=total,
                                   max_pending_rows=4 * total))
    region = binomial.make_region(rows_per_call, mode="infer_async",
                                  model=mp, serving=queue)
    opts = binomial.make_inputs(total, seed=13)
    chunks = [opts[i:i + rows_per_call]
              for i in range(0, total, rows_per_call)]

    def run_once():
        handles = [region(opts=c) for c in chunks]
        queue.flush(mp, reason="bench")
        for h in handles:
            h.result(30)

    was_traced, was_shadow = TRACER.enabled, SHADOW.enabled
    was_breaker = BREAKERS.enabled
    offs, ons = [], []
    try:
        with use_mesh(mesh):
            disable_tracing()
            SHADOW.enabled = False
            BREAKERS.enabled = True
            _measure(run_once, reps=1, warmup=3)  # compile outside timing
            gc.disable()
            try:
                for i in range(pairs):
                    halves = [(False, offs), (True, ons)]
                    if i % 2:
                        halves.reverse()
                    for on, times in halves:
                        BREAKERS.enabled = on
                        t0 = time.perf_counter()
                        run_once()
                        times.append(time.perf_counter() - t0)
                    if i % 10 == 9:
                        gc.collect()
            finally:
                gc.enable()
    finally:
        TRACER.enabled = was_traced
        SHADOW.enabled = was_shadow
        BREAKERS.enabled = was_breaker
        BREAKERS.reset(mp)
    ratio = min(offs) / min(ons)
    print(f"[breaker idle overhead] breaker-enabled serving retains "
          f"{ratio * 100:.1f}% of breaker-disabled rows/s over {pairs} "
          f"interleaved pairs (off {min(offs) * 1e3:.3f}ms / on "
          f"{min(ons) * 1e3:.3f}ms)", flush=True)
    if ratio < FAULT_IDLE_MIN_RATIO:
        raise SystemExit(
            f"breaker idle overhead gate FAILED: enabled/disabled "
            f"rows/s ratio {ratio:.3f} < {FAULT_IDLE_MIN_RATIO} (an idle "
            f"breaker costs more than "
            f"{100 * (1 - FAULT_IDLE_MIN_RATIO):.0f}%)")
    return ratio


def fault_drill_check():
    """Injected dispatch faults must trip the breaker and lose nothing.

    Drives the breaker through its full CLOSED → OPEN → HALF_OPEN →
    CLOSED cycle end-to-end through the public serving path:

      1. clean phase — batches through the queue resolve finite and the
         breaker stays CLOSED;
      2. fault phase — ``engine.apply:raise:every=1`` makes every batch
         dispatch fail.  Every handle must still resolve (zero-lost:
         ``AsyncRegionResult.result`` degrades to the accurate path) and
         the breaker must trip OPEN within :data:`FAULT_OPEN_BATCHES`
         batches; while OPEN, submits short-circuit to the accurate
         path without touching the queue at all;
      3. recovery phase — faults cleared, the cooldown elapses, probe
         traffic closes the breaker again.

    The cycle must be observable: an ``ObsServer`` scrape during the
    OPEN phase must carry ``repro_resilience_breaker_state``, the
    transition counter and the fallback counter (and validate as
    Prometheus text).  Prints time-to-open, the measured fallback
    latency cost, and time-to-recover for EXPERIMENTS.md.
    """
    import tempfile
    import urllib.request

    from repro.core import approx_ml, tensor_functor
    from repro.obs import ObsServer, validate_exposition
    from repro.resilience import BREAKERS, FAULTS, BreakerPolicy
    from repro.resilience.breaker import CLOSED, OPEN
    from repro.serve import FlushPolicy, ServeQueue

    rows_per_call, n_callers = 8, 8
    tmp = pathlib.Path(tempfile.mkdtemp(prefix="serve_fault_drill_"))
    mp = _bundle(tmp / "surrogate")
    rngs = {"i": (0, rows_per_call)}
    fin = tensor_functor("fin: [i, 0:5] = ([i, 0:5])")
    fout = tensor_functor("fout: [i, 0:1] = ([i, 0:1])")
    queue = ServeQueue(FlushPolicy(max_batch_rows=1024))
    region = approx_ml(lambda x: {"out": x[:, :1] * 2.0},
                       name="fault_drill", inputs={"x": (fin, rngs)},
                       outputs={"out": (fout, rngs)},
                       mode="infer_async", model=mp, serving=queue)
    cooldown = 2.0
    breaker = BREAKERS.configure(mp, BreakerPolicy(
        failure_threshold=0.5, ewma_alpha=0.5, min_samples=4,
        open_cooldown_s=cooldown, probe_n=2, probe_every=1))
    rng = np.random.default_rng(7)
    chunks = [rng.standard_normal((rows_per_call, 5)).astype(np.float32)
              for _ in range(n_callers)]
    submitted = resolved = 0

    def run_batch():
        nonlocal submitted, resolved
        handles = [region(x=c) for c in chunks]
        submitted += len(handles)
        outs = []
        queue.flush(mp, reason="bench")
        for h in handles:
            out = h.result(30)
            if not np.all(np.isfinite(np.asarray(out["out"]))):
                raise SystemExit("fault drill FAILED: non-finite rows "
                                 "reached a caller")
            outs.append(out)
        resolved += len(outs)
        return handles

    was_breaker = BREAKERS.enabled
    BREAKERS.enabled = True
    server = ObsServer().start()
    try:
        # 1. clean phase: surrogate serves, breaker stays CLOSED
        for _ in range(3):
            run_batch()
        if breaker.state != CLOSED:
            raise SystemExit(f"fault drill FAILED: breaker is "
                             f"{breaker.state} after clean traffic")

        # 2. fault phase: every dispatch raises; handles degrade to the
        #    accurate path and the failure EWMA trips the breaker
        FAULTS.configure("engine.apply:raise:every=1")
        t0 = time.perf_counter()
        open_after = None
        for batch in range(FAULT_OPEN_BATCHES):
            run_batch()
            if breaker.state != CLOSED:
                open_after = batch + 1
                break
        time_to_open = time.perf_counter() - t0
        snap = breaker.snapshot()
        if open_after is None:
            raise SystemExit(
                f"fault drill FAILED: breaker still CLOSED after "
                f"{FAULT_OPEN_BATCHES} all-failing batches ({snap})")
        print(f"[fault drill] tripped {snap['state']} after {open_after} "
              f"failing batch(es) in {time_to_open * 1e3:.0f}ms "
              f"(ewma={snap['ewma']})", flush=True)

        # while OPEN every submit short-circuits: accurate-path answers,
        # nothing enqueued.  Time it — this is the fallback latency cost.
        t0 = time.perf_counter()
        handles = run_batch()
        fallback_ms = (time.perf_counter() - t0) * 1e3
        if any(h.deferred() for h in handles):
            raise SystemExit("fault drill FAILED: an OPEN breaker let a "
                             "request reach the serve queue")
        if queue.depth() != 0:
            raise SystemExit(f"fault drill FAILED: {queue.depth()} rows "
                             f"parked on the queue while OPEN")
        print(f"[fault drill] OPEN short-circuit: {n_callers} calls "
              f"served accurately in {fallback_ms:.0f}ms, queue untouched",
              flush=True)

        # the cycle must be scrapeable while it is happening
        with urllib.request.urlopen(server.url("/metrics"),
                                    timeout=10) as r:
            text = r.read().decode("utf-8")
        validate_exposition(text)
        for family in ("repro_resilience_breaker_state{",
                       "repro_resilience_breaker_transitions_total{",
                       "repro_resilience_fallback_total{",
                       "repro_resilience_faults_injected_total{"):
            if family not in text:
                raise SystemExit(f"fault drill FAILED: /metrics has no "
                                 f"{family.rstrip('{')} samples")

        # 3. recovery: faults off, cooldown elapses, probes re-close it
        FAULTS.clear()
        t0 = time.perf_counter()
        time.sleep(cooldown + 0.05)
        recovered_after = None
        for batch in range(6):
            run_batch()
            if breaker.state == CLOSED:
                recovered_after = batch + 1
                break
        time_to_recover = time.perf_counter() - t0
        if recovered_after is None:
            raise SystemExit(f"fault drill FAILED: breaker never closed "
                             f"after recovery ({breaker.snapshot()})")
        if breaker.state == OPEN:
            raise SystemExit("fault drill FAILED: breaker re-opened on "
                             "clean probe traffic")
        print(f"[fault drill] recovered CLOSED after {recovered_after} "
              f"probe batch(es), {time_to_recover:.2f}s past fault "
              f"clear (cooldown {cooldown}s)", flush=True)

        if resolved != submitted:
            raise SystemExit(f"fault drill FAILED: {submitted} submitted "
                             f"but only {resolved} resolved")
        print(f"[fault drill] OK: {submitted}/{submitted} requests "
              f"resolved finite across the full "
              f"CLOSED→OPEN→HALF_OPEN→CLOSED cycle; zero lost", flush=True)
        return {"time_to_open_s": time_to_open,
                "fallback_ms": fallback_ms,
                "time_to_recover_s": time_to_recover}
    finally:
        server.stop()
        FAULTS.clear()
        BREAKERS.enabled = was_breaker
        BREAKERS.reset(mp)


def _tenant_board():
    """3 tenants, mixed QoS: two latency-tier (unequal weights) and one
    throughput-tier tenant that will carry the skewed burst."""
    from repro.serve import TenantBoard, TenantSpec
    return TenantBoard([
        TenantSpec("lat-a", tier="latency", weight=2.0),
        TenantSpec("bulk", tier="throughput", weight=1.0),
        TenantSpec("lat-b", tier="latency", weight=1.0),
    ])


def _tenant_run(bundles, *, skew, rounds, k_chunks=3, rows_per_chunk=8):
    """Drive one tenant-traffic run; returns the board's snapshot.

    Per round every tenant submits ``k_chunks`` chunks against its own
    bundle (the hot tenant submits ``skew``x that), the hot tenant first
    — the worst case for FIFO — then the round drains with an explicit
    all-keys flush, whose key order the tenancy board picks by DRR under
    overload.  Thread-free queue: deterministic timing, caller's thread.
    """
    from repro.serve import FlushPolicy, ServeQueue
    board = _tenant_board()
    policy = FlushPolicy(max_batch_rows=64, max_pending_rows=1 << 16)
    queue = ServeQueue(policy, tenancy=board)
    rng = np.random.default_rng(11)
    chunk = {t: rng.standard_normal((rows_per_chunk, 5)).astype(np.float32)
             for t in bundles}
    order = ["bulk", "lat-a", "lat-b"]

    def one_round():
        futs = []
        for t in order:
            reps = k_chunks * (skew if t == "bulk" else 1)
            futs += [queue.submit(bundles[t], chunk[t], tenant=t)
                     for _ in range(reps)]
        queue.flush()
        for f in futs:
            f.result(30)

    one_round()  # warmup: compiles land outside the measured rounds
    board_fresh = _tenant_board()
    queue.tenancy = board_fresh
    queue._batcher.tenancy = board_fresh
    for _ in range(rounds):
        one_round()
    return board_fresh.snapshot()


def tenant_check(fast=False, markdown=False):
    """Gate the multi-tenant control plane end to end.

    Three gates, per the control-plane contract:

      1. **isolation** — under :data:`TENANT_SKEW`x load skew toward the
         throughput tenant, no tenant's p99 may degrade more than
         :data:`TENANT_P99_MAX_RATIO`x vs the unskewed baseline on the
         same DRR scheduler;
      2. **zero drops** — every submitted request resolves in both runs
         (admission throttles at the door; it never loses work);
      3. **residency** — with the byte budget set so only
         ~:data:`TENANT_RESIDENCY_FIT` of 3 served bundles fit resident,
         the budget is never exceeded (peak watermark), at least one
         LRU eviction happens, and every evicted bundle serves again
         through the shared invalidate->reload path.
    """
    import tempfile

    from repro.core.engine import InferenceEngine
    from repro.serve import FlushPolicy, ServeQueue
    from repro.serve.residency import RESIDENCY

    tmp = pathlib.Path(tempfile.mkdtemp(prefix="tenant_bench_"))
    bundles = {t: _bundle(tmp / t) for t in ("lat-a", "bulk", "lat-b")}
    rounds = 8 if fast else 16

    base = _tenant_run(bundles, skew=1, rounds=rounds)
    skewed = _tenant_run(bundles, skew=TENANT_SKEW, rounds=rounds)

    results = []
    failures = []
    drops_total = 0
    for t in sorted(bundles):
        b99 = base[t]["latency_p99_ms"]
        s99 = skewed[t]["latency_p99_ms"]
        drops = base[t]["dropped_rows"] + skewed[t]["dropped_rows"]
        drops_total += drops
        ratio = (max(s99, TENANT_P99_FLOOR_MS)
                 / max(b99, TENANT_P99_FLOOR_MS))
        results.append({
            "tenant": t, "tier": base[t]["tier"],
            "weight": base[t]["weight"],
            "base_p99_ms": b99, "skew_p99_ms": s99, "p99_ratio": ratio,
            "served_rows_skew": skewed[t]["served_rows"],
            "occupancy_skew": skewed[t]["occupancy"],
            "dropped_rows": drops,
        })
        if ratio > TENANT_P99_MAX_RATIO:
            failures.append(
                f"tenant {t!r} p99 degraded {ratio:.2f}x under "
                f"{TENANT_SKEW}x skew ({b99:.2f}ms -> {s99:.2f}ms, "
                f"max {TENANT_P99_MAX_RATIO}x)")
    if drops_total:
        failures.append(f"{drops_total} rows dropped (must be zero)")

    # --- residency: 3 bundles served through a budget fitting ~2.5 ---
    InferenceEngine.invalidate()  # scenario-local byte accounting
    one = InferenceEngine.get(bundles["lat-a"]).resident_nbytes
    budget = int(one * TENANT_RESIDENCY_FIT)
    RESIDENCY.set_budget(budget)
    RESIDENCY.reset_stats()
    res_drops = 0
    try:
        for b in bundles.values():
            t = RESIDENCY.prefetch(b)  # admission-time warm
            if t is not None:
                t.join(30)
        board = _tenant_board()
        queue = ServeQueue(FlushPolicy(max_batch_rows=128,
                                       max_pending_rows=1 << 16),
                           tenancy=board)
        rng = np.random.default_rng(13)
        for _ in range(3):
            futs = [queue.submit(b, rng.standard_normal((8, 5))
                                 .astype(np.float32), tenant=t)
                    for t, b in bundles.items()]
            queue.flush()
            for f in futs:
                f.result(30)
        rsnap = RESIDENCY.snapshot()
        res_drops = sum(s["dropped_rows"]
                        for s in board.snapshot().values())
    finally:
        RESIDENCY.set_budget(None)
    if rsnap["peak_bytes"] > budget:
        failures.append(f"residency budget exceeded: peak "
                        f"{rsnap['peak_bytes']}B > budget {budget}B")
    if rsnap["evictions"] < 1:
        failures.append("residency never evicted despite 3 bundles over "
                        f"a {TENANT_RESIDENCY_FIT}-bundle budget")
    if res_drops:
        failures.append(f"residency phase dropped {res_drops} rows")

    residency = {"budget_bytes": budget, "peak_bytes": rsnap["peak_bytes"],
                 "evictions": rsnap["evictions"],
                 "prefetches": rsnap["prefetches"],
                 "resident_bundles": rsnap["resident_bundles"],
                 "bundle_bytes": one}
    if markdown:
        print(_tenant_markdown(results, residency))
    for r in results:
        print(f"[tenant {r['tenant']}] tier={r['tier']} "
              f"w={r['weight']:.0f} base_p99={r['base_p99_ms']:.2f}ms "
              f"skew_p99={r['skew_p99_ms']:.2f}ms "
              f"ratio={r['p99_ratio']:.2f} drops={r['dropped_rows']}",
              flush=True)
    print(f"[tenant residency] peak={residency['peak_bytes']}B "
          f"budget={budget}B evictions={residency['evictions']} "
          f"prefetches={residency['prefetches']}", flush=True)
    if failures:
        raise SystemExit("tenant gate FAILED: " + "; ".join(failures))
    print(f"[tenant gate] OK: {len(results)} tenants isolated under "
          f"{TENANT_SKEW}x skew, zero drops, residency within budget",
          flush=True)
    return {"tenants": results, "residency": residency,
            "skew": TENANT_SKEW, "rounds": rounds,
            "gate": {"p99_max_ratio": TENANT_P99_MAX_RATIO,
                     "worst_p99_ratio": max(r["p99_ratio"]
                                            for r in results)}}


def _tenant_markdown(results, residency):
    out = ["### Multi-tenant isolation "
           f"({TENANT_SKEW}x skew toward `bulk`)", "",
           "| tenant | tier | weight | base p99 | skewed p99 | ratio | "
           "drops |", "|---|---|---:|---:|---:|---:|---:|"]
    for r in results:
        out.append(f"| {r['tenant']} | {r['tier']} | {r['weight']:.0f} | "
                   f"{r['base_p99_ms']:.2f}ms | {r['skew_p99_ms']:.2f}ms | "
                   f"{r['p99_ratio']:.2f}x | {r['dropped_rows']} |")
    out += ["", f"Residency: peak {residency['peak_bytes']}B of "
            f"{residency['budget_bytes']}B budget "
            f"({residency['evictions']} evictions, "
            f"{residency['prefetches']} prefetches, "
            f"{residency['resident_bundles']} of 3 bundles resident)."]
    return "\n".join(out)


def _markdown(rows, model_err):
    kv = dict(item.split("=", 1) for item in rows[0][2].split(";"))
    out = ["### Serving throughput (8-device host mesh)", "",
           "| path | rows/s |", "|---|---:|",
           f"| per-call `MLRegion._infer` | {kv['percall_rows_s']} |",
           f"| coalesced `ServeQueue` | {kv['coalesced_rows_s']} |",
           f"| adaptive controller | {kv['adaptive_rows_s']} |",
           "", "### Measured vs roofline batch latency (adaptive queue)",
           "",
           "| bucket | batches | measured ms | roofline ms | error |",
           "|---:|---:|---:|---:|---:|"]
    for r in model_err:
        out.append(f"| {r['bucket']} | {r['batches']} | "
                   f"{r['measured_ms']:.3f} | {r['roofline_ms']:.3f} | "
                   f"{r['err_pct']:+.0f}% |")
    return "\n".join(out)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true",
                    help=f"fail unless coalesced >= {CHECK_SPEEDUP}x per-call"
                         " rows/s and outputs are bitwise equal")
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--markdown", action="store_true",
                    help="print markdown tables incl. the per-bucket "
                         "measured-vs-roofline latency error "
                         "(for EXPERIMENTS.md)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="run with tracing on, write the Chrome trace + "
                         "metrics snapshots to PATH(.metrics.json/.prom) "
                         "and fail unless every sampled request's spans "
                         f"cover >= {TRACE_MIN_COVERAGE:.0%} of its "
                         "enqueue->resolve latency")
    ap.add_argument("--overhead-check", action="store_true",
                    help="gate instrumentation cost: tracing on must "
                         f"retain >= {OVERHEAD_MIN_RATIO:.0%} of untraced "
                         "rows/s (interleaved-pair median ratio)")
    ap.add_argument("--shadow-check", action="store_true",
                    help="gate shadow-quality cost (sampling at "
                         f"{SHADOW_RATE:.0%} must retain >= "
                         f"{SHADOW_MIN_RATIO:.0%} of unsampled rows/s) and "
                         "prove injected weight corruption fires the "
                         "CRITICAL drift alert")
    ap.add_argument("--fault-check", action="store_true",
                    help="gate breaker idle cost (enabled must retain "
                         f">= {FAULT_IDLE_MIN_RATIO:.0%} of disabled "
                         "rows/s) and drive the full fault drill: "
                         "injected dispatch faults trip the breaker "
                         "OPEN, zero requests lost, recovery observable "
                         "on /metrics")
    ap.add_argument("--tenant-check", action="store_true",
                    help="gate the multi-tenant control plane: under "
                         f"{TENANT_SKEW}x load skew no tenant's p99 may "
                         f"degrade > {TENANT_P99_MAX_RATIO}x vs the "
                         "unskewed baseline, zero requests dropped, and "
                         "the residency byte budget is never exceeded "
                         "while serving more bundles than fit resident")
    args = ap.parse_args()
    if args.tenant_check:
        # self-contained scenario (own queues/bundles): run before the
        # throughput sweep so its latency windows see only tenant traffic
        payload = tenant_check(fast=args.fast, markdown=args.markdown)
        write_bench_json("tenant", payload)
        return
    if args.trace:
        from repro.obs import enable_tracing
        enable_tracing()
    rows, model_err = serving_throughput_full(fast=args.fast)
    if args.trace:
        export_trace(args.trace)
    if args.markdown:
        print(_markdown(rows, model_err))
    else:
        print("name,us_per_call,derived")
        for n, us, derived in rows:
            print(f"{n},{us:.2f},{derived}", flush=True)
    kv = dict(item.split("=", 1) for item in rows[0][2].split(";"))
    bench_json = {
        "rows_per_s": float(kv["coalesced_rows_s"]),
        "percall_rows_per_s": float(kv["percall_rows_s"]),
        "adaptive_rows_per_s": float(kv["adaptive_rows_s"]),
        "p50_ms": float(kv["p50_ms"]), "p99_ms": float(kv["p99_ms"]),
        "occupancy": float(kv["occupancy"]),
        "gate": {"speedup_x": float(kv["speedup_x"]),
                 "required_speedup_x": CHECK_SPEEDUP,
                 "bitwise_equal": kv["bitwise_equal"] == "True"},
    }
    if args.check:
        speedup = float(kv["speedup_x"])
        same = kv["bitwise_equal"] == "True"
        if speedup < CHECK_SPEEDUP or not same:
            write_bench_json("serve", bench_json)
            raise SystemExit(
                f"serving smoke FAILED: speedup_x={speedup:.2f} "
                f"(need >= {CHECK_SPEEDUP}) bitwise_equal={same}")
        print(f"[serve smoke] OK: {speedup:.2f}x coalesced over per-call")
    if args.overhead_check:
        bench_json["gate"]["trace_overhead_ratio"] = \
            overhead_check(fast=args.fast)
    if args.fault_check:
        fault_overhead_check(fast=args.fast)
        fault_drill_check()
    if args.shadow_check:
        bench_json["gate"]["shadow_overhead_ratio"] = \
            shadow_overhead_check(fast=args.fast)
        shadow_alert_check()
        if args.trace:
            # refresh the metrics snapshots so the exported artifacts
            # (and the CI quality report rendered from them) include the
            # shadow-quality families the checks just populated
            from repro.obs import default_registry
            path = pathlib.Path(args.trace)
            metrics = default_registry()
            path.with_suffix(".metrics.json").write_text(
                json.dumps(metrics.collect(), indent=1))
            path.with_suffix(".prom").write_text(metrics.dump())
    write_bench_json("serve", bench_json)


if __name__ == "__main__":
    main()
