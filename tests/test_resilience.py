"""Failure-path coverage: fault injection, retry/split, breaker,
dead-dispatcher, close(), and pod dropout (single-process harness).

The spawned 2-process host-drop drill lives at the bottom under the
``slow`` marker (the multihost/chaos CI lanes run it).
"""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import approx_ml, tensor_functor
from repro.launch import multihost
from repro.nn import MLP
from repro.core.region import Landing
from repro.nn.serialize import save_model
from repro.obs import default_registry
from repro.obs.quality import SHADOW
from repro.resilience import (BREAKERS, FAULTS, BreakerPolicy,
                              CircuitBreaker, FaultInjector, InjectedFault,
                              RetryPolicy, parse_plan)
from repro.resilience.breaker import CLOSED, HALF_OPEN, OPEN
from repro.serve import FlushPolicy, ServeQueue
from repro.serve.batcher import Batcher, NonFiniteOutput
from repro.serve.queue import ServeFuture, _StatsGate


@pytest.fixture(autouse=True)
def _clean_globals():
    """Every test starts from quiet process-wide resilience state."""
    FAULTS.clear()
    BREAKERS.reset()
    BREAKERS.enabled = True
    SHADOW.reset()
    multihost.POD_HEALTH.reset()
    yield
    FAULTS.clear()
    BREAKERS.reset()
    BREAKERS.enabled = True
    SHADOW.reset()
    multihost.POD_HEALTH.reset()


# ------------------------------------------------------------- helpers -----
_ifn = tensor_functor("rin: [i, 0:2] = ([i, 0:2])")
_ofn = tensor_functor("rout: [i, 0:1] = ([i, 0:1])")


def _bundle(tmp, name="m"):
    net = MLP((1, 2), [8], 1)
    return save_model(tmp / name, net, net.init(jax.random.PRNGKey(0)))


def _region(n, mode, model, serving=None):
    rngs = {"i": (0, n)}
    return approx_ml(lambda x: {"out": x[:, :1] * 2 + x[:, 1:] * 0.5},
                     name="res", inputs={"x": (_ifn, rngs)},
                     outputs={"out": (_ofn, rngs)},
                     mode=mode, model=model, serving=serving)


def _rows(n, seed=0):
    return np.random.default_rng(seed).normal(size=(n, 2)).astype(np.float32)


class _StubEngine:
    """Row-wise fake engine: y = 2x (first feature), with scriptable
    failures so dispatch retry/split paths can be driven exactly."""

    def __init__(self, fail_first=0, poison_value=None):
        self.fail_first = fail_first
        self.poison_value = poison_value
        self.calls = 0

    def apply_batched(self, x, **kw):
        self.calls += 1
        xh = np.asarray(x)
        if self.calls <= self.fail_first:
            raise RuntimeError("transient stub failure")
        if self.poison_value is not None and \
                np.any(xh == self.poison_value):
            raise RuntimeError("poisoned row in batch")
        return jnp.asarray(xh[:, :1] * 2.0)


def _queue(engine, *, attempts=1, **pol):
    pol.setdefault("max_batch_rows", 1 << 30)
    b = Batcher(engine_for=lambda key: engine,
                retry=RetryPolicy(max_attempts=attempts, base_delay_s=0.0,
                                  max_delay_s=0.0, jitter=0.0))
    return ServeQueue(FlushPolicy(**pol), batcher=b)


# ---------------------------------------------------------- fault plans ----
def test_fault_plan_parse_and_validation():
    rules = parse_plan("engine.apply:raise:after=2,n=1;"
                       "pod.flush:drop:pid=1,stall=9")
    assert len(rules) == 2
    assert rules[0].site == "engine.apply" and rules[0].after == 2
    assert rules[1].mode == "drop" and rules[1].stall_s == 9.0
    with pytest.raises(ValueError):
        parse_plan("nosite:raise")
    with pytest.raises(ValueError):
        parse_plan("engine.apply:nomode")
    with pytest.raises(ValueError):
        parse_plan("engine.apply")
    with pytest.raises(ValueError):
        parse_plan("engine.apply:raise:badparam")


def test_fault_triggers_after_every_n():
    f = FaultInjector("engine.apply:raise:after=2,every=2,n=2")
    fired = []
    for i in range(10):
        try:
            f.fire("engine.apply")
        except InjectedFault:
            fired.append(i)
    # calls 0,1 skipped (after=2); then every 2nd matching call, max 2
    assert fired == [2, 4]


def test_fault_probability_is_seed_deterministic():
    def pattern():
        f = FaultInjector("engine.apply:raise:p=0.5,seed=7")
        out = []
        for _ in range(32):
            try:
                f.fire("engine.apply")
                out.append(0)
            except InjectedFault:
                out.append(1)
        return out

    a, b = pattern(), pattern()
    assert a == b and 0 < sum(a) < 32


def test_fault_pid_and_key_scoping(monkeypatch):
    f = FaultInjector("engine.apply:raise:pid=1;batcher.scatter:nan:key=abc")
    # no REPRO_PROCESS_ID in env: pid-scoped rule never matches
    monkeypatch.delenv("REPRO_PROCESS_ID", raising=False)
    assert f.fire("engine.apply") is None
    monkeypatch.setenv("REPRO_PROCESS_ID", "1")
    with pytest.raises(InjectedFault):
        f.fire("engine.apply")
    assert f.fire("batcher.scatter", key="zzz") is None
    rule = f.fire("batcher.scatter", key="x/abc/y")
    assert rule is not None and rule.mode == "nan"


def test_fault_stall_sleeps():
    f = FaultInjector("engine.apply:stall:stall=0.05,n=1")
    t0 = time.monotonic()
    rule = f.fire("engine.apply")
    assert rule is not None and time.monotonic() - t0 >= 0.05


def test_retry_policy_backoff_caps():
    p = RetryPolicy(max_attempts=5, base_delay_s=0.01, max_delay_s=0.04,
                    jitter=0.0)
    assert p.delay_for(0) == 0.01
    assert p.delay_for(1) == 0.02
    assert p.delay_for(10) == 0.04  # capped
    j = RetryPolicy(jitter=0.5, seed=1)
    d = [j.delay_for(0) for _ in range(8)]
    assert all(0.005 <= x <= 0.01 for x in d)


# -------------------------------------------------------- dispatch paths ---
def test_retry_resolves_transient_failure():
    eng = _StubEngine(fail_first=2)
    q = _queue(eng, attempts=3)
    x = _rows(4)
    fut = q.submit("k", x)
    q.flush("k")
    np.testing.assert_allclose(np.asarray(fut.result(5)), x[:, :1] * 2.0,
                               rtol=1e-6)
    assert eng.calls == 3  # two transient failures, one success
    snap = q.stats("k").snapshot()
    assert snap["batches"] == 1 and snap["batches_failed"] == 0


def test_split_retry_isolates_poisoned_request():
    eng = _StubEngine(poison_value=np.float32(666.0))
    q = _queue(eng, attempts=1)
    good_a, good_b = _rows(3, seed=1), _rows(2, seed=2)
    poison = _rows(3, seed=3)
    poison[1, 0] = 666.0
    fa = q.submit("k", good_a)
    fp = q.submit("k", poison)
    fb = q.submit("k", good_b)
    q.flush("k")
    # siblings of the poisoned request still get exact results
    np.testing.assert_allclose(np.asarray(fa.result(5)),
                               good_a[:, :1] * 2.0, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(fb.result(5)),
                               good_b[:, :1] * 2.0, rtol=1e-6)
    with pytest.raises(RuntimeError, match="poisoned"):
        fp.result(5)
    snap = q.stats("k").snapshot()
    assert snap["requests_failed"] == 1 and snap["rows_failed"] == 3
    assert q.depth("k") == 0


def test_engine_load_failure_fails_batch_once_no_retry():
    calls = []

    def engine_for(key):
        calls.append(key)
        raise FileNotFoundError("no bundle")

    b = Batcher(engine_for=engine_for,
                retry=RetryPolicy(max_attempts=3, base_delay_s=0.0))
    q = ServeQueue(FlushPolicy(max_batch_rows=1 << 30), batcher=b)
    f1, f2 = q.submit("k", _rows(2)), q.submit("k", _rows(2, 1))
    q.flush("k")
    for f in (f1, f2):
        with pytest.raises(FileNotFoundError):
            f.result(5)
    # deterministic load failure: exactly one engine resolve, one failed
    # batch — no retry, no split
    assert len(calls) == 1
    assert q.stats("k").snapshot()["batches_failed"] == 1


# the batcher's two entry points share one batch routine: a local flush
# and a single-process collective one
ROUTES = {"flush": lambda q, key: q.flush(key),
          "pod_flush": lambda q, key: q.pod_flush(key)}


def _lands(key: str) -> dict:
    snap = default_registry().collect().get("repro_batch_land_total",
                                            {"values": []})
    return {v["labels"]["route"]: v["value"] for v in snap["values"]
            if v["labels"]["key"] == key}


# each entry point, with host rows and (equal requests that ask for
# device rows) with rows landed on the device
@pytest.mark.parametrize("route,device", [
    pytest.param(r, d, id=r + ("-device" if d else ""))
    for d in (False, True) for r in sorted(ROUTES)])
def test_nonfinite_screening_isolates_poisoned_request(route, device):
    eng = _StubEngine()
    q = _queue(eng)
    FAULTS.configure("batcher.scatter:nan:n=1")
    land = Landing(jax.devices()[0], False, (3, 1), None) if device \
        else None
    xa, xb = _rows(3, seed=4), _rows(3 if device else 2, seed=5)
    before = _lands("k")
    fa = q.submit("k", xa, land=land)
    fb = q.submit("k", xb, land=land)
    ROUTES[route](q, "k")
    after = _lands("k")
    assert {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)} == {"device" if device else "host": 1}
    # the injected NaN lands on the first request's rows only
    with pytest.raises(NonFiniteOutput):
        fa.result(5)
    got = fb.result(5)
    assert isinstance(got, jax.Array if device else np.ndarray)
    np.testing.assert_allclose(np.asarray(got), xb[:, :1] * 2.0, rtol=1e-6)
    snap = q.stats("k").snapshot()
    assert snap["requests_failed"] == 1 and snap["rows_failed"] == 3
    assert snap["batches"] == 1  # the clean remainder still counts


def test_nonfinite_never_silently_returned():
    class _NaNEngine(_StubEngine):
        def apply_batched(self, x, **kw):
            return jnp.full((np.asarray(x).shape[0], 1), np.nan,
                            jnp.float32)

    q = _queue(_NaNEngine())
    f = q.submit("k", _rows(2))
    q.flush("k")
    with pytest.raises(NonFiniteOutput):
        f.result(5)


# ------------------------------------------------------- dead dispatcher ---
def test_dispatcher_crash_fails_pending_futures_fast(monkeypatch):
    # max_delay_s set: result() trusts the dispatcher thread instead of
    # flushing on demand, so the crash handler resolves the future
    q = ServeQueue(FlushPolicy(max_batch_rows=4, max_delay_s=60.0,
                               block_timeout_s=60.0))

    def boom():
        raise RuntimeError("boom")

    q.start()
    assert q.healthy()
    time.sleep(0.2)  # let the thread reach its idle cv.wait first
    monkeypatch.setattr(q, "_due_locked", boom)
    # the dying thread re-raises on purpose (traceback to stderr); keep
    # pytest's thread-exception reporter from flagging the expected one
    monkeypatch.setattr(threading, "excepthook", lambda _a: None)
    t0 = time.monotonic()
    fut = q.submit("k", _rows(4))  # max-batch notify wakes the thread
    with pytest.raises(RuntimeError, match="dispatcher thread died"):
        fut.result(10)
    # failed immediately by the crash handler, not by block_timeout_s
    assert time.monotonic() - t0 < 5.0
    assert not q.healthy()
    assert q.liveness()["crashed"] is not None
    with pytest.raises(RuntimeError, match="dispatcher thread died"):
        q.submit("k", _rows(1))
    assert q.depth() == 0
    assert q.stats("k").snapshot()["requests_failed"] == 1


# ------------------------------------------------------------- close() -----
def test_close_drain_serves_pending_then_refuses():
    eng = _StubEngine()
    q = _queue(eng)
    x = _rows(3)
    fut = q.submit("k", x)
    q.close(drain=True)
    np.testing.assert_allclose(np.asarray(fut.result(5)), x[:, :1] * 2.0,
                               rtol=1e-6)
    with pytest.raises(RuntimeError, match="closed"):
        q.submit("k", _rows(1))
    q.close(drain=True)  # idempotent
    # shadow worker is stopped (it restarts lazily if re-enabled later)
    t = SHADOW._thread
    assert t is None or not t.is_alive()


def test_close_no_drain_fails_pending():
    q = _queue(_StubEngine())
    fut = q.submit("k", _rows(2))
    q.close(drain=False)
    with pytest.raises(RuntimeError, match="closed"):
        fut.result(5)
    assert q.depth() == 0


# ----------------------------------------------------------- the breaker ---
def _breaker(clock, **kw):
    kw.setdefault("min_samples", 4)
    kw.setdefault("failure_threshold", 0.5)
    kw.setdefault("open_cooldown_s", 1.0)
    kw.setdefault("probe_n", 2)
    kw.setdefault("probe_every", 2)
    return CircuitBreaker("b", BreakerPolicy(**kw), clock=clock)


def test_breaker_full_cycle():
    now = [0.0]
    b = _breaker(lambda: now[0])
    assert b.state == CLOSED and b.allow()
    for _ in range(6):
        b.record_failure()
    assert b.state == OPEN
    assert not b.allow()  # cooldown not elapsed
    now[0] += 1.5
    assert b.allow()  # first probe admits
    assert b.state == HALF_OPEN
    b.record_success()
    b.record_success()
    assert b.state == CLOSED
    # hysteresis: the EWMA was reset — one failure cannot re-trip
    b.record_failure()
    assert b.state == CLOSED


def test_breaker_probe_failure_reopens_and_restamps():
    now = [0.0]
    b = _breaker(lambda: now[0])
    for _ in range(6):
        b.record_failure()
    now[0] += 1.5
    assert b.allow() and b.state == HALF_OPEN
    b.record_failure()
    assert b.state == OPEN
    # re-stamped: the cooldown starts over from the probe failure
    now[0] += 0.5
    assert not b.allow()
    now[0] += 1.0
    assert b.allow() and b.state == HALF_OPEN


def test_breaker_half_open_throttles_traffic():
    now = [0.0]
    b = _breaker(lambda: now[0], probe_every=4)
    for _ in range(6):
        b.record_failure()
    now[0] += 1.5
    admitted = [b.allow() for _ in range(9)]
    # first probe + every 4th thereafter; the rest is turned away
    assert sum(admitted) == 3


def test_breaker_quality_critical_trips_closed_breaker():
    key = "qkey"
    b = BREAKERS.configure(key, BreakerPolicy(open_cooldown_s=60.0))
    SHADOW.set_budget(key, 0.01)
    for _ in range(5):  # hysteresis needs breach_n consecutive breaches
        SHADOW.observe(key, rmse=1.0)
    assert SHADOW.state(key) == "CRITICAL"
    assert not BREAKERS.allow(key)
    assert b.state == OPEN


def test_breaker_board_disabled_is_transparent():
    BREAKERS.enabled = False
    for _ in range(32):
        BREAKERS.record_failure("x")
    assert BREAKERS.allow("x")
    assert BREAKERS.snapshot() == {}


@settings(max_examples=30)
@given(stream=st.integers(min_value=0, max_value=2 ** 20 - 1),
       threshold=st.floats(min_value=0.3, max_value=0.7))
def test_breaker_never_flaps_at_trip_threshold(stream, threshold):
    """Property: with a frozen clock, any outcome stream trips at most
    once (OPEN is absorbing until the cooldown elapses), the breaker
    never jumps OPEN->CLOSED directly, and consecutive closes/trips are
    separated by >= min_samples fresh observations."""
    policy = BreakerPolicy(failure_threshold=threshold, min_samples=4,
                           open_cooldown_s=1.0, probe_n=2, probe_every=2)
    b = CircuitBreaker("p", policy, clock=lambda: 0.0)
    trips, prev = 0, b.state
    obs_since_closed = 0
    for i in range(20):
        bit = (stream >> i) & 1
        b.allow()
        if bit:
            b.record_failure()
        else:
            b.record_success()
        cur = b.state
        if prev == CLOSED:
            obs_since_closed += 1
        assert not (prev == OPEN and cur == CLOSED)
        if prev == CLOSED and cur == OPEN:
            trips += 1
            assert obs_since_closed >= policy.min_samples
        prev = cur
    assert trips <= 1  # frozen clock: OPEN can never even reach HALF_OPEN


@settings(max_examples=20)
@given(steps=st.integers(min_value=1, max_value=40),
       dt=st.floats(min_value=0.01, max_value=0.5))
def test_breaker_reopen_rate_bounded_by_cooldown(steps, dt):
    """Advancing clock: OPEN->HALF_OPEN transitions are bounded by
    elapsed/cooldown + 1 — the breaker cannot probe-flap faster than its
    cooldown no matter how adversarial the traffic."""
    now = [0.0]
    b = _breaker(lambda: now[0], open_cooldown_s=1.0)
    for _ in range(6):
        b.record_failure()
    half_opens = 0
    for _ in range(steps):
        now[0] += dt
        prev = b.state
        b.allow()
        if prev == OPEN and b.state == HALF_OPEN:
            half_opens += 1
        b.record_failure()  # worst case: every probe fails, re-opens
    assert half_opens <= now[0] / 1.0 + 1


# ------------------------------------------------------ region fallback ----
def test_region_infer_falls_back_when_breaker_open(tmp_path):
    bundle = str(_bundle(tmp_path))
    b = BREAKERS.configure(bundle, BreakerPolicy(min_samples=2,
                                                 open_cooldown_s=60.0))
    n = 4
    region = _region(n, "infer", bundle)
    x = _rows(n, seed=7)
    surrogate = np.asarray(region(x=x)["out"])
    b.record_failure()
    b.record_failure()
    assert b.state == OPEN
    out = np.asarray(region(x=x)["out"])
    accurate = x[:, :1] * 2 + x[:, 1:] * 0.5
    np.testing.assert_allclose(out, accurate, rtol=1e-6)
    assert not np.allclose(out, surrogate)  # it really switched paths


def test_region_infer_async_falls_back_when_breaker_open(tmp_path):
    bundle = str(_bundle(tmp_path))
    b = BREAKERS.configure(bundle, BreakerPolicy(min_samples=2,
                                                 open_cooldown_s=60.0))
    b.record_failure()
    b.record_failure()
    n = 3
    q = ServeQueue(FlushPolicy(max_batch_rows=1 << 30))
    region = _region(n, "infer_async", bundle, serving=q)
    x = _rows(n, seed=8)
    res = region(x=x)
    assert not res.deferred()  # resolved through the accurate path
    np.testing.assert_allclose(np.asarray(res.result()["out"]),
                               x[:, :1] * 2 + x[:, 1:] * 0.5, rtol=1e-6)
    assert q.depth() == 0  # nothing ever hit the queue


def test_async_result_falls_back_on_dispatch_failure(tmp_path):
    bundle = str(_bundle(tmp_path))
    b = Batcher(engine_for=lambda key: (_ for _ in ()).throw(
                    RuntimeError("engine down")),
                retry=RetryPolicy(max_attempts=1, base_delay_s=0.0))
    q = ServeQueue(FlushPolicy(max_batch_rows=1 << 30), batcher=b)
    n = 3
    region = _region(n, "infer_async", bundle, serving=q)
    x = _rows(n, seed=9)
    res = region(x=x)
    assert res.deferred()
    q.flush()  # dispatch fails; the future carries the exception
    out = np.asarray(res.result(5)["out"])  # ...but result() degrades
    np.testing.assert_allclose(out, x[:, :1] * 2 + x[:, 1:] * 0.5,
                               rtol=1e-6)


# ------------------------------------------------------- future / gates ----
def test_serve_future_first_resolution_wins():
    q = ServeQueue(FlushPolicy())
    f = ServeFuture(q, "k")
    assert f.set_result(np.ones(2))
    assert not f.set_exception(RuntimeError("late loser"))
    np.testing.assert_array_equal(f.result(1), np.ones(2))
    g = ServeFuture(q, "k")
    assert g.set_exception(RuntimeError("first"))
    assert not g.set_result(np.ones(2))
    with pytest.raises(RuntimeError, match="first"):
        g.result(1)


def test_stats_gate_kill_suppresses_zombie_delivery():
    class _Rec:
        def __init__(self):
            self.batches, self.failures = [], []

        def on_batch(self, **kw):
            self.batches.append(kw)

        def on_failure(self, **kw):
            self.failures.append(kw)

    rec = _Rec()
    gate = _StatsGate(rec)
    assert gate.kill()  # nothing delivered yet: watchdog takes over
    gate.on_batch(rows=4)
    gate.on_failure(rows=4)
    assert rec.batches == [] and rec.failures == []
    live = _StatsGate(rec)
    live.on_batch(rows=2)
    assert not live.kill()  # delivered: the round completed
    assert len(rec.batches) == 1


# ------------------------------------------------------------ pod health ---
def test_pod_health_rounds_and_degrade():
    h = multihost.PodHealth()
    assert h.beat() == 1 and h.beat() == 2
    assert h.check_round(1) == ()  # no KV client solo: name nobody
    h.mark_degraded([2, 1])
    h.mark_degraded([1])
    snap = h.snapshot()
    assert snap["degraded"] and snap["offenders"] == [1, 2]


def test_pod_health_rejoin_with_stub_barrier():
    h = multihost.PodHealth()
    h.mark_degraded([1])
    assert not h.try_rejoin(timeout_s=0.2,
                            barrier_fn=lambda: time.sleep(5))  # hangs
    assert h.degraded
    fails = lambda: (_ for _ in ()).throw(RuntimeError("peer gone"))
    assert not h.try_rejoin(timeout_s=1.0, barrier_fn=fails)
    assert h.degraded
    assert h.try_rejoin(timeout_s=1.0, barrier_fn=lambda: None)
    assert not h.degraded and h.offenders == ()


def test_healthz_names_pod_offenders():
    from repro.obs.server import ObsServer
    multihost.POD_HEALTH.mark_degraded([1])
    ready, detail = ObsServer().health()
    assert not ready
    assert "pod:host-1" in detail["critical"]
    multihost.POD_HEALTH.reset()
    ready, detail = ObsServer().health()
    assert "pod:host-1" not in detail["critical"]


def test_pod_flush_watchdog_degrades_instead_of_hanging(monkeypatch):
    """Single-process harness for the watchdog: dispatch_pod hangs (a
    'dropped peer'), the flush must degrade within the timeout and still
    serve every request locally."""
    eng = _StubEngine()

    class _HangingBatcher(Batcher):
        def dispatch_pod(self, key, requests, stats, *, ctx=None,
                         reason="pod"):
            time.sleep(30.0)

    b = _HangingBatcher(engine_for=lambda key: eng,
                        retry=RetryPolicy(max_attempts=1))
    q = ServeQueue(FlushPolicy(max_batch_rows=1 << 30), batcher=b)
    monkeypatch.setattr(multihost, "is_multiprocess", lambda: True)
    monkeypatch.setenv(multihost.ENV_POD_WATCHDOG, "0.3")
    x = _rows(4, seed=11)
    fut = q.submit("k", x)
    t0 = time.monotonic()
    q.pod_flush("k")
    assert time.monotonic() - t0 < 5.0  # degraded, did not wait 30s
    np.testing.assert_allclose(np.asarray(fut.result(5)), x[:, :1] * 2.0,
                               rtol=1e-6)
    assert multihost.POD_HEALTH.degraded
    # while degraded, later flushes skip the collective entirely
    fut2 = q.submit("k", x)
    t0 = time.monotonic()
    q.pod_flush("k")
    assert time.monotonic() - t0 < 1.0
    assert fut2.done()


# ---------------------------------------------------- spawned pod drill ----
@pytest.mark.slow
def test_host_drop_drill_two_processes():
    multihost.run_host_drop_drill(processes=2, devices_per_host=2,
                                  stall_s=15.0, watchdog_s=2.0)
