"""Multi-tenant control plane: admission, fair share, QoS, residency.

The fairness and refill guarantees are *properties* (hypothesis-shim
driven): DRR must never starve a positive-weight tenant even when
capacity admits one key per round, and a token bucket's level between
takes must never decrease — whatever the clock does.  The residency
tests drive the eviction->reload path under real thread races: an
evicted bundle's next request must trigger exactly one reload, and no
reader may ever observe a torn (half-loaded) engine.
"""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.engine import InferenceEngine
from repro.nn import MLP
from repro.nn.serialize import save_model
from repro.serve import (RESIDENCY, FlushPolicy, ResidencyManager,
                         ServeQueue, TenantBoard, TenantSpec,
                         TenantThrottled)
from repro.serve.tenancy import DEFAULT_TENANT, DeficitRoundRobin, TokenBucket
from repro.tune import AdaptiveFlushController


@pytest.fixture(autouse=True)
def _clean_engine_state():
    InferenceEngine.invalidate()
    RESIDENCY.set_budget(None)
    yield
    InferenceEngine.invalidate()
    RESIDENCY.set_budget(None)
    RESIDENCY.reset_stats()


def _bundle(tmp, name="m"):
    net = MLP((1, 2), [8], 1)
    return save_model(tmp / name, net, net.init(jax.random.PRNGKey(0)))


def _rows(n, seed=0):
    return np.random.default_rng(seed).normal(size=(n, 2)).astype(np.float32)


# ------------------------------------------------------- token bucket ------
class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


@settings(max_examples=30)
@given(seed=st.integers(0, 10**6), rate=st.floats(0.5, 200.0),
       burst=st.floats(1.0, 100.0))
def test_token_bucket_refill_monotone(seed, rate, burst):
    """Between takes the level never decreases — even when the clock
    jitters backwards — and never exceeds the burst."""
    rng = np.random.default_rng(seed)
    clock = _FakeClock()
    b = TokenBucket(rate, burst, clock)
    b.take(burst)  # drain to 0 so refill has room to move
    prev = b.level()
    for _ in range(50):
        clock.t += float(rng.uniform(-0.05, 0.2))  # may step backwards
        lvl = b.level()
        assert lvl >= prev - 1e-9, "refill drained the bucket"
        assert lvl <= burst + 1e-9
        prev = lvl


def test_token_bucket_oversized_debt():
    """A request larger than the burst admits against a FULL bucket
    (driving the level negative) — otherwise it could never serve."""
    clock = _FakeClock()
    b = TokenBucket(10.0, 16.0, clock)
    assert b.take(64)          # full bucket: oversized admitted as debt
    assert b.level() < 0
    assert not b.take(1)       # in debt: nothing else admits
    clock.t += 1e9
    assert b.take(16)          # fully refilled (and capped at burst)


def test_token_bucket_throttles_then_refills():
    clock = _FakeClock()
    board = TenantBoard([TenantSpec("t", rate_rows_per_s=10.0,
                                    burst_rows=8)], clock=clock)
    board.admit("t", 8, block=False)
    with pytest.raises(TenantThrottled):
        board.admit("t", 8, block=False)
    clock.t += 0.8  # 8 rows of refill at 10 rows/s
    board.admit("t", 8, block=False)


# ---------------------------------------------------------- fair share -----
@settings(max_examples=25)
@given(nw=st.integers(2, 4), seed=st.integers(0, 10**6))
def test_drr_never_starves_positive_weight(nw, seed):
    """Worst case for fairness: every tenant permanently backlogged,
    capacity admits ONE key per round.  Every positive-weight tenant —
    however light — must keep getting served, at roughly its weight
    share."""
    rng = np.random.default_rng(seed)
    weights = {f"t{i}": float(rng.uniform(0.25, 4.0)) for i in range(nw)}
    rows = 64
    drr = DeficitRoundRobin(quantum_rows=float(rows))
    items = [(f"k{i}", t, rows) for i, t in enumerate(sorted(weights))]
    key_tenant = {k: t for k, t, _ in items}
    served = {t: 0 for t in weights}
    rounds = 400
    for _ in range(rounds):
        first = drr.order(items, weights)[0]
        drr.charge(key_tenant[first], rows)
        served[key_tenant[first]] += 1
    total_w = sum(weights.values())
    for t, w in weights.items():
        floor = max(1, int(rounds * w / total_w / 4))
        assert served[t] >= floor, (
            f"tenant {t} (weight {w:.2f}) served {served[t]}/{rounds} "
            f"rounds, below the {floor} fair-share floor: starved")


def test_drr_order_prefers_uncharged_tenant():
    drr = DeficitRoundRobin(quantum_rows=64.0)
    items = [("kh", "heavy", 48), ("kl", "light", 8)]
    weights = {"heavy": 1.0, "light": 1.0}
    drr.order(items, weights)
    drr.charge("heavy", 48)
    drr.charge("light", 8)
    assert drr.order(items, weights) == ["kl", "kh"]


def test_queue_flush_order_uses_drr_under_overload(tmp_path):
    board = TenantBoard([TenantSpec("heavy", weight=1.0),
                         TenantSpec("light", weight=1.0)])
    # max_batch_rows=48: each key stays below the inline-flush trigger,
    # but the 52 pending rows across >= 2 keys engage the DRR order
    queue = ServeQueue(FlushPolicy(max_batch_rows=48,
                                   max_pending_rows=1 << 16),
                       tenancy=board)
    kh, kl = _bundle(tmp_path, "h"), _bundle(tmp_path, "l")
    futs = [queue.submit(kh, _rows(22), tenant="heavy"),
            queue.submit(kh, _rows(22), tenant="heavy"),
            queue.submit(kl, _rows(8), tenant="light")]
    queue.flush()
    for f in futs:
        f.result(30)
    # round 1 charged heavy 44 vs light 8: round 2 must put light first
    queue.submit(kh, _rows(22), tenant="heavy")
    queue.submit(kh, _rows(22), tenant="heavy")
    f = queue.submit(kl, _rows(8), tenant="light")
    assert queue._flush_order() == [str(kl), str(kh)]
    queue.flush()
    f.result(30)
    snap = queue.snapshot()
    assert snap["tenants"]["light"]["served_rows"] == 16
    assert snap["tenants"]["heavy"]["served_rows"] == 88
    assert snap["tenants"]["light"]["dropped_rows"] == 0
    assert "residency" in snap
    queue.close()


# ----------------------------------------------------- board accounting ----
def test_board_backpressure_and_offenders():
    clock = _FakeClock()
    board = TenantBoard([TenantSpec("t", max_pending_rows=16)], clock=clock)
    board.on_enqueue("t", "k", 16)
    assert not board.has_room("t", 1)
    board.on_dispatch("t", 16)
    assert board.has_room("t", 16)
    # a tenant with nothing pending always admits (oversized batches
    # flush alone — same no-deadlock rule as the queue's global gate)
    assert board.has_room("t", 64)

    assert board.offenders() == []
    board.on_dropped("t", 1, 8)
    assert board.offenders() == ["t"]
    clock.t += TenantBoard.OFFENDER_WINDOW_S + 1
    assert board.offenders() == []  # old drops age out


def test_queue_tenant_offenders_surface(tmp_path):
    board = TenantBoard()
    queue = ServeQueue(FlushPolicy(max_batch_rows=64), tenancy=board)
    board.on_dropped("noisy", 1, 8)
    assert queue.tenant_offenders() == ["noisy"]
    queue.close()


def test_unknown_tenant_inherits_default_spec():
    board = TenantBoard(default_spec=TenantSpec(max_pending_rows=32))
    assert board.spec_for("newcomer").max_pending_rows == 32
    assert board.spec_for("newcomer").tenant == "newcomer"
    board.on_enqueue("newcomer", "k", 8)
    assert board.tenant_for_key("k") == "newcomer"
    assert board.tenant_for_key("unbound") == DEFAULT_TENANT


# ------------------------------------------------------------- QoS tiers ---
def test_controller_qos_tier_bounds():
    """A latency tenant's target CAPS the deadline; a throughput
    tenant's target RAISES the ceiling past the static policy."""
    board = TenantBoard([
        TenantSpec("rt", tier="latency", deadline_target_s=5e-4),
        TenantSpec("batch", tier="throughput", deadline_target_s=0.5),
    ])
    board.on_enqueue("rt", "k_rt", 8)
    board.on_enqueue("batch", "k_batch", 8)
    policy = FlushPolicy(max_delay_s=0.02)
    # huge widths: the unbounded service cap lands well above both the
    # static deadline and the latency target, so the tier bound is what
    # decides in each direction
    ctrl = AdaptiveFlushController(
        policy, widths_for=lambda key: [8, 8192, 8192, 8192, 4],
        service_factor=1e6, tenancy=board)
    d_rt = ctrl.delay_for("k_rt", None)
    d_batch = ctrl.delay_for("k_batch", None)
    assert d_rt <= 5e-4 + 1e-9
    assert d_batch > policy.max_delay_s  # raised past the static cap
    assert d_batch <= 0.5 + 1e-9
    assert ctrl.last_decision["k_rt"]["qos_tier"] == "latency"
    assert ctrl.last_decision["k_batch"]["qos_tier"] == "throughput"
    # unbound key: tier-free decision clamps to the static policy
    assert ctrl.delay_for("k_free", None) <= policy.max_delay_s + 1e-9
    assert ctrl.last_decision["k_free"]["qos_tier"] is None


def test_queue_wires_tenancy_into_controller(tmp_path):
    board = TenantBoard()
    ctrl = AdaptiveFlushController(widths_for=lambda key: [2, 8, 1])
    queue = ServeQueue(FlushPolicy(max_batch_rows=64), controller=ctrl,
                       tenancy=board)
    assert ctrl.tenancy is board
    assert queue._batcher.tenancy is board
    queue.close()


# ------------------------------------------------------------- residency ---
def test_residency_budget_evicts_lru():
    r = ResidencyManager(budget_bytes=100)
    assert r.note_load("a", 60) == []
    assert r.note_load("b", 60) == ["a"]          # LRU out, never self
    assert r.resident_bytes() == 60
    assert r.peak_bytes <= 100                     # never over budget
    assert r.note_load("huge", 500) == ["b"]       # oversized still loads
    assert r.resident() == {"huge": 500}
    r.drop("huge")
    r.drop("huge")                                 # idempotent
    assert r.resident_bytes() == 0
    assert r.snapshot()["evictions"] == 2


def test_residency_touch_refreshes_lru():
    r = ResidencyManager(budget_bytes=120)
    r.note_load("a", 50)
    r.note_load("b", 50)
    r.touch("a")                                   # a is now MRU
    assert r.note_load("c", 50) == ["b"]


def test_evicted_bundle_reloads_exactly_once(tmp_path, monkeypatch):
    """3 threads race the first request after an eviction: the engine
    cache lock must admit exactly ONE reload, and every thread must see
    the fully-loaded engine (outputs identical to pre-eviction)."""
    mp = _bundle(tmp_path)
    x = jnp.asarray(_rows(16))
    y_ref = np.asarray(InferenceEngine.get(mp).apply_batched(x))

    loads = []
    lock = threading.Lock()
    orig = InferenceEngine._load

    def counted(self):
        with lock:
            loads.append(self.path)
        return orig(self)

    monkeypatch.setattr(InferenceEngine, "_load", counted)
    InferenceEngine.invalidate(mp)  # the eviction (same path the
    loads.clear()                   # residency manager's victims take)

    barrier = threading.Barrier(3)
    outs, errs = [], []

    def request():
        try:
            barrier.wait(10)
            outs.append(np.asarray(InferenceEngine.get(mp)
                                   .apply_batched(x)))
        except Exception as exc:  # pragma: no cover - diagnostic
            errs.append(exc)

    threads = [threading.Thread(target=request) for _ in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    assert not errs
    assert len(outs) == 3
    assert loads.count(str(mp)) == 1, (
        f"{loads.count(str(mp))} reloads for one eviction")
    for y in outs:
        np.testing.assert_array_equal(y, y_ref)


def test_no_torn_reads_under_concurrent_submit_and_evict(tmp_path):
    """3 request threads hammer get()+apply while the main thread keeps
    evicting: every single response must be bit-identical to the
    reference — a torn (half-loaded) engine read would differ or raise."""
    mp = _bundle(tmp_path)
    x = jnp.asarray(_rows(16))
    y_ref = np.asarray(InferenceEngine.get(mp).apply_batched(x))
    stop = threading.Event()
    errs = []

    def hammer():
        try:
            while not stop.is_set():
                y = np.asarray(InferenceEngine.get(mp).apply_batched(x))
                if not np.array_equal(y, y_ref):
                    errs.append("torn read: output mismatch")
                    return
        except Exception as exc:
            errs.append(repr(exc))

    threads = [threading.Thread(target=hammer) for _ in range(3)]
    for t in threads:
        t.start()
    for _ in range(20):
        InferenceEngine.invalidate(mp)
    stop.set()
    for t in threads:
        t.join(30)
    assert not errs, errs[:3]


def test_residency_prefetch_warms_bundle(tmp_path):
    mp = _bundle(tmp_path)
    t = RESIDENCY.prefetch(mp)
    assert t is not None
    t.join(30)
    assert str(mp) in RESIDENCY.resident()
    assert RESIDENCY.prefetch(mp) is None  # already resident: no-op


# ----------------------------------------------------- end-to-end submit ---
@pytest.mark.parametrize("route", ["flush", "pod_flush"])
def test_tenant_submit_roundtrip_and_latency_accounting(tmp_path, route):
    """The outcomes land on the tenants through either entry point of the
    batcher's one batch routine (a single-process pod_flush included)."""
    board = TenantBoard([TenantSpec("a", tier="latency", weight=2.0),
                         TenantSpec("b")])
    queue = ServeQueue(FlushPolicy(max_batch_rows=256), tenancy=board)
    mp = _bundle(tmp_path)
    fa = queue.submit(mp, _rows(8, seed=1), tenant="a")
    fb = queue.submit(mp, _rows(8, seed=2), tenant="b")
    getattr(queue, route)()
    fa.result(30), fb.result(30)
    snap = board.snapshot()
    # the second submit rebinds the shared key, so request->tenant
    # attribution (not key binding) must drive the served accounting
    assert snap["a"]["served_rows"] == 8
    assert snap["b"]["served_rows"] == 8
    assert snap["a"]["pending_rows"] == 0
    assert snap["a"]["latency_p99_ms"] > 0.0
    assert abs(sum(s["occupancy"] for s in snap.values()) - 1.0) < 1e-9
    queue.close()
