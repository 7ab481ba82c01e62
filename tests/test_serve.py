"""repro.serve: queue/batcher flush policies, bucket padding round-trip,
multiplexed regions, deadline determinism, stats, backpressure — plus the
engine's bucketed apply + sharding-resolution cache it rides on."""
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps import binomial, miniweather
from repro.core import approx_ml, tensor_functor
from repro.core.engine import InferenceEngine
from repro.dist.sharding import ShardCtx, use_mesh
from repro.launch.mesh import make_local_mesh
from repro.nn import MLP
from repro.nn.layers import Activation, Conv2D, Sequential
from repro.nn.serialize import save_model
from repro.serve import (Backpressure, FlushPolicy, ServeQueue, bucket_size)

_ifn = tensor_functor("sin: [i, 0:2] = ([i, 0:2])")
_ofn = tensor_functor("sout: [i, 0:1] = ([i, 0:1])")


def _lin_bundle(tmp, name="m", seed=0, hidden=16):
    """Untrained MLP bundle: serving semantics don't need accuracy."""
    net = MLP((1, 2), [hidden], 1)
    params = net.init(jax.random.PRNGKey(seed))
    return save_model(tmp / name, net, params)


def _region(n, mode, model, serving=None):
    rngs = {"i": (0, n)}
    return approx_ml(lambda x: {"out": x[:, :1] * 2 + x[:, 1:] * 0.5},
                     name="lin", inputs={"x": (_ifn, rngs)},
                     outputs={"out": (_ofn, rngs)},
                     mode=mode, model=model, serving=serving)


def _rows(n, seed=0):
    return jnp.asarray(np.random.default_rng(seed)
                       .normal(size=(n, 2)).astype(np.float32))


# ------------------------------------------------------------- buckets -----
def test_bucket_size_pow2_and_min():
    assert bucket_size(1) == 8
    assert bucket_size(8) == 8
    assert bucket_size(9) == 16
    assert bucket_size(100) == 128
    assert bucket_size(3, min_bucket=2) == 4
    assert bucket_size(0, min_bucket=1) == 1


def test_bucket_for_respects_data_shard_count():
    from repro.serve import bucket_for
    # no mesh: plain power-of-two behavior
    assert bucket_for(6, 8, 1) == 8
    # 16 data shards: a small batch must not shrink below the shard
    # count or spec_for drops the data axis and the batch replicates
    assert bucket_for(6, 8, 16) == 16
    assert bucket_for(20, 8, 16) == 32
    # non-power-of-two shard counts still divide the bucket
    assert bucket_for(6, 8, 6) == 12
    assert bucket_for(13, 8, 6) == 18
    assert all(bucket_for(n, 8, 6) % 6 == 0 for n in range(1, 50))


def test_bucket_size_edges():
    from repro.serve import bucket_for
    # n=0: the floor governs (a zero-row dispatch never happens, but the
    # controller's target math must not blow up on it)
    assert bucket_size(0) == 8
    assert bucket_for(0, 8, 16) == 16
    # n just past a power of two: next bucket, not the same one
    assert bucket_size(9) == 16
    assert bucket_size(129) == 256
    assert bucket_size(1025) == 2048
    assert bucket_for(257, 8, 8) == 512


def test_bucket_for_more_shards_than_rows():
    from repro.serve import bucket_for
    # n_shards > n: the bucket must still cover every shard, or the
    # data axis silently drops to replication
    assert bucket_for(3, 8, 16) == 16
    assert bucket_for(1, 2, 6) == 6
    assert bucket_for(5, 2, 6) == 6
    for n in range(1, 8):
        b = bucket_for(n, 2, 12)
        assert b >= 12 and b % 12 == 0


def test_deadline_flush_under_concurrent_submitters(tmp_path):
    """Many threads race the dispatcher's deadline: every future must
    resolve exactly once, with totals consistent and rows bit-identical
    to a synchronous engine call (the corner the adaptive controller
    leans on — per-key deadlines recomputed while submits keep landing).
    """
    import threading
    mp = _lin_bundle(tmp_path, "conc")
    eng = InferenceEngine.get(mp)
    q = ServeQueue(FlushPolicy(max_batch_rows=10 ** 6, max_delay_s=0.01,
                               max_pending_rows=10 ** 6))
    results, errors = {}, []

    def submitter(tid):
        try:
            for i in range(4):
                x = _rows(3, seed=100 * tid + i)
                f = q.submit(mp, x)
                results[(tid, i)] = (x, f)
                time.sleep(0.003)
        except Exception as e:  # pragma: no cover - failure reporting
            errors.append(e)

    with q:
        threads = [threading.Thread(target=submitter, args=(t,))
                   for t in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        outs = {k: (x, np.asarray(f.result(timeout=10)))
                for k, (x, f) in results.items()}
    assert not errors
    assert len(outs) == 32
    for x, y in outs.values():
        np.testing.assert_array_equal(y, np.asarray(eng(x)))
    st = q.stats(mp).snapshot()
    assert st["rows_completed"] == st["rows_enqueued"] == 96
    assert st["requests_completed"] == 32 and st["requests_failed"] == 0
    assert st["queue_depth_rows"] == 0 and st["queue_depth_requests"] == 0
    assert st["flush_reasons"].get("deadline", 0) >= 1
    assert st["arrival_rate_rows_s"] > 0


def test_apply_batched_matches_call_and_pads(tmp_path):
    mp = _lin_bundle(tmp_path)
    eng = InferenceEngine.get(mp)
    x = _rows(13)
    direct = np.asarray(eng(x))
    batched = np.asarray(eng.apply_batched(x))  # padded to 16, sliced to 13
    assert batched.shape[0] == 13
    np.testing.assert_array_equal(batched, direct)


# ------------------------------------------------- flush: explicit/size ----
def test_explicit_flush_and_bucket_padding_roundtrip(tmp_path):
    mp = _lin_bundle(tmp_path)
    q = ServeQueue(FlushPolicy(max_batch_rows=1024, min_bucket=8))
    xa, xb = _rows(3, seed=1), _rows(2, seed=2)
    fa, fb = q.submit(mp, xa), q.submit(mp, xb)
    assert not fa.done() and q.depth(mp) == 5
    assert q.flush() == 5
    # padded rows never leak: each caller gets exactly its rows back,
    # bit-identical to a synchronous engine call on its own inputs
    eng = InferenceEngine.get(mp)
    ya, yb = np.asarray(fa.result(1)), np.asarray(fb.result(1))
    assert ya.shape[0] == 3 and yb.shape[0] == 2
    np.testing.assert_array_equal(ya, np.asarray(eng(xa)))
    np.testing.assert_array_equal(yb, np.asarray(eng(xb)))
    st = q.stats(mp).snapshot()
    assert st["batches"] == 1
    assert st["bucket_rows"] == 8 and st["padded_rows"] == 3
    assert st["batch_occupancy"] == pytest.approx(5 / 8)
    assert st["queue_depth_rows"] == 0 and st["queue_depth_requests"] == 0


def test_max_batch_rows_flushes_inline(tmp_path):
    mp = _lin_bundle(tmp_path)
    q = ServeQueue(FlushPolicy(max_batch_rows=8))
    futs = [q.submit(mp, _rows(4, seed=i)) for i in range(2)]
    # 4+4 rows hit max_batch_rows: flushed by the second submit itself
    assert all(f.done() for f in futs)
    assert q.stats(mp).snapshot()["flush_reasons"] == {"max_batch": 1}


def test_future_result_flushes_on_demand(tmp_path):
    mp = _lin_bundle(tmp_path)
    q = ServeQueue(FlushPolicy(max_batch_rows=1024))
    f = q.submit(mp, _rows(4))
    assert not f.done()
    out = f.result(timeout=5)  # thread-free queue: result() makes progress
    assert out.shape == (4, 1)
    assert q.stats(mp).snapshot()["flush_reasons"] == {"demand": 1}


def test_submit_shape_mismatch_rejected(tmp_path):
    mp = _lin_bundle(tmp_path)
    q = ServeQueue()
    q.submit(mp, _rows(2))
    with pytest.raises(ValueError, match="feature-shape mismatch"):
        q.submit(mp, jnp.zeros((2, 3)))
    q.flush()


# -------------------------------------------------------- multiplexing -----
def test_multiplexed_bundles_one_queue(tmp_path):
    mp1 = _lin_bundle(tmp_path, "m1", seed=1)
    mp2 = _lin_bundle(tmp_path, "m2", seed=2)
    q = ServeQueue(FlushPolicy(max_batch_rows=1024))
    xs = [_rows(4, seed=i) for i in range(4)]
    # interleave submissions across the two bundles
    f1a, f2a = q.submit(mp1, xs[0]), q.submit(mp2, xs[1])
    f1b, f2b = q.submit(mp1, xs[2]), q.submit(mp2, xs[3])
    q.flush()
    e1, e2 = InferenceEngine.get(mp1), InferenceEngine.get(mp2)
    np.testing.assert_array_equal(np.asarray(f1a.result(1)),
                                  np.asarray(e1(xs[0])))
    np.testing.assert_array_equal(np.asarray(f2a.result(1)),
                                  np.asarray(e2(xs[1])))
    np.testing.assert_array_equal(np.asarray(f1b.result(1)),
                                  np.asarray(e1(xs[2])))
    np.testing.assert_array_equal(np.asarray(f2b.result(1)),
                                  np.asarray(e2(xs[3])))
    # each key got exactly one coalesced batch with its own stats
    assert q.stats(mp1).snapshot()["batches"] == 1
    assert q.stats(mp2).snapshot()["batches"] == 1
    assert q.stats(mp1).snapshot()["rows_completed"] == 8


# ------------------------------------------------------ deadline flush -----
def test_deadline_flush_thread_bit_identical_to_sync(tmp_path):
    mp = _lin_bundle(tmp_path)
    x = _rows(6, seed=3)
    sync_region = _region(6, "infer", mp)
    ref = np.asarray(sync_region(x=x)["out"])
    q = ServeQueue(FlushPolicy(max_batch_rows=10 ** 6, max_delay_s=0.05))
    with q:  # dispatcher thread enforces the deadline
        region = _region(6, "infer_async", mp, serving=q)
        h = region(x=x)
        out = np.asarray(h.result(timeout=10)["out"])
    np.testing.assert_array_equal(out, ref)  # bit-identical, incl. padding
    assert q.stats(mp).snapshot()["flush_reasons"].get("deadline", 0) >= 1


def test_deadline_flush_poll_deterministic(tmp_path):
    mp = _lin_bundle(tmp_path)
    q = ServeQueue(FlushPolicy(max_batch_rows=10 ** 6, max_delay_s=0.02))
    f = q.submit(mp, _rows(4))
    assert q.poll() == 0  # deadline not reached yet
    time.sleep(0.03)
    assert q.poll() == 4
    assert f.done()
    assert q.stats(mp).snapshot()["flush_reasons"] == {"deadline": 1}


# -------------------------------------------------------- backpressure -----
def test_backpressure_raises_when_not_blocking(tmp_path):
    mp = _lin_bundle(tmp_path)
    q = ServeQueue(FlushPolicy(max_batch_rows=10 ** 6, max_pending_rows=8,
                               block=False))
    q.submit(mp, _rows(8))
    with pytest.raises(Backpressure):
        q.submit(mp, _rows(4))
    q.flush()
    q.submit(mp, _rows(4))  # space again after the flush
    q.flush()


def test_backpressure_oversized_request_admitted_when_empty(tmp_path):
    mp = _lin_bundle(tmp_path)
    q = ServeQueue(FlushPolicy(max_batch_rows=10 ** 6, max_pending_rows=4,
                               block=False))
    f = q.submit(mp, _rows(16))  # larger than the cap: must not deadlock
    q.flush()
    assert f.result(1).shape == (16, 1)


def test_backpressure_thread_free_drains_inline(tmp_path):
    """Single-threaded driver: a full queue flushes itself to make space
    rather than waiting on a drain nobody else can perform."""
    mp = _lin_bundle(tmp_path)
    q = ServeQueue(FlushPolicy(max_batch_rows=10 ** 6, max_pending_rows=8,
                               block=True, block_timeout_s=5.0))
    f1 = q.submit(mp, _rows(8))
    f2 = q.submit(mp, _rows(8))  # full: inline backpressure drain, admit
    assert f1.done()  # the drain dispatched the first request
    assert q.stats(mp).snapshot()["flush_reasons"]["backpressure"] == 1
    q.flush()
    assert f2.result(1).shape == (8, 1)


def test_backpressure_block_timeout_with_idle_thread(tmp_path):
    """Threaded queue whose policy never flushes (no deadline, huge batch):
    a blocked submit must give up after block_timeout_s."""
    mp = _lin_bundle(tmp_path)
    q = ServeQueue(FlushPolicy(max_batch_rows=10 ** 6, max_pending_rows=8,
                               block=True, block_timeout_s=0.05))
    q.start()
    try:
        q.submit(mp, _rows(8))
        t0 = time.monotonic()
        with pytest.raises(Backpressure, match="blocked"):
            q.submit(mp, _rows(8))
        assert time.monotonic() - t0 >= 0.04
    finally:
        q.stop()


def test_backpressure_unblocks_on_dispatcher_drain(tmp_path):
    mp = _lin_bundle(tmp_path)
    q = ServeQueue(FlushPolicy(max_batch_rows=8, max_pending_rows=8,
                               block=True, block_timeout_s=10.0))
    with q:
        q.submit(mp, _rows(8))  # fills the queue; thread flushes (max_batch)
        f = q.submit(mp, _rows(8))  # blocks until the drain, then enqueues
        out = f.result(timeout=10)
    assert out.shape == (8, 1)


# ---------------------------------------------------------- statistics -----
def test_stats_counters_and_latency(tmp_path):
    mp = _lin_bundle(tmp_path)
    q = ServeQueue(FlushPolicy(max_batch_rows=1024, min_bucket=8))
    for i in range(3):
        q.submit(mp, _rows(2, seed=i))
    q.flush()
    st = q.stats(mp).snapshot()
    assert st["requests_enqueued"] == 3 and st["rows_enqueued"] == 6
    assert st["requests_completed"] == 3 and st["rows_completed"] == 6
    assert st["bucket_rows"] == 8 and st["padded_rows"] == 2
    assert st["latency_p50_ms"] > 0
    assert st["latency_p99_ms"] >= st["latency_p50_ms"]
    assert st["rows_per_s"] > 0
    assert st["queue_depth_rows"] == 0


def test_batch_failure_propagates_to_all_futures(tmp_path):
    q = ServeQueue()
    key = str(tmp_path / "no_such_bundle")
    f1 = q.submit(key, _rows(2))
    f2 = q.submit(key, _rows(2))
    q.flush()
    with pytest.raises(Exception):
        f1.result(1)
    with pytest.raises(Exception):
        f2.result(1)
    # failed work never counts as served: completed/rows_per_s stay zero
    st = q.stats(key).snapshot()
    assert st["batches"] == 0 and st["batches_failed"] == 1
    assert st["requests_completed"] == 0 and st["requests_failed"] == 2
    assert st["rows_completed"] == 0 and st["rows_failed"] == 4
    assert st["rows_per_s"] == 0.0
    assert st["queue_depth_rows"] == 0 and st["queue_depth_requests"] == 0


# ----------------------------------------------------- region async API ----
def test_region_infer_async_bit_identical_to_infer(tmp_path):
    mp = _lin_bundle(tmp_path)
    q = ServeQueue(FlushPolicy(max_batch_rows=1024))
    r_async = _region(8, "infer_async", mp, serving=q)
    r_sync = _region(8, "infer", mp)
    x = _rows(8, seed=4)
    h = r_async(x=x)
    assert h.deferred() and not h.done()
    q.flush()
    np.testing.assert_array_equal(np.asarray(h.result(1)["out"]),
                                  np.asarray(r_sync(x=x)["out"]))


def test_region_infer_async_requires_queue(tmp_path):
    mp = _lin_bundle(tmp_path)
    with pytest.raises(AssertionError, match="serving"):
        _region(8, "infer_async", mp)


def test_region_infer_async_inside_trace_degrades_sync(tmp_path):
    mp = _lin_bundle(tmp_path)
    q = ServeQueue()
    r = _region(8, "infer_async", mp, serving=q)
    x = _rows(8, seed=5)

    @jax.jit
    def step(x):
        return r(x=x).result()["out"]  # resolved synchronously in-trace

    np.testing.assert_allclose(np.asarray(step(x)),
                               np.asarray(_region(8, "infer", mp)(x=x)["out"]),
                               rtol=1e-6, atol=1e-6)
    assert q.depth() == 0  # nothing parked on the host queue


def test_predicated_region_serving_defers(tmp_path):
    mp = _lin_bundle(tmp_path)
    q = ServeQueue(FlushPolicy(max_batch_rows=1024))
    r = _region(8, "predicated", mp, serving=q)
    x = _rows(8, seed=6)
    # accurate branch: resolved immediately, same handle interface
    h_acc = r(predicate=False, x=x)
    assert not h_acc.deferred() and h_acc.done()
    np.testing.assert_allclose(np.asarray(h_acc.result()["out"]),
                               np.asarray(x[:, :1] * 2 + x[:, 1:] * 0.5),
                               rtol=1e-6)
    # ML branch: defers through the queue
    h_ml = r(predicate=True, x=x)
    assert h_ml.deferred() and not h_ml.done()
    q.flush()
    np.testing.assert_array_equal(
        np.asarray(h_ml.result(1)["out"]),
        np.asarray(_region(8, "infer", mp)(x=x)["out"]))


def _fingerprint_checks(mp) -> float:
    from repro.obs.metrics import default_registry
    return default_registry().counter(
        "repro_engine_fingerprint_checks_total", "",
        ("bundle",)).value(bundle=str(mp))


def test_bundle_fingerprint_checked_once_per_batch(tmp_path, monkeypatch):
    """Async region calls only read the spec and make no stat of the
    bundle; the batcher's per-batch get() checks it once per flush.  A
    sync region serves with the engine it looks up, so it checks per
    call."""
    from repro.core import engine as engine_mod
    mp = _lin_bundle(tmp_path)
    InferenceEngine.get(mp)  # loaded before counting: a miss stats too
    stats = []
    real = engine_mod._bundle_mtime
    monkeypatch.setattr(engine_mod, "_bundle_mtime",
                        lambda path: stats.append(path) or real(path))
    q = ServeQueue(FlushPolicy(max_batch_rows=1 << 20))
    r = _region(8, "infer_async", mp, serving=q)
    before = _fingerprint_checks(mp)
    handles = [r(x=_rows(8, seed=s)) for s in range(8)]
    assert stats == [] and _fingerprint_checks(mp) == before
    q.flush()
    for h in handles:
        assert h.result(5)["out"].shape == (8, 1)
    assert stats == [str(mp)]
    assert _fingerprint_checks(mp) == before + 1
    r_sync = _region(8, "infer", mp)
    for s in range(3):
        r_sync(x=_rows(8, seed=s))
    assert _fingerprint_checks(mp) == before + 4
    assert len(stats) == 4


def test_async_region_serves_rewritten_bundle(tmp_path):
    """The serve path still sees a bundle rewritten on disk: a step after
    the rewrite, and rows submitted before it and flushed after, get the
    new weights from the same engine, refreshed in place."""
    net = MLP((1, 2), [16], 1)
    p0 = net.init(jax.random.PRNGKey(0))
    mp = save_model(tmp_path / "m", net, p0)
    future = [os.path.getmtime(tmp_path / "m" / "params.npz")]

    def rewrite(scale):
        params = jax.tree.map(lambda w: w * scale, p0)
        save_model(tmp_path / "m", net, params)
        future[0] += 5  # past the filesystem's timestamp granularity
        for f in ("spec.json", "params.npz"):
            os.utime(tmp_path / "m" / f, (future[0], future[0]))
        return params

    def served(handle):
        return np.asarray(handle.result(5)["out"])

    q = ServeQueue(FlushPolicy(max_batch_rows=1 << 20))
    r = _region(8, "infer_async", mp, serving=q)
    x = _rows(8, seed=7)
    h = r(x=x)
    q.flush()
    y0 = served(h)
    eng = InferenceEngine.get(mp)
    np.testing.assert_allclose(y0, np.asarray(net.apply(p0, x)),
                               rtol=1e-5, atol=1e-6)
    # rewritten between two steps
    p1 = rewrite(3.0)
    h = r(x=x)
    q.flush()
    y1 = served(h)
    np.testing.assert_allclose(y1, np.asarray(net.apply(p1, x)),
                               rtol=1e-5, atol=1e-6)
    assert float(np.abs(y1 - y0).max()) > 1e-3
    assert InferenceEngine.get(mp) is eng
    # submitted before the rewrite, flushed after it
    h = r(x=x)
    p2 = rewrite(5.0)
    q.flush()
    y2 = served(h)
    np.testing.assert_allclose(y2, np.asarray(net.apply(p2, x)),
                               rtol=1e-5, atol=1e-6)
    assert float(np.abs(y2 - y1).max()) > 1e-3
    assert InferenceEngine.get(mp) is eng


# ----------------------------------------------------------- app drivers ---
def test_binomial_chunked_async_driver(tmp_path):
    net = MLP((1, 5), [16], 1)
    mp = save_model(tmp_path / "bin", net, net.init(jax.random.PRNGKey(0)))
    q = ServeQueue(FlushPolicy(max_batch_rows=10 ** 6))
    region = binomial.make_region(8, mode="infer_async", model=mp, serving=q)
    opts = binomial.make_inputs(32, seed=9)
    out = binomial.price_chunks_async(opts, region, q, chunk=8)
    r_sync = binomial.make_region(32, mode="infer", model=mp)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(r_sync(opts=opts)["out"]))
    st = q.stats(mp).snapshot()
    assert st["batches"] == 1 and st["rows_completed"] == 32


def test_miniweather_ensemble_async_driver(tmp_path):
    # conv-only surrogate: grid -> grid, matches the stencil bridge shapes
    ny, nx = miniweather.NY - 2, miniweather.NX - 2
    net = Sequential([Conv2D(8, 3), Activation("relu"), Conv2D(4, 3)],
                     (1, ny, nx, 20))
    mp = save_model(tmp_path / "mw", net, net.init(jax.random.PRNGKey(0)))
    q = ServeQueue(FlushPolicy(max_batch_rows=10 ** 6))
    region = miniweather.make_region(mode="infer_async", model=mp, serving=q)
    states = [miniweather.init_state(seed=s) for s in range(3)]
    outs = miniweather.run_ensemble_async(states, steps=2, region=region,
                                          queue=q)
    # reference: each member advanced with synchronous inference
    r_sync = miniweather.make_region(mode="infer", model=mp)
    for s0, got in zip(states, outs):
        ref = s0
        for _ in range(2):
            ref = r_sync(state=ref)["state"]
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    st = q.stats(mp).snapshot()
    assert st["batches"] == 2  # one coalesced batch per sweep step
    assert st["rows_completed"] == 6  # 3 members x 2 steps


# ------------------------------------------- engine placement/caching -----
def test_engine_sharding_resolution_cached(tmp_path, monkeypatch):
    mp = _lin_bundle(tmp_path, "cache")
    eng = InferenceEngine(mp)  # private instance: isolate the cache
    calls = {"n": 0}
    orig = ShardCtx.sharding_for

    def counting(self, shape, axes):
        calls["n"] += 1
        return orig(self, shape, axes)

    monkeypatch.setattr(ShardCtx, "sharding_for", counting)
    x = _rows(8)
    with use_mesh(make_local_mesh()):
        for _ in range(4):
            eng(x)
        assert calls["n"] == 1  # resolved once, cached per (shape, mesh)
        eng(_rows(16))
        assert calls["n"] == 2  # new shape resolves once more
        for _ in range(3):
            eng(_rows(16, seed=7))
        assert calls["n"] == 2


def test_engine_place_skips_redundant_device_put(tmp_path):
    mp = _lin_bundle(tmp_path, "skip")
    eng = InferenceEngine(mp)
    x = _rows(8)
    with use_mesh(make_local_mesh()) as ctx:
        placed = eng._place(x, ctx)
        assert eng._place(placed, ctx) is placed  # already there: no-op


def test_dispatcher_thread_serves_under_submitters_mesh(tmp_path):
    """ShardCtx is thread-local: a deadline flush on the dispatcher thread
    must re-install the submitter's mesh or the batch serves unsharded."""
    mp = _lin_bundle(tmp_path, "threadmesh")
    eng = InferenceEngine.get(mp)
    eng._applies.clear()
    eng._shardings.clear()
    mesh = make_local_mesh()
    q = ServeQueue(FlushPolicy(max_batch_rows=10 ** 6, max_delay_s=0.02))
    with q:
        with use_mesh(mesh):
            f = q.submit(mp, _rows(8))
        out = f.result(timeout=10)
    assert out.shape == (8, 1)
    assert q.stats(mp).snapshot()["flush_reasons"].get("deadline", 0) >= 1
    # the apply compiled for (mesh, False), not for the no-mesh key None
    assert (mesh, False) in eng._applies
    assert any(k[1] == mesh for k in eng._shardings)


def test_engine_reload_drops_sharding_cache(tmp_path):
    mp = _lin_bundle(tmp_path, "reload")
    eng = InferenceEngine(mp)
    with use_mesh(make_local_mesh()) as ctx:
        eng._place(_rows(8), ctx)
        assert len(eng._shardings) == 1
        eng.reload()
        assert len(eng._shardings) == 0
