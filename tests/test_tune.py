"""repro.tune: VMEM accounting, tune cache, kernel tuner, adaptive flush
controller, and the hot-path hardening it rides on (pooled scratch
buffers, engine context normalization)."""
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import InferenceEngine
from repro.kernels.fused_mlp.fused_mlp import fits_vmem
from repro.nn import MLP
from repro.nn.serialize import save_model
from repro.serve import FlushPolicy, ScratchPool, ServeQueue
from repro.serve.stats import ServeStats
from repro.tune import (AdaptiveFlushController, TuneCache, autotune,
                        candidate_tiles, predict_batch_latency_s,
                        serve_buckets, sweep_fused_mlp, widths_from_spec)
from repro.tune.cache import best_tile, shape_key


def _rows(n, seed=0, feat=2):
    return jnp.asarray(np.random.default_rng(seed)
                       .normal(size=(n, feat)).astype(np.float32))


def _bundle(tmp, name="m", hidden=16, feat=2):
    net = MLP((1, feat), [hidden], 1)
    return save_model(tmp / name, net, net.init(jax.random.PRNGKey(0)))


# ---------------------------------------------------------- fits_vmem ------
def test_fits_vmem_counts_bias_and_tile_padding():
    widths = (8, 128)
    # exact accounting for f32: weights 8x128, bias one (8,128) tile,
    # in/out activation tiles double-buffered at max width 128
    exact = (8 * 128 + 8 * 128 + 2 * 2 * 128 * 128) * 4
    assert fits_vmem(widths, 128, budget=exact)
    assert not fits_vmem(widths, 128, budget=exact - 1)
    # the old accounting (no bias, no padding, single-buffered) said
    # ~135KB; a budget between the two must now be rejected — accepting
    # it is exactly the near-budget overflow the tuner cannot survive
    assert not fits_vmem(widths, 128, budget=200_000)


def test_fits_vmem_pads_ragged_weight_rows():
    # [129, 5] occupies a (136, 128) f32 tile in VMEM, not 129x5
    padded_w = 136 * 128 * 4
    bias = 8 * 128 * 4
    acts = 2 * 2 * 8 * 256 * 4  # tile 8, max width padded 129 -> 256
    exact = padded_w + bias + acts
    assert fits_vmem((129, 5), 8, budget=exact)
    assert not fits_vmem((129, 5), 8, budget=exact - 1)


def test_fits_vmem_batch_tile_scales_activations():
    widths = (64, 64)
    assert fits_vmem(widths, 8, budget=2 ** 20)
    # activation tiles grow with the batch tile and must hit the budget
    assert not fits_vmem(widths, 4096, budget=2 ** 20)


# --------------------------------------------------------- tune cache ------
def test_tune_cache_roundtrip_and_persistence(tmp_path):
    c = TuneCache("fused_mlp", tmp_path / "fused_mlp.json")
    rec = {"batch_tile": 64, "us": 10.0, "exact": True}
    c.store([5, 16, 1], jnp.float32, "cpu", 256, rec)
    assert c.lookup([5, 16, 1], jnp.float32, "cpu", 256)["batch_tile"] == 64
    assert c.lookup([5, 16, 1], jnp.float32, "cpu", 512) is None
    # a fresh instance reads the same file: persistence across processes
    c2 = TuneCache("fused_mlp", tmp_path / "fused_mlp.json")
    assert c2.lookup([5, 16, 1], jnp.float32, "cpu", 256)["us"] == 10.0


def test_tune_cache_corrupt_file_is_a_miss(tmp_path):
    p = tmp_path / "fused_mlp.json"
    p.write_text("{not json")
    c = TuneCache("fused_mlp", p)
    assert c.lookup([1, 2], jnp.float32, "cpu", 8) is None
    c.store([1, 2], jnp.float32, "cpu", 8, {"batch_tile": 8, "exact": True})
    assert c.lookup([1, 2], jnp.float32, "cpu", 8)["batch_tile"] == 8


def test_tune_cache_reloads_on_external_write(tmp_path):
    p = tmp_path / "fused_mlp.json"
    c1 = TuneCache("fused_mlp", p)
    c2 = TuneCache("fused_mlp", p)
    c1.store([3, 4], jnp.float32, "cpu", 8, {"batch_tile": 4, "exact": True})
    # c2 sees c1's write via the mtime fingerprint, no restart needed
    assert c2.lookup([3, 4], jnp.float32, "cpu", 8)["batch_tile"] == 4


def test_best_tile_refuses_unvalidated_entries(tmp_path, monkeypatch):
    import repro.tune.cache as cache_mod
    c = TuneCache("fused_mlp", tmp_path / "fused_mlp.json")
    monkeypatch.setattr(cache_mod, "_default", {"fused_mlp": c})
    widths = [5, 16, 1]
    assert best_tile(widths, jnp.float32, "cpu", 256) is None  # untuned
    c.store(widths, jnp.float32, "cpu", 256,
            {"batch_tile": 64, "exact": False})
    assert best_tile(widths, jnp.float32, "cpu", 256) is None  # not exact
    c.store(widths, jnp.float32, "cpu", 256,
            {"batch_tile": 64, "exact": True})
    assert best_tile(widths, jnp.float32, "cpu", 256) == 64
    # eager batch sizes bucket to the serve shape: 200 -> bucket 256
    assert best_tile(widths, jnp.float32, "cpu", 200) == 64


def test_tune_cache_migrates_legacy_flat_file(tmp_path):
    """A schema-1 cache (flat {key: record}, bare batch_tile) must lift
    into the namespaced schema-2 layout on first load — atomically, so
    deployed caches and the CI actions/cache entry survive the registry
    refactor — and keep serving its entries."""
    import json
    p = tmp_path / "fused_mlp.json"
    key = shape_key([5, 16, 1], jnp.float32, "cpu", 256)
    p.write_text(json.dumps({key: {"batch_tile": 64, "us": 10.0,
                                   "exact": True}}))
    c = TuneCache("fused_mlp", p)
    rec = c.lookup([5, 16, 1], jnp.float32, "cpu", 256)
    assert rec["batch_tile"] == 64
    assert rec["params"] == {"batch_tile": 64}  # record migrated
    # ... and the winner reaches the dispatch path
    import repro.tune.cache as cache_mod
    data = json.loads(p.read_text())
    assert data["schema"] == cache_mod.SCHEMA  # file rewritten
    assert data["kernel"] == "fused_mlp"
    assert data["entries"][key]["params"] == {"batch_tile": 64}
    # a fresh instance reads the migrated layout directly
    c2 = TuneCache("fused_mlp", p)
    assert c2.lookup([5, 16, 1], jnp.float32, "cpu", 256)["us"] == 10.0


def test_best_params_namespaced_per_kernel(tmp_path, monkeypatch):
    from repro.tune import best_params
    import repro.tune.cache as cache_mod
    fa = TuneCache("flash_attention", tmp_path / "flash_attention.json")
    fa.put("k1", {"params": {"block_q": 32, "block_kv": 64},
                  "exact": True})
    fa.put("k2", {"params": {"block_q": 16, "block_kv": 16},
                  "exact": False})
    monkeypatch.setattr(cache_mod, "_default", {"flash_attention": fa})
    assert best_params("flash_attention", ["k1"]) == {"block_q": 32,
                                                      "block_kv": 64}
    assert best_params("flash_attention", ["k2"]) is None  # unvalidated
    assert best_params("flash_attention", ["k2", "k1"]) == \
        {"block_q": 32, "block_kv": 64}  # ordered fallback chain


def test_corrupt_record_is_a_miss(tmp_path, monkeypatch):
    # a well-formed file whose record is not a dict: the cache's own
    # corruption, read as a miss (the registry no longer catches errors)
    import json
    from repro.tune import best_params
    import repro.tune.cache as cache_mod
    p = tmp_path / "fused_mlp.json"
    p.write_text(json.dumps({"schema": cache_mod.SCHEMA,
                             "kernel": "fused_mlp", "entries": {"k": 5}}))
    monkeypatch.setattr(cache_mod, "_default",
                        {"fused_mlp": TuneCache("fused_mlp", p)})
    assert best_params("fused_mlp", ["k"]) is None


def test_shape_key_stable():
    assert shape_key([5, 16, 1], jnp.float32, "cpu", 256) == \
        shape_key((5, 16, 1), jnp.float32, "cpu", 256)


def test_shape_key_normalizes_dtype_spellings():
    """The tuner stores jnp.float32 (a type); the serving path looks up
    x.dtype (a np.dtype) — one cache key, or the cache never hits."""
    x = jnp.zeros((1,), jnp.float32)
    keys = {shape_key([5, 16, 1], d, "cpu", 64)
            for d in (jnp.float32, np.float32, x.dtype, "float32")}
    assert len(keys) == 1
    assert "class" not in next(iter(keys))


def test_best_tile_exact_batch_before_pow2_bucket(tmp_path, monkeypatch):
    """Shard-rounded dispatch buckets (e.g. 12 on a 6-shard mesh) are
    not powers of two; the exact batch must hit before re-bucketing."""
    import repro.tune.cache as cache_mod
    c = TuneCache("fused_mlp", tmp_path / "fused_mlp.json")
    monkeypatch.setattr(cache_mod, "_default", {"fused_mlp": c})
    widths = [5, 16, 1]
    c.store(widths, jnp.float32, "cpu", 12, {"batch_tile": 4, "exact": True})
    c.store(widths, jnp.float32, "cpu", 16, {"batch_tile": 8, "exact": True})
    assert best_tile(widths, jnp.float32, "cpu", 12) == 4   # exact bucket
    assert best_tile(widths, jnp.float32, "cpu", 13) == 8   # pow2 fallback


def test_sweep_to_serving_path_end_to_end(tmp_path, monkeypatch):
    """No stubs between store and lookup: a swept record must be what
    fused_mlp_op actually applies (guards key-spelling regressions)."""
    import repro.kernels.fused_mlp.ops as ops_mod
    import repro.tune.cache as cache_mod
    c = TuneCache("fused_mlp", tmp_path / "fused_mlp.json")
    monkeypatch.setattr(cache_mod, "_default", {"fused_mlp": c})
    rec = sweep_fused_mlp([4, 16, 2], 32, cache=c, reps=1, warmup=0)
    seen = {}
    orig = ops_mod.fused_mlp

    def spy(x, ws, bs, acts, *, batch_tile, interpret):
        seen["tile"] = batch_tile
        return orig(x, ws, bs, acts, batch_tile=batch_tile,
                    interpret=interpret)

    monkeypatch.setattr(ops_mod, "fused_mlp", spy)
    rng = np.random.default_rng(0)
    ws = [jnp.asarray(rng.normal(size=(4, 16)).astype(np.float32)),
          jnp.asarray(rng.normal(size=(16, 2)).astype(np.float32))]
    bs = [jnp.asarray(rng.normal(size=(16,)).astype(np.float32)),
          jnp.asarray(rng.normal(size=(2,)).astype(np.float32))]
    x = jnp.asarray(rng.normal(size=(32, 4)).astype(np.float32))
    ops_mod.fused_mlp_op(x, ws, bs, ("relu", "identity"), force_kernel=True)
    assert seen["tile"] == rec["batch_tile"]


def test_autotune_warms_per_shard_batches(tmp_path):
    mp = _bundle(tmp_path, "shardtune", hidden=8, feat=3)
    c = TuneCache("fused_mlp", tmp_path / "cache.json")
    autotune(mp, buckets=[16], n_shards=4, cache=c, reps=1, warmup=0)
    backend = jax.default_backend()
    # both the global dispatch bucket and the per-shard local batch the
    # shard_map body will trace with are warmed
    assert c.lookup([3, 8, 1], jnp.float32, backend, 16) is not None
    assert c.lookup([3, 8, 1], jnp.float32, backend, 4) is not None


# ------------------------------------------------------- kernel tuner ------
def test_candidate_tiles_vmem_filtered_and_bucket_clipped():
    cands = candidate_tiles([4, 16, 2], 64)
    assert cands[0] == 128  # default always swept (kernel pads B up)
    assert all(t <= 64 for t in cands[1:])
    assert 64 in cands
    # a huge net rejects fat tiles but keeps thin ones
    wide = [2048, 2048, 2048]
    thin = candidate_tiles(wide, 512, extra=(8,))
    assert all(fits_vmem(wide, t) for t in thin)


def test_sweep_fused_mlp_picks_exact_winner(tmp_path):
    c = TuneCache("fused_mlp", tmp_path / "fused_mlp.json")
    rec = sweep_fused_mlp([4, 16, 2], 32, cache=c, reps=1, warmup=0)
    assert rec["exact"] is True
    tiles = [s["params"]["batch_tile"] for s in rec["swept"]]
    assert 128 in tiles  # the default is always in the comparison set
    valid_us = [s["us"] for s in rec["swept"] if s["exact"]]
    assert rec["us"] == min(valid_us)
    assert rec["us"] <= rec["default_us"]      # winner is the argmin,
    assert rec["speedup_x"] >= 1.0             # so this is structural
    # second call is a cache hit: identical record, no re-measure
    again = sweep_fused_mlp([4, 16, 2], 32, cache=c, reps=1, warmup=0)
    assert again == rec


def test_autotune_from_bundle_path(tmp_path):
    mp = _bundle(tmp_path, "tuneme", hidden=8, feat=3)
    c = TuneCache("fused_mlp", tmp_path / "cache.json")
    recs = autotune(mp, buckets=[8], cache=c, reps=1, warmup=0)
    assert len(recs) == 1 and recs[0]["exact"]
    assert c.lookup([3, 8, 1], jnp.float32,
                    jax.default_backend(), 8) is not None


def test_autotune_rejects_non_mlp_bundle(tmp_path):
    from repro.nn.layers import Activation, Conv2D, Sequential
    net = Sequential([Conv2D(4, 3), Activation("relu")], (1, 8, 8, 2))
    mp = save_model(tmp_path / "conv", net, net.init(jax.random.PRNGKey(0)))
    with pytest.raises(ValueError, match="not a pure MLP"):
        autotune(mp, buckets=[8])


def test_widths_from_spec():
    spec = {"in_shape": [1, 5],
            "layers": [{"kind": "dense", "features": 16},
                       {"kind": "act", "name": "relu"},
                       {"kind": "dense", "features": 1}]}
    assert widths_from_spec(spec) == [5, 16, 1]
    # flatten folds trailing dims into the feature width
    spec_f = {"in_shape": [1, 4, 3],
              "layers": [{"kind": "flatten"},
                         {"kind": "dense", "features": 2}]}
    assert widths_from_spec(spec_f) == [12, 2]
    assert widths_from_spec(
        {"in_shape": [1, 8, 8, 2],
         "layers": [{"kind": "conv2d", "features": 4}]}) is None


def test_fused_mlp_op_consults_tune_cache(monkeypatch):
    import repro.kernels.fused_mlp.ops as ops_mod
    import repro.tune.cache as cache_mod
    seen = {}
    orig = ops_mod.fused_mlp

    def spy(x, ws, bs, acts, *, batch_tile, interpret):
        seen["tile"] = batch_tile
        return orig(x, ws, bs, acts, batch_tile=batch_tile,
                    interpret=interpret)

    monkeypatch.setattr(ops_mod, "fused_mlp", spy)
    monkeypatch.setattr(cache_mod, "best_params",
                        lambda kernel, keys: {"batch_tile": 32})
    rng = np.random.default_rng(0)
    ws = [jnp.asarray(rng.normal(size=(4, 16)).astype(np.float32))]
    bs = [jnp.asarray(rng.normal(size=(16,)).astype(np.float32))]
    x = jnp.asarray(rng.normal(size=(64, 4)).astype(np.float32))
    ops_mod.fused_mlp_op(x, ws, bs, ("identity",), force_kernel=True)
    assert seen["tile"] == 32  # tuned tile, not the hardcoded default
    # a cached tile that no longer fits VMEM falls back to the default
    monkeypatch.setattr(cache_mod, "best_params",
                        lambda kernel, keys: {"batch_tile": 1 << 20})
    ops_mod.fused_mlp_op(x, ws, bs, ("identity",), force_kernel=True)
    assert seen["tile"] == 128


def test_serve_buckets_cover_policy_range():
    assert serve_buckets(8, 1024) == [8, 16, 32, 64, 128, 256, 512, 1024]
    # shard floor raises the smallest bucket and keeps divisibility
    bs = serve_buckets(8, 100, n_shards=6)
    assert bs[0] == 12 and all(b % 6 == 0 for b in bs)


# ------------------------------------------------- generic kernel sweep ----
def test_sweep_stencil_gather_bit_exact_winner(tmp_path):
    from repro.tune import sweep
    c = TuneCache("stencil_gather", tmp_path / "stencil_gather.json")
    problem = {"h": 40, "w": 40, "out_h": 36, "out_w": 36,
               "offsets": ((0, 1), (1, 0), (0, 0)), "origin": (1, 1),
               "dtype": "float32"}
    rec = sweep("stencil_gather", problem, cache=c, reps=1, warmup=0)
    assert rec["exact"] is True
    assert {"block_h", "block_w"} <= set(rec["params"])
    # the spec default is always the baseline, so this is structural
    assert rec["speedup_x"] >= 1.0
    # cached: a second sweep returns the stored record unmeasured
    assert sweep("stencil_gather", problem, cache=c, reps=1,
                 warmup=0) == rec


def test_sweep_flash_attention_validates_to_spec_tolerance(tmp_path):
    """Flash attention declares a tolerance (online-softmax block order
    changes rounding); every stored winner must still validate."""
    from repro.tune import sweep
    c = TuneCache("flash_attention", tmp_path / "flash_attention.json")
    problem = {"b": 1, "sq": 16, "skv": 16, "h": 1, "kv": 1, "hd": 8,
               "causal": True, "q_offset": 0, "dtype": "float32"}
    rec = sweep("flash_attention", problem, cache=c, reps=1, warmup=0)
    assert rec["exact"] is True
    assert {"block_q", "block_kv"} <= set(rec["params"])
    valid_us = [s["us"] for s in rec["swept"] if s["exact"]]
    assert rec["us"] == min(valid_us)


def test_sweep_record_reaches_registry_dispatch(tmp_path, monkeypatch):
    """No stubs between store and lookup: a swept stencil winner must be
    what the registry dispatch actually applies."""
    import repro.tune.cache as cache_mod
    from repro.kernels import registry
    from repro.tune import sweep
    c = TuneCache("stencil_gather", tmp_path / "stencil_gather.json")
    monkeypatch.setattr(cache_mod, "_default", {"stencil_gather": c})
    spec = registry.get_spec("stencil_gather")
    problem = {"h": 40, "w": 40, "out_h": 36, "out_w": 36,
               "offsets": ((0, 1), (1, 0), (0, 0)), "origin": (1, 1),
               "dtype": "float32"}
    rec = sweep(spec, problem, cache=c, reps=1, warmup=0)
    seen = {}
    orig = spec.run_call

    def spy(problem, arrays, params, *, interpret):
        seen.update(params)
        return orig(problem, arrays, params, interpret=interpret)

    monkeypatch.setattr(spec, "run_call", spy)
    arrays = spec.make_call(problem, np.random.default_rng(0))
    registry.dispatch(spec, problem, arrays, force_kernel=True)
    assert seen == rec["params"]


def test_autotune_registered_skips_paramless_kernels(tmp_path, monkeypatch):
    """rwkv6_chunk has no tunables — the deploy warm-up must not sweep
    it (there is nothing to pick)."""
    import repro.tune.kernel_tuner as kt
    from repro.tune import autotune_registered
    swept = []
    monkeypatch.setattr(
        kt, "sweep",
        lambda spec, problem, **kw: swept.append(spec.name) or
        {"params": {}, "us": 1.0, "default_us": 1.0, "speedup_x": 1.0,
         "exact": True})
    autotune_registered(["rwkv6_chunk"])
    assert swept == []
    autotune_registered(["stencil_gather"])
    assert swept == ["stencil_gather"]


# ------------------------------------------------ adaptive controller ------
def _ctrl(policy=None, widths=(5, 16, 1), **kw):
    policy = policy or FlushPolicy(max_batch_rows=1024, max_delay_s=0.05)
    return AdaptiveFlushController(policy,
                                   widths_for=lambda key: list(widths), **kw)


def test_predict_latency_monotone_in_batch():
    lo = predict_batch_latency_s([5, 128, 1], 8)
    hi = predict_batch_latency_s([5, 128, 1], 4096)
    assert hi >= lo > 0


def test_controller_unknown_widths_degrades_to_static():
    pol = FlushPolicy(max_batch_rows=1024, max_delay_s=0.03)
    c = AdaptiveFlushController(
        pol, widths_for=lambda key: (_ for _ in ()).throw(IOError("gone")))
    assert c.delay_for("k", None) == 0.03
    assert c.batch_rows_for("k", None) == 1024


def test_controller_cold_stats_use_service_cap_not_static():
    c = _ctrl(service_factor=4.0, overhead_s=1e-4)
    d = c.delay_for("k", None)  # no stats at all: model-only decision
    # bounded by the service cap (~4x predicted latency), far below the
    # 50ms static deadline — low-arrival callers stop paying the full
    # static delay the moment the model is known
    assert c.min_delay_s <= d < 0.01
    assert d <= 4.0 * c.predict_latency_s([5, 16, 1], 1024) + 1e-9


def test_controller_high_rate_clamps_to_min_delay():
    c = _ctrl(min_delay_s=5e-4)
    st = ServeStats("k")
    now = time.monotonic()
    st._arrivals = deque([(now - 1.0 + 0.1 * i, 10 ** 6) for i in range(10)],
                         maxlen=256)
    st.requests_enqueued = 10
    d = c.delay_for("k", st)
    assert d == pytest.approx(5e-4)
    assert c.last_decision["k"]["arrival_rate_rows_s"] > 0


def test_controller_warmup_gates_rate_term_only():
    c = _ctrl(warmup_requests=8)
    st = ServeStats("k")
    st.requests_enqueued = 2  # below warmup: rate must not be consulted
    st._arrivals = deque([(time.monotonic(), 10 ** 9)] * 2, maxlen=256)
    d = c.delay_for("k", st)
    assert c.last_decision["k"]["arrival_rate_rows_s"] == 0.0
    assert d > 0


def test_controller_bucket_target_amortizes_overhead():
    # compute-bound toy peaks: the target lands strictly between the
    # floor and the cap, where per-row latency is within eps of flat
    pol = FlushPolicy(max_batch_rows=4096, min_bucket=8)
    c = AdaptiveFlushController(pol, widths_for=lambda k: [64, 64],
                                peak_flops=1e9, overhead_s=1e-4)
    t = c.batch_rows_for("k", None)
    assert 8 < t < 4096
    assert t & (t - 1) == 0  # power of two


# ------------------------------------------- measured-latency loop ---------
def _warm_stats(bucket, busy_s, n=3, key="k"):
    st = ServeStats(key)
    for _ in range(n):
        st.on_batch(requests=1, rows=bucket, bucket=bucket, reason="t",
                    busy_s=busy_s, latencies_s=[busy_s])
    return st


def test_stats_batch_latency_ewma_and_warmup_gate():
    st = ServeStats("k")
    assert st.batch_latency_s(64) is None  # cold
    # first observation of a bucket carries its one-time jit compile:
    # it must never blend into the EWMA the controller trusts
    st.on_batch(requests=1, rows=64, bucket=64, reason="t", busy_s=0.900,
                latencies_s=[0.9])
    assert st.batch_latency_s(64, min_batches=2) is None  # below min obs
    st.on_batch(requests=1, rows=64, bucket=64, reason="t", busy_s=0.020,
                latencies_s=[0.02])
    # the second observation *replaces* the compile-tainted seed
    assert st.batch_latency_s(64, min_batches=2) == pytest.approx(0.020)
    st.on_batch(requests=1, rows=64, bucket=64, reason="t", busy_s=0.010,
                latencies_s=[0.01])
    ewma = st.batch_latency_s(64, min_batches=2)
    # from the third batch on, a plain EWMA tracks the service time
    assert 0.010 < ewma < 0.020
    assert st.batch_latencies()[64][1] == 3
    snap = st.snapshot()
    assert snap["batch_latency_batches"] == {64: 3}
    assert snap["batch_latency_ewma_ms"][64] == pytest.approx(ewma * 1e3,
                                                              rel=1e-3)


def test_stats_failed_dispatches_never_feed_the_latency_model():
    st = ServeStats("k")
    st.on_enqueue(8)
    st.on_failure(requests=1, rows=8, reason="t", busy_s=5.0)
    assert st.batch_latencies() == {}


def test_controller_measured_latency_tightens_the_cap():
    """A roofline prior that overestimates the service time (huge
    overhead guess) holds lone callers too long; once the true latency
    is measured, the cap shrinks to the tight measured factor."""
    c = _ctrl(overhead_s=5e-3, measured_min_batches=2, decision_ttl_s=0.0)
    measured = 5e-4
    # warm the bucket the service cap prices: nothing pending -> the
    # smallest dispatchable bucket
    st = _warm_stats(c.policy.min_bucket, measured, n=3)
    d = c.delay_for("k", st)
    dec = c.last_decision["k"]
    assert dec["latency_source"] == "measured"
    assert dec["batch_latency_s"] == pytest.approx(measured, rel=1e-6)
    assert d == pytest.approx(
        c.measured_service_factor * measured, rel=1e-6)
    assert d < c.service_factor * dec["predicted_batch_latency_s"]


def test_controller_measured_latency_never_inflates_the_cap():
    """The anti-feedback property: serving getting *slower* than the
    prior must not lengthen deadlines (that would compound a slowdown
    into queueing delay)."""
    c = _ctrl(measured_min_batches=2, decision_ttl_s=0.0)
    cold = c.delay_for("cold", None)  # roofline-only bound, same widths
    st = _warm_stats(c.policy.min_bucket, 5.0, n=3)  # pathological 5s
    d = c.delay_for("k", st)
    assert d <= cold + 1e-9


def test_controller_corrects_roofline_from_nearest_warm_bucket():
    """Unmeasured buckets borrow the nearest warm bucket's measured /
    predicted ratio — one warm bucket recalibrates the whole curve."""
    c = _ctrl(measured_min_batches=2, decision_ttl_s=0.0)
    widths = [5, 16, 1]
    st = _warm_stats(64, busy_s=10.0 * c.predict_latency_s(widths, 64), n=3)
    lat, source = c.latency_s(widths, 256, st)
    assert source == "corrected"
    assert lat == pytest.approx(10.0 * c.predict_latency_s(widths, 256),
                                rel=0.05)


def test_controller_cap_bucket_matches_shard_rounded_dispatch():
    """The batcher's dispatch buckets are shard-rounded (bucket_for),
    not always powers of two; the cap must price the bucket the
    dispatch will actually produce so the exact-measured path hits."""
    c = _ctrl(measured_min_batches=2, decision_ttl_s=0.0)
    st = ServeStats("k")
    for _ in range(3):  # warm the 12-row bucket a 6-shard mesh dispatches
        st.on_enqueue(12)
        st.on_batch(requests=1, rows=12, bucket=12, reason="t",
                    busy_s=0.003, latencies_s=[0.003])
    st.on_enqueue(10)  # 10 rows pending: pow2 says 16, observed says 12
    c.delay_for("k", st)
    dec = c.last_decision["k"]
    assert dec["cap_bucket"] == 12
    assert dec["latency_source"] == "measured"
    assert dec["batch_latency_s"] == pytest.approx(0.003)


def test_controller_cold_stats_fall_back_to_roofline_prior():
    c = _ctrl(decision_ttl_s=0.0)
    st = ServeStats("k")  # no batches completed yet
    c.delay_for("k", st)
    assert c.last_decision["k"]["latency_source"] == "roofline"


def test_controller_open_loop_flag_ignores_measurements():
    c = _ctrl(use_measured=False, decision_ttl_s=0.0)
    st = _warm_stats(c.batch_rows_for("k", None), 5.0, n=10)
    c.delay_for("k", st)
    dec = c.last_decision["k"]
    assert dec["latency_source"] == "roofline"
    assert dec["batch_latency_s"] == dec["predicted_batch_latency_s"]


def test_controller_broken_stats_degrade_to_roofline():
    class _Boom(ServeStats):
        def batch_latency_s(self, *a, **kw):
            raise RuntimeError("stats backend gone")

    c = _ctrl(decision_ttl_s=0.0)
    st = _Boom("k")
    d = c.delay_for("k", st)
    assert d is not None
    assert c.last_decision["k"]["latency_source"] == "roofline"


def test_measured_latency_flows_through_real_queue(tmp_path):
    """End to end: batches served through a real queue warm the stats,
    and the controller's next decision prices the measured latency."""
    mp = _bundle(tmp_path)
    pol = FlushPolicy(max_batch_rows=1024, max_delay_s=0.05)
    ctrl = AdaptiveFlushController(pol, warmup_requests=4,
                                   measured_min_batches=1,
                                   decision_ttl_s=0.0)
    q = ServeQueue(pol, controller=ctrl)
    for i in range(6):
        q.submit(mp, _rows(4, seed=i))
        q.flush(mp)
    assert q.stats(mp).batch_latencies()  # batches recorded
    ctrl.delay_for(mp, q.stats(mp))
    assert ctrl.last_decision[mp]["latency_source"] in ("measured",
                                                        "corrected")


# -------------------------------------------- queue/controller wiring ------
class _StubController:
    def __init__(self, delay=None, rows=None, boom=False):
        self._delay, self._rows, self._boom = delay, rows, boom

    def delay_for(self, key, stats):
        if self._boom:
            raise RuntimeError("controller crashed")
        return self._delay

    def batch_rows_for(self, key, stats):
        if self._boom:
            raise RuntimeError("controller crashed")
        return self._rows


def test_queue_adaptive_deadline_via_poll(tmp_path):
    mp = _bundle(tmp_path)
    q = ServeQueue(FlushPolicy(max_batch_rows=10 ** 6, max_delay_s=None),
                   controller=_StubController(delay=0.02, rows=10 ** 6))
    f = q.submit(mp, _rows(4))
    assert q.poll() == 0  # adaptive deadline not reached yet
    time.sleep(0.03)
    assert q.poll() == 4  # fired from the controller, static policy has none
    assert f.done()
    assert q.stats(mp).snapshot()["flush_reasons"] == {"deadline": 1}


def test_queue_adaptive_batch_trigger(tmp_path):
    mp = _bundle(tmp_path)
    q = ServeQueue(FlushPolicy(max_batch_rows=10 ** 6),
                   controller=_StubController(delay=None, rows=16))
    q.submit(mp, _rows(8, seed=1))
    f = q.submit(mp, _rows(8, seed=2))  # 16 rows: adaptive trigger fires
    assert f.done()
    assert q.stats(mp).snapshot()["flush_reasons"] == {"max_batch": 1}


def test_queue_controller_failure_degrades_to_static(tmp_path):
    mp = _bundle(tmp_path)
    q = ServeQueue(FlushPolicy(max_batch_rows=16, max_delay_s=None),
                   controller=_StubController(boom=True))
    q.submit(mp, _rows(8, seed=1))
    f = q.submit(mp, _rows(8, seed=2))  # static max-batch still applies
    assert f.done()


def test_queue_cold_controller_demand_flush_no_deadlock(tmp_path):
    """Thread + controller whose delay is None (static None, widths
    unknown): a waiting future must still make its own progress."""
    mp = _bundle(tmp_path)
    q = ServeQueue(FlushPolicy(max_batch_rows=10 ** 6, max_delay_s=None),
                   controller=_StubController(delay=None, rows=10 ** 6))
    q.start()
    try:
        f = q.submit(mp, _rows(4))
        assert f.result(timeout=5).shape == (4, 1)
    finally:
        q.stop()


def test_real_controller_end_to_end_bit_identical(tmp_path):
    mp = _bundle(tmp_path)
    pol = FlushPolicy(max_batch_rows=1024, max_delay_s=0.05)
    q = ServeQueue(pol, controller=AdaptiveFlushController(pol))
    with q:
        futs = [q.submit(mp, _rows(4, seed=i)) for i in range(10)]
        outs = [f.result(10) for f in futs]
    eng = InferenceEngine.get(mp)
    for i, o in enumerate(outs):
        np.testing.assert_array_equal(np.asarray(o),
                                      np.asarray(eng(_rows(4, seed=i))))
    st = q.stats(mp).snapshot()
    assert st["rows_completed"] == 40 and st["queue_depth_rows"] == 0


# ------------------------------------------------- engine hot path ---------
def test_apply_batched_donate_bit_identical(tmp_path):
    mp = _bundle(tmp_path, "donate")
    eng = InferenceEngine(mp)
    x = _rows(13, seed=3)
    base = np.asarray(eng(x))[:13]
    # 13 rows pad to 16 inside the batched apply: the rows that come back
    # are bit-identical to the eager call's
    batched = np.asarray(eng.apply_batched(_rows(13, seed=3)))
    np.testing.assert_array_equal(batched, base)
    # eager and batched calls share the one compiled apply of the context
    assert list(eng._applies) == [None]


def test_apply_batched_prepadded_skips_rebucket(tmp_path):
    mp = _bundle(tmp_path, "prepad")
    eng = InferenceEngine(mp)
    x16 = _rows(16, seed=4)
    out = np.asarray(eng.apply_batched(_rows(16, seed=4), prepadded=True))
    np.testing.assert_array_equal(out, np.asarray(eng(x16)))


def test_engine_meshless_ctx_shares_compile_cache(tmp_path):
    from repro.dist.sharding import use_mesh
    mp = _bundle(tmp_path, "norm")
    eng = InferenceEngine(mp)
    x = _rows(8, seed=5)
    eng(x)
    with use_mesh(None):  # the batcher's no-mesh request ctx
        eng(x)
    assert len(eng._applies) == 1  # same compiled apply, no duplicate


# ------------------------------------------------------ scratch pool -------
def test_scratch_pool_reuses_only_free_buffers():
    p = ScratchPool()
    a = p.take((8, 4), np.float32)
    a[:] = 1.0
    b = p.take((8, 4), np.float32)  # `a` alive: must get fresh memory
    b[:] = 2.0
    assert (a == 1.0).all() and p.stats()["misses"] == 2
    del a, b
    c = p.take((8, 4), np.float32)  # views dropped: pool hit
    assert p.stats()["hits"] == 1
    del c


def test_scratch_pool_row_views_pin_buffer():
    p = ScratchPool()
    buf = p.take((16, 2), np.float32)
    buf[:] = 7.0
    view = buf[3:5]
    del buf
    nxt = p.take((16, 2), np.float32)  # row view alive: no reuse
    nxt[:] = 0.0
    assert (view == 7.0).all()


def test_scratch_pool_grows_and_handles_empty():
    p = ScratchPool()
    small = p.take((4,), np.float32)
    del small
    big = p.take((1024, 8), np.float64)  # larger than any pooled buffer
    assert big.shape == (1024, 8)
    z = p.take((0, 4), np.float32)
    assert z.shape == (0, 4)


def test_batcher_scratch_gather_bit_identical_across_flushes(tmp_path):
    mp = _bundle(tmp_path, "scatter")
    q = ServeQueue(FlushPolicy(max_batch_rows=1024))
    eng = InferenceEngine.get(mp)
    for round_ in range(3):  # repeated flushes reuse the pooled buffers
        futs = [q.submit(mp, _rows(3, seed=10 * round_ + i))
                for i in range(3)]
        q.flush()
        for i, f in enumerate(futs):
            np.testing.assert_array_equal(
                np.asarray(f.result(1)),
                np.asarray(eng(_rows(3, seed=10 * round_ + i))))
    pool = q._batcher.scratch.stats()
    assert pool["hits"] > 0  # steady state is allocation-free
