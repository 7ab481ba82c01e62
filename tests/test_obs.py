"""repro.obs: tracing (span taxonomy, trace-id propagation across
threads and pod processes), the metrics registry (Prometheus text
contract, warn-once fallback visibility), coverage analysis, and the
pod flight recorder.

The spawned test mirrors tests/test_multihost.py's idiom: a module-level
worker referenced as ``"test_obs:<fn>"`` runs inside a real 2-process
``jax.distributed`` pod with ``REPRO_TRACE=1``, so the obs layer is
exercised exactly as ``dryrun --pod-smoke --obs`` runs it.
"""
import json
import logging

import jax
import numpy as np
import pytest

from repro.obs import (TRACER, MetricsRegistry, default_registry,
                       disable_tracing, enable_tracing, merge_chrome_traces,
                       request_coverage, warn_once)
from repro.obs.metrics import note_static_fallback
from repro.serve import FlushPolicy, ServeQueue


@pytest.fixture(autouse=True)
def _tracer_reset():
    """Tracing is process-global state: leave it as these tests found it
    (off, empty rings) so tier-1 neighbors never see stray spans."""
    yield
    TRACER.enabled = False
    TRACER.annotate = False
    TRACER.clear()


def _bundle(tmp, seed=0):
    from repro.nn import MLP
    from repro.nn.serialize import save_model
    net = MLP((1, 2), [16], 1)
    return save_model(tmp / "m", net, net.init(jax.random.PRNGKey(seed)))


def _rows(n, seed=0):
    return np.random.default_rng(seed).normal(size=(n, 2)).astype(np.float32)


# ------------------------------------------------------------ tracer unit ---

def test_disabled_tracer_records_nothing():
    disable_tracing()
    TRACER.record("x", 0.0, 1.0)
    TRACER.instant("y")
    with TRACER.span("z"):
        pass
    assert TRACER.events() == [] or all(
        s.name not in ("x", "y", "z") for s in TRACER.events())


def test_span_context_and_record_land_in_ring():
    enable_tracing()
    TRACER.clear()
    with TRACER.span("work", cat="test", trace="t1", args={"k": 1}):
        pass
    TRACER.record("past", 1.0, 2.0, cat="test", trace="t1")
    TRACER.instant("mark", cat="test")
    by_name = {s.name: s for s in TRACER.events()}
    assert by_name["work"].trace == "t1" and by_name["work"].args == {"k": 1}
    assert by_name["work"].dur_s >= 0.0
    assert by_name["past"].dur_s == pytest.approx(1.0)
    assert by_name["mark"].t0 == by_name["mark"].t1  # instant


def test_ring_evicts_oldest_per_thread():
    t = type(TRACER)(ring_size=4)
    t.enable()
    for i in range(10):
        t.record(f"s{i}", 0.0, 1.0)
    names = [s.name for s in t.events()]
    assert names == ["s6", "s7", "s8", "s9"]


def test_trace_ids_are_unique_and_pid_prefixed():
    import os
    ids = {TRACER.new_trace_id() for _ in range(100)}
    assert len(ids) == 100
    assert all(i.startswith(f"{os.getpid():x}.") for i in ids)


def test_chrome_events_format_and_export(tmp_path):
    enable_tracing()
    TRACER.clear()
    with TRACER.span("dur", cat="c", trace="tr.1", args={"a": 2}):
        pass
    TRACER.instant("pt", cat="c")
    evs = TRACER.chrome_events()
    dur = next(e for e in evs if e["name"] == "dur")
    pt = next(e for e in evs if e["name"] == "pt")
    # trace id merges into args; ph X carries dur, instants carry scope
    assert dur["ph"] == "X" and dur["args"] == {"a": 2, "trace": "tr.1"}
    assert "dur" in dur and dur["cat"] == "c"
    assert pt["ph"] == "i" and pt["s"] == "t"
    out = tmp_path / "trace.json"
    TRACER.export_chrome_trace(out)
    doc = json.loads(out.read_text())
    assert {e["name"] for e in doc["traceEvents"]} >= {"dur", "pt"}
    # timestamps are wall-clock microseconds (mergeable across processes)
    import time
    assert abs(dur["ts"] / 1e6 - time.time()) < 60.0


def test_merge_chrome_traces_sorts_by_ts(tmp_path):
    a = [{"name": "b", "ts": 2.0}, {"name": "a", "ts": 1.0}]
    b = [{"name": "c", "ts": 1.5}]
    out = tmp_path / "merged.json"
    merged = merge_chrome_traces([a, b], out)
    assert [e["name"] for e in merged] == ["a", "c", "b"]
    assert json.loads(out.read_text())["traceEvents"] == merged


def test_request_coverage_union_and_gaps():
    def ev(trace, ts, dur):
        return {"name": "s", "ph": "X", "ts": ts, "dur": dur,
                "args": {"trace": trace}}
    events = [
        ev("full", 0.0, 50.0), ev("full", 50.0, 50.0),     # tiles [0,100]
        ev("gappy", 0.0, 25.0), ev("gappy", 75.0, 25.0),   # hole [25,75]
        ev("overlap", 0.0, 80.0), ev("overlap", 40.0, 60.0),
        {"name": "noise", "ph": "i", "ts": 1.0, "args": {"trace": "full"}},
        {"name": "untagged", "ph": "X", "ts": 0.0, "dur": 9.0, "args": {}},
    ]
    cov = request_coverage(events)
    assert set(cov) == {"full", "gappy", "overlap"}
    assert cov["full"]["coverage"] == pytest.approx(1.0)
    assert cov["full"]["spans"] == 2          # the instant does not count
    assert cov["gappy"]["coverage"] == pytest.approx(0.5)
    assert cov["overlap"]["coverage"] == pytest.approx(1.0)
    assert cov["overlap"]["window_us"] == pytest.approx(100.0)


# --------------------------------------------------------------- metrics ----

def test_counter_gauge_histogram_roundtrip():
    reg = MetricsRegistry()
    c = reg.counter("c_total", "help", ("k",))
    c.inc(2, k="a")
    c.inc(k="a")
    assert c.value(k="a") == 3.0 and c.value(k="b") == 0.0
    g = reg.gauge("g", "help", ("k",))
    g.set(5, k="x")
    g.inc(-2, k="x")
    assert g.value(k="x") == 3.0
    h = reg.histogram("h_seconds", "help", ("k",), buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v, k="q")
    snap = h.snapshot(k="q")
    assert snap["count"] == 3 and snap["sum"] == pytest.approx(5.55)
    assert snap["buckets"] == {0.1: 1, 1.0: 2}  # cumulative


def test_metric_label_mismatch_raises():
    reg = MetricsRegistry()
    c = reg.counter("c_total", "", ("k",))
    with pytest.raises(ValueError, match="labels"):
        c.inc(1, wrong="a")
    with pytest.raises(ValueError, match="re-registered"):
        reg.gauge("c_total", "", ("k",))


def test_prometheus_dump_contract():
    reg = MetricsRegistry()
    reg.counter("req_total", "requests served", ("key",)).inc(
        4, key='p"ath\nx')
    h = reg.histogram("lat_seconds", "latency", ("key",), buckets=(0.5,))
    h.observe(0.25, key="a")
    h.observe(2.0, key="a")
    text = reg.dump()
    assert "# HELP req_total requests served" in text
    assert "# TYPE req_total counter" in text
    # label values escape quotes and newlines per the exposition format
    assert 'req_total{key="p\\"ath\\nx"} 4' in text
    assert "# TYPE lat_seconds histogram" in text
    assert 'lat_seconds_bucket{key="a",le="0.5"} 1' in text
    assert 'lat_seconds_bucket{key="a",le="+Inf"} 2' in text
    assert 'lat_seconds_sum{key="a"} 2.25' in text
    assert 'lat_seconds_count{key="a"} 2' in text


def test_collect_is_json_roundtrippable():
    reg = MetricsRegistry()
    reg.counter("c_total", "h", ("k",)).inc(1, k="v")
    reg.histogram("h_s", "h", (), buckets=(1.0,)).observe(0.5)
    data = json.loads(json.dumps(reg.collect()))
    assert data["c_total"]["type"] == "counter"
    assert data["c_total"]["values"][0] == {"labels": {"k": "v"},
                                            "value": 1.0}
    assert data["h_s"]["values"][0]["count"] == 1


def test_warn_once_logs_once_counts_every(caplog):
    tag = "test-warn-once-unique-tag"
    c = default_registry().counter("repro_obs_warnings_total",
                                   "warn_once firings by tag", ("tag",))
    before = c.value(tag=tag)
    with caplog.at_level(logging.WARNING, logger="repro.obs"):
        warn_once(tag, "the message")
        warn_once(tag, "the message")
    assert c.value(tag=tag) == before + 2
    assert sum("the message" in r.message for r in caplog.records) == 1


# ------------------------------------------- serve-path instrumentation ----

def test_trace_id_rides_submit_to_dispatcher_thread(tmp_path):
    """Satellite contract: the id minted at submit appears in spans from
    the submitter thread (queue.submit) and the dispatcher thread
    (serve.request), and together they tile enqueue->resolve."""
    mp_path = _bundle(tmp_path)
    enable_tracing()
    TRACER.clear()
    q = ServeQueue(FlushPolicy(max_batch_rows=1 << 30,
                               max_delay_s=0.005)).start()
    try:
        fut = q.submit(mp_path, _rows(3))
        fut.result(30)
    finally:
        q.stop()
    spans = TRACER.events()
    sub = next(s for s in spans if s.name == "queue.submit")
    assert sub.trace is not None
    req = next(s for s in spans if s.name == "serve.request"
               and s.trace == sub.trace)
    # recorded from different threads, same request id
    assert req.thread == "repro-serve-dispatch"
    assert sub.thread != req.thread
    # the batch's apply span rode the same dispatch
    assert any(s.name == "batch.apply" for s in spans)
    cov = request_coverage(TRACER.chrome_events())
    assert cov[sub.trace]["coverage"] >= 0.95


def test_inline_flush_spans_single_thread(tmp_path):
    """Thread-free queues flush inline: both spans come from the
    submitting thread but still share the request's trace id."""
    mp_path = _bundle(tmp_path)
    enable_tracing()
    TRACER.clear()
    q = ServeQueue(FlushPolicy(max_batch_rows=2))  # 3 rows > 2: inline
    q.submit(mp_path, _rows(3)).result(30)
    spans = TRACER.events()
    sub = next(s for s in spans if s.name == "queue.submit")
    req = next(s for s in spans if s.name == "serve.request")
    assert sub.trace == req.trace and sub.thread == req.thread


def _async_region(mp_path, queue, n=4):
    from repro.core import approx_ml, tensor_functor

    def accurate(x):
        raise AssertionError("the surrogate serves every call here")

    rngs = {"i": (0, n)}
    return approx_ml(
        accurate, name="obs-region",
        inputs={"x": (tensor_functor("oin: [i, 0:2] = ([i, 0:2])"), rngs)},
        outputs={"y": (tensor_functor("oout: [i, 0:1] = ([i, 0:1])"),
                       rngs)},
        mode="infer_async", model=mp_path, serving=queue)


def _region_round_trip(reg, queue, mp_path, n=4):
    handle = reg(x=_rows(n))
    queue.flush(mp_path)
    return handle.result(30)


_REGION_TREE = {"region.call": ("engine.get", "region.bridge_in",
                                "queue.submit"),
                "region.result": ("region.wait", "region.bridge_out")}


def test_region_call_span_tree_shares_one_trace_id(tmp_path):
    """An eager infer_async call and its result(): region.call holds
    engine.get, the input bridge and queue.submit; region.result holds
    the wait and the output bridge; all of them, and the dispatcher's
    serve.request, carry the one id the region call minted."""
    mp_path = _bundle(tmp_path)
    q = ServeQueue(FlushPolicy(max_batch_rows=1 << 30))
    reg = _async_region(mp_path, q)
    _region_round_trip(reg, q, mp_path)  # compiles outside the trace
    enable_tracing()
    TRACER.clear()
    out = _region_round_trip(reg, q, mp_path)
    assert out["y"].shape == (4, 1)
    by_name = {}
    for s in TRACER.events():
        assert s.name not in by_name, f"{s.name} recorded twice"
        by_name[s.name] = s
    call = by_name["region.call"]
    assert call.args == {"region": "obs-region", "rows": 4}
    assert by_name["queue.submit"].args["rows"] == 4
    for parent, children in _REGION_TREE.items():
        p = by_name[parent]
        for child in children:
            c = by_name[child]
            assert p.t0 <= c.t0 <= c.t1 <= p.t1, (parent, child)
    assert call.t1 <= by_name["region.result"].t0
    ids = {by_name[n].trace for n in ("serve.request", *_REGION_TREE,
                                      *sum(_REGION_TREE.values(), ()))}
    assert ids == {call.trace} and call.trace is not None
    # call to bridge-out: only the caller's own flush lies outside them
    cov = request_coverage(TRACER.chrome_events())[call.trace]
    assert cov["spans"] == 8


def test_every_interval_span_but_serve_request_reaches_profiler(
        tmp_path, monkeypatch):
    """With annotate on, every interval span of the served path is also
    a profiler TraceAnnotation; serve.request, stamped after the fact
    from its enqueue time, is the one that is not."""
    entered = []

    class StubAnnotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            entered.append(self.name)
            return self

        def __exit__(self, *exc):
            return False

    mp_path = _bundle(tmp_path)
    q = ServeQueue(FlushPolicy(max_batch_rows=1 << 30))
    reg = _async_region(mp_path, q)
    _region_round_trip(reg, q, mp_path)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", StubAnnotation)
    enable_tracing(annotate=True)
    TRACER.clear()
    _region_round_trip(reg, q, mp_path)
    recorded = {s.name for s in TRACER.events() if s.t1 > s.t0}
    assert {"region.call", "region.result", "batch.scatter",
            "serve.request"} <= recorded
    assert recorded - {"serve.request"} == set(entered)
    assert "serve.request" not in entered


def test_untraced_region_call_records_nothing(tmp_path, monkeypatch):
    """Tracing off: the region path records no span and mints no id."""
    mp_path = _bundle(tmp_path)
    q = ServeQueue(FlushPolicy(max_batch_rows=1 << 30))
    reg = _async_region(mp_path, q)
    disable_tracing()
    TRACER.clear()

    def no_id():
        raise AssertionError("a trace id was minted with tracing off")

    monkeypatch.setattr(TRACER, "new_trace_id", no_id)
    handle = reg(x=_rows(4))
    q.flush(mp_path)
    assert handle.result(30)["y"].shape == (4, 1)
    assert handle._future.trace is None
    assert TRACER.events() == []


def test_compile_listener_counts_new_shapes_only(tmp_path):
    """One XLA compile for a batch shape the engine has not run, none for
    a repeat; with tracing on each compile is an xla.compile span."""
    from repro.core.engine import InferenceEngine
    reg = default_registry()
    compiles = reg.counter("repro_xla_compile_total",
                           "XLA backend compiles in this process")
    eng = InferenceEngine.get(_bundle(tmp_path, seed=3))
    x = jax.device_put(_rows(8))
    enable_tracing()
    TRACER.clear()
    before = compiles.value()
    jax.block_until_ready(eng.apply_batched(x))
    assert compiles.value() == before + 1
    jax.block_until_ready(eng.apply_batched(x))
    assert compiles.value() == before + 1
    spans = [s for s in TRACER.events() if s.name == "xla.compile"]
    assert len(spans) == 1
    assert spans[0].args == {"fun": "jit(apply_fn)"}
    assert spans[0].dur_s > 0


def test_batched_apply_module_is_named_jit_apply_fn(tmp_path):
    """The benchmark's apply_roofline finds the engine's apply in the
    device trace by the XLA module name ``jit_apply_fn``."""
    from repro.core.engine import InferenceEngine
    from repro.dist.sharding import current_ctx
    eng = InferenceEngine.get(_bundle(tmp_path, seed=4))
    x = jax.device_put(_rows(8))
    fn = eng._apply_for(current_ctx())
    hlo = fn.lower(eng.params, x).compile().as_text()
    assert hlo.startswith("HloModule jit_apply_fn"), hlo[:80]


def test_pod_flush_single_process_traced(tmp_path):
    mp_path = _bundle(tmp_path)
    enable_tracing()
    TRACER.clear()
    q = ServeQueue(FlushPolicy(max_batch_rows=1 << 30))
    fut = q.submit(mp_path, _rows(4))
    q.pod_flush(mp_path)
    fut.result(30)
    spans = TRACER.events()
    sub = next(s for s in spans if s.name == "queue.submit")
    req = next(s for s in spans if s.name == "serve.request")
    assert sub.trace == req.trace
    agree = next(s for s in spans if s.name == "pod.agree")
    assert agree.cat == "pod"


def test_untraced_requests_have_no_trace_id(tmp_path):
    mp_path = _bundle(tmp_path)
    disable_tracing()
    q = ServeQueue(FlushPolicy(max_batch_rows=1 << 30))
    fut = q.submit(mp_path, _rows(2))
    q.flush(mp_path)
    fut.result(30)
    assert all(s.name != "queue.submit" for s in TRACER.events())


def test_serve_metrics_published(tmp_path):
    mp_path = _bundle(tmp_path)
    q = ServeQueue(FlushPolicy(max_batch_rows=1 << 30))
    reg = default_registry()
    rows_done = reg.counter("repro_serve_rows_completed_total",
                            "rows completed", ("key",))
    before = rows_done.value(key=mp_path)
    q.submit(mp_path, _rows(6)).result(30)
    assert rows_done.value(key=mp_path) == before + 6
    assert reg.gauge("repro_serve_queue_depth_rows", "pending rows",
                     ("key",)).value(key=mp_path) == 0
    text = reg.dump()
    for family in ("repro_serve_queue_depth_rows",
                   "repro_serve_batch_occupancy",
                   "repro_serve_batch_latency_seconds_bucket",
                   "repro_serve_request_latency_seconds_bucket"):
        assert family in text


def test_latency_window_knob(tmp_path):
    """Satellite contract: the stats latency window is a ServeQueue
    constructor knob, and snapshot percentiles honor it."""
    mp_path = _bundle(tmp_path)
    q = ServeQueue(FlushPolicy(max_batch_rows=1 << 30), latency_window=4)
    st = q.stats(mp_path)
    assert st.latency_window == 4 and st._lat.maxlen == 4
    for lat in (1.0, 2.0, 3.0, 4.0, 5.0, 6.0):
        st.on_batch(requests=1, rows=1, bucket=8, reason="t",
                    busy_s=0.0, latencies_s=[lat])
    snap = st.snapshot()
    # only the newest 4 latencies (3..6s) survive the window
    assert snap["latency_p50_ms"] == pytest.approx(4500.0)
    assert ServeQueue(FlushPolicy()).latency_window == 2048  # default


def test_controller_error_degrades_with_warning(tmp_path):
    """Satellite contract: a controller failure serves the static policy
    and surfaces through the metrics layer with the offending key."""
    class BoomController:
        def delay_for(self, key, stats):
            raise RuntimeError("boom")

        def batch_rows_for(self, key, stats):
            raise RuntimeError("boom")

    mp_path = _bundle(tmp_path)
    fallback = default_registry().counter(
        "repro_controller_static_fallback_total",
        "adaptive-controller decisions degraded to the static policy",
        ("key", "reason"))
    before = fallback.value(key=mp_path, reason="controller-error")
    q = ServeQueue(FlushPolicy(max_batch_rows=4),
                   controller=BoomController())
    q.submit(mp_path, _rows(6)).result(30)  # 6 > 4: static trigger fires
    assert fallback.value(key=mp_path,
                          reason="controller-error") > before


def test_snapshot_sorts_outside_lock(tmp_path):
    """Satellite regression guard: snapshot() must not sort the window
    while holding the stats lock (on_batch from the dispatcher must not
    contend with a monitor thread's percentile scan).  Structural check:
    the full window sort happens on a copy, leaving the deque order
    untouched."""
    from repro.serve.stats import ServeStats
    st = ServeStats("k", latency_window=8)
    st.on_batch(requests=1, rows=1, bucket=8, reason="t", busy_s=0.0,
                latencies_s=[3.0, 1.0, 2.0])
    snap = st.snapshot()
    assert snap["latency_p50_ms"] == pytest.approx(2000.0)
    assert list(st._lat) == [3.0, 1.0, 2.0]  # insertion order preserved


# ------------------------------------------------------ kernel provenance ---

def test_resolve_params_info_provenance(monkeypatch):
    from repro.kernels import registry as kreg
    spec = kreg.get_spec("fused_mlp")
    problem = {"widths": (2, 16, 1), "acts": ("relu", "identity"),
               "dtype": "float32", "batch": 64}
    monkeypatch.setattr(kreg, "tuned_params", lambda s, p: {})  # untuned
    params, prov = kreg.resolve_params_info(spec, problem, None)
    assert prov == "default" and params == spec.defaults()
    params, prov = kreg.resolve_params_info(spec, problem,
                                            {"batch_tile": 16})
    assert prov == "explicit" and params["batch_tile"] == 16
    # a tuned winner flips provenance to tuned
    monkeypatch.setattr(kreg, "tuned_params",
                        lambda s, p: {"batch_tile": 32})
    params, prov = kreg.resolve_params_info(spec, problem, None)
    assert prov == "tuned" and params["batch_tile"] == 32


def test_resolve_params_vmem_fallback(monkeypatch):
    from repro.kernels import registry as kreg
    spec = kreg.get_spec("fused_mlp")
    problem = {"widths": (2, 16, 1), "acts": ("relu", "identity"),
               "dtype": "float32", "batch": 64}
    monkeypatch.setattr(spec, "fits", lambda p, params, budget=None: False)
    params, prov = kreg.resolve_params_info(spec, problem,
                                            {"batch_tile": 4096})
    assert prov == "default:vmem-fallback" and params == spec.defaults()


# ------------------------------------------------------- flight recorder ----

def test_local_and_pod_snapshot_single_process():
    from repro.obs import local_snapshot, pod_snapshot
    enable_tracing()
    TRACER.clear()
    TRACER.instant("snap.mark", cat="test")
    local = local_snapshot()
    assert any(e["name"] == "snap.mark" for e in local["events"])
    assert isinstance(local["metrics"], dict) and "pid" in local
    snaps = pod_snapshot()
    assert len(snaps) == 1 and snaps[0]["process"] == local["process"]


def test_allgather_bytes_single_process():
    from repro.launch import multihost
    out = multihost.allgather_bytes(b"payload \x00\xff")
    assert out == [b"payload \x00\xff"]
    assert multihost.allgather_bytes(b"") == [b""]


def test_metrics_report_renders_markdown(tmp_path, capsys):
    from repro.obs import metrics_report
    reg = MetricsRegistry()
    reg.counter("c_total", "a counter", ("k",)).inc(3, k="x")
    reg.histogram("lat_seconds", "latency", ("k",),
                  buckets=(0.1, 1.0)).observe(0.5, k="x")
    mpath = tmp_path / "metrics.json"
    mpath.write_text(json.dumps(reg.collect()))
    enable_tracing()
    TRACER.clear()
    TRACER.record("batch.apply", 0.0, 0.010, cat="batch")
    tpath = tmp_path / "trace.json"
    TRACER.export_chrome_trace(tpath)
    rc = metrics_report.main(["--metrics", str(mpath), "--trace",
                              str(tpath), "--markdown"])
    assert rc in (0, None)
    out = capsys.readouterr().out
    assert "c_total" in out and "lat_seconds" in out
    assert "batch.apply" in out


# --------------------------------------------- exposition edge cases (7) ----

def test_dump_escapes_backslash_quote_newline():
    from repro.obs.server import validate_exposition
    reg = MetricsRegistry()
    raw = 'a\\b"c\nd'
    reg.counter("esc_total", "h", ("k",)).inc(1, k=raw)
    text = reg.dump()
    assert 'esc_total{k="a\\\\b\\"c\\nd"} 1' in text
    # the validator's unescape recovers the original value exactly
    # (backslash first, so \\n is a backslash + n, not a newline)
    samples = [ln for ln in text.splitlines()
               if ln.startswith("esc_total{")]
    assert len(samples) == 1
    validate_exposition(text)


def test_dump_renders_nan_and_infinities():
    import math
    from repro.obs.server import validate_exposition
    reg = MetricsRegistry()
    g = reg.gauge("weird", "h", ("k",))
    g.set(float("nan"), k="n")
    g.set(float("inf"), k="p")
    g.set(float("-inf"), k="m")
    text = reg.dump()
    assert 'weird{k="n"} NaN' in text
    assert 'weird{k="p"} +Inf' in text
    assert 'weird{k="m"} -Inf' in text
    info = validate_exposition(text)
    assert info["samples"] == 3
    # %g would have emitted 'nan'/'inf', which Prometheus rejects
    assert "} nan" not in text and "} inf" not in text


def test_empty_registry_dumps_and_validates():
    from repro.obs.server import validate_exposition
    reg = MetricsRegistry()
    assert validate_exposition(reg.dump()) == {"samples": 0,
                                               "families": {}}
    # registered-but-never-observed families still emit HELP/TYPE only
    reg.counter("quiet_total", "h", ("k",))
    info = validate_exposition(reg.dump())
    assert info == {"samples": 0, "families": {"quiet_total": "counter"}}


def test_histogram_dump_satisfies_exposition_contract():
    from repro.obs.server import validate_exposition
    reg = MetricsRegistry()
    h = reg.histogram("lat_s", "h", ("k",), buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 0.5, 7.0):
        h.observe(v, k="a")
    h.observe(2.5, k="b")
    text = reg.dump()
    # +Inf bucket == _count per labelset, buckets cumulative
    assert 'lat_s_bucket{k="a",le="+Inf"} 4' in text
    assert 'lat_s_count{k="a"} 4' in text
    assert 'lat_s_bucket{k="b",le="0.1"} 0' in text
    info = validate_exposition(text)
    assert info["families"]["lat_s"] == "histogram"


# ----------------------------------------------- ring drop counter (sat 1) ---

def test_ring_eviction_counts_drops():
    t = type(TRACER)(ring_size=4)
    t.enable()
    for i in range(10):
        t.record(f"s{i}", 0.0, 1.0)
    counts = t.drop_counts()
    assert sum(counts.values()) == 6  # 10 recorded - 4 retained
    t.clear()  # clear keeps the drop totals (they are cumulative)
    assert sum(t.drop_counts().values()) == 6


def test_publish_drop_counts_is_delta_based():
    enable_tracing()
    TRACER.clear()
    c = default_registry().counter(
        "repro_trace_dropped_total",
        "spans evicted from a full per-thread trace ring", ("thread",))
    import threading
    label = threading.current_thread().name
    TRACER.publish_drop_counts()   # flush any prior sessions' deltas
    before = c.value(thread=label)
    overflow = TRACER.ring_size + 5
    for i in range(overflow):
        TRACER.record(f"d{i}", 0.0, 1.0)
    assert TRACER.publish_drop_counts() >= 5
    assert c.value(thread=label) == before + 5
    # publishing again without new evictions adds nothing (delta, not
    # cumulative re-add)
    TRACER.publish_drop_counts()
    assert c.value(thread=label) == before + 5


# ------------------------------------- report quantiles + --json (sat 2) ----

def test_quantile_interpolation_from_buckets():
    from repro.obs.metrics_report import quantile_from_buckets
    # 10 obs uniform in (0,1], 10 in (1,2]: p50 = 1.0, p75 = 1.5
    buckets = {1.0: 10, 2.0: 20, float("inf"): 20}
    assert quantile_from_buckets(buckets, 20, 0.50) == pytest.approx(1.0)
    assert quantile_from_buckets(buckets, 20, 0.75) == pytest.approx(1.5)
    # first bucket interpolates from lower bound 0
    assert quantile_from_buckets(buckets, 20, 0.25) == pytest.approx(0.5)
    # quantile in the +Inf bucket clamps to the largest finite bound
    buckets = {1.0: 10, float("inf"): 40}
    assert quantile_from_buckets(buckets, 40, 0.99) == 1.0
    assert quantile_from_buckets({}, 0, 0.5) is None


def test_metrics_report_json_mode(tmp_path, capsys):
    from repro.obs import metrics_report
    reg = MetricsRegistry()
    reg.counter("c_total", "h", ("k",)).inc(3, k="x")
    h = reg.histogram("lat_seconds", "h", ("k",), buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 0.7, 0.9):
        h.observe(v, k="x")
    mpath = tmp_path / "metrics.json"
    mpath.write_text(json.dumps(reg.collect()))
    rc = metrics_report.main(["--metrics", str(mpath), "--json"])
    assert rc == 0
    doc = json.loads(capsys.readouterr().out)
    snap = doc["snapshots"][0]
    assert snap["metrics"]["c_total"]["values"][0]["value"] == 3.0
    hq = snap["histogram_quantiles"]["lat_seconds"][0]
    assert hq["count"] == 4
    assert 0.0 < hq["p50"] <= hq["p90"] <= hq["p99"] <= 1.0


def test_metrics_report_renders_quality_section(tmp_path, capsys):
    from repro.obs import metrics_report
    reg = MetricsRegistry()
    reg.gauge("repro_quality_rmse", "h", ("key",)).set(0.02, key="b1")
    reg.gauge("repro_quality_alert_state", "h", ("key",)).set(2, key="b1")
    reg.counter("repro_quality_samples_total", "h",
                ("key", "region")).inc(7, key="b1", region="r")
    mpath = tmp_path / "metrics.json"
    mpath.write_text(json.dumps(reg.collect()))
    metrics_report.main(["--metrics", str(mpath), "--markdown"])
    out = capsys.readouterr().out
    assert "Surrogate quality (shadow-scored)" in out
    assert "| b1 | 0.02 |" in out and "CRITICAL" in out


# ------------------------------------------------- spawned 2-process pod ----

def _traced_pod_worker():
    """Runs inside a spawned pod process with REPRO_TRACE=1: submit,
    collective pod_flush, then report this host's spans + snapshot."""
    import numpy as np

    from repro.dist.sharding import use_mesh
    from repro.launch.mesh import make_pod_mesh
    from repro.obs import TRACER, pod_snapshot
    from repro.serve import FlushPolicy, ServeQueue

    import jax
    pid = jax.process_index()
    import pathlib
    import tempfile
    tmp = pathlib.Path(tempfile.mkdtemp(prefix=f"obs_pod_{pid}_"))
    from repro.nn import MLP
    from repro.nn.serialize import save_model
    net = MLP((1, 2), [16], 1)
    # every host loads identical weights (seed 0): one shared bundle key
    mp_path = save_model(tmp / "m", net, net.init(jax.random.PRNGKey(0)))

    q = ServeQueue(FlushPolicy(max_batch_rows=1 << 30))
    x = np.full((3, 2), float(pid), np.float32)
    with use_mesh(make_pod_mesh(), multi_pod=True):
        fut = q.submit(mp_path, x)
        q.pod_flush(mp_path)
        fut.result(60)
    spans = [{"name": s.name, "trace": s.trace, "cat": s.cat}
             for s in TRACER.events()]
    snaps = pod_snapshot()  # collective: every host must reach this
    return {"pid": pid, "enabled": TRACER.enabled, "spans": spans,
            "snap_processes": sorted(s["process"] for s in snaps),
            "snap_events": sum(len(s["events"]) for s in snaps)}


@pytest.mark.slow
def test_pod_flush_trace_ids_across_two_processes():
    """Satellite contract, collective leg: each host's request id rides
    its pod_flush dispatch, and pod_snapshot all-gathers both hosts'
    rings (REPRO_TRACE=1 injected by the harness, as dryrun --obs
    does)."""
    from repro.launch import multihost
    res = multihost.spawn_local_pod(
        2, "test_obs:_traced_pod_worker", devices_per_host=2,
        timeout_s=300.0, extra_env={"REPRO_TRACE": "1"})
    assert [r["pid"] for r in res] == [0, 1]
    for r in res:
        assert r["enabled"]
        sub = next(s for s in r["spans"] if s["name"] == "queue.submit")
        req = next(s for s in r["spans"] if s["name"] == "serve.request")
        assert sub["trace"] is not None and sub["trace"] == req["trace"]
        assert any(s["name"] == "pod.agree" for s in r["spans"])
        # the flight recorder gathered both hosts' rings on every host
        assert r["snap_processes"] == [0, 1]
        assert r["snap_events"] > 0
    # ids minted on different processes never collide in a merged trace
    t0 = next(s["trace"] for s in res[0]["spans"]
              if s["name"] == "queue.submit")
    t1 = next(s["trace"] for s in res[1]["spans"]
              if s["name"] == "queue.submit")
    assert t0 != t1
