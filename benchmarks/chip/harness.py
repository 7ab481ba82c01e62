"""The benchmark's harness: find a cell by name, set it up from the seed,
drive its closed loop through the program's region path, read its
metrics, and decide ``correct``.

Everything that belongs to one architecture, configuration, traffic mix
or metric is a file found by its name (``BENCHMARK.json`` names them):

- ``configs/<config>.json``: the surrogate's ``arch`` and its sizes,
  tier, region declaration, input features and their ranges, the limits
  of the comparison (``check``), and optionally the smaller sizes its
  CPU tests run at (``cpu_size``, see ``cpu_config``);
- ``archs/<arch>.py``, named by the configuration's ``arch``: the
  functions of ``ARCH_API``, which make the seeded weights, the bundle
  the program loads, the callers' inputs, the f32 forward pass of the
  reference and the algorithm's FLOPs and bytes.  Nothing else varies by
  architecture;
- ``traffic/<traffic>.json``: loop kind, callers per step, rows per
  caller, distinct steps, steps sampled for the comparison;
- ``metrics/<metric>.py``: a ``read(rec)`` that returns the metric's
  value from a run's record, or ``None`` where it finds nothing to read.

The path a step drives is the user's: every caller makes one
``approx_ml`` region call (``mode="infer_async"``) on the cell's
``ServeQueue``, the step flushes the queue once, and every caller takes
its rows through ``result()``; the step ends when all of them are ready.
From the program the harness takes only that path and its spans and
counters; weights, inputs, the reference and the comparison are its own.
"""
from __future__ import annotations

import contextlib
import functools
import gc
import importlib.util
import json
import pathlib
import random
import re
import shutil
import sys
import tempfile
import time

import jax
import numpy as np

import reference
import trace_reduce

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]

WARMUP_STEPS = 2          # the first compiles (or loads the cache)
RESULT_TIMEOUT_S = 60.0   # a caller waits this long for its rows
TRACE_SECONDS = 4.0       # longest traced window of a --trace 1 run
TRACE_RING = 1 << 18      # program tracer entries per thread


#: what ``archs/<arch>.py`` provides:
#: ``make_weights(config, seed) -> model``, the seeded weights and
#: normalization the reference and the bundle both use;
#: ``write_bundle(path, config, model) -> str``, the bundle the program
#: loads by path; ``make_inputs(config, traffic, seed)``, every caller's
#: rows at every distinct step, ``inputs[step][caller]``;
#: ``forward(config, model, x, dot)``, the f32 forward pass with every
#: product through ``dot`` (``reference.DOTS``), which returns the
#: outputs, or ``(outputs, defined)`` with one boolean a row that is False
#: where the reference's own arithmetic leaves the row's answer undefined
#: at f32 (a routing decision within a margin the configuration states);
#: ``flops_per_row(config) -> int`` and ``call_bytes(config, rows) ->
#: int``, the algorithm's counts (``work.py`` gives the rule)
ARCH_API = ("make_weights", "write_bundle", "make_inputs", "forward",
            "flops_per_row", "call_bytes")


#: keys a configuration's ``cpu_size`` may never replace
CPU_FIXED = ("arch", "tier", "matmul_precision", "region", "features",
             "check")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ------------------------------------------------------- finding by name ---
def load_benchmark(root=ROOT) -> dict:
    return json.loads((pathlib.Path(root) / "BENCHMARK.json").read_text())


def load_part(kind: str, name: str, base=HERE) -> dict:
    return json.loads((pathlib.Path(base) / kind / f"{name}.json")
                      .read_text())


def _load_module(kind: str, name: str, base):
    path = pathlib.Path(base) / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(path)
    mod_name = f"chipbench_{kind}_" + re.sub(r"\W", "_", name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str, base=HERE):
    """``read`` of ``metrics/<name>.py``."""
    return _load_module("metrics", name, base).read


def load_arch(name: str, base=HERE):
    """The module ``archs/<name>.py``, which provides ``ARCH_API``."""
    mod = _load_module("archs", name, base)
    missing = [f for f in ARCH_API if not callable(getattr(mod, f, None))]
    if missing:
        raise AttributeError(f"archs/{name}.py lacks {missing}")
    return mod


def find_cell(name: str, bench: dict, base=HERE) -> dict:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    w = cells[name]

    def applies(m):
        return "workloads" not in m or name in m["workloads"]

    config = load_part("configs", w["config"], base)
    if "arch" not in config:
        raise KeyError(f"configuration {w['config']!r} names no \"arch\"")
    return {"workload": w, "base": base, "config": config,
            "arch": load_arch(config["arch"], base),
            "traffic": load_part("traffic", w["traffic"], base),
            "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
            "per_layer": [m for m in bench["per_layer"] if applies(m)]}


def _whole_numbers(value) -> bool:
    """A whole number, or a non-empty list of them."""
    values = value if isinstance(value, list) else [value]
    return bool(values) and all(
        isinstance(v, int) and not isinstance(v, bool) for v in values)


def cpu_config(config: dict) -> dict:
    """The configuration as its CPU tests build and serve it: each key of
    its optional ``cpu_size`` replaced by the value given there.
    ``cpu_size`` may name only keys the configuration has whose values,
    there and in ``cpu_size``, are whole numbers or lists of them (widths,
    counts of experts or layers), and none of ``CPU_FIXED``.  A chip run
    never reads it: ``find_cell`` and ``run_cell`` take the published
    sizes."""
    size = config.get("cpu_size", {})
    for key, value in size.items():
        if key in CPU_FIXED or key not in config or not (
                _whole_numbers(config[key]) and _whole_numbers(value)):
            raise ValueError(
                f"cpu_size may not replace {key!r}: it replaces only whole "
                f"numbers, or lists of them, that the configuration has, "
                f"and never one of {CPU_FIXED}")
    out = {k: v for k, v in config.items() if k != "cpu_size"}
    out.update(size)
    return out


def require_devices(chips: int) -> list:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX runs on {devs[0].platform!r}, not a TPU; this "
                     f"benchmark has no CPU fallback")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX finds "
                     f"{len(devs)}")
    return devs


# --------------------------------------------------------------- set-up ---
def _no_accurate_path(**arrays):
    raise RuntimeError("the accurate path is not part of the benchmark: a "
                       "call the surrogate did not serve counts as failed")


def make_region(config, traffic, bundle: str, queue):
    from repro.core import approx_ml, tensor_functor
    reg = config["region"]
    rngs = {"i": (0, int(traffic["rows_per_caller"]))}
    return approx_ml(_no_accurate_path, name=reg["name"],
                     inputs={reg["input"]: (tensor_functor(reg["in_functor"]),
                                            rngs)},
                     outputs={reg["output"]:
                              (tensor_functor(reg["out_functor"]), rngs)},
                     mode="infer_async", model=bundle, serving=queue)


class CompileCounter:
    """Programs lowered and compiled by XLA while it is open (a program
    loaded from the persistent cache is lowered, not compiled)."""

    _EVENTS = {"/jax/core/compile/jaxpr_to_mlir_module_duration": "lowered",
               "/jax/core/compile/backend_compile_duration": "compiled"}

    def __init__(self):
        self.counts = {"lowered": 0, "compiled": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kwargs):
        kind = self._EVENTS.get(event)
        if kind is not None:
            self.counts[kind] += 1

    def snapshot(self) -> dict:
        return dict(self.counts)

    def close(self):
        jax.monitoring.unregister_event_duration_listener(self._on)


def _counter_totals() -> dict:
    """Kernel dispatches by provenance and breaker fallbacks so far."""
    from repro.obs import default_registry
    snap = default_registry().collect()

    def values(metric):
        return snap.get(metric, {"values": []})["values"]

    disp = {}
    for v in values("repro_kernel_dispatch_total"):
        k = f"{v['labels'].get('kernel')}:{v['labels'].get('provenance')}"
        disp[k] = disp.get(k, 0) + v["value"]
    fb = sum(v["value"] for v in values("repro_resilience_fallback_total"))
    return {"dispatch": disp, "fallback": fb}


def _delta(after: dict, before: dict) -> dict:
    return {"dispatch": {k: v - before["dispatch"].get(k, 0)
                         for k, v in after["dispatch"].items()
                         if v - before["dispatch"].get(k, 0)},
            "fallback": after["fallback"] - before["fallback"]}


# ----------------------------------------------------------- the window ---
class _Loop:
    """One closed-loop step: every caller's region call, one flush, every
    caller's ``result()``, ready.  ``annotate`` writes the benchmark's own
    spans into the profiler's trace."""

    def __init__(self, region, queue, key, config, annotate: bool):
        self.region, self.queue, self.key = region, queue, key
        self.inp = config["region"]["input"]
        self.out = config["region"]["output"]
        self.ann = (jax.profiler.TraceAnnotation if annotate
                    else lambda name: contextlib.nullcontext())
        self.first_error = None

    def _failed(self, exc):
        if self.first_error is None:
            self.first_error = repr(exc)

    def step(self, xs):
        ann, inp = self.ann, self.inp
        clock = time.perf_counter
        caller_s = 0.0
        handles = []
        t0 = clock()
        with ann("bench.step"):
            for x in xs:
                a = clock()
                try:
                    with ann("bench.region"):
                        handles.append(self.region(**{inp: x}))
                except Exception as e:  # counted as a failed call
                    self._failed(e)
                    handles.append(None)
                caller_s += clock() - a
            with ann("bench.flush"):
                self.queue.flush(self.key)
            outs = []
            for h in handles:
                a = clock()
                try:
                    with ann("bench.result"):
                        outs.append(None if h is None else
                                    h.result(RESULT_TIMEOUT_S)[self.out])
                except Exception as e:  # counted as a failed call
                    self._failed(e)
                    outs.append(None)
                caller_s += clock() - a
            jax.block_until_ready([o for o in outs if o is not None])
        return outs, (t0, clock(), caller_s)


class _Reservoir:
    """A sample, drawn from the seed, of ``size`` steps of the window."""

    def __init__(self, size: int, seed: int):
        self.size, self.rng = size, random.Random(seed)
        self.kept, self.seen = [], 0

    def offer(self, item):
        if self.seen < self.size:
            self.kept.append(item)
        else:
            j = self.rng.randint(0, self.seen)
            if j < self.size:
                self.kept[j] = item
        self.seen += 1


def _profile_options():
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def _xplane(trace_dir) -> pathlib.Path:
    found = sorted(pathlib.Path(trace_dir).glob(
        "plugins/profile/*/*.xplane.pb"))
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(found)}")
    return found[0]


def run_cell(cell: dict, *, seed: int, seconds: float, trace: bool,
             t_start: float, devices: list, keep_trace=None,
             log=sys.stderr) -> dict:
    """Set up, measure, check and read one run of ``cell``."""
    from repro.core.engine import InferenceEngine
    from repro.obs import TRACER, enable_tracing
    from repro.serve import FlushPolicy, ServeQueue

    config, traffic, w = cell["config"], cell["traffic"], cell["workload"]
    arch = cell["arch"]
    if traffic["loop"] != "closed":
        raise ValueError(f"loop {traffic['loop']!r} is not implemented")
    if config["matmul_precision"] != "highest":
        raise ValueError(f"the reference and its control are written for "
                         f"f32 at highest matmul precision; the "
                         f"configuration states "
                         f"{config['matmul_precision']!r}")
    chips = int(w["chips"])
    devices = devices[:chips]
    callers = int(traffic["callers"])
    rows_step = callers * int(traffic["rows_per_caller"])

    tmp = pathlib.Path(tempfile.mkdtemp(prefix="chipbench-"))
    stack = contextlib.ExitStack()
    counter = CompileCounter()
    phases = {"start": time.perf_counter() - t_start}

    def phase(name):
        phases[name] = time.perf_counter() - t_start - sum(phases.values())

    try:
        model = arch.make_weights(config, seed)
        phase("weights")
        bundle = arch.write_bundle(tmp / "bundle", config, model)
        phase("bundle")
        inputs = arch.make_inputs(config, traffic, seed)
        jax.block_until_ready(inputs)
        phase("inputs")
        queue = ServeQueue(FlushPolicy(max_batch_rows=2 * rows_step,
                                       max_pending_rows=2 * rows_step))
        stack.callback(queue.close)
        region = make_region(config, traffic, bundle, queue)
        if chips > 1:
            from repro.dist.sharding import use_mesh
            from repro.launch.mesh import make_pod_mesh
            mesh = make_pod_mesh()
            if mesh.shape["data"] != chips:
                raise NoChip(f"the data axis spans {mesh.shape['data']} "
                             f"chips, the cell asks for {chips}")
            stack.enter_context(use_mesh(mesh))
        loop = _Loop(region, queue, bundle, config, annotate=trace)
        before = _counter_totals()
        for i in range(WARMUP_STEPS):
            loop.step(inputs[i % len(inputs)])
            phase(f"warmup{i}")
        tier = InferenceEngine.get(bundle).tier
        if tier != config["tier"]:
            raise RuntimeError(f"the engine serves tier {tier!r}, the "
                               f"configuration states {config['tier']!r}")

        window = min(seconds, TRACE_SECONDS) if trace else seconds
        sample = _Reservoir(int(traffic["sampled_steps"]), seed)
        steps = []
        rows = attempted = failed = 0
        if trace:
            TRACER.clear()
            drops0 = sum(TRACER.drop_counts().values())
            enable_tracing(ring_size=TRACE_RING, annotate=True)
            jax.profiler.start_trace(str(tmp / "trace"),
                                     profiler_options=_profile_options())
        compiles0 = counter.snapshot()
        # what set-up left behind (a compile's objects, above all) is not
        # scanned by the collector in the window, so a run that compiled
        # and one that loaded the cache measure alike
        gc.collect()
        gc.freeze()
        t_w0 = time.perf_counter()
        cpu_w0 = time.thread_time()
        setup_s = t_w0 - t_start
        phase("profiler" if trace else "rest")
        with loop.ann("bench.window"):
            i = 0
            while True:
                p = i % len(inputs)
                outs, rec = loop.step(inputs[p])
                ok = sum(o is not None for o in outs)
                attempted += callers
                failed += callers - ok
                rows += ok * int(traffic["rows_per_caller"])
                steps.append(rec)
                sample.offer((p, outs))
                i += 1
                if rec[1] - t_w0 >= window:
                    break
        t_w1 = steps[-1][1]
        cpu_w = time.thread_time() - cpu_w0
        compiles = {k: v - compiles0[k]
                    for k, v in counter.snapshot().items()}
        spans = None
        if trace:
            jax.profiler.stop_trace()
            spans = TRACER.events()
            TRACER.disable()
            TRACER.publish_drop_counts()
            dropped = sum(TRACER.drop_counts().values()) - drops0
            TRACER.clear()
            if dropped:
                raise RuntimeError(f"the program tracer dropped {dropped} "
                                   f"spans: its ring is too small")
        memory_peak = max(int((d.memory_stats() or {})
                              .get("peak_bytes_in_use", 0)) for d in devices)

        # free the program's state before the reference runs
        kept = [(p, [None if o is None else np.asarray(o) for o in outs])
                for p, outs in sample.kept]
        kept_x = {p: np.concatenate([np.asarray(x) for x in inputs[p]])
                  for p in {p for p, _ in kept}}
        del inputs, sample
        stack.close()
        InferenceEngine.invalidate(bundle)
        counts = _delta(_counter_totals(), before)

        t_c0 = time.perf_counter()
        gap, left_out = _compare(functools.partial(arch.forward, config),
                                 model, kept, kept_x)
        compare_s = time.perf_counter() - t_c0
        reduced = None
        if trace:
            xplane = _xplane(tmp / "trace")
            if keep_trace is not None:
                pathlib.Path(keep_trace).mkdir(parents=True, exist_ok=True)
                shutil.copy(xplane, pathlib.Path(keep_trace) / xplane.name)
            reduced = trace_reduce.reduce(
                trace_reduce.extract(trace_reduce.load(xplane)))
    finally:
        gc.unfreeze()
        stack.close()
        counter.close()
        shutil.rmtree(tmp, ignore_errors=True)

    if loop.first_error is not None:
        print(f"first failed call: {loop.first_error}", file=log, flush=True)
    failed_calls = failed + int(counts["fallback"])
    checks = {
        "max_rel_err": {"value": gap,
                        "limit": config["check"]["max_rel_err"]},
        # a configuration that states no bound leaves no row out
        "rows_left_out": {"value": left_out, "limit": config["check"].get(
            "max_rows_left_out", 0)},
        "failed_calls": {"value": failed_calls, "limit": 0},
        "ref_dispatches": {"value": sum(
            v for k, v in counts["dispatch"].items() if k.endswith(":ref")),
            "limit": 0},
        "vmem_fallback_dispatches": {"value": sum(
            v for k, v in counts["dispatch"].items()
            if k.endswith("vmem-fallback")), "limit": 0},
    }
    rec = {"config": config, "traffic": traffic, "chips": chips,
           "rows_per_step": rows_step,
           "flops_per_row": arch.flops_per_row(config),
           # one apply call at the rows one chip serves
           "call_bytes": arch.call_bytes(config, rows_step // chips),
           "device_kind": devices[0].device_kind,
           "setup_s": setup_s, "window_s": t_w1 - t_w0, "rows": rows,
           "steps": steps, "spans": spans, "trace": reduced}
    wanted = cell["per_layer"] if trace else cell["end_to_end"]
    metrics = {}
    for m in wanted:
        value = load_reader(m["name"], cell["base"])(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": memory_peak}
    out = {"correct": all(c["value"] <= c["limit"] for c in checks.values()),
           "attempted": attempted, "failed": failed_calls,
           "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["window"] = {"steps": len(steps), "seconds": t_w1 - t_w0,
                     "step_ms_max": 1e3 * max(b - a for a, b, _ in steps),
                     # the loop's own CPU time: where it tracks the window
                     # from run to run, a slower run waited and did not
                     # compute more slowly
                     "loop_cpu_s": cpu_w,
                     "setup_s": setup_s, "setup_phases": phases,
                     "compare_s": compare_s,
                     "compiles": compiles,
                     "dispatch": counts["dispatch"],
                     "breaker_fallbacks": counts["fallback"]}
    out["checks"] = checks
    return out


def _compare(forward, model, kept, kept_x) -> tuple:
    """``(gap, left_out)`` over the sampled steps: each step's rows, as
    every caller got them back, against the reference (``forward``, the
    architecture's with the configuration bound) over the same step's
    inputs (``reference.compare``): the widest relative gap on the rows
    the reference defines, and the share of rows it leaves out."""
    refs = dict(zip(kept_x, reference.run(forward, model, kept_x.values())))
    pairs = []
    for p, outs in kept:
        if any(o is None for o in outs):
            return float("inf"), 0.0
        pairs.append((np.concatenate([o.reshape(o.shape[0], -1)
                                      for o in outs]), refs[p]))
    gap, left_out, rows = reference.compare(pairs)
    return gap, left_out / rows
