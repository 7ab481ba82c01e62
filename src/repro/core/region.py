"""Execution control: the ``approx ml`` region (paper §III, §IV-B).

``MLRegion`` wraps the *accurate execution path* (a JAX-traceable function)
and, per the paper's three ml-modes:

  * ``collect``    — run the accurate path, bridge its inputs/outputs to
                     tensor space, and append (inputs, outputs, runtime) to
                     the SurrogateDB group of this region;
  * ``infer``      — replace the region with surrogate inference through
                     the data bridge;
  * ``predicated`` — a runtime boolean picks the path per invocation; both
                     execution paths live in the same traced program
                     (``lax.cond``), the JAX analogue of HPAC's dual
                     execution paths in one binary;
  * ``infer_async``— (serving extension) enqueue the bridged rows on a
                     ``repro.serve.ServeQueue`` and return an
                     :class:`AsyncRegionResult`; many callers' requests
                     coalesce into one mesh-wide batch before inference.

A ``serving=`` queue can also be attached to a ``predicated`` region: the
eager ML path then defers through the queue (both branches return
:class:`AsyncRegionResult` so the caller's interface is uniform), while
traced calls keep the synchronous in-program ``lax.cond``.

Eager calls are host-timed exactly; calls inside a jit trace fall back to
ordered ``io_callback`` timing/persistence (documented approximation).
"""
from __future__ import annotations

import functools
import time
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import io_callback

from repro.core.database import SurrogateDB
from repro.core.engine import InferenceEngine
from repro.core.functor import TensorFunctor
from repro.core.tensor_map import TensorMap
from repro.obs import TRACER
from repro.obs import metrics as _m
from repro.obs.quality import SHADOW
from repro.resilience.breaker import BREAKERS


_BRIDGES = _m.counter(
    "repro_region_bridge_total",
    "region data bridges taken, by direction and by path: the caller's "
    "array itself (identity) or a bridge program",
    ("region", "direction", "path"))


def _is_traced(*arrays):
    return any(isinstance(x, jax.core.Tracer)
               for a in arrays for x in jax.tree.leaves(a))


class AsyncRegionResult:
    """Deferred region invocation handle (``infer_async`` / serving).

    ``result()`` blocks on the serve future (flushing on demand when the
    queue has no dispatcher thread) and runs the output data bridge in
    the caller's thread — so bridging cost is paid by whoever consumes
    the result, not by the dispatcher.
    """

    __slots__ = ("_region", "_arrays", "_future", "_done")

    def __init__(self, region, arrays, future=None, resolved=None):
        self._region, self._arrays = region, arrays
        self._future = future
        self._done = resolved  # pre-resolved outputs (accurate path)

    def done(self) -> bool:
        return self._done is not None or self._future.done()

    def deferred(self) -> bool:
        """True when this invocation actually went through the queue."""
        return self._future is not None

    def result(self, timeout: Optional[float] = None) -> dict:
        if self._done is None:
            trace = self._future.trace  # the region call's, when traced
            if trace is None:
                self._done = self._resolve(timeout, None)
            else:
                with TRACER.span("region.result", cat="region",
                                 trace=trace):
                    self._done = self._resolve(timeout, trace)
        return self._done

    def _resolve(self, timeout: Optional[float], trace: Optional[str]
                 ) -> dict:
        region = self._region
        try:
            with TRACER.child("region.wait", trace, cat="region"):
                Y = self._future.result(timeout)
        except TimeoutError:
            raise  # not a surrogate failure: the caller set the budget
        except Exception:
            # zero-lost contract: a failed dispatch (injected fault,
            # non-finite screen, dead dispatcher) degrades to the
            # accurate path instead of surfacing the serve error
            if not (BREAKERS.enabled and region.model_path):
                raise
            BREAKERS.record_failure(region.model_path)
            return region._fallback(self._arrays, "result")
        return region._rows_out(Y, self._arrays, trace)


class MLRegion:
    def __init__(self, name: str, fn: Callable, *,
                 inputs: Dict[str, Tuple[TensorFunctor, dict]],
                 outputs: Dict[str, Tuple[TensorFunctor, dict]],
                 mode: str = "predicated",
                 model: Optional[str] = None,
                 database: Optional[str] = None,
                 serving=None):
        assert mode in ("collect", "infer", "predicated", "infer_async")
        self.name, self.fn, self.mode = name, fn, mode
        self.inputs, self.outputs = inputs, outputs
        self.model_path = model
        self.serving = serving  # repro.serve.ServeQueue (or None)
        if mode == "infer_async":
            assert serving is not None, \
                f"region {name}: mode='infer_async' needs a serving= queue"
        self.db = (database if isinstance(database, SurrogateDB)
                   else SurrogateDB(database)) if database else None

    # ------------------------------------------------------ data bridge ---
    def bridge_in(self, arrays: dict):
        """App memory -> model input tensor [sweep..., features]."""
        parts = []
        for name, (functor, ranges) in self.inputs.items():
            tm = TensorMap(functor, arrays[name], ranges, "to")
            parts.append(tm.to_tensor())
        t = parts[0] if len(parts) == 1 else jnp.concatenate(
            [p.reshape(p.shape[:1] + (-1,)) if p.ndim > 1 else p[:, None]
             for p in parts], axis=-1)
        return t

    def bridge_out_tensors(self, out_arrays: dict):
        parts = []
        for name, (functor, ranges) in self.outputs.items():
            tm = TensorMap(functor, out_arrays[name], ranges, "to")
            parts.append(tm.to_tensor())
        return parts[0] if len(parts) == 1 else jnp.concatenate(
            [p.reshape(p.shape[:1] + (-1,)) for p in parts], axis=-1)

    # the bridges are pure gather/scatter/reshape programs over static
    # functor descriptors, so one jit per region collapses their eager
    # op-by-op dispatch (which dominated small per-call serving) into a
    # single compiled call — bit-identical, no float arithmetic involved
    @functools.cached_property
    def _bridge_in_jit(self):
        return jax.jit(self.bridge_in)

    @functools.cached_property
    def _bridge_from_jit(self):
        return jax.jit(self.bridge_from)

    # a functor that maps the caller's array onto itself needs no program:
    # (name, shape) of the region's one input / output whose functor is
    # the identity at that shape, else None; decided on the first call
    @functools.cached_property
    def _identity_in(self):
        return _identity(self.inputs)

    @functools.cached_property
    def _identity_out(self):
        return _identity(self.outputs)

    def bridge_from(self, tensor, arrays: dict):
        """Model output tensor -> app memory (through the out functors).

        Pure outputs (not also region inputs) get a synthesized zero
        template covering exactly the functor's written window.
        """
        out = {}
        offset = 0
        for name, (functor, ranges) in self.outputs.items():
            if name in arrays:
                template = arrays[name]
            else:
                probe = TensorMap(functor, None, ranges, "from")
                template = jnp.zeros(probe.min_array_shape(), tensor.dtype)
            tm = TensorMap(functor, template, ranges, "from")
            want = tm.tensor_shape
            n = int(np.prod(want[len(want) - _feat_dims(tm):])) if want else 1
            if len(self.outputs) == 1:
                piece = tensor.reshape(want)
            else:
                flatfeat = tensor.reshape(tensor.shape[0], -1)
                piece = flatfeat[:, offset:offset + n].reshape(want)
                offset += n
            out[name] = tm.from_tensor(piece)
        return out

    # ------------------------------------------------------- execution ----
    def engine(self, trace: Optional[str] = None) -> InferenceEngine:
        assert self.model_path, f"region {self.name}: no model path"
        # always resolve through the process-wide cache.  Freshness is
        # checked where the engine serves: get() compares the bundle's
        # on-disk fingerprint, and reloads a bundle the NAS loop retrained
        # under this region's feet, on every sync call; on the serve path
        # the batcher's get() does so once per batch
        return InferenceEngine.get(self.model_path, trace)

    def _rows_in(self, eng: InferenceEngine, arrays: dict,
                 trace: Optional[str] = None, args: Optional[dict] = None):
        """Bridge app arrays to ``eng``-shaped f32 rows [n, *in_shape[1:]].
        A traced call passes its trace id and its span's ``args``."""
        in_shape = tuple(eng.spec["in_shape"])
        ident = self._identity_in
        with TRACER.child("region.bridge_in", trace, cat="region"):
            x = arrays[ident[0]] if ident else None
            # a jax.Array is immutable, so the queue may park it as the
            # rows; a numpy array is copied by the program (the caller may
            # change it before the flush)
            if isinstance(x, jax.Array) and x.shape == ident[1]:
                X, path = x, "identity"
            else:
                X, path = self._bridge_in_jit(arrays), "program"
            _BRIDGES.inc(region=self.name, direction="in", path=path)
            X = X.reshape((-1,) + in_shape[1:]).astype(jnp.float32)
        if args is not None:
            args["rows"] = int(X.shape[0])
        return X

    def _rows_out(self, Y, arrays: dict, trace: Optional[str] = None):
        """Bridge served rows ``Y`` to the region's outputs.  Through an
        identity functor they are ``Y`` itself, shaped and cast as the
        program's template; host rows (row views of the batcher's pooled
        buffer) take one copying put to where the program would have run:
        the device of the template when the caller committed it, else the
        default device (a pure output's program reads no caller array)."""
        ident = self._identity_out
        with TRACER.child("region.bridge_out", trace, cat="region"):
            if ident is not None:
                name, shape = ident
                t = arrays.get(name)  # None: a pure output
                host = isinstance(Y, np.ndarray)
                homes = _committed_devices(t) if host else ()
                if (t is None or np.shape(t) == shape) and len(homes) <= 1:
                    Y = Y.reshape(shape)
                    if host:
                        Y = jax.device_put(Y, next(iter(homes), None),
                                           may_alias=False)
                    if t is not None:
                        Y = Y.astype(jax.dtypes.canonicalize_dtype(t.dtype))
                    _BRIDGES.inc(region=self.name, direction="out",
                                 path="identity")
                    return {name: Y}
            _BRIDGES.inc(region=self.name, direction="out", path="program")
            return self._bridge_from_jit(Y, arrays)

    def _call_span(self, body, arrays: dict):
        """``body(arrays, trace, args)`` inside this eager call's
        ``region.call`` span, under a trace id minted here; only called
        with tracing on."""
        trace = TRACER.new_trace_id()
        args = {"region": self.name}
        with TRACER.span("region.call", cat="region", trace=trace,
                         args=args):
            return body(arrays, trace, args)

    def _fallback(self, arrays: dict, path: str) -> dict:
        """Serve this invocation from the accurate path (breaker OPEN or
        a dispatch failure), wearing the surrogate's output contract."""
        BREAKERS.note_fallback(self.model_path, path)
        with TRACER.span("resilience.fallback", cat="region",
                         args={"region": self.name, "key": self.model_path,
                               "path": path}):
            return self._accurate(arrays, collect=False)

    def _infer(self, arrays: dict):
        traced = _is_traced(arrays)
        if traced or not TRACER.enabled:
            return self._infer_call(arrays, None, None, traced)
        return self._call_span(self._infer_call, arrays)

    def _infer_call(self, arrays: dict, trace: Optional[str],
                    args: Optional[dict], traced: bool = False):
        use_breaker = (BREAKERS.enabled and self.model_path is not None
                       and not traced)
        if use_breaker and not BREAKERS.allow(self.model_path):
            return self._fallback(arrays, "infer")
        try:
            eng = self.engine(trace)
            Xb = self._rows_in(eng, arrays, trace, args)
            Y = eng(Xb)
        except Exception:
            if not use_breaker:
                raise
            BREAKERS.record_failure(self.model_path)
            return self._fallback(arrays, "infer")
        if use_breaker:
            BREAKERS.record_success(self.model_path)
        if SHADOW.enabled and not _is_traced(arrays, Xb) and SHADOW.sample():
            self._shadow_submit(arrays, rows=int(Xb.shape[0]), Y=Y)
        return self._rows_out(Y, arrays, trace)

    def _infer_async(self, arrays: dict) -> AsyncRegionResult:
        """Enqueue this invocation on the serve queue, keyed (multiplexed)
        by bundle path; inside a trace there is no host queue to park rows
        on, so traced calls degrade to synchronous inference."""
        if _is_traced(arrays):
            return AsyncRegionResult(self, arrays,
                                     resolved=self._infer(arrays))
        if not TRACER.enabled:
            return self._enqueue(arrays, None, None)
        return self._call_span(self._enqueue, arrays)

    def _enqueue(self, arrays: dict, trace: Optional[str],
                 args: Optional[dict]) -> AsyncRegionResult:
        if (BREAKERS.enabled and self.model_path is not None
                and not BREAKERS.allow(self.model_path)):
            # breaker OPEN (or HALF_OPEN non-probe): resolve through the
            # accurate path immediately, same handle contract
            return AsyncRegionResult(
                self, arrays,
                resolved=self._fallback(arrays, "infer_async"))
        # only the spec's in_shape is read here: the batcher's per-batch
        # get() checks the bundle on disk and decides which weights serve
        # these rows.  A retrain that changes in_shape between that check
        # and this call shapes the rows by the cached spec, the same race
        # as a retrain between submit and flush
        eng = InferenceEngine.cached(self.model_path, trace)
        Xb = self._rows_in(eng, arrays, trace, args)
        fut = self.serving.submit(self.model_path, Xb, trace=trace)
        if SHADOW.enabled and SHADOW.sample():
            self._shadow_submit(arrays, rows=int(Xb.shape[0]), future=fut)
        return AsyncRegionResult(self, arrays, future=fut)

    def _shadow_submit(self, arrays: dict, *, rows: int, Y=None,
                       future=None) -> None:
        """Capture this sampled invocation for background accuracy
        scoring: the surrogate's output rows vs the accurate function's
        bridged output over a *snapshot* of the inputs (the app may
        mutate its buffers after the region returns).  The accurate
        replay runs later on the scorer's worker thread — never here."""
        snap = {k: np.array(v) for k, v in arrays.items()}
        if future is not None:
            pred = lambda: np.asarray(future.result(60.0))  # noqa: E731
            trace = future.trace
        else:
            pred = lambda: np.asarray(Y)  # noqa: E731
            trace = None

        def ref():
            return np.asarray(self.bridge_out_tensors(self.fn(**snap)))

        SHADOW.submit(self.model_path, pred=pred, ref=ref,
                      region=self.name, rows=rows, trace=trace)

    def _n_sweep(self) -> int:
        functor = next(iter(self.inputs.values()))[0]
        return len(functor.sweep_symbols)

    def _rows(self, X):
        """DB row layout (paper §V-B): outer dim = unique data identifier.

        One sweep dim (e.g. pose/option index): each sweep entry is a row.
        Spatial sweeps (stencils): the whole tensor is one row.
        """
        X = np.asarray(X)
        if self._n_sweep() <= 1:
            return X.reshape(X.shape[0], -1) if X.ndim > 1 else X[:, None]
        return X[None]

    def _accurate(self, arrays: dict, collect: bool):
        if collect and not _is_traced(arrays):
            # eager: exact wall-clock of the accurate path (paper Table III)
            X = np.asarray(self.bridge_in(arrays))
            t0 = time.perf_counter()
            outs = self.fn(**arrays)
            jax.block_until_ready(outs)
            dt = time.perf_counter() - t0
            Y = np.asarray(self.bridge_out_tensors(outs))
            self.db.group(self.name).append(self._rows(X), self._rows(Y), dt)
            return outs
        outs = self.fn(**arrays)
        if collect:
            X = self.bridge_in(arrays)
            Y = self.bridge_out_tensors(outs)

            def tap(xv, yv):
                self.db.group(self.name).append(self._rows(xv),
                                                self._rows(yv), float("nan"))
                return np.int32(0)

            io_callback(tap, jax.ShapeDtypeStruct((), jnp.int32), X, Y,
                        ordered=True)
        return outs

    def __call__(self, predicate=None, **arrays):
        mode = self.mode
        if mode == "collect":
            return self._accurate(arrays, collect=True)
        if mode == "infer":
            return self._infer(arrays)
        if mode == "infer_async":
            return self._infer_async(arrays)
        # predicated: true -> inference, false -> accurate (+collection)
        assert predicate is not None, "predicated region needs a predicate"
        if not _is_traced(arrays) and not isinstance(predicate, jax.core.Tracer):
            if self.serving is not None:
                # serving hook: the ML path defers through the queue; the
                # accurate path resolves immediately but wears the same
                # handle so callers need not branch on the predicate
                if bool(predicate):
                    return self._infer_async(arrays)
                return AsyncRegionResult(
                    self, arrays,
                    resolved=self._accurate(arrays,
                                            collect=self.db is not None))
            return (self._infer(arrays) if bool(predicate)
                    else self._accurate(arrays, collect=self.db is not None))
        # traced: both paths in one program
        names = list(self.outputs.keys())

        def t_inf(arr):
            return tuple(self._infer(arr)[n] for n in names)

        def t_acc(arr):
            outs = self.fn(**arr)
            return tuple(outs[n] for n in names)

        res = jax.lax.cond(predicate, t_inf, t_acc, arrays)
        return dict(zip(names, res))


def _feat_dims(tm: TensorMap) -> int:
    _, feat = tm._lhs_dims()
    return len(feat)


def _identity(side: dict):
    """(name, shape) when ``side`` (a region's inputs or outputs) is one
    array whose functor is the identity at ``shape``, else None."""
    if len(side) != 1:
        return None
    (name, (functor, ranges)), = side.items()
    shape = TensorMap(functor, None, ranges).identity_shape()
    return None if shape is None else (name, shape)


def _committed_devices(tree) -> set:
    """The devices of the committed jax.Arrays in ``tree``: a jitted
    bridge that reads them runs there (on the default device when none
    is)."""
    return {d for x in jax.tree.leaves(tree)
            if isinstance(x, jax.Array) and x.committed
            for d in x.devices()}


def approx_ml(fn=None, **kw) -> MLRegion:
    """Factory mirroring the ``#pragma approx ml(...)`` clause."""
    name = kw.pop("name", getattr(fn, "__name__", "region"))
    return MLRegion(name, fn, **kw)
