"""Inference engine: loads a model bundle once, jit-compiles its apply, and
serves region invocations (the Torch-C++ role in the paper's runtime).

Supports sharded inference: with a mesh installed (``repro.dist.sharding
.use_mesh``), surrogate batches are placed and constrained over the
``data`` axis, so ``MLRegion`` inference scales across chips like any
other data-parallel workload — the compiled apply is cached per sharding
context, so the same engine serves eager CPU calls and sharded meshes.
On TPU the engine routes pure-MLP bundles through the ``fused_mlp``
Pallas kernel (all layers resident in VMEM — the paper's Observation 2,
hardware-utilization, reinterpreted for TPU).

Bundles retrained in-process (the NAS loop rewrites ``params.npz``) are
not served stale: ``get()`` re-reads a bundle whose on-disk fingerprint
(mtime_ns + size) changed since load, and ``invalidate()``/``reload()``
force it — retrain paths that bypass the fingerprint (exotic filesystems
with coarse timestamps) should call ``invalidate()`` after writing.
Freshness is checked where the engine serves: per call on the synchronous
region path, per batch on the serve path (the batcher's ``get()``).  An
async region call only reads the spec to shape its rows, so it looks the
engine up with ``cached()``, which makes no file-system call.
"""
from __future__ import annotations

import os
import threading
from typing import Optional

import jax
import jax.numpy as jnp

from repro.dist.buckets import bucket_for
from repro.dist.sharding import constrain, current_ctx
from repro.nn.serialize import load_model
from repro.obs import TRACER, watch_compiles
from repro.obs import metrics as _m

watch_compiles()

_FINGERPRINT_CHECKS = _m.counter(
    "repro_engine_fingerprint_checks_total",
    "get() comparisons of a cached engine with its bundle on disk",
    ("bundle",))


def bundle_norm(spec, net):
    """The bundle's (x_mu, x_sd, y_mu, y_sd) normalization arrays, or
    None when it was trained unnormalized.  Shared with the quant gate
    (:mod:`repro.quant.gate`), which must compare f32 and int8-simulated
    outputs in the same physical units the per-bundle RMSE budgets are
    written in."""
    extra = spec.get("extra") or {}
    if "x_mu" not in extra:
        return None
    import numpy as np
    ish = tuple(spec["in_shape"][1:])
    osh = tuple(net.out_shape()[1:])
    return tuple(jnp.asarray(np.asarray(extra[k], np.float32).reshape(s))
                 for k, s in (("x_mu", ish), ("x_sd", ish),
                              ("y_mu", osh), ("y_sd", osh)))


def _bundle_mtime(path: str) -> tuple:
    """(mtime_ns, size) fingerprint of the bundle files.

    ns resolution closes the same-second rewrite window on modern
    filesystems; in-process retrain paths (nas.nested.save_trial) call
    invalidate() explicitly and do not rely on this.
    """
    newest, total = 0, 0
    for name in ("spec.json", "params.npz"):
        f = os.path.join(path, name)
        if os.path.exists(f):
            stat = os.stat(f)
            newest = max(newest, stat.st_mtime_ns)
            total += stat.st_size
    return (newest, total)


class InferenceEngine:
    _cache: dict = {}
    # guards _cache and in-place reloads: concurrent get() calls on an
    # evicted/stale bundle must produce exactly ONE reload (the serve
    # path may race a residency eviction from another thread), and a
    # reader must never observe a half-loaded engine.  Reentrant: a
    # load under the lock may evict LRU victims, which pops this same
    # cache.
    _cache_lock = threading.RLock()

    def __init__(self, model_path: str, use_kernel: str = "auto"):
        self.path = str(model_path)
        self.use_kernel = use_kernel
        self._applies: dict = {}  # one compiled apply per sharding context
        # resolved NamedSharding per (shape, mesh, multi_pod): spec_for is
        # pure python over every dim and was re-run on every eager call
        self._shardings: dict = {}
        self._load()

    def _load(self):
        # a region's first call can happen inside someone else's jit trace
        # (predicated lax.cond, infer_async degrading in-trace): params
        # must be concrete arrays, never constants staged onto that trace
        with jax.ensure_compile_time_eval():
            self.net, self.params, self.spec = load_model(self.path)
        self._mtime = _bundle_mtime(self.path)
        self._applies.clear()
        self._shardings.clear()
        # precision tier is a load-time property: the gate verdict is
        # bound to the bundle fingerprint, so any reload re-resolves it
        # (gate_bundle() invalidates the engine cache after a verdict)
        self._qlayers = None
        self._qacts = None
        self.tier = self._resolve_tier()
        if self.tier == "int8":
            self._quantize_residency()
        # residency accounting: meter this load's bytes against the LRU
        # byte budget and drop whatever the manager says must go.  The
        # victims leave through invalidate() — eviction and retrain
        # invalidation share one path on purpose.
        self.resident_nbytes = self._params_nbytes()
        from repro.serve.residency import RESIDENCY
        for victim in RESIDENCY.note_load(self.path, self.resident_nbytes):
            type(self).invalidate(victim)

    def _params_nbytes(self) -> int:
        """Bytes of device residency this bundle's weights occupy
        (params, plus the int8 layers + scales when quantized)."""
        import numpy as np

        def nbytes(leaf) -> int:
            try:
                return int(leaf.size) * int(np.dtype(leaf.dtype).itemsize)
            except Exception:
                return 0

        total = sum(nbytes(p) for p in jax.tree_util.tree_leaves(self.params))
        if self._qlayers is not None:
            total += sum(nbytes(a)
                         for a in jax.tree_util.tree_leaves(self._qlayers))
        return total

    def _resolve_tier(self) -> str:
        """Which precision tier this engine serves (resolved once per
        load — the serve path must not re-read env vars or gate files
        per batch).

        ``REPRO_QUANT`` modes: ``auto`` (default) serves int8 only on
        TPU — off-TPU the int8-simulating oracle is *slower* than the
        f32 path, so quantization buys nothing; ``force``/``1`` serves
        int8 on any backend (CI drills the full quantized path in
        interpret/oracle mode); ``never``/``0`` pins f32.  In every mode
        except ``never`` the bundle must have **passed its accuracy
        gate** — a gate-fail (or stale/absent) verdict serves f32 even
        under ``force``; that is the fail-safe the gate exists for.  An
        error while reading the verdict propagates: it is a fault, not a
        verdict.
        """
        mode = os.environ.get("REPRO_QUANT", "auto").strip().lower()
        if mode in ("never", "0", "off"):
            return "f32"
        if self.use_kernel == "never" or not self._is_pure_mlp():
            return "f32"
        if mode not in ("force", "1") and jax.default_backend() != "tpu":
            return "f32"
        from repro.quant.gate import gate_passed
        return "int8" if gate_passed(self.path) else "f32"

    def _quantize_residency(self):
        """Quantize the dense stack once at load (per-output-channel
        int8 weights + f32 scales), using the exact ``scale_mult`` the
        gate verdict blessed — serving must run the same numbers the
        gate measured, not a fresh calibration."""
        from repro.kernels.fused_mlp.ops import mlp_stack_from_spec
        from repro.quant.gate import verdict
        from repro.quant.quantize import quantize_params
        rec = verdict(self.path) or {}
        sm = float(rec.get("scale_mult", 1.0))
        with jax.ensure_compile_time_eval():
            _, weights, biases, acts = mlp_stack_from_spec(
                self.spec, self.params, jnp.zeros((1, 1), jnp.float32))
            self._qlayers = tuple(
                tuple(q) for q in quantize_params(weights, biases,
                                                  scale_mult=sm))
        self._qacts = tuple(acts)
        _m.counter("repro_quant_eligible_total",
                   "bundle loads that resolved to the int8 tier",
                   ("bundle",)).inc(1, bundle=self.path)

    @classmethod
    def get(cls, model_path, trace=None) -> "InferenceEngine":
        """Process-wide cache: a model file is loaded once (paper §IV-B).

        A bundle rewritten on disk since it was loaded (NAS retraining)
        is transparently reloaded in place, so long-lived regions holding
        this engine see the fresh weights.  ``trace`` is the trace id of
        a traced region call: the lookup is then its ``engine.get`` span.
        """
        return cls._lookup(str(model_path), trace, check=True)

    @classmethod
    def cached(cls, model_path, trace=None) -> "InferenceEngine":
        """``get()`` without the on-disk fingerprint check: the cached
        engine, loaded on a miss.  For callers that only read the spec
        and leave serving to a path that calls ``get()`` itself."""
        return cls._lookup(str(model_path), trace, check=False)

    @classmethod
    def _lookup(cls, key: str, trace, check: bool) -> "InferenceEngine":
        if trace is None:
            return cls._get(key, check)
        with TRACER.child("engine.get", trace, cat="engine"):
            return cls._get(key, check)

    @classmethod
    def _get(cls, key: str, check: bool) -> "InferenceEngine":
        with cls._cache_lock:
            eng = cls._cache.get(key)
            if eng is None:
                eng = cls._cache[key] = cls(key)
            elif check:
                _FINGERPRINT_CHECKS.inc(1, bundle=key)
                if _bundle_mtime(key) != eng._mtime:
                    # any fingerprint change reloads — including
                    # rollbacks to an older bundle (copy2/mv preserve
                    # the original, older mtime)
                    eng.reload()
        from repro.serve.residency import RESIDENCY
        RESIDENCY.touch(key)
        return eng

    @classmethod
    def invalidate(cls, model_path=None):
        """Drop cached engine(s) so the next get() reloads from disk.

        Residency eviction lands here too: the manager's LRU victims are
        invalidated exactly like a retrained bundle, so both reload
        through the same get() path."""
        with cls._cache_lock:
            if model_path is None:
                cls._cache.clear()
            else:
                cls._cache.pop(str(model_path), None)
        from repro.serve.residency import RESIDENCY
        RESIDENCY.drop(model_path)

    def reload(self):
        """Re-read the bundle from disk and drop compiled applies."""
        self._load()

    def _is_pure_mlp(self):
        kinds = [l["kind"] for l in self.spec["layers"]]
        return all(k in ("dense", "act", "flatten") for k in kinds)

    def _build(self, ctx=None):
        net = self.net
        norm = bundle_norm(self.spec, net)
        mesh = ctx.mesh if ctx is not None else None
        data_axes = (ctx.mesh_axes_for("data") if ctx is not None else ())

        if self.tier == "int8" and self._qlayers is not None:
            # gated quantized tier: serve the load-time int8 residency.
            # On TPU this dispatches the fused_mlp_int8 Pallas kernel;
            # off-TPU (REPRO_QUANT=force drills) the registry routes the
            # same call to the int8-simulating jnp oracle, so the served
            # numbers are the gated numbers on every backend.
            from repro.kernels.fused_mlp import int8 as qops
            qlayers = self._qlayers

            def raw(params, x):
                return qops.fused_mlp_int8_from_spec(
                    self.spec, list(qlayers), x, mesh=mesh,
                    data_axes=data_axes)
        elif self.use_kernel != "never" and self._is_pure_mlp() and \
                jax.default_backend() == "tpu":
            from repro.kernels.fused_mlp import ops as fused_ops
            # under a multi-shard data axis the kernel runs per shard via
            # shard_map, keeping the VMEM-resident fast path under GSPMD

            def raw(params, x):
                return fused_ops.fused_mlp_from_spec(
                    self.spec, params, x, mesh=mesh, data_axes=data_axes)
        else:
            def raw(params, x):
                return net.apply(params, x)

        # the name makes the compiled module ``jit_apply_fn``, which the
        # chip benchmark's apply_roofline finds in the device trace
        def apply_fn(params, x):
            x = constrain(x, *(("data",) + (None,) * (x.ndim - 1)))
            if norm is not None:
                x = (x - norm[0]) / norm[1]
            y = raw(params, x)
            if norm is not None:
                y = y * norm[3] + norm[2]
            return constrain(y, *(("data",) + (None,) * (y.ndim - 1)))

        return jax.jit(apply_fn)

    def _apply_for(self, ctx):
        """Compiled apply for the active sharding context (traced under it,
        so the data-axis constraints bind to that mesh)."""
        # a mesh-less ctx (use_mesh(None), e.g. the batcher re-installing
        # a no-mesh submitter's context) compiles to the same program as
        # no ctx at all — share the cache entry or the serve path pays a
        # duplicate compile for every bucket shape
        key = (ctx.mesh, ctx.multi_pod) \
            if ctx is not None and ctx.mesh is not None else None
        fn = self._applies.get(key)
        if fn is None:
            fn = self._applies[key] = self._build(ctx)
        return fn

    def _place(self, x, ctx):
        """Batch placement over the data axis, with the resolved sharding
        cached per (shape, mesh): spec resolution ran on *every* eager
        call before, and device_put is skipped when x already lives there
        (repeated bucket shapes from the serve batcher)."""
        if ctx is None or ctx.mesh is None or isinstance(x, jax.core.Tracer):
            return x
        if isinstance(x, jax.Array) and not x.is_fully_addressable:
            # a global (multi-process) array was already placed at
            # assembly (ShardCtx.make_global); a device_put here would be
            # a cross-process reshard and raises on most backends
            return x
        key = (x.shape, ctx.mesh, ctx.multi_pod)
        if key not in self._shardings:
            self._shardings[key] = ctx.sharding_for(
                x.shape, ("data",) + (None,) * (x.ndim - 1))
        sharding = self._shardings[key]
        if sharding is not None and getattr(x, "sharding", None) != sharding:
            x = jax.device_put(x, sharding)
        return x

    def __call__(self, x):
        ctx = current_ctx()
        fn = self._apply_for(ctx)
        # place the surrogate batch over the data axis before compute
        # so per-chip work is batch/n_data_shards
        return fn(self.params, self._place(x, ctx))

    def apply_batched(self, x, *, min_bucket: int = 8,
                      prepadded: bool = False):
        """Serve a coalesced mega-batch: rows padded up to the next
        power-of-two bucket so the jit cache stays at <= log2(max batch)
        entries per context, then sliced back to the caller's row count.
        Under a mesh the bucket floor is raised to the data-shard count
        (and rounded to a multiple of it), so small batches never lose
        the data axis to the divisibility fallback.

        ``prepadded=True`` says ``x`` is already bucket-shaped (the
        Batcher pads into its scratch buffer) — re-bucketing is skipped;
        bucket rounding is not idempotent for non-power-of-two shard
        counts, so the engine must not second-guess it.

        Row-wise nets make the padding invisible: output row i depends
        only on input row i, so callers get bit-identical rows to a
        same-input synchronous ``__call__`` (tests/test_serve.py).
        """
        ctx = current_ctx()
        n = int(x.shape[0])
        if not prepadded:
            shards = (ctx.axis_size("data")
                      if ctx is not None and ctx.mesh is not None else 1)
            b = bucket_for(n, min_bucket, shards)
            if b != n:
                x = jnp.concatenate(
                    [x, jnp.zeros((b - n,) + x.shape[1:], x.dtype)], axis=0)
        fault = None
        if not isinstance(x, jax.core.Tracer):
            from repro.resilience.faults import FAULTS
            if FAULTS.enabled:
                # raise/stall act inside fire(); nan/inf/corrupt come back
                # as a rule for us to apply around the compute below
                fault = FAULTS.fire("engine.apply", key=self.path)
                if fault is not None and fault.mode == "corrupt":
                    # persistent until reload — drives the shadow scorer
                    # (and through it the breaker's quality trip)
                    self.params = jax.tree_util.tree_map(
                        lambda p: p + fault.scale, self.params)
        fn = self._apply_for(ctx)
        x = self._place(x, ctx)
        if self.tier == "int8" and not isinstance(x, jax.core.Tracer):
            _m.counter("repro_quant_served_rows_total",
                       "rows served by the gated int8 tier",
                       ("bundle",)).inc(n, bundle=self.path)
        y = fn(self.params, x)
        if fault is not None and fault.mode in ("nan", "inf"):
            # eager elementwise op: poisons every row while preserving
            # the output's sharding (works on global pod arrays too)
            y = y * fault.value
        # a full-bucket batch (the pod path's pre-padded global arrays)
        # skips the slice: slicing a non-addressable array outside jit
        # raises, and [:n] of n rows is the identity anyway
        return y if n == int(y.shape[0]) else y[:n]

    def infer_shape(self, in_shape):
        return self.net.out_shape()
