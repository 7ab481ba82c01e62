"""Registry shim + spec adapter for the fused-MLP inference kernel.

Backend dispatch (on-TPU / ``force_kernel`` / interpret fallback) and
tuned-parameter resolution live in :mod:`repro.kernels.registry`; this
module only declares the kernel's :class:`KernelSpec` — how to derive a
problem from a call, synthesize sweep inputs, key the tune cache, and
cost VMEM — plus the shard_map wrapper and the engine's spec adapter.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.kernels import registry
from repro.kernels.fused_mlp.fused_mlp import fits_vmem, fused_mlp
from repro.kernels.fused_mlp.ref import fused_mlp_ref

DEFAULT_TILE = 128
_TILE_LADDER = (16, 32, 64, 128, 256, 512)


# ----------------------------------------------------------- KernelSpec ----
def _inspect(x, weights, biases, acts):
    widths = (int(weights[0].shape[0]),) + tuple(int(w.shape[1])
                                                 for w in weights)
    problem = {"widths": widths, "acts": tuple(acts),
               "batch": int(x.shape[0]), "dtype": str(np.dtype(x.dtype))}
    return problem, (x, tuple(weights), tuple(biases))


def _run(problem, arrays, params, *, interpret):
    x, ws, bs = arrays
    return fused_mlp(x, list(ws), list(bs), problem["acts"],
                     batch_tile=params["batch_tile"], interpret=interpret)


def _ref(problem, arrays):
    x, ws, bs = arrays
    return fused_mlp_ref(x, list(ws), list(bs), problem["acts"])


def _make(problem, rng):
    widths, dtype = problem["widths"], problem["dtype"]
    ws = tuple(jnp.asarray(rng.normal(size=(a, b)).astype(np.float32) * 0.3,
                           dtype) for a, b in zip(widths[:-1], widths[1:]))
    bs = tuple(jnp.asarray(rng.normal(size=(b,)).astype(np.float32) * 0.1,
                           dtype) for b in widths[1:])
    x = jnp.asarray(rng.normal(size=(problem["batch"], widths[0]))
                    .astype(np.float32), dtype)
    return (x, ws, bs)


def _key(problem, backend):
    from repro.tune.cache import shape_key
    return shape_key(problem["widths"], problem["dtype"], backend,
                     problem["batch"])


def _keys(problem, backend):
    """Exact batch first (serve-path dispatches and per-shard shard_map
    batches arrive bucket-shaped, including non-pow2 shard-rounded
    buckets), then the power-of-two bucket covering eager calls."""
    from repro.serve.batcher import bucket_size
    from repro.tune.cache import shape_key
    b = problem["batch"]
    return [shape_key(problem["widths"], problem["dtype"], backend, bb)
            for bb in dict.fromkeys((b, bucket_size(b)))]


def candidate_tiles(widths, bucket, extra=(), dtype="float32"):
    """Tiles worth sweeping for one bucket: the standard ladder clipped
    to the bucket, the bucket itself (grid of 1), and any extras —
    deduped, VMEM-checked at the problem's actual dtype width (a bf16
    net packs twice the tiles of an f32 one), default first so ties
    keep the default.  (The single source for the fused_mlp candidate
    set; the tuner and the spec both consume it.)"""
    dtype_bytes = np.dtype(dtype).itemsize
    tiles = [DEFAULT_TILE]
    for t in _TILE_LADDER + (int(bucket),) + tuple(extra):
        t = int(t)
        if 0 < t <= bucket and t not in tiles:
            tiles.append(t)
    return [t for t in tiles if fits_vmem(widths, t,
                                          dtype_bytes=dtype_bytes)]


def _cands(problem):
    return [{"batch_tile": t}
            for t in candidate_tiles(problem["widths"], problem["batch"],
                                     dtype=problem["dtype"])]


def _fits(problem, params, budget=None):
    # per-operand dtype threading: the cost model prices tiles at the
    # problem's dtype width, not a hardcoded f32
    return fits_vmem(problem["widths"], params["batch_tile"], budget=budget,
                     dtype_bytes=np.dtype(problem["dtype"]).itemsize)


def _supports(problem):
    return fits_vmem(problem["widths"],
                     dtype_bytes=np.dtype(problem["dtype"]).itemsize)


SPEC = registry.register(registry.KernelSpec(
    name="fused_mlp",
    params=(registry.TunableParam("batch_tile", DEFAULT_TILE, _TILE_LADDER),),
    inspect=_inspect, run_call=_run, ref_call=_ref, make_call=_make,
    cache_key=_key, cache_keys=_keys, candidates=_cands, fits=_fits,
    supports=_supports, tol=None,
    default_problems=(
        {"widths": (5, 128, 128, 1), "acts": ("relu", "relu", "identity"),
         "batch": 256, "dtype": "float32"},
        {"widths": (16, 256, 256, 4), "acts": ("relu", "relu", "identity"),
         "batch": 512, "dtype": "float32"},
    )))


# ------------------------------------------------------------------ ops ----
def fused_mlp_op(x, weights, biases, acts, *, force_kernel=False,
                 batch_tile=None):
    problem, arrays = _inspect(x, weights, biases, acts)
    return registry.dispatch(SPEC, problem, arrays,
                             force_kernel=force_kernel,
                             overrides={"batch_tile": batch_tile})


def fused_mlp_sharded(x, weights, biases, acts, *, mesh, data_axes,
                      force_kernel=False, batch_tile=None):
    """Batch-sharded fused MLP under GSPMD via shard_map.

    Weights replicate (the whole net already fits VMEM per chip — that is
    the kernel's premise); the batch splits over ``data_axes`` and each
    shard runs the VMEM-resident kernel on its local rows, so pure-MLP
    bundles keep the fast path when the engine serves a sharded mesh.

    Falls back to the unsharded op when the batch does not divide the
    shard count (serve-path buckets are powers of two, so in practice
    only tiny eager calls fall back).
    """
    n_shards = 1
    for a in data_axes:
        n_shards *= mesh.shape[a]
    if n_shards <= 1 or x.shape[0] % n_shards:
        return fused_mlp_op(x, weights, biases, acts,
                            force_kernel=force_kernel,
                            batch_tile=batch_tile)
    ax = data_axes[0] if len(data_axes) == 1 else tuple(data_axes)
    xspec = P(*((ax,) + (None,) * (x.ndim - 1)))

    def local(xs, ws, bs):
        # xs carries the *per-shard* batch here, so the tuned-tile
        # lookup keys on the rows each chip actually serves
        return fused_mlp_op(xs, ws, bs, acts, force_kernel=force_kernel,
                            batch_tile=batch_tile)

    f = jax.shard_map(local, mesh=mesh, in_specs=(xspec, P(), P()),
                      out_specs=xspec, check_vma=False)
    return f(x, list(weights), list(biases))


def mlp_stack_from_spec(spec, params, x):
    """Walk a pure-dense Sequential bundle spec into the fused kernel's
    call shape: ``(x, weights, biases, acts)``.

    Layer spec pattern: dense [act] dense [act] ... ; activations between
    denses become the per-layer act, trailing dense gets 'identity'.
    ``params=None`` walks acts/flatten only (weights come back empty) —
    the int8 adapter serves pre-quantized residency instead.
    """
    weights, biases, acts = [], [], []
    pending_w = None
    plist = params if params is not None else [None] * len(spec["layers"])
    for layer_spec, p in zip(spec["layers"], plist):
        if layer_spec["kind"] == "dense":
            if pending_w is not None:
                acts.append("identity")
            if p is not None:
                weights.append(p["w"])
                biases.append(p.get("b", jnp.zeros((p["w"].shape[1],),
                                                   p["w"].dtype)))
            pending_w = True
        elif layer_spec["kind"] == "act":
            acts.append(layer_spec["name"])
            pending_w = None
        elif layer_spec["kind"] == "flatten":
            x = x.reshape(x.shape[0], -1)
    if pending_w is not None:
        acts.append("identity")
    return x, weights, biases, acts


def fused_mlp_from_spec(spec, params, x, *, mesh=None, data_axes=()):
    """Adapter: run a pure-dense Sequential bundle through the kernel."""
    x, weights, biases, acts = mlp_stack_from_spec(spec, params, x)
    if mesh is not None and data_axes:
        return fused_mlp_sharded(x, weights, biases, acts, mesh=mesh,
                                 data_axes=tuple(data_axes))
    return fused_mlp_op(x, weights, biases, acts)
