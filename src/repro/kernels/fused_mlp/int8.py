"""Pallas TPU kernel: int8 fused MLP (the quantized serving tier).

Same shape as :mod:`repro.kernels.fused_mlp.fused_mlp` — whole net
resident in VMEM, batch tiled over the grid — but the weight matrices
arrive **statically quantized per output channel** (int8 values + one
f32 scale per column, prepared once at bundle load by
:mod:`repro.quant.quantize`), and each activation tile is **dynamically
quantized per row inside the kernel**: absmax/127 row scales, an
int8 x int8 -> int32 MXU dot, and the rank-1 dequant
(``hs[:, None] * ws[None, :]``) fused straight into the bias+activation
epilogue.  Activations never leave VMEM between layers, and the HBM
traffic the roofline prices — the weights — drops to a quarter of the
f32 kernel's.

Validation tolerance (declared on the spec, consumed by the tuner and
the registry parity tests): the oracle is the int8-*simulating* jnp
path (:func:`repro.quant.quantize.quant_mlp_ref`), not the f32 net —
quantization error is the quant gate's concern, measured against real
calibration rows per bundle, not a kernel-correctness concern.  Kernel
vs oracle differ only where an activation sits exactly on a rounding
boundary and the two paths' f32 rounding pushes it to different int8
steps; one flipped step moves that lane by ``absmax/127``, so the
tolerance is sized to one quantization step of a unit-scale activation
(2/127 ~ 1.6e-2) rather than f32 epsilon.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

from repro.kernels import registry
from repro.kernels.fused_mlp.fused_mlp import _ACTS

QMAX = 127.0

DEFAULT_TILE = 128
_TILE_LADDER = (16, 32, 64, 128, 256, 512)

#: one int8 re-quantization step of a unit-scale activation (see module
#: docstring: a borderline round can legitimately differ between the
#: kernel and the simulation oracle)
TOL = (2e-2, 2e-2)


def _kernel(*refs, n_layers, acts):
    x_ref = refs[0]
    o_ref = refs[-1]
    wsb = refs[1:-1]  # per layer: wq (int8), ws (f32), b (f32)
    h = x_ref[...].astype(jnp.float32)
    for l in range(n_layers):
        wq = wsb[3 * l][...]
        ws = wsb[3 * l + 1][...]
        b = wsb[3 * l + 2][...]
        absmax = jnp.max(jnp.abs(h), axis=1, keepdims=True)
        hs = jnp.where(absmax > 0, absmax, 1.0) / QMAX
        hq = jnp.round(h / hs).astype(jnp.int8)
        acc = jnp.dot(hq, wq, preferred_element_type=jnp.int32)
        h = _ACTS[acts[l]](acc.astype(jnp.float32) * hs * ws + b)
    o_ref[...] = h.astype(o_ref.dtype)


def fits_vmem_int8(widths, batch_tile=128, budget=None, act_bytes=4):
    """Per-operand VMEM accounting for one grid step of the int8 kernel.

    Unlike the f32 predicate, tiles are priced at their **own** dtypes:
    int8 weights pad to the (32, 128) int8 register layout (1 byte per
    element), the f32 scale/bias rows to (8, 128), and the activation
    working set counts the f32 tile (in/out, double-buffered), its int8
    quantized twin, and the int32 accumulator.
    """
    from repro.kernels.registry import device_vmem_budget, tile_bytes
    if budget is None:
        budget = device_vmem_budget()
    wbytes = sum(tile_bytes(a, b, 1)
                 for a, b in zip(widths[:-1], widths[1:]))
    sbytes = 2 * sum(tile_bytes(1, b, 4) for b in widths[1:])  # ws + b
    mw = max(widths)
    abytes = (2 * 2 * tile_bytes(batch_tile, mw, act_bytes)  # h in/out x2
              + tile_bytes(batch_tile, mw, 1)                # hq scratch
              + tile_bytes(batch_tile, mw, 4))               # int32 acc
    return wbytes + sbytes + abytes <= budget


def fused_mlp_int8(x, qlayers, acts, *, batch_tile: int = 128,
                   interpret: bool):
    """x: [B, F0] float; qlayers: [(wq int8 [Fi,Fo], ws f32 [Fo],
    b f32 [Fo]), ...]; acts: per-layer activation name."""
    B, F0 = x.shape
    n_layers = len(qlayers)
    Fo = qlayers[-1][0].shape[1]
    pb = -B % batch_tile
    xp = jnp.pad(x, ((0, pb), (0, 0)))
    grid = ((B + pb) // batch_tile,)

    in_specs = [pl.BlockSpec((batch_tile, F0), lambda i: (i, 0))]
    args = [xp]
    for wq, ws, b in qlayers:
        in_specs.append(pl.BlockSpec(wq.shape, lambda i: (0, 0)))
        in_specs.append(pl.BlockSpec(ws.shape, lambda i: (0,)))
        in_specs.append(pl.BlockSpec(b.shape, lambda i: (0,)))
        args += [wq, ws, b]

    out = pl.pallas_call(
        functools.partial(_kernel, n_layers=n_layers, acts=tuple(acts)),
        out_shape=jax.ShapeDtypeStruct((B + pb, Fo), x.dtype),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((batch_tile, Fo), lambda i: (i, 0)),
        interpret=interpret,
    )(*args)
    return out[:B]


# ----------------------------------------------------------- KernelSpec ----
def _inspect(x, qlayers, acts):
    widths = (int(qlayers[0][0].shape[0]),) + tuple(int(q[0].shape[1])
                                                    for q in qlayers)
    problem = {"widths": widths, "acts": tuple(acts),
               "batch": int(x.shape[0]), "dtype": str(np.dtype(x.dtype))}
    return problem, (x, tuple(tuple(q) for q in qlayers))


def _run(problem, arrays, params, *, interpret):
    x, qlayers = arrays
    return fused_mlp_int8(x, list(qlayers), problem["acts"],
                          batch_tile=params["batch_tile"],
                          interpret=interpret)


def _ref(problem, arrays):
    from repro.quant.quantize import quant_mlp_ref
    x, qlayers = arrays
    return quant_mlp_ref(x, list(qlayers), problem["acts"])


def _make(problem, rng):
    from repro.quant.quantize import quantize_params
    widths, dtype = problem["widths"], problem["dtype"]
    ws = [rng.normal(size=(a, b)).astype(np.float32) * 0.3
          for a, b in zip(widths[:-1], widths[1:])]
    bs = [rng.normal(size=(b,)).astype(np.float32) * 0.1
          for b in widths[1:]]
    x = jnp.asarray(rng.normal(size=(problem["batch"], widths[0]))
                    .astype(np.float32), dtype)
    return (x, tuple(tuple(q) for q in quantize_params(ws, bs)))


def _key(problem, backend):
    from repro.tune.cache import shape_key
    return shape_key(problem["widths"], problem["dtype"], backend,
                     problem["batch"])


def _keys(problem, backend):
    from repro.serve.batcher import bucket_size
    from repro.tune.cache import shape_key
    b = problem["batch"]
    return [shape_key(problem["widths"], problem["dtype"], backend, bb)
            for bb in dict.fromkeys((b, bucket_size(b)))]


def candidate_tiles_int8(widths, bucket, extra=()):
    """Tiles worth sweeping for one bucket under the *int8* VMEM model
    (a net too fat for the f32 kernel can still fit quantized)."""
    tiles = [DEFAULT_TILE]
    for t in _TILE_LADDER + (int(bucket),) + tuple(extra):
        t = int(t)
        if 0 < t <= bucket and t not in tiles:
            tiles.append(t)
    return [t for t in tiles if fits_vmem_int8(widths, t)]


def _cands(problem):
    return [{"batch_tile": t}
            for t in candidate_tiles_int8(problem["widths"],
                                          problem["batch"])]


def _fits(problem, params, budget=None):
    act_bytes = np.dtype(problem["dtype"]).itemsize
    return fits_vmem_int8(problem["widths"], params["batch_tile"],
                          budget=budget, act_bytes=act_bytes)


def _supports(problem):
    return fits_vmem_int8(problem["widths"],
                          act_bytes=np.dtype(problem["dtype"]).itemsize)


SPEC = registry.register(registry.KernelSpec(
    name="fused_mlp_int8",
    params=(registry.TunableParam("batch_tile", DEFAULT_TILE, _TILE_LADDER),),
    inspect=_inspect, run_call=_run, ref_call=_ref, make_call=_make,
    cache_key=_key, cache_keys=_keys, candidates=_cands, fits=_fits,
    supports=_supports, tol=TOL, tier="int8",
    default_problems=(
        {"widths": (5, 128, 128, 1), "acts": ("relu", "relu", "identity"),
         "batch": 256, "dtype": "float32"},
        {"widths": (16, 256, 256, 4), "acts": ("relu", "relu", "identity"),
         "batch": 512, "dtype": "float32"},
    )))


# ------------------------------------------------------------------ ops ----
def fused_mlp_int8_op(x, qlayers, acts, *, force_kernel=False,
                      batch_tile=None):
    problem, arrays = _inspect(x, qlayers, acts)
    return registry.dispatch(SPEC, problem, arrays,
                             force_kernel=force_kernel,
                             overrides={"batch_tile": batch_tile})


def fused_mlp_int8_sharded(x, qlayers, acts, *, mesh, data_axes,
                           force_kernel=False, batch_tile=None):
    """Batch-sharded int8 fused MLP: quantized weights+scales replicate
    (they fit VMEM per chip by the kernel's premise), the batch splits
    over ``data_axes`` — the int8 twin of ``fused_mlp_sharded``."""
    n_shards = 1
    for a in data_axes:
        n_shards *= mesh.shape[a]
    if n_shards <= 1 or x.shape[0] % n_shards:
        return fused_mlp_int8_op(x, qlayers, acts,
                                 force_kernel=force_kernel,
                                 batch_tile=batch_tile)
    ax = data_axes[0] if len(data_axes) == 1 else tuple(data_axes)
    xspec = P(*((ax,) + (None,) * (x.ndim - 1)))

    def local(xs, qs):
        return fused_mlp_int8_op(xs, qs, acts, force_kernel=force_kernel,
                                 batch_tile=batch_tile)

    f = jax.shard_map(local, mesh=mesh, in_specs=(xspec, P()),
                      out_specs=xspec, check_vma=False)
    return f(x, [tuple(q) for q in qlayers])


def fused_mlp_int8_from_spec(spec, qlayers, x, *, mesh=None, data_axes=()):
    """Adapter: run a pure-dense bundle through the int8 kernel using
    pre-quantized layer residency (``InferenceEngine`` quantizes once at
    load; see ``engine._quant_residency``)."""
    from repro.kernels.fused_mlp.ops import mlp_stack_from_spec
    x, _, _, acts = mlp_stack_from_spec(spec, None, x)
    if mesh is not None and data_axes:
        return fused_mlp_int8_sharded(x, qlayers, acts, mesh=mesh,
                                      data_axes=tuple(data_axes))
    return fused_mlp_int8_op(x, qlayers, acts)
