"""Registry shim + spec for the stencil-gather (im2col) data bridge.

Tunables: the output row/column tiles ``block_h``/``block_w``.  The
kernel is a pure gather, so validation is bit-exact; the tile choice
only trades grid-step overhead against tile-padding waste.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import registry
from repro.kernels.stencil_gather.ref import stencil_gather_ref
from repro.kernels.stencil_gather.stencil_gather import halo, stencil_gather

_H_LADDER = (8, 16, 32, 64)
_W_LADDER = (128, 256, 512)


# ----------------------------------------------------------- KernelSpec ----
def _inspect(x, *, offsets, out_h, out_w, origin=(0, 0)):
    offsets = tuple(tuple(int(v) for v in o) for o in offsets)
    problem = {"h": int(x.shape[0]), "w": int(x.shape[1]),
               "out_h": int(out_h), "out_w": int(out_w),
               "offsets": offsets, "origin": tuple(int(v) for v in origin),
               "dtype": str(np.dtype(x.dtype))}
    return problem, (x,)


def _run(problem, arrays, params, *, interpret):
    return stencil_gather(arrays[0], problem["offsets"], problem["out_h"],
                          problem["out_w"], origin=problem["origin"],
                          block_h=params["block_h"],
                          block_w=params["block_w"], interpret=interpret)


def _ref(problem, arrays):
    return stencil_gather_ref(arrays[0], problem["offsets"],
                              problem["out_h"], problem["out_w"],
                              origin=problem["origin"])


def _make(problem, rng):
    x = jnp.asarray(rng.normal(size=(problem["h"], problem["w"]))
                    .astype(np.float32), problem["dtype"])
    return (x,)


def _halo(problem):
    o0, o1 = problem["origin"]
    dys = [o0 + dy for dy, _ in problem["offsets"]]
    dxs = [o1 + dx for _, dx in problem["offsets"]]
    return max(dys), max(dxs)


def _key(problem, backend):
    """Tile choice depends on the output extent, the feature count, and
    the halo — not on the individual offsets, so stencils sharing those
    share a tuned entry (tile params are correctness-neutral)."""
    dy, dx = _halo(problem)
    p = problem
    shape = (f"h{p['h']}-w{p['w']}-oh{p['out_h']}-ow{p['out_w']}-"
             f"f{len(p['offsets'])}-dy{dy}-dx{dx}")
    return f"{shape}|{p['dtype']}|{backend}"


def _fits(problem, params, budget=None):
    """The full source grid, padded to whole blocks plus the aligned
    halo, is VMEM-resident beside the ``[F, block_h, block_w]`` output
    tile; the pipeline double-buffers both."""
    if budget is None:
        budget = registry.device_vmem_budget()
    bh, bw = params["block_h"], params["block_w"]
    hh, hw = halo([_halo(problem)])
    gh = max(problem["h"], registry.round_up(problem["out_h"], bh) + hh)
    gw = max(problem["w"], registry.round_up(problem["out_w"], bw) + hw)
    t = registry.tile_bytes
    out_tile = len(problem["offsets"]) * t(bh, bw)
    return 2 * (t(gh, gw) + out_tile) <= budget


def _cands(problem):
    clip = {"block_h": registry.round_up(problem["out_h"], 8),
            "block_w": registry.round_up(problem["out_w"], 128)}
    return registry.ladder_candidates(
        SPEC.params, clip, fits=lambda c: _fits(problem, c))


SPEC = registry.register(registry.KernelSpec(
    name="stencil_gather",
    params=(registry.TunableParam("block_h", 8, _H_LADDER),
            registry.TunableParam("block_w", 128, _W_LADDER)),
    inspect=_inspect, run_call=_run, ref_call=_ref, make_call=_make,
    cache_key=_key, candidates=_cands, fits=_fits, tol=None,
    default_problems=(
        # miniweather-like sweep grid, 5-point stencil
        {"h": 512, "w": 512, "out_h": 508, "out_w": 508,
         "offsets": ((0, 1), (2, 0), (1, 1), (0, 0), (1, 2)),
         "origin": (1, 1), "dtype": "float32"},
    )))


# ------------------------------------------------------------------ ops ----
@functools.partial(jax.jit, static_argnames=("offsets", "out_h", "out_w",
                                             "origin", "force_kernel",
                                             "block_h", "block_w"))
def stencil_gather_op(x, *, offsets, out_h, out_w, origin=(0, 0),
                      force_kernel=False, block_h=None, block_w=None):
    problem, arrays = _inspect(x, offsets=offsets, out_h=out_h, out_w=out_w,
                               origin=origin)
    return registry.dispatch(SPEC, problem, arrays,
                             force_kernel=force_kernel,
                             overrides={"block_h": block_h,
                                        "block_w": block_w})


def functor_offsets(tensor_map):
    """Extract static (dy, dx) offsets from a 2-D point-slice TensorMap."""
    offs = []
    for desc in tensor_map.descriptors:
        for eo in desc.elem_offsets:
            offs.append((desc.offsets[0] + eo[0], desc.offsets[1] + eo[1]))
    return tuple(offs)
