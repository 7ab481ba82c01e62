"""Pallas TPU kernel for the data-bridge stencil gather (paper Fig. 4).

The tensor-map hot path for stencil functors is an im2col-style gather:
for every sweep point (i, j) emit F features, each a fixed (dy, dx) offset
read of the source grid.  On TPU we tile the OUTPUT over (8, 128)-aligned
blocks; the source grid is VMEM-resident and every feature is a shifted
view of one block-plus-halo window — no HBM round-trips between features,
unlike F separate strided slices.

Mosaic only loads VMEM at offsets it can prove tile-aligned (a multiple
of 8 rows and 128 lanes for f32), so a read at ``(i0 + dy, j0 + dx)`` is
refused.  The kernel instead loads the aligned window that covers the
block and its halo once, and shifts it in registers with ``pltpu.roll``
per feature; the shifted block is then the window's aligned top-left
corner.  The kernel writes features lane-dense as ``[F, H, W]`` and the
wrapper transposes to the ``[H, W, F]`` im2col layout.

Offsets are static (they come from symbolic shape extraction), so the
feature loop unrolls at trace time into vector moves.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.registry import round_up

SUBLANE, LANE = 8, 128


def halo(offsets):
    """(rows, lanes) the aligned window adds below and right of a block:
    the largest offset on each axis, rounded up to the tile."""
    return (round_up(max(dy for dy, _ in offsets), SUBLANE),
            round_up(max(dx for _, dx in offsets), LANE))


def _shift(win, d, axis):
    """``win`` moved ``d`` places towards index 0 along ``axis``."""
    n = win.shape[axis]
    return win if d % n == 0 else pltpu.roll(win, n - d % n, axis)


def _kernel(x_ref, o_ref, *, offsets, block_h, block_w, halo_h, halo_w):
    """x_ref: full (padded) grid in VMEM; o_ref: [F, block_h, block_w]."""
    i0 = pl.multiple_of(pl.program_id(0) * block_h, block_h)
    j0 = pl.multiple_of(pl.program_id(1) * block_w, block_w)
    win = x_ref[pl.ds(i0, block_h + halo_h), pl.ds(j0, block_w + halo_w)]
    rows = {}
    for f, (dy, dx) in enumerate(offsets):
        if dy not in rows:
            rows[dy] = _shift(win, dy, 0)
        o_ref[f] = _shift(rows[dy], dx, 1)[:block_h, :block_w]


def stencil_gather(x, offsets, out_h, out_w, *, origin=(0, 0),
                   block_h: int = 8, block_w: int = 128, interpret: bool):
    """Gather im2col features.

    x: [H, W] source grid.  offsets: list of (dy, dx) per feature, relative
    to the sweep origin.  Returns [out_h, out_w, F] with
    ``out[i, j, f] = x[origin0 + i + dy_f, origin1 + j + dx_f]``.
    """
    F = len(offsets)
    offs = tuple((origin[0] + dy, origin[1] + dx) for dy, dx in offsets)
    if min(min(o) for o in offs) < 0:
        raise ValueError(f"stencil_gather: offsets {offsets} from origin "
                         f"{origin} read before the grid's first row/column")
    halo_h, halo_w = halo(offs)
    gh = -(-out_h // block_h)
    gw = -(-out_w // block_w)
    # pad so every aligned block + halo window stays in bounds
    H, W = x.shape
    xp = jnp.pad(x, ((0, max(0, gh * block_h + halo_h - H)),
                     (0, max(0, gw * block_w + halo_w - W))))

    out = pl.pallas_call(
        functools.partial(_kernel, offsets=offs, block_h=block_h,
                          block_w=block_w, halo_h=halo_h, halo_w=halo_w),
        out_shape=jax.ShapeDtypeStruct((F, gh * block_h, gw * block_w),
                                       x.dtype),
        grid=(gh, gw),
        in_specs=[pl.BlockSpec(xp.shape, lambda i, j: (0, 0))],
        out_specs=pl.BlockSpec((F, block_h, block_w),
                               lambda i, j: (0, i, j)),
        interpret=interpret,
    )(xp)
    return jnp.transpose(out[:, :out_h, :out_w], (1, 2, 0))
