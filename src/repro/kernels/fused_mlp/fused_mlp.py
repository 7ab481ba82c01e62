"""Pallas TPU kernel: whole-surrogate fused MLP inference.

The paper's NAS space produces small dense networks (hidden <= 4096).  On
GPU each layer is a separate cuBLAS call with HBM round-trips between
layers; on TPU the whole net fits VMEM, so one kernel keeps weights
resident, tiles the batch over the grid, and chains the layers on the MXU
with no intermediate HBM traffic — the TPU-native reading of the paper's
Observation 2 (surrogates win by raising hardware utilization).

VMEM budget: sum(W_l) + 2 * batch_tile * max_width * 4B must stay under
the device's VMEM budget (queried per device kind, 12 MiB off-TPU);
``fits_vmem`` guards this and the registry dispatch falls back to the
jnp path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_ACTS = {
    "relu": lambda x: jnp.maximum(x, 0.0),
    "gelu": jax.nn.gelu,
    "tanh": jnp.tanh,
    "silu": jax.nn.silu,
    "sigmoid": jax.nn.sigmoid,
    "identity": lambda x: x,
}


def _kernel(*refs, n_layers, acts):
    x_ref = refs[0]
    o_ref = refs[-1]
    wb = refs[1:-1]  # alternating w, b
    h = x_ref[...]
    for l in range(n_layers):
        w = wb[2 * l][...]
        b = wb[2 * l + 1][...]
        # Mosaic's default contracts f32 operands in one bf16 pass (on a
        # v5e, 5.8e-3 of max|y| off an f32 reference at 5-512-512-1);
        # the f32 tier must serve f32 numbers
        h = jnp.dot(h, w, preferred_element_type=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST) + b
        h = _ACTS[acts[l]](h)
    o_ref[...] = h.astype(o_ref.dtype)


def fits_vmem(widths, batch_tile=128, budget=None, dtype_bytes=4):
    """Exact VMEM accounting for one grid step of the fused kernel.

    VMEM tiles are padded to the TPU register layout — (8, 128) sublane x
    lane for f32 — so a [129, 5] weight occupies 136 x 128 lanes, not
    129 x 5.  Bias rows cost a full (8, 128)-padded tile each, and the
    batch tile rounds up to a sublane multiple.  The tuner trusts this
    predicate to reject configs that would overflow, so it must account
    every resident byte: weights + biases + input/output activation
    tiles (double-buffered pipeline: 2x each).

    ``budget=None`` queries the actual device's VMEM via the backend
    (:func:`repro.kernels.registry.device_vmem_budget`; 12 MiB off-TPU).
    """
    from repro.kernels.registry import device_vmem_budget, tile_bytes
    if budget is None:
        budget = device_vmem_budget()
    wbytes = sum(tile_bytes(a, b, dtype_bytes)
                 for a, b in zip(widths[:-1], widths[1:]))
    bbytes = sum(tile_bytes(1, b, dtype_bytes) for b in widths[1:])
    abytes = 2 * 2 * tile_bytes(batch_tile, max(widths), dtype_bytes)
    return wbytes + bbytes + abytes <= budget


def fused_mlp(x, weights, biases, acts, *, batch_tile: int = 128,
              interpret: bool):
    """x: [B, F0]; weights: list of [F_l, F_{l+1}]; acts: per-layer name."""
    B, F0 = x.shape
    n_layers = len(weights)
    Fo = weights[-1].shape[1]
    pb = -B % batch_tile
    xp = jnp.pad(x, ((0, pb), (0, 0)))
    grid = ((B + pb) // batch_tile,)

    in_specs = [pl.BlockSpec((batch_tile, F0), lambda i: (i, 0))]
    args = [xp]
    for w, b in zip(weights, biases):
        in_specs.append(pl.BlockSpec(w.shape, lambda i: (0, 0)))
        in_specs.append(pl.BlockSpec(b.shape, lambda i: (0,)))
        args += [w, b]

    out = pl.pallas_call(
        functools.partial(_kernel, n_layers=n_layers, acts=tuple(acts)),
        out_shape=jax.ShapeDtypeStruct((B + pb, Fo), x.dtype),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((batch_tile, Fo), lambda i: (i, 0)),
        interpret=interpret,
    )(*args)
    return out[:B]
