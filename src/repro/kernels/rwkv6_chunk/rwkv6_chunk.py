"""Pallas TPU kernel: RWKV6 (Finch) WKV chunk scan.

One program per (batch, head).  The [hd_k, hd_v] state matrix lives in a
VMEM accumulator; the time loop runs *inside* the kernel (fori_loop), so
the recurrence never round-trips HBM between tokens — the portable jnp
path needs O(c * hd^2) associative-scan intermediates instead.  Rank-1
updates map to VPU outer products; hd = 64 keeps lanes full.

    S_t = diag(w_t) S_{t-1} + k_t^T v_t
    o_t = r_t (S_{t-1} + diag(u) k_t^T v_t)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, o_ref, sT_ref, *,
            seq_len):
    hd = r_ref.shape[-1]
    S = s0_ref[0, 0].astype(jnp.float32)  # [hd, hd]
    u = u_ref[0].astype(jnp.float32)      # [hd]

    def body(t, S):
        r = r_ref[0, t, 0, :].astype(jnp.float32)
        k = k_ref[0, t, 0, :].astype(jnp.float32)
        v = v_ref[0, t, 0, :].astype(jnp.float32)
        w = w_ref[0, t, 0, :].astype(jnp.float32)
        kv = k[:, None] * v[None, :]             # [hd_k, hd_v]
        o = (r[None, :] @ (S + u[:, None] * kv))[0]  # [hd_v]
        o_ref[0, t, 0, :] = o.astype(o_ref.dtype)
        return w[:, None] * S + kv

    S = jax.lax.fori_loop(0, seq_len, body, S)
    sT_ref[0, 0] = S.astype(sT_ref.dtype)


def rwkv6_chunk(r, k, v, w, u, s0, *, interpret):
    """r,k,v,w: [B, T, H, hd]; u: [H, hd]; s0: [B, H, hd, hd].

    Returns (o [B, T, H, hd], sT [B, H, hd, hd]).
    """
    B, T, H, hd = r.shape
    out = pl.pallas_call(
        functools.partial(_kernel, seq_len=T),
        out_shape=(
            jax.ShapeDtypeStruct((B, T, H, hd), r.dtype),
            jax.ShapeDtypeStruct((B, H, hd, hd), jnp.float32),
        ),
        grid=(B, H),
        in_specs=[
            pl.BlockSpec((1, T, 1, hd), lambda b, h: (b, 0, h, 0)),
            pl.BlockSpec((1, T, 1, hd), lambda b, h: (b, 0, h, 0)),
            pl.BlockSpec((1, T, 1, hd), lambda b, h: (b, 0, h, 0)),
            pl.BlockSpec((1, T, 1, hd), lambda b, h: (b, 0, h, 0)),
            pl.BlockSpec((1, hd), lambda b, h: (h, 0)),
            pl.BlockSpec((1, 1, hd, hd), lambda b, h: (b, h, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, T, 1, hd), lambda b, h: (b, 0, h, 0)),
            pl.BlockSpec((1, 1, hd, hd), lambda b, h: (b, h, 0, 0)),
        ),
        interpret=interpret,
    )(r, k, v, w, u, s0)
    return out
