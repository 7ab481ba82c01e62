"""Pallas TPU flash attention (online softmax, GQA-native).

Grid: (batch, q_heads, Sq / block_q).  Each program holds one q block
[block_q, hd] in VMEM plus its kv head's full K/V [Skv, hd] (the
BlockSpec index map selects kv head q_head // group — GQA without
materializing repeated KV, unlike the portable jnp path).  The kv loop is
a `fori_loop` over block_k chunks with running (max, denom, acc) carried
in VMEM — scores never exist at [Sq, Skv] size.

Causal masking uses absolute positions (q_offset supports decode windows).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, *, block_k, causal, q_offset,
            kv_valid, scale):
    bq, hd = q_ref.shape[1], q_ref.shape[3]
    skv = k_ref.shape[1]
    q = q_ref[0, :, 0, :].astype(jnp.float32) * scale
    qi = pl.program_id(2)
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0) + q_offset

    nk = skv // block_k

    def body(ki, carry):
        acc, m, l = carry
        k = k_ref[0, pl.dslice(ki * block_k, block_k), 0, :].astype(jnp.float32)
        v = v_ref[0, pl.dslice(ki * block_k, block_k), 0, :].astype(jnp.float32)
        s = q @ k.T  # [bq, block_k]
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)
        mask = k_pos < kv_valid
        if causal:
            mask = mask & (k_pos <= q_pos)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=1, keepdims=True)
        acc_new = acc * corr + p @ v
        return acc_new, m_new, l_new

    acc = jnp.zeros((bq, hd), jnp.float32)
    m = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((bq, 1), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, nk, body, (acc, m, l))
    o_ref[0, :, 0, :] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal=True, block_q=128, block_k=128,
                    q_offset=0, kv_valid_len=None, interpret):
    """q: [B, Sq, H, hd]; k, v: [B, Skv, KV, hd] with H % KV == 0."""
    B, Sq, H, hd = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    group = H // KV
    scale = 1.0 / (hd ** 0.5)
    pq = -Sq % block_q
    pk = -Skv % block_k
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    valid = Skv if kv_valid_len is None else kv_valid_len

    grid = (B, H, (Sq + pq) // block_q)
    out = pl.pallas_call(
        functools.partial(_kernel, block_k=block_k, causal=causal,
                          q_offset=q_offset, kv_valid=valid, scale=scale),
        out_shape=jax.ShapeDtypeStruct((B, Sq + pq, H, hd), q.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, 1, hd),
                         lambda b, h, i: (b, i, h, 0)),
            pl.BlockSpec((1, Skv + pk, 1, hd),
                         lambda b, h, i, g=group: (b, 0, h // g, 0)),
            pl.BlockSpec((1, Skv + pk, 1, hd),
                         lambda b, h, i, g=group: (b, 0, h // g, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, 1, hd),
                               lambda b, h, i: (b, i, h, 0)),
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :Sq]
