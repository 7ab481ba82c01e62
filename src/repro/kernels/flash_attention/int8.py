"""Pallas TPU flash attention with an int8 KV/score path.

Decode-regime attention is KV-read-bound: each query block streams the
whole KV cache from HBM.  This variant stores K and V as int8 (plus
per-token K scales and per-channel V scales — a quarter of the f32 KV
bytes on the bandwidth-bound axis) and computes the score dot on the
MXU as int8 x int8 -> int32:

  * **q** is quantized per row *inside the kernel* (absmax/127 row
    scales): the score dot contracts over head_dim, so the row scale
    commutes out exactly — ``s = (qq @ kq.T) * (qs * scale) * ks.T``;
  * **k** is quantized per token (scale constant over head_dim, the
    contraction axis of the score dot);
  * softmax and the p@v dot stay f32: V dequantizes in VMEM right
    before the accumulate.  Quantizing p would couple its rounding to
    the online-softmax block structure (the running max differs per
    block_kv choice), making candidates incomparable against a
    block-independent oracle; dequantizing V locally keeps the HBM
    savings — V still *travels* as int8 — while the oracle stays exact.

The declared tolerance mirrors ``fused_mlp_int8``'s rationale: kernel
and int8-simulating oracle agree except where a q value rounds to a
different int8 step between the two paths' f32 orders — one step of a
unit-scale row, not f32 epsilon.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels import registry
from repro.kernels.flash_attention.flash_attention import NEG_INF

QMAX = 127.0

_BLOCK_LADDER = (16, 32, 64, 128, 256)
_DEFAULT_BLOCK = 128

TOL = (2e-2, 2e-2)


def _kernel(q_ref, kq_ref, ks_ref, vq_ref, vs_ref, o_ref, *, block_k,
            causal, q_offset, kv_valid, scale):
    bq, hd = q_ref.shape[1], q_ref.shape[3]
    skv = kq_ref.shape[1]
    q = q_ref[0, :, 0, :].astype(jnp.float32)
    absmax = jnp.max(jnp.abs(q), axis=1, keepdims=True)
    qs = jnp.where(absmax > 0, absmax, 1.0) / QMAX
    qq = jnp.round(q / qs).astype(jnp.int8)
    qi = pl.program_id(2)
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0) \
        + q_offset
    vs = vs_ref[0, 0, 0, :]  # per-channel V scales [hd]

    nk = skv // block_k

    def body(ki, carry):
        acc, m, l = carry
        kq = kq_ref[0, pl.dslice(ki * block_k, block_k), 0, :]
        ks = ks_ref[0, pl.dslice(ki * block_k, block_k), 0, 0]
        vq = vq_ref[0, pl.dslice(ki * block_k, block_k), 0, :]
        s32 = jnp.dot(qq, kq.T, preferred_element_type=jnp.int32)
        # rank-1 dequant: row scale x token scale, with 1/sqrt(hd) folded
        s = s32.astype(jnp.float32) * (qs * scale) * ks[None, :]
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
        # V dequantizes in VMEM: it crossed HBM as int8, compute is f32
        v = vq.astype(jnp.float32) * vs[None, :]
        acc_new = acc * corr + p @ v
        return acc_new, m_new, l_new

    acc = jnp.zeros((bq, hd), jnp.float32)
    m = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((bq, 1), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, nk, body, (acc, m, l))
    o_ref[0, :, 0, :] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def flash_attention_int8(q, kq, ks, vq, vs, *, causal=True, block_q=128,
                         block_k=128, q_offset=0, kv_valid_len=None,
                         interpret):
    """q: [B, Sq, H, hd] float; kq/vq: int8 [B, Skv, KV, hd];
    ks: f32 [B, Skv, KV, 1] per-token; vs: f32 [B, 1, KV, hd]
    per-channel (see :func:`repro.quant.quantize.quantize_kv`)."""
    B, Sq, H, hd = q.shape
    Skv, KV = kq.shape[1], kq.shape[2]
    group = H // KV
    scale = 1.0 / (hd ** 0.5)
    pq = -Sq % block_q
    pk = -Skv % block_k
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    kqp = jnp.pad(kq, ((0, 0), (0, pk), (0, 0), (0, 0)))
    ksp = jnp.pad(ks, ((0, 0), (0, pk), (0, 0), (0, 0)),
                  constant_values=1.0)
    vqp = jnp.pad(vq, ((0, 0), (0, pk), (0, 0), (0, 0)))
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
            pl.BlockSpec((1, Skv + pk, 1, 1),
                         lambda b, h, i, g=group: (b, 0, h // g, 0)),
            pl.BlockSpec((1, Skv + pk, 1, hd),
                         lambda b, h, i, g=group: (b, 0, h // g, 0)),
            pl.BlockSpec((1, 1, 1, hd),
                         lambda b, h, i, g=group: (b, 0, h // g, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, 1, hd),
                               lambda b, h, i: (b, i, h, 0)),
        interpret=interpret,
    )(qp, kqp, ksp, vqp, vs)
    return out[:, :Sq]


def flash_attention_int8_ref(q, kq, ks, vq, vs, *, causal=True,
                             q_offset=0):
    """int8-simulating naive-softmax oracle: identical quantization
    decisions (q per row, K/V pre-quantized), materialized scores.
    Block-structure independent — any (block_q, block_kv) candidate
    must match it."""
    B, Sq, H, hd = q.shape
    Skv, KV = kq.shape[1], kq.shape[2]
    group = H // KV
    scale = 1.0 / (hd ** 0.5)
    qf = jnp.asarray(q, jnp.float32)
    absmax = jnp.max(jnp.abs(qf), axis=-1, keepdims=True)
    qs = jnp.where(absmax > 0, absmax, 1.0) / QMAX
    qq = jnp.round(qf / qs).astype(jnp.int8)
    # expand GQA heads: kv head h // group serves q head h
    kqe = jnp.repeat(kq, group, axis=2)
    kse = jnp.repeat(ks, group, axis=2)
    vqe = jnp.repeat(vq, group, axis=2)
    vse = jnp.repeat(vs, group, axis=2)
    s32 = jnp.einsum("bqhd,bkhd->bhqk", qq, kqe,
                     preferred_element_type=jnp.int32)
    s = (s32.astype(jnp.float32)
         * jnp.transpose(qs * scale, (0, 2, 1, 3))  # [B,H,Sq,1]
         * jnp.transpose(kse, (0, 2, 3, 1)))        # [B,H,1,Skv]
    k_pos = jnp.arange(Skv)[None, :]
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        q_pos = jnp.arange(Sq)[:, None] + q_offset
        mask = k_pos <= q_pos
    s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    v = vqe.astype(jnp.float32) * vse  # [B,Skv,H,hd]
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    return o.astype(q.dtype)


# ----------------------------------------------------------- KernelSpec ----
def _inspect(q, kq, ks, vq, vs, *, causal=True, q_offset=0):
    B, Sq, H, hd = q.shape
    problem = {"b": int(B), "sq": int(Sq), "skv": int(kq.shape[1]),
               "h": int(H), "kv": int(kq.shape[2]), "hd": int(hd),
               "causal": bool(causal), "q_offset": int(q_offset),
               "dtype": str(np.dtype(q.dtype))}
    return problem, (q, kq, ks, vq, vs)


def _run(problem, arrays, params, *, interpret):
    q, kq, ks, vq, vs = arrays
    return flash_attention_int8(q, kq, ks, vq, vs,
                                causal=problem["causal"],
                                q_offset=problem["q_offset"],
                                block_q=params["block_q"],
                                block_k=params["block_kv"],
                                interpret=interpret)


def _ref(problem, arrays):
    q, kq, ks, vq, vs = arrays
    return flash_attention_int8_ref(q, kq, ks, vq, vs,
                                    causal=problem["causal"],
                                    q_offset=problem["q_offset"])


def _make(problem, rng):
    from repro.quant.quantize import quantize_kv

    def t(*shape):
        return jnp.asarray(rng.normal(size=shape).astype(np.float32),
                           problem["dtype"])
    q = t(problem["b"], problem["sq"], problem["h"], problem["hd"])
    k = t(problem["b"], problem["skv"], problem["kv"], problem["hd"])
    v = t(problem["b"], problem["skv"], problem["kv"], problem["hd"])
    kq, ks, vq, vs = quantize_kv(k, v)
    return (q, kq, ks, vq, vs)


def _key(problem, backend):
    p = problem
    shape = (f"b{p['b']}-sq{p['sq']}-skv{p['skv']}-h{p['h']}-kv{p['kv']}-"
             f"hd{p['hd']}-c{int(p['causal'])}")
    return f"{shape}|{p['dtype']}|{backend}"


def _fits(problem, params, budget=None):
    """Per-operand VMEM pricing: the q block and f32 scratch at the
    activation dtype, K/V resident as *int8* tiles plus their f32 scale
    strips — the whole point of the variant's cost model."""
    if budget is None:
        budget = registry.device_vmem_budget()
    bq, bk = params["block_q"], params["block_kv"]
    hd = problem["hd"]
    act = np.dtype(problem["dtype"]).itemsize
    skv_p = registry.round_up(problem["skv"], bk)
    t = registry.tile_bytes
    resident = (2 * t(bq, hd, act)          # q block, double-buffered
                + 2 * 2 * t(skv_p, hd, 1)   # int8 K and V, double-buffered
                + 2 * t(skv_p, 1, 4)        # K token scales
                + 2 * t(1, hd, 4)           # V channel scales
                + t(bq, hd, 1)              # qq scratch
                + t(bq, bk, 4)              # f32 score block
                + t(bk, hd, 4)              # dequantized V chunk
                + t(bq, hd, 4)              # acc
                + 2 * t(bq, 1, 4)           # m, l
                + 2 * t(bq, hd, act))       # out block, double-buffered
    return resident <= budget


def _cands(problem):
    clip = {"block_q": registry.round_up(problem["sq"], 16),
            "block_kv": registry.round_up(problem["skv"], 16)}
    return registry.ladder_candidates(
        SPEC.params, clip, fits=lambda c: _fits(problem, c))


SPEC = registry.register(registry.KernelSpec(
    name="flash_attention_int8",
    params=(registry.TunableParam("block_q", _DEFAULT_BLOCK, _BLOCK_LADDER),
            registry.TunableParam("block_kv", _DEFAULT_BLOCK,
                                  _BLOCK_LADDER)),
    inspect=_inspect, run_call=_run, ref_call=_ref, make_call=_make,
    cache_key=_key, candidates=_cands, fits=_fits,
    tol=TOL, tier="int8",
    default_problems=(
        # the decode regime the int8 KV path exists for: short q block
        # against a long quantized cache
        {"b": 4, "sq": 32, "skv": 512, "h": 8, "kv": 2, "hd": 64,
         "causal": True, "q_offset": 480, "dtype": "float32"},
    )))


# ------------------------------------------------------------------ ops ----
@functools.partial(jax.jit, static_argnames=("causal", "q_offset",
                                             "force_kernel", "block_q",
                                             "block_kv"))
def flash_attention_int8_op(q, kq, ks, vq, vs, *, causal=True, q_offset=0,
                            force_kernel=False, block_q=None,
                            block_kv=None):
    """Attention over a pre-quantized KV cache (see
    :func:`repro.quant.quantize.quantize_kv` for the layout)."""
    problem, arrays = _inspect(q, kq, ks, vq, vs, causal=causal,
                               q_offset=q_offset)
    return registry.dispatch(SPEC, problem, arrays,
                             force_kernel=force_kernel,
                             overrides={"block_q": block_q,
                                        "block_kv": block_kv})
