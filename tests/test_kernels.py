"""Per-kernel interpret-mode validation against the pure-jnp oracles,
with shape/dtype sweeps (hypothesis drives the stencil/flash cases)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.fused_mlp.fused_mlp import fused_mlp
from repro.kernels.fused_mlp.ref import fused_mlp_ref
from repro.kernels.rwkv6_chunk.ref import rwkv6_chunk_ref
from repro.kernels.rwkv6_chunk.rwkv6_chunk import rwkv6_chunk
from repro.kernels.stencil_gather.ref import stencil_gather_ref
from repro.kernels.stencil_gather.stencil_gather import stencil_gather


@settings(max_examples=10, deadline=None)
@given(
    h=st.integers(12, 40), w=st.integers(12, 40),
    seed=st.integers(0, 100),
    dtype=st.sampled_from(["float32", "bfloat16"]),
)
def test_stencil_gather_sweep(h, w, seed, dtype):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(h, w)).astype(np.float32)).astype(dtype)
    offs = ((0, 1), (2, 0), (1, 1), (0, 0), (1, 2))
    oh, ow = h - 3, w - 3
    a = stencil_gather(x, offs, oh, ow, origin=(1, 1), block_h=8, block_w=16,
                       interpret=True)
    b = stencil_gather_ref(x, offs, oh, ow, origin=(1, 1))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("widths,acts", [
    ((8, 32, 1), ("relu", "identity")),
    ((6, 64, 16, 4), ("gelu", "tanh", "identity")),
    ((5, 128, 2), ("silu", "identity")),
])
@pytest.mark.parametrize("batch", [16, 37, 130])
def test_fused_mlp_sweep(widths, acts, batch):
    rng = np.random.default_rng(0)
    ws = [jnp.asarray(rng.normal(size=(a, b)).astype(np.float32) * 0.3)
          for a, b in zip(widths[:-1], widths[1:])]
    bs = [jnp.asarray(rng.normal(size=(b,)).astype(np.float32) * 0.1)
          for b in widths[1:]]
    x = jnp.asarray(rng.normal(size=(batch, widths[0])).astype(np.float32))
    a = fused_mlp(x, ws, bs, acts, batch_tile=32, interpret=True)
    b = fused_mlp_ref(x, ws, bs, acts)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                               atol=2e-5)


@settings(max_examples=8, deadline=None)
@given(
    b=st.integers(1, 3),
    sq=st.sampled_from([32, 64, 96]),
    kv_heads=st.sampled_from([1, 2, 4]),
    group=st.sampled_from([1, 2]),
    causal=st.booleans(),
    dtype=st.sampled_from(["float32", "bfloat16"]),
)
def test_flash_attention_sweep(b, sq, kv_heads, group, causal, dtype):
    rng = np.random.default_rng(1)
    H = kv_heads * group
    q = jnp.asarray(rng.normal(size=(b, sq, H, 16)).astype(np.float32)).astype(dtype)
    k = jnp.asarray(rng.normal(size=(b, sq, kv_heads, 16)).astype(np.float32)).astype(dtype)
    v = jnp.asarray(rng.normal(size=(b, sq, kv_heads, 16)).astype(np.float32)).astype(dtype)
    a = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32,
                        interpret=True)
    r = flash_attention_ref(q, k, v, causal=causal)
    tol = 5e-2 if dtype == "bfloat16" else 1e-5
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(r, np.float32), rtol=tol, atol=tol)


def test_flash_attention_kv_valid_len():
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(1, 1, 2, 16)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, 64, 2, 16)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(1, 64, 2, 16)).astype(np.float32))
    a = flash_attention(q, k, v, causal=False, kv_valid_len=40, block_q=8,
                        block_k=16, interpret=True)
    r = flash_attention_ref(q[:, :, :, :], k[:, :40], v[:, :40], causal=False)
    np.testing.assert_allclose(np.asarray(a), np.asarray(r), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("T", [8, 33, 64])
@pytest.mark.parametrize("hd", [8, 16])
def test_rwkv6_chunk_sweep(T, hd):
    rng = np.random.default_rng(3)
    B, H = 2, 2
    r, k, v = (jnp.asarray(rng.normal(size=(B, T, H, hd)).astype(np.float32))
               for _ in range(3))
    w = jnp.asarray(rng.uniform(0.7, 0.999, (B, T, H, hd)).astype(np.float32))
    u = jnp.asarray(rng.normal(size=(H, hd)).astype(np.float32))
    s0 = jnp.asarray(rng.normal(size=(B, H, hd, hd)).astype(np.float32)) * 0.1
    oa, sa = rwkv6_chunk(r, k, v, w, u, s0, interpret=True)
    ob, sb = rwkv6_chunk_ref(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.asarray(oa), np.asarray(ob), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(sa), np.asarray(sb), rtol=1e-5,
                               atol=1e-5)


def test_rwkv6_chunk_matches_block_chunked_path():
    """Kernel oracle == the model's associative-scan chunked formulation."""
    from repro.configs.archs import reduced
    from repro.configs.base import get_config
    from repro.models import blocks

    cfg = reduced(get_config("rwkv6-1.6b"))
    p = blocks.rwkv6_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model),
                          jnp.float32).astype(cfg.jdtype)
    y1, st1 = blocks.rwkv6_seq(cfg, p, x, chunk=8)
    y2, st2 = blocks.rwkv6_seq(cfg, p, x, chunk=32)
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y2, np.float32), rtol=2e-2,
                               atol=2e-2)
    np.testing.assert_allclose(np.asarray(st1["S"]), np.asarray(st2["S"]),
                               rtol=2e-2, atol=2e-2)


# ------------------------------------------------ fused_mlp under GSPMD ----
def test_fused_mlp_sharded_falls_back_on_single_shard():
    """1-device mesh: the wrapper must route to the plain op (no shard_map)."""
    from repro.kernels.fused_mlp.ops import fused_mlp_sharded
    from repro.launch.mesh import make_local_mesh
    rng = np.random.default_rng(4)
    ws = [jnp.asarray(rng.normal(size=(6, 32)).astype(np.float32) * 0.3),
          jnp.asarray(rng.normal(size=(32, 2)).astype(np.float32) * 0.3)]
    bs = [jnp.asarray(rng.normal(size=(32,)).astype(np.float32) * 0.1),
          jnp.asarray(rng.normal(size=(2,)).astype(np.float32) * 0.1)]
    x = jnp.asarray(rng.normal(size=(16, 6)).astype(np.float32))
    mesh = make_local_mesh()
    out = fused_mlp_sharded(x, ws, bs, ("relu", "identity"),
                            mesh=mesh, data_axes=("data",))
    ref = fused_mlp_ref(x, ws, bs, ("relu", "identity"))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_fused_mlp_sharded_parity_8_shards():
    """Parity vs the unsharded kernel ref on a real 8-way data mesh.

    Subprocess: the 8 host devices must be forced before jax initializes
    (same pattern as tests/test_dist.py).
    """
    import pathlib
    import subprocess
    import sys
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import jax, numpy as np
import jax.numpy as jnp
from jax.sharding import Mesh
from repro.kernels.fused_mlp.ops import fused_mlp_sharded
from repro.kernels.fused_mlp.ref import fused_mlp_ref

mesh = Mesh(np.asarray(jax.devices()).reshape(8), ("data",))
rng = np.random.default_rng(0)
ws = [jnp.asarray(rng.normal(size=(a, b)).astype(np.float32) * 0.3)
      for a, b in ((6, 64), (64, 16), (16, 3))]
bs = [jnp.asarray(rng.normal(size=(b,)).astype(np.float32) * 0.1)
      for b in (64, 16, 3)]
acts = ("gelu", "relu", "identity")
x = jnp.asarray(rng.normal(size=(64, 6)).astype(np.float32))
# eager shard_map path
out = fused_mlp_sharded(x, ws, bs, acts, mesh=mesh, data_axes=("data",))
ref = fused_mlp_ref(x, ws, bs, acts)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5,
                           atol=2e-5)
# jitted (the engine's serving path traces it under jit)
jout = jax.jit(lambda x: fused_mlp_sharded(
    x, ws, bs, acts, mesh=mesh, data_axes=("data",)))(x)
np.testing.assert_allclose(np.asarray(jout), np.asarray(ref), rtol=2e-5,
                           atol=2e-5)
# non-divisible batch falls back to the unsharded op, still correct
xo = jnp.asarray(rng.normal(size=(13, 6)).astype(np.float32))
oo = fused_mlp_sharded(xo, ws, bs, acts, mesh=mesh, data_axes=("data",))
np.testing.assert_allclose(np.asarray(oo),
                           np.asarray(fused_mlp_ref(xo, ws, bs, acts)),
                           rtol=2e-5, atol=2e-5)
# Pallas interpret kernel per shard (the TPU VMEM path's CPU oracle)
kout = fused_mlp_sharded(x, ws, bs, acts, mesh=mesh, data_axes=("data",),
                         force_kernel=True)
np.testing.assert_allclose(np.asarray(kout), np.asarray(ref), rtol=2e-5,
                           atol=2e-5)
print("SHARDED_MLP_OK")
"""
    root = pathlib.Path(__file__).resolve().parents[1]
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=str(root))
    assert "SHARDED_MLP_OK" in out.stdout, out.stderr[-2000:]
