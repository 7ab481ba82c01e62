"""Cross-kernel registry dispatch suite.

One parameterized parity contract for every registered kernel (replacing
per-kernel ad-hoc dispatch tests): the kernel path in interpret mode
must match the *jitted* ref oracle — bit-for-bit where the spec declares
``tol=None`` (fused_mlp, stencil_gather), to the spec tolerance where
the block structure legitimately changes rounding (flash attention's
online softmax, rwkv6's in-kernel recurrence) — and the off-TPU default
dispatch must route to the oracle itself.  Plus the dispatch plumbing:
override precedence, tuned-cache consultation, VMEM-overflow fallback,
and the device-budget query.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import registry
from repro.tune.cache import TuneCache

# small interpret-friendly problems, one per registered kernel
PROBLEMS = {
    "fused_mlp": {"widths": (4, 16, 2), "acts": ("relu", "identity"),
                  "batch": 32, "dtype": "float32"},
    "flash_attention": {"b": 1, "sq": 32, "skv": 32, "h": 2, "kv": 1,
                        "hd": 16, "causal": True, "q_offset": 0,
                        "dtype": "float32"},
    "flash_attention_int8": {"b": 1, "sq": 16, "skv": 64, "h": 2, "kv": 1,
                             "hd": 16, "causal": True, "q_offset": 48,
                             "dtype": "float32"},
    "fused_mlp_int8": {"widths": (4, 16, 2), "acts": ("relu", "identity"),
                       "batch": 32, "dtype": "float32"},
    "stencil_gather": {"h": 24, "w": 24, "out_h": 20, "out_w": 20,
                       "offsets": ((0, 1), (1, 0), (0, 0), (1, 2)),
                       "origin": (1, 1), "dtype": "float32"},
    "rwkv6_chunk": {"b": 1, "t": 16, "h": 2, "hd": 8, "dtype": "float32"},
}

KERNELS = sorted(PROBLEMS)


def _assert_matches(spec, out, ref):
    a_leaves, b_leaves = jax.tree.leaves(out), jax.tree.leaves(ref)
    assert len(a_leaves) == len(b_leaves)
    for a, b in zip(a_leaves, b_leaves):
        if spec.tol is None:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        else:
            rtol, atol = spec.tol
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=rtol, atol=atol)


def test_all_builtin_kernels_registered():
    assert [s.name for s in registry.all_specs()] == KERNELS


@pytest.mark.parametrize("name", KERNELS)
def test_force_kernel_interpret_matches_jitted_oracle(name):
    """force_kernel off-TPU runs the Pallas kernel in interpret mode;
    its output must match the jitted ref oracle per the spec's declared
    comparison (bit-for-bit unless a tolerance is declared)."""
    spec = registry.get_spec(name)
    problem = PROBLEMS[name]
    arrays = spec.make_call(problem, np.random.default_rng(0))
    out = jax.jit(lambda *a: registry.dispatch(
        spec, problem, a, force_kernel=True))(*arrays)
    ref = jax.jit(lambda *a: spec.ref_call(problem, a))(*arrays)
    _assert_matches(spec, out, ref)


@pytest.mark.parametrize("name", KERNELS)
def test_off_tpu_dispatch_falls_back_to_oracle(name):
    """Without force_kernel on a non-TPU backend the dispatch must take
    the oracle path — identical output by construction."""
    assert jax.default_backend() != "tpu"  # test env invariant
    spec = registry.get_spec(name)
    problem = PROBLEMS[name]
    arrays = spec.make_call(problem, np.random.default_rng(1))
    out = registry.dispatch(spec, problem, arrays)
    ref = spec.ref_call(problem, arrays)
    np.testing.assert_array_equal(
        np.asarray(jax.tree.leaves(out)[0]),
        np.asarray(jax.tree.leaves(ref)[0]))


@pytest.mark.parametrize("name", [n for n in KERNELS
                                  if registry.get_spec(n).params])
def test_candidates_defaults_first_and_fit(name):
    spec = registry.get_spec(name)
    cands = spec.candidates(PROBLEMS[name])
    assert cands[0] == spec.defaults()
    if spec.fits is not None:
        assert all(spec.fits(PROBLEMS[name], c) for c in cands)


def test_dispatch_override_beats_tuned_and_default(monkeypatch):
    spec = registry.get_spec("fused_mlp")
    problem = PROBLEMS["fused_mlp"]
    arrays = spec.make_call(problem, np.random.default_rng(2))
    seen = {}
    orig = spec.run_call

    def spy(problem, arrays, params, *, interpret):
        seen.update(params)
        return orig(problem, arrays, params, interpret=interpret)

    monkeypatch.setattr(spec, "run_call", spy)
    registry.dispatch(spec, problem, arrays, force_kernel=True,
                      overrides={"batch_tile": 16})
    assert seen["batch_tile"] == 16


def test_dispatch_consults_namespaced_tune_cache(tmp_path, monkeypatch):
    """A validated winner stored under the kernel's namespaced cache is
    what the dispatch applies — across kernels, not just fused_mlp."""
    import repro.tune.cache as cache_mod
    spec = registry.get_spec("flash_attention")
    problem = PROBLEMS["flash_attention"]
    c = TuneCache("flash_attention", tmp_path / "flash_attention.json")
    key = spec.cache_key(problem, jax.default_backend())
    c.put(key, {"params": {"block_q": 16, "block_kv": 16}, "exact": True})
    monkeypatch.setattr(cache_mod, "_default", {"flash_attention": c})
    seen = {}
    orig = spec.run_call

    def spy(problem, arrays, params, *, interpret):
        seen.update(params)
        return orig(problem, arrays, params, interpret=interpret)

    monkeypatch.setattr(spec, "run_call", spy)
    arrays = spec.make_call(problem, np.random.default_rng(3))
    registry.dispatch(spec, problem, arrays, force_kernel=True)
    assert seen == {"block_q": 16, "block_kv": 16}
    # unvalidated entries are refused: defaults apply
    c.put(key, {"params": {"block_q": 32, "block_kv": 32}, "exact": False})
    seen.clear()
    registry.dispatch(spec, problem, arrays, force_kernel=True)
    assert seen == spec.defaults()


def test_resolve_params_rejects_vmem_overflow():
    """A tuned/override config that would overflow this device's VMEM
    budget falls back to the defaults (a cache written on a roomier
    machine must not push this one over)."""
    spec = registry.get_spec("fused_mlp")
    problem = PROBLEMS["fused_mlp"]
    params = registry.resolve_params(spec, problem,
                                     overrides={"batch_tile": 1 << 20})
    assert params == spec.defaults()


def test_fused_mlp_unsupported_net_takes_oracle_even_forced(monkeypatch):
    """A net too big for VMEM must take the oracle path even under
    force_kernel — `supports` gates the kernel path entirely."""
    spec = registry.get_spec("fused_mlp")
    problem = {"widths": (4096, 4096, 4096), "acts": ("relu", "identity"),
               "batch": 8, "dtype": "float32"}
    called = {}
    orig = spec.ref_call

    def spy(problem, arrays):
        called["ref"] = True
        return orig(problem, arrays)

    monkeypatch.setattr(spec, "ref_call", spy)
    arrays = spec.make_call(problem, np.random.default_rng(4))
    registry.dispatch(spec, problem, arrays, force_kernel=True)
    assert called.get("ref")


# ------------------------------------------------------- op-level shims ----
def test_flash_attention_op_block_overrides():
    from repro.kernels.flash_attention.ops import flash_attention_op
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.normal(size=(1, 32, 2, 16)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, 32, 1, 16)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(1, 32, 1, 16)).astype(np.float32))
    a = flash_attention_op(q, k, v, force_kernel=True, block_q=16,
                           block_kv=16)
    r = flash_attention_op(q, k, v)  # oracle path off-TPU
    np.testing.assert_allclose(np.asarray(a), np.asarray(r), rtol=2e-5,
                               atol=2e-5)


def test_stencil_gather_op_block_overrides():
    from repro.kernels.stencil_gather.ops import stencil_gather_op
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.normal(size=(24, 24)).astype(np.float32))
    offs = ((0, 1), (1, 0), (0, 0))
    a = stencil_gather_op(x, offsets=offs, out_h=20, out_w=20,
                          origin=(1, 1), force_kernel=True, block_h=16,
                          block_w=128)
    r = stencil_gather_op(x, offsets=offs, out_h=20, out_w=20,
                          origin=(1, 1))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(r))


def test_rwkv6_chunk_op_dispatch_parity():
    from repro.kernels.rwkv6_chunk.ops import rwkv6_chunk_op
    rng = np.random.default_rng(7)
    B, T, H, hd = 1, 16, 2, 8
    r, k, v = (jnp.asarray(rng.normal(size=(B, T, H, hd)).astype(np.float32))
               for _ in range(3))
    w = jnp.asarray(rng.uniform(0.7, 0.99, (B, T, H, hd)).astype(np.float32))
    u = jnp.asarray(rng.normal(size=(H, hd)).astype(np.float32))
    s0 = jnp.zeros((B, H, hd, hd), np.float32)
    ok, sk = rwkv6_chunk_op(r, k, v, w, u, s0, force_kernel=True)
    orf, srf = rwkv6_chunk_op(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.asarray(ok), np.asarray(orf), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(sk), np.asarray(srf), rtol=1e-5,
                               atol=1e-5)


# ---------------------------------------------------------- VMEM budget ----
def test_device_vmem_budget_off_tpu_keeps_old_constant():
    assert jax.default_backend() != "tpu"
    assert registry.device_vmem_budget() == 12 * 2 ** 20


@pytest.mark.parametrize("kind,budget_mib", [
    ("TPU v4", None), ("TPU v5 lite", 12), ("TPU v5p", None),
    ("TPU v3", None), ("TPU v99-future", None),
])
def test_vmem_budget_table(kind, budget_mib):
    # v5e's entry is the one checked by compiles (test_tpu_compile.py);
    # a kind with no checked entry raises instead of guessing
    if budget_mib is None:
        with pytest.raises(ValueError, match="no VMEM budget"):
            registry._vmem_budget_for_kind(kind)
    else:
        assert registry._vmem_budget_for_kind(kind) == budget_mib * 2 ** 20


def test_fits_vmem_default_budget_queries_device():
    from repro.kernels.fused_mlp.fused_mlp import fits_vmem
    widths = (64, 64)
    assert fits_vmem(widths, 8) == \
        fits_vmem(widths, 8, budget=registry.device_vmem_budget())


def test_ladder_candidates_defaults_first_and_clipped():
    params = (registry.TunableParam("a", 8, (4, 8, 16, 32)),
              registry.TunableParam("b", 128, (64, 128, 256)))
    cands = registry.ladder_candidates(params, clip={"a": 16, "b": 128})
    assert cands[0] == {"a": 8, "b": 128}
    assert all(c["a"] <= 16 for c in cands)
    assert all(c["b"] <= 128 for c in cands)
    # a fits filter prunes but never drops the defaults-first ordering
    fit = registry.ladder_candidates(params, fits=lambda c: c["a"] != 4)
    assert fit[0] == {"a": 8, "b": 128}
    assert all(c["a"] != 4 for c in fit)
