"""The served kernels compiled for a described TPU v5e, with no chip.

The TPU compiler is installed with jaxlib and compiles for a topology
that is described but not attached, so Mosaic's refusals (tiles it
cannot prove aligned, more VMEM than a kernel may use) show up here and
not on a chip.  Nothing runs: these tests only lower and compile.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and the test runner's
workers all import this file.  Code that asks ``jax.default_backend()``
still sees the CPU, so the tests that go through the registry's
dispatch tell it, here, that it serves a TPU of the described kind.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels import registry

WIDTHS = (5, 512, 512, 1)
ACTS = ("relu", "relu", "identity")


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else it logs under /tmp
        from jax.experimental import topologies
        try:
            t = topologies.get_topology_desc(platform="tpu",
                                             topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield t


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def as_tpu(topo, monkeypatch):
    """Registry dispatch as on the described chip: the kernel path, not
    interpret mode, budgeted for the chip's ``device_kind``."""
    kind = topo.devices[0].device_kind
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(registry, "device_vmem_budget",
                        lambda: registry._vmem_budget_for_kind(kind))


def _sds(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _mlp_args(widths, batch, sharding, kernel, weights_sharding=None):
    wsh = weights_sharding or sharding
    x = _sds((batch, widths[0]), sharding)
    pairs = list(zip(widths[:-1], widths[1:]))
    if kernel == "fused_mlp_int8":
        return x, [(_sds((a, b), wsh, jnp.int8), _sds((b,), wsh),
                    _sds((b,), wsh)) for a, b in pairs]
    return x, [_sds((a, b), wsh) for a, b in pairs], \
        [_sds((b,), wsh) for _, b in pairs]


def _op(kernel):
    if kernel == "fused_mlp_int8":
        from repro.kernels.fused_mlp.int8 import fused_mlp_int8_op
        return lambda x, qs: fused_mlp_int8_op(x, qs, ACTS)
    from repro.kernels.fused_mlp.ops import fused_mlp_op
    return lambda x, ws, bs: fused_mlp_op(x, ws, bs, ACTS)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("bucket", [8, 32768])
@pytest.mark.parametrize("kernel", ["fused_mlp", "fused_mlp_int8"])
def test_served_mlp_kernel_compiles_for_v5e(as_tpu, one_chip, kernel,
                                            bucket):
    args = _mlp_args(WIDTHS, bucket, one_chip, kernel)
    assert "tpu_custom_call" in _compiled_text(_op(kernel), *args)


@pytest.mark.parametrize("kernel", ["fused_mlp", "fused_mlp_int8"])
def test_vmem_budget_edge_compiles_for_v5e(topo, one_chip, kernel):
    """The widest net the cost model admits at the chip kind's budget,
    at the default tile, is one the compiler accepts."""
    spec = registry.get_spec(kernel)
    budget = registry._vmem_budget_for_kind(topo.devices[0].device_kind)
    tile = spec.defaults()["batch_tile"]

    def fits(h):
        problem = {"widths": (5, h, h, 1), "dtype": "float32"}
        return spec.fits(problem, {"batch_tile": tile}, budget=budget)

    h = 128
    while fits(h + 128):
        h += 128
    widths = (5, h, h, 1)
    args = _mlp_args(widths, 1024, one_chip, kernel)
    run = (lambda x, qs: spec.run_call(
        {"acts": ACTS}, (x, qs), {"batch_tile": tile}, interpret=False)) \
        if kernel == "fused_mlp_int8" else \
        (lambda x, ws, bs: spec.run_call(
            {"acts": ACTS}, (x, ws, bs), {"batch_tile": tile},
            interpret=False))
    assert "tpu_custom_call" in _compiled_text(run, *args)


@pytest.mark.parametrize("block_h,block_w", [(8, 128), (64, 512)])
def test_stencil_gather_compiles_for_v5e(one_chip, block_h, block_w):
    from repro.kernels.stencil_gather.stencil_gather import stencil_gather
    p = registry.get_spec("stencil_gather").default_problems[0]

    def gather(x):
        return stencil_gather(x, p["offsets"], p["out_h"], p["out_w"],
                              origin=p["origin"], block_h=block_h,
                              block_w=block_w, interpret=False)

    text = _compiled_text(gather, _sds((p["h"], p["w"]), one_chip))
    assert "tpu_custom_call" in text


def test_sharded_fused_mlp_compiles_over_four_chips(as_tpu, topo):
    from repro.kernels.fused_mlp.ops import fused_mlp_sharded
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 4), ("pod", "data"))
    x, ws, bs = _mlp_args(WIDTHS, 32768, NamedSharding(mesh, P("data")),
                          "fused_mlp",
                          weights_sharding=NamedSharding(mesh, P()))

    def served(x, ws, bs):
        return fused_mlp_sharded(x, ws, bs, ACTS, mesh=mesh,
                                 data_axes=("data",))

    assert "tpu_custom_call" in _compiled_text(served, x, ws, bs)
