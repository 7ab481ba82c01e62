"""``chip_smoke.py`` drilled on the CPU at a tiny size.

The smoke's phases run here with a 5-32-1 net on a few hundred options.
The test steers what only a chip would do: the kernels run through the
Pallas path in interpret mode, the bridged rows count as
device-resident, and ``REPRO_QUANT=force`` stands in for ``auto`` on a
TPU.  Each fallback the smoke's counter check exists to catch is then
forced, and the counter it reads must move and fail the check.
"""
import importlib.util
import os
import pathlib
import subprocess
import sys

import pytest

from repro.apps import binomial
from repro.core.engine import InferenceEngine
from repro.kernels import registry
from repro.kernels.fused_mlp import int8 as mlp_int8
from repro.nn import MLP
from repro.obs import TRACER
from repro.resilience.breaker import BREAKERS
from repro.serve.batcher import Batcher

ROOT = pathlib.Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location("chip_smoke",
                                               ROOT / "chip_smoke.py")
smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(smoke)

TINY = {"callers": 4, "chunk": 64, "sweeps": 1}


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    work = tmp_path_factory.mktemp("smoke")
    bundle, db, val_rmse = smoke.collect_and_train(
        work, seed=0, n_options=384, net=MLP((1, 5), [32], 1), epochs=2)
    opts = binomial.make_inputs(TINY["callers"] * TINY["chunk"], seed=1)
    return bundle, db, val_rmse, opts, smoke.reference(bundle, opts)


@pytest.fixture
def drill(trained, tmp_path, monkeypatch):
    """Chip-like steering around one drill, undone afterwards."""
    import repro.tune.cache as cache_mod
    from repro.quant.gate import GATE_NAMESPACE
    bundle = trained[0]
    monkeypatch.setattr(cache_mod, "_default", {
        k: cache_mod.TuneCache(k, path=tmp_path / f"{k}.json")
        for k in (GATE_NAMESPACE, "fused_mlp", "fused_mlp_int8")})
    monkeypatch.setenv("REPRO_QUANT", "force")
    dispatch = registry.dispatch
    monkeypatch.setattr(registry, "dispatch", lambda *a, **k: dispatch(
        *a, **dict(k, force_kernel=True)))
    monkeypatch.setattr(Batcher, "_device_resident",
                        staticmethod(lambda x: True))
    InferenceEngine.invalidate(str(bundle))
    BREAKERS.reset(str(bundle))
    TRACER.clear()
    TRACER.enable()
    base = smoke.fallback_counts(bundle)
    yield lambda: {k: v - base[k]
                   for k, v in smoke.fallback_counts(bundle).items()}
    TRACER.disable()
    TRACER.clear()
    BREAKERS.reset(str(bundle))
    InferenceEngine.invalidate(str(bundle))


def test_device_check_refuses_the_cpu():
    with pytest.raises(smoke.SmokeFailure, match="no TPU"):
        smoke.device_check()


def test_script_exits_nonzero_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         capture_output=True, text=True, env=env,
                         cwd=str(tmp_path), timeout=120)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert '"ok"' not in out.stdout
    assert "collect:" not in out.stdout  # failed before any CPU work


def test_phases_serve_both_tiers_with_no_fallback(trained, drill):
    bundle, db, val_rmse, opts, ref = trained
    f32 = smoke.serve_sweeps(bundle, opts, **TINY)
    assert f32["tier"] == "f32"
    smoke.check_f32("served f32", f32["rows"], ref)
    rec = smoke.gate_int8(bundle, db, val_rmse)
    i8 = smoke.serve_sweeps(bundle, opts, **TINY)
    assert i8["tier"] == "int8"
    smoke.check_int8(i8["rows"], ref, rec["budget"])
    ds = smoke.dispatches()
    assert {d["kernel"] for d in ds} == {"fused_mlp_int8"}
    # interpret mode is what the chip run must never show
    with pytest.raises(smoke.SmokeFailure, match="interpret mode"):
        smoke.check_dispatches("int8", ds, "fused_mlp_int8")
    with pytest.raises(smoke.SmokeFailure, match="no fused_mlp kernel"):
        smoke.check_dispatches("f32", [], "fused_mlp")
    smoke.check_no_fallback(drill())


def _fail_apply(*a, **k):
    raise RuntimeError("injected engine failure")


FORCINGS = {
    "breaker_fallback":
        lambda mp: mp.setattr(InferenceEngine, "apply_batched", _fail_apply),
    "ref_dispatch":
        lambda mp: mp.setattr(mlp_int8.SPEC, "supports", lambda p: False),
    "vmem_fallback_dispatch":
        lambda mp: mp.setattr(registry, "tuned_params",
                              lambda s, p: {"batch_tile": 1 << 20}),
}


@pytest.mark.parametrize("counter", sorted(FORCINGS))
def test_forced_fallback_moves_its_counter(trained, drill, monkeypatch,
                                           counter):
    bundle, db, val_rmse, opts, ref = trained
    smoke.gate_int8(bundle, db, val_rmse)
    FORCINGS[counter](monkeypatch)
    smoke.serve_sweeps(bundle, opts, **TINY)
    moved = drill()
    assert moved[counter] > 0, moved
    with pytest.raises(smoke.SmokeFailure, match="hidden fallbacks"):
        smoke.check_no_fallback(moved)
