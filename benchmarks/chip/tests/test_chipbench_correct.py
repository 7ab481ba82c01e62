"""The comparison that decides ``correct``, on the CPU: the control (the
reference one precision step lower) fails each configuration's limit
while the program passes it, and a run whose timed path is broken
underneath the harness comes out not correct.  Every configuration is
built at the size its CPU tests run at (``harness.cpu_config``); beside
those of ``BENCHMARK.json`` runs one whose published widths hold over a
gigabyte of f32 weights."""
import functools
import json
import os
import pathlib
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
CHIP = pathlib.Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
sys.path[:0] = [str(CHIP), str(ROOT / "src")]

import harness  # noqa: E402
import reference  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIGS = [c["name"] for c in BENCH["configs"]]

#: the binomial net at widths whose f32 weights hold 1.07 GB, which its
#: CPU tests build at 5-64-64-1
GIGABYTE = dict(harness.load_part("configs", "binomial-mlp-5-512-512-1"),
                name="mlp-5-16384-16384-1", widths=[5, 16384, 16384, 1],
                cpu_size={"widths": [5, 64, 64, 1]})
#: a cell of it: binomial-ranks's traffic
GIGABYTE_CELL = "gigabyte-ranks"


def _config(name):
    if name == GIGABYTE["name"]:
        return GIGABYTE
    return harness.load_part("configs", name)


def _recording(arch, asked):
    """``arch`` whose ``make_weights`` notes each configuration it is
    asked for in ``asked``."""
    make = arch.make_weights

    def make_weights(config, seed):
        asked.append(config)
        return make(config, seed)

    arch.make_weights = make_weights
    return arch


def _gaps(ref, *served):
    """``reference.compare``'s gap of each served output against ``ref``,
    on the rows the reference defines."""
    return [reference.compare([(reference.split(s)[0], ref)])[0]
            for s in served]


@pytest.mark.parametrize("name", CONFIGS + [GIGABYTE["name"]])
def test_control_fails_the_limit_and_the_reference_path_passes(name):
    published = _config(name)
    config = harness.cpu_config(published)
    asked = []
    arch = _recording(harness.load_arch(config["arch"]), asked)
    forward = functools.partial(arch.forward, config)
    limit = config["check"]["max_rel_err"]
    traffic = {"distinct_steps": 1, "callers": 1, "rows_per_caller": 2048}
    for seed in (1, 2, 3):
        model = arch.make_weights(config, seed)
        x = np.asarray(arch.make_inputs(config, traffic, seed)[0][0])
        ref, = reference.run(forward, model, [x])
        ctl, = reference.run(forward, model, [x], precision="3pass")
        # the same reference in blocks of rows reads well inside the limit
        blocked, = reference.run(forward, model, [x], block_rows=512)
        ctl_gap, blocked_gap = _gaps(ref, ctl, blocked)
        assert ctl_gap > limit
        assert blocked_gap < limit / 4
    assert asked == [harness.cpu_config(published)] * 3


def test_a_gigabyte_configuration_is_built_on_the_cpu_at_its_cpu_size(
        tmp_path):
    """Its weights hold over 1 GB at the published widths and some
    kilobytes at ``cpu_size``; ``find_cell``, which a chip run goes
    through, still gives the published widths."""
    arch = harness.load_arch("mlp")
    assert arch.call_bytes(GIGABYTE, 0) > 1e9
    assert arch.call_bytes(harness.cpu_config(GIGABYTE), 0) < 1e5
    assert "cpu_size" not in harness.cpu_config(GIGABYTE)
    for kind in ("archs", "traffic"):
        shutil.copytree(CHIP / kind, tmp_path / kind)
    (tmp_path / "configs").mkdir()
    (tmp_path / "configs" / f"{GIGABYTE['name']}.json").write_text(
        json.dumps(GIGABYTE))
    bench = dict(BENCH, workloads=[{
        "name": GIGABYTE_CELL, "config": GIGABYTE["name"],
        "traffic": "ranks-64x512", "chips": 1, "why": "test"}])
    cell = harness.find_cell(GIGABYTE_CELL, bench, tmp_path)
    assert cell["config"] == GIGABYTE
    assert cell["config"]["widths"] == [5, 16384, 16384, 1]


@pytest.mark.parametrize("size", [
    {"check": {"max_rel_err": 1.0}}, {"arch": "mlp"},
    {"n_experts": 4}, {"widths": [5, 64.5, 1]}, {"activation": 2}])
def test_a_cpu_size_may_replace_only_whole_numbers_the_config_has(size):
    key, = size
    with pytest.raises(ValueError, match=repr(key)):
        harness.cpu_config(dict(GIGABYTE, cpu_size=size))


CELLS = [w["name"] for w in BENCH["workloads"]]


def _tiny_cell(name="minibude-bulk", callers=4, asked=None):
    """The cell's own architecture, configuration (at its ``cpu_size``) and
    traffic at a tiny size on one device; the configurations its weights
    are made for are noted in ``asked``."""
    if name == GIGABYTE_CELL:
        cell = harness.find_cell("binomial-ranks", BENCH)
        cell["config"] = GIGABYTE
    else:
        cell = harness.find_cell(name, BENCH)
    cell["config"] = harness.cpu_config(cell["config"])
    cell["arch"] = _recording(cell["arch"], [] if asked is None else asked)
    cell["workload"] = dict(cell["workload"], chips=1)
    cell["traffic"] = dict(cell["traffic"], callers=callers,
                           rows_per_caller=64, distinct_steps=2,
                           sampled_steps=2)
    return cell


def _run(cell, seed=5):
    import jax
    return harness.run_cell(cell, seed=seed, seconds=0.3, trace=False,
                            t_start=time.perf_counter(),
                            devices=jax.devices(), log=sys.stderr)


@pytest.mark.parametrize("name", CELLS + [GIGABYTE_CELL])
def test_a_sound_run_is_correct(name):
    asked = []
    cell = _tiny_cell(name, asked=asked)
    out = _run(cell)
    assert out["correct"], out["checks"]
    assert out["checks"]["rows_left_out"] == {"value": 0.0, "limit": 0}
    assert asked == [cell["config"]]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"rows_per_s", "step_ms_p95", "setup_s"}
    assert out["window"]["compiles"] == {"lowered": 0, "compiled": 0}
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", ["alter_one_answer",
                                   "leave_out_half"])
@pytest.mark.parametrize("name", CELLS + [GIGABYTE_CELL])
def test_a_broken_engine_output_is_not_correct(break_engine, name, fault):
    break_engine(fault)
    out = _run(_tiny_cell(name))
    assert not out["correct"]
    assert out["checks"]["max_rel_err"]["value"] > \
        out["checks"]["max_rel_err"]["limit"]


def test_the_program_weights_are_released_before_the_reference_runs(
        monkeypatch):
    """When ``_compare`` is entered, no live device array holds one of the
    served bundle's parameters: a reference copy of a model that fills
    most of a chip fits only then."""
    import jax
    cell = _tiny_cell()
    bundles = []
    write = cell["arch"].write_bundle

    def write_bundle(path, config, model):
        bundles.append(write(path, config, model))
        return bundles[-1]

    cell["arch"].write_bundle = write_bundle
    held = []
    compare = harness._compare

    def checked(*args):
        with np.load(pathlib.Path(bundles[0]) / "params.npz") as z:
            params = [z[k] for k in z.files]
        assert len(params) == 14  # weights and biases of 7 dense layers
        held.extend(a.shape for a in jax.live_arrays() for p in params
                    if a.shape == p.shape and a.dtype == p.dtype
                    and np.array_equal(np.asarray(a), p))
        return compare(*args)

    monkeypatch.setattr(harness, "_compare", checked)
    out = _run(cell)
    assert out["correct"], out["checks"]
    assert held == []


_FOUR_DEVICES = r"""
import json, sys, time
sys.path[:0] = [{chip!r}, {src!r}]
import jax, harness
from repro.serve.batcher import Batcher
assert len(jax.devices()) == 4
cell = harness.find_cell("minibude-bulk", harness.load_benchmark())
cell["config"] = harness.cpu_config(cell["config"])
cell["workload"] = dict(cell["workload"], chips=4)
cell["traffic"] = dict(cell["traffic"], callers=4, rows_per_caller=64,
                       distinct_steps=2, sampled_steps=2)
def run():
    return harness.run_cell(cell, seed=9, seconds=0.3, trace=False,
                            t_start=time.perf_counter(),
                            devices=jax.devices())
sound = run()
to_host = Batcher._to_host
def first_shard_only(self, Y, **kw):
    shards = len(Y.addressable_shards)
    out = to_host(self, Y, **kw)
    out[out.shape[0] // shards:] = 0.0
    return out
Batcher._to_host = first_shard_only
broken = run()
print(json.dumps({{"sound": sound["correct"], "broken": broken["correct"],
                  "count": sound["device"]["count"]}}))
"""


def test_the_exchange_between_chips_left_out_is_not_correct():
    """The four-chip path (a 1x4 data mesh) on four virtual CPU devices: a
    sound run is correct; one whose read-back takes only the first
    shard's rows (the rest of the landed batch is whatever the buffer
    held) is not."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = _FOUR_DEVICES.format(chip=str(CHIP), src=str(ROOT / "src"))
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    assert json.loads(p.stdout.strip().splitlines()[-1]) == \
        {"sound": True, "broken": False, "count": 4}


def test_a_failed_call_is_not_correct(monkeypatch):
    from repro.core.engine import InferenceEngine
    apply = InferenceEngine.apply_batched
    calls = []

    def fails_after_warm_up(self, x, **kw):
        calls.append(1)
        if len(calls) > harness.WARMUP_STEPS:
            raise RuntimeError("injected")
        return apply(self, x, **kw)

    monkeypatch.setattr(InferenceEngine, "apply_batched", fails_after_warm_up)
    out = _run(_tiny_cell())
    assert not out["correct"]
    assert out["failed"] > 0
