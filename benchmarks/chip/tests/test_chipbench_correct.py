"""The comparison that decides ``correct``, on the CPU: the control (the
reference one precision step lower) fails each configuration's limit
while the program passes it, and a run whose timed path is broken
underneath the harness comes out not correct."""
import functools
import json
import os
import pathlib
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


@pytest.mark.parametrize("name", CONFIGS)
def test_control_fails_the_limit_and_the_reference_path_passes(name):
    config = harness.load_part("configs", name)
    arch = harness.load_arch(config["arch"])
    forward = functools.partial(arch.forward, config)
    limit = config["check"]["max_rel_err"]
    traffic = {"distinct_steps": 1, "callers": 1, "rows_per_caller": 2048}
    for seed in (1, 2, 3):
        model = arch.make_weights(config, seed)
        x = np.asarray(arch.make_inputs(config, traffic, seed)[0][0])
        ref, = reference.run(forward, model, [x])
        ctl, = reference.run(forward, model, [x], precision="3pass")
        assert reference.max_rel_err(ctl, ref) > limit
        # the same reference in blocks of rows reads well inside the limit
        blocked, = reference.run(forward, model, [x], block_rows=512)
        assert reference.max_rel_err(blocked, ref) < limit / 4


CELLS = [w["name"] for w in BENCH["workloads"]]


def _tiny_cell(name="minibude-bulk", callers=4):
    """The cell's own architecture, configuration and traffic at a tiny
    size on one device."""
    cell = harness.find_cell(name, BENCH)
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


@pytest.mark.parametrize("name", CELLS)
def test_a_sound_run_is_correct(name):
    out = _run(_tiny_cell(name))
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"rows_per_s", "step_ms_p95", "setup_s"}
    assert out["window"]["compiles"] == {"lowered": 0, "compiled": 0}
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", ["alter_one_answer",
                                   "leave_out_half"])
@pytest.mark.parametrize("name", CELLS)
def test_a_broken_engine_output_is_not_correct(break_engine, name, fault):
    break_engine(fault)
    out = _run(_tiny_cell(name))
    assert not out["correct"]
    assert out["checks"]["max_rel_err"]["value"] > \
        out["checks"]["max_rel_err"]["limit"]


_FOUR_DEVICES = r"""
import json, sys, time
sys.path[:0] = [{chip!r}, {src!r}]
import jax, harness
from repro.serve.batcher import Batcher
assert len(jax.devices()) == 4
cell = harness.find_cell("minibude-bulk", harness.load_benchmark())
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
