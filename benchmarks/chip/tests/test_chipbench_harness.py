"""The chip benchmark's harness on the CPU: cells, configurations,
traffic mixes and metric readers found by name, seeded traffic, the
reference against the program's own forward pass, and no result without
a TPU."""
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

import generate  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_every_cell_resolves_to_its_files():
    configs = {c["name"]: c for c in BENCH["configs"]}
    for w in BENCH["workloads"]:
        cell = harness.find_cell(w["name"], BENCH)
        assert cell["config"]["name"] == w["config"]
        assert configs[w["config"]]["file"] == \
            f"benchmarks/chip/configs/{w['config']}.json"
        assert cell["arch"].flops_per_row(cell["config"]) > 0
        assert {m["name"] for m in cell["end_to_end"]} >= {"setup_s"}
        for m in cell["end_to_end"] + cell["per_layer"]:
            assert callable(harness.load_reader(m["name"]))


def test_unknown_cell_raises():
    with pytest.raises(KeyError, match="no workload"):
        harness.find_cell("no-such-cell", BENCH)


# an architecture that is not a chain of dense layers: dense, LayerNorm,
# relu, dense, which the engine serves on its XLA path (not fused_mlp)
TOY_ARCH = '''"""Dense layers with a LayerNorm between them."""
import functools

import jax
import jax.numpy as jnp
import numpy as np

import generate
import work


@functools.partial(jax.jit, static_argnums=0)
def _weights(widths, words):
    a, h, o = widths
    k = jax.random.split(jax.random.wrap_key_data(words), 6)
    return {"w1": jax.random.normal(k[0], (a, h)) * (2.0 / a) ** 0.5,
            "b1": jax.random.normal(k[1], (h,)) * 0.1,
            "scale": 1.0 + 0.1 * jax.random.normal(k[2], (h,)),
            "bias": 0.1 * jax.random.normal(k[3], (h,)),
            "w2": jax.random.normal(k[4], (h, o)) * (2.0 / h) ** 0.5,
            "b2": jax.random.normal(k[5], (o,)) * 0.1}


def make_weights(config, seed):
    words = jnp.asarray(generate.seed_words(seed))
    return {"params": jax.device_get(_weights(tuple(config["widths"]),
                                              words)),
            "norm": generate.norm_stats(config)}


def write_bundle(path, config, model):
    from repro.nn.layers import Activation, Dense, LayerNorm, Sequential
    from repro.nn.serialize import save_model
    a, h, o = config["widths"]
    net = Sequential([Dense(h), LayerNorm(), Activation("relu"), Dense(o)],
                     (1, a))
    p = model["params"]
    params = [{"w": p["w1"], "b": p["b1"]},
              {"scale": p["scale"], "bias": p["bias"]}, {},
              {"w": p["w2"], "b": p["b2"]}]
    extra = {k: np.asarray(v).tolist()
             for k, v in zip(("x_mu", "x_sd", "y_mu", "y_sd"), model["norm"])}
    return save_model(path, net, params, extra=extra)


def make_inputs(config, traffic, seed):
    return generate.make_inputs(config, traffic, seed)


def forward(config, model, x, dot):
    p = model["params"]
    x_mu, x_sd, y_mu, y_sd = model["norm"]
    h = dot((x - x_mu) / x_sd, p["w1"]) + p["b1"]
    mu = h.mean(-1, keepdims=True)
    var = ((h - mu) ** 2).mean(-1, keepdims=True)
    h = (h - mu) / jnp.sqrt(var + 1e-6) * p["scale"] + p["bias"]
    h = jnp.maximum(h, 0.0)
    return (dot(h, p["w2"]) + p["b2"]) * y_sd + y_mu


def flops_per_row(config):
    a, h, o = config["widths"]
    return 2 * (a * h + h * o)


def call_bytes(config, rows):
    a, h, o = config["widths"]
    params = a * h + h + 2 * h + h * o + o
    return work.F32_BYTES * (params + rows * (a + o))
'''


def test_new_files_are_found_with_no_edit_elsewhere(tmp_path, break_engine):
    """A new architecture, configuration, traffic mix, metric and cell
    are new files and entries alone; the new architecture's cell runs
    whole to ``correct: true``, and to false with one answer altered."""
    import jax
    base = tmp_path / "chip"
    shutil.copytree(CHIP, base, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    (base / "traffic" / "ranks-8x16.json").write_text(json.dumps(
        {"loop": "closed", "callers": 8, "rows_per_caller": 16,
         "distinct_steps": 2, "sampled_steps": 1}))
    (base / "metrics" / "steps_seen.py").write_text(
        "def read(rec):\n    return len(rec['steps']) or None\n")
    (base / "archs" / "toy_ln.py").write_text(TOY_ARCH)
    config = json.loads(
        (CHIP / "configs" / "binomial-mlp-5-512-512-1.json").read_text())
    config.update(name="toy-ln-5-32-1", arch="toy_ln", widths=[5, 32, 1])
    del config["activation"]
    (base / "configs" / "toy-ln-5-32-1.json").write_text(json.dumps(config))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "toy-ln-5-32-1", "source": "test",
                             "file": "benchmarks/chip/configs/"
                                     "toy-ln-5-32-1.json",
                             "reduced": [], "why": "test"})
    bench["workloads"] += [{"name": "binomial-tiny",
                            "config": "binomial-mlp-5-512-512-1",
                            "traffic": "ranks-8x16", "chips": 1,
                            "why": "test"},
                           {"name": "toy-ln-tiny", "config": "toy-ln-5-32-1",
                            "traffic": "ranks-8x16", "chips": 1,
                            "why": "test"}]
    bench["per_layer"].append({"name": "steps_seen", "unit": "1",
                               "better": "higher", "source": "host_clock",
                               "layer": "whole step", "moves": "rows_per_s",
                               "workloads": ["binomial-tiny", "toy-ln-tiny"]})
    cell = harness.find_cell("binomial-tiny", bench, base)
    assert cell["traffic"]["callers"] == 8
    assert [m["name"] for m in cell["per_layer"]] == ["steps_seen"]
    read = harness.load_reader("steps_seen", base)
    assert read({"steps": [(0, 1, 0)] * 3}) == 3

    def run():
        return harness.run_cell(harness.find_cell("toy-ln-tiny", bench, base),
                                seed=2 ** 33 + 3, seconds=0.3, trace=False,
                                t_start=time.perf_counter(),
                                devices=jax.devices())

    out = run()
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"rows_per_s", "step_ms_p95", "setup_s"}
    break_engine("alter_one_answer")
    out = run()
    assert not out["correct"]
    assert out["checks"]["max_rel_err"]["value"] > \
        out["checks"]["max_rel_err"]["limit"]


@pytest.mark.parametrize("arch,error", [(None, KeyError),
                                        ("no_such_arch", FileNotFoundError)])
def test_a_configuration_without_a_known_arch_raises(tmp_path, arch, error):
    config = harness.load_part("configs", "binomial-mlp-5-512-512-1")
    del config["arch"]
    if arch is not None:
        config["arch"] = arch
    for kind in ("configs", "traffic"):
        shutil.copytree(CHIP / kind, tmp_path / kind)
    (tmp_path / "configs" / "binomial-mlp-5-512-512-1.json").write_text(
        json.dumps(config))
    with pytest.raises(error, match="arch"):
        harness.find_cell("binomial-ranks", BENCH, tmp_path)


def test_readers_return_nothing_where_there_is_nothing_to_read():
    rec = {"steps": [], "spans": None, "trace": None, "rows": 0,
           "window_s": 0.0, "setup_s": 1.0, "flops_per_row": 96,
           "call_bytes": 4 * (57 + 8 * 6),
           "chips": 1, "rows_per_step": 8, "device_kind": "cpu"}
    for m in BENCH["per_layer"]:
        assert harness.load_reader(m["name"])(rec) is None, m["name"]


@pytest.mark.parametrize("cell", ["binomial-ranks", "minibude-bulk"])
def test_traffic_is_the_same_for_the_same_seed(cell):
    c = harness.find_cell(cell, BENCH)
    traffic = dict(c["traffic"], callers=3, rows_per_caller=16,
                   distinct_steps=2)
    seed = 2 ** 33 + 7  # wider than 32 bits
    a = generate.make_inputs(c["config"], traffic, seed)
    b = generate.make_inputs(c["config"], traffic, seed)
    other = generate.make_inputs(c["config"], traffic, seed + 2 ** 32)
    assert len(a) == 2 and len(a[0]) == 3
    for xa, xb, xo in zip(sum(a, []), sum(b, []), sum(other, [])):
        assert xa.shape == (16, len(c["config"]["features"]))
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))
        assert not np.array_equal(np.asarray(xa), np.asarray(xo))
    lo, hi = generate.feature_ranges(c["config"])
    x = np.concatenate([np.asarray(v) for v in sum(a, [])])
    assert np.all(x >= lo) and np.all(x <= hi)


def test_reference_agrees_with_the_program_forward_pass(tmp_path):
    import jax
    from repro.core.engine import bundle_norm
    from repro.nn.serialize import load_model
    cell = harness.find_cell("binomial-ranks", BENCH)
    arch = cell["arch"]
    config = dict(cell["config"], widths=[5, 32, 16, 1])
    model = arch.make_weights(config, 3)
    bundle = arch.write_bundle(tmp_path / "b", config, model)
    net, params, spec = load_model(bundle)
    mu_x, sd_x, mu_y, sd_y = bundle_norm(spec, net)
    x = np.asarray(generate.make_inputs(
        config, {"distinct_steps": 1, "callers": 1, "rows_per_caller": 64},
        3)[0][0])
    with jax.default_matmul_precision("highest"):
        y = np.asarray(net.apply(params, (x - mu_x) / sd_x) * sd_y + mu_y)
    ref, = reference.run(functools.partial(arch.forward, config), model,
                         [x])
    assert ref.shape == (64, 1)
    assert reference.max_rel_err(y, ref) < 1e-6


def test_a_configuration_at_another_precision_is_refused():
    import jax
    cell = harness.find_cell("minibude-bulk", BENCH)
    cell["config"] = dict(cell["config"], matmul_precision="high")
    with pytest.raises(ValueError, match="highest"):
        harness.run_cell(cell, seed=1, seconds=0.1, trace=False,
                         t_start=0.0, devices=jax.devices())


def test_max_rel_err_reads_a_non_finite_row_as_infinitely_far():
    ref = np.ones((4, 1), np.float32)
    served = ref.copy()
    served[2] = np.nan
    assert reference.max_rel_err(served, ref) == float("inf")
    assert reference.max_rel_err(ref, ref) == 0.0


def _run(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "binomial-ranks", "--seed", "1", "--seconds", "1", "--trace", "0",
         *extra], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_run_exits_nonzero_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "not a TPU" in p.stderr
    assert "metrics" not in p.stdout and p.stdout.strip() == ""


def test_run_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert "metrics" not in p.stdout
