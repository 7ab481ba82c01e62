"""``archs/mlp.py`` against the dense-layer chain that the harness had
written in before a configuration named its architecture: for both MLP
configurations, the same seeded weights, bundle, inputs, reference and
control outputs and work counts, bit for bit.  The functions prefixed
``_before_`` are that code as it was, kept here as the reference the
module is held to."""
import functools
import math
import os
import pathlib
import sys

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
CHIP = pathlib.Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
sys.path[:0] = [str(CHIP), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import generate  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402
import trace_reduce  # noqa: E402

MLP = harness.load_arch("mlp")
CONFIGS = {name: harness.load_part("configs", name) for name in (
    "binomial-mlp-5-512-512-1", "minibude-mlp-6-1024-819-655-524-419-335-1")}
SEEDS = (7, 2 ** 33 + 5)


# ------------------------------------------------- the code as it was ---
@functools.partial(jax.jit, static_argnums=0)
def _before_weights(widths, words):
    key = jax.random.wrap_key_data(words, impl="threefry2x32")
    layers = []
    for i, (a, b) in enumerate(zip(widths[:-1], widths[1:])):
        kw, kb = jax.random.split(jax.random.fold_in(key, i))
        w = jax.random.normal(kw, (a, b), jnp.float32) * math.sqrt(2.0 / a)
        layers.append((w, jax.random.normal(kb, (b,), jnp.float32) * 0.1))
    return layers


def _before_model(config, seed):
    words = np.random.SeedSequence(int(seed) % 2 ** 64).generate_state(
        2, np.uint32)
    layers = _before_weights(tuple(int(w) for w in config["widths"]),
                             jnp.asarray(words))
    lo = np.array([f["lo"] for f in config["features"]], np.float32)
    hi = np.array([f["hi"] for f in config["features"]], np.float32)
    norm = (((lo + hi) / 2).astype(np.float32),
            ((hi - lo) / math.sqrt(12.0)).astype(np.float32),
            np.array([config["output_norm"]["mu"]], np.float32),
            np.array([config["output_norm"]["sd"]], np.float32))
    return {"layers": jax.device_get(layers), "norm": norm}


def _before_write_bundle(path, config, layers, norm):
    from repro.nn.layers import MLP as Net, Dense
    from repro.nn.serialize import save_model
    widths = config["widths"]
    net = Net((1, widths[0]), widths[1:-1], widths[-1],
              act=config["activation"])
    it = iter(layers)
    params = []
    for layer in net.layers:
        if isinstance(layer, Dense):
            w, b = next(it)
            params.append({"w": w, "b": b})
        else:
            params.append({})
    extra = {k: np.asarray(v).tolist()
             for k, v in zip(("x_mu", "x_sd", "y_mu", "y_sd"), norm)}
    return save_model(path, net, params, extra=extra)


def _before_inputs(config, traffic, seed):
    lo = np.array([f["lo"] for f in config["features"]], np.float32)
    hi = np.array([f["hi"] for f in config["features"]], np.float32)
    steps, callers = int(traffic["distinct_steps"]), int(traffic["callers"])
    rng = np.random.default_rng(
        np.random.SeedSequence(int(seed) % 2 ** 64).spawn(1)[0])
    u = rng.random((steps, callers, int(traffic["rows_per_caller"]),
                    lo.shape[0]), dtype=np.float32)
    x = lo + u * (hi - lo)
    return [[x[s, c] for c in range(callers)] for s in range(steps)]


def _before_forward(model, x, *, activation="relu", precision="highest"):
    dot = reference.DOTS[precision]
    act = {"relu": lambda h: jnp.maximum(h, 0.0)}[activation]
    x_mu, x_sd, y_mu, y_sd = model["norm"]
    h = (x - x_mu) / x_sd
    layers = model["layers"]
    for i, (w, b) in enumerate(layers):
        h = dot(h, w) + b
        if i + 1 < len(layers):
            h = act(h)
    return h * y_sd + y_mu


def _before_run(model, x, *, activation, precision, block_rows):
    f = jax.jit(functools.partial(_before_forward, activation=activation,
                                  precision=precision))
    with jax.default_matmul_precision("highest"):
        outs = [np.asarray(f(model, jnp.asarray(x[i:i + block_rows])))
                for i in range(0, x.shape[0], block_rows)]
    return np.concatenate(outs, axis=0)


def _before_flops_per_row(widths):
    return 2 * sum(a * b for a, b in zip(widths[:-1], widths[1:]))


def _before_call_bytes(widths, rows):
    params = sum(a * b + b for a, b in zip(widths[:-1], widths[1:]))
    return 4 * params + 4 * rows * (widths[0] + widths[-1])


# ------------------------------------------------------------ the tests ---
def _same_tree(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape
        assert x.tobytes() == y.tobytes()


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_configuration_names_the_mlp(name):
    config = CONFIGS[name]
    assert config["arch"] == "mlp"
    assert config["widths"][0] == len(config["features"])


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_weights_are_the_same(name, seed):
    config = CONFIGS[name]
    _same_tree(MLP.make_weights(config, seed), _before_model(config, seed))


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_bundle_is_the_same(name, tmp_path):
    config = CONFIGS[name]
    model = MLP.make_weights(config, SEEDS[1])
    now = pathlib.Path(MLP.write_bundle(tmp_path / "now", config, model))
    before = pathlib.Path(_before_write_bundle(
        tmp_path / "before", config, model["layers"], model["norm"]))
    assert sorted(p.name for p in now.iterdir()) == \
        sorted(p.name for p in before.iterdir()) == \
        ["params.npz", "spec.json"]
    assert (now / "spec.json").read_bytes() == \
        (before / "spec.json").read_bytes()
    # the zip stamps each member with the time it was written, so the
    # arrays are compared, not the file's bytes
    with np.load(now / "params.npz") as a, \
            np.load(before / "params.npz") as b:
        assert a.files == b.files
        for k in a.files:
            assert a[k].dtype == b[k].dtype and \
                a[k].tobytes() == b[k].tobytes()


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_inputs_are_the_same(name, seed):
    config = CONFIGS[name]
    traffic = {"distinct_steps": 3, "callers": 2, "rows_per_caller": 40}
    _same_tree(MLP.make_inputs(config, traffic, seed),
               _before_inputs(config, traffic, seed))


@pytest.mark.parametrize("precision", sorted(reference.DOTS))
@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_reference_and_control_are_the_same(name, precision):
    config = CONFIGS[name]
    model = MLP.make_weights(config, SEEDS[0])
    x = np.asarray(generate.make_inputs(
        config, {"distinct_steps": 1, "callers": 1, "rows_per_caller": 768},
        SEEDS[0])[0][0])
    forward = functools.partial(MLP.forward, config)
    now, = reference.run(forward, model, [x], precision=precision,
                         block_rows=512)
    before = _before_run(model, x, activation=config["activation"],
                         precision=precision, block_rows=512)
    assert now.shape == (768, 1)
    assert now.tobytes() == before.tobytes()


@pytest.mark.parametrize("name,flops", [
    ("binomial-mlp-5-512-512-1", 530_432),
    ("minibude-mlp-6-1024-819-655-524-419-335-1", 4_169_442)])
def test_work_counts_are_the_same(name, flops):
    config = CONFIGS[name]
    assert MLP.flops_per_row(config) == flops == \
        _before_flops_per_row(config["widths"])
    for rows in (0, 1, 32768, 65536):
        assert MLP.call_bytes(config, rows) == \
            _before_call_bytes(config["widths"], rows)


def test_recorded_trace_reads_as_before():
    """``apply_roofline`` and ``mfu`` over the two steps of
    ``binomial-ranks`` recorded on a TPU v5e, from the counts a run now
    records, read the values they read from the net's widths."""
    config = CONFIGS["binomial-mlp-5-512-512-1"]
    tr = trace_reduce.reduce(trace_reduce.extract(trace_reduce.load(
        CHIP / "tests" / "data" / "binomial-ranks-2steps.xplane.pb.gz")))
    rec = {"trace": tr, "rows_per_step": 32768, "chips": 1,
           "device_kind": "TPU v5 lite", "rows": 2 * 32768,
           "window_s": tr["window_s"],
           "flops_per_row": MLP.flops_per_row(config),
           "call_bytes": MLP.call_bytes(config, 32768)}
    assert harness.load_reader("apply_roofline")(rec) == 10.408935980168401
    assert harness.load_reader("mfu")(rec) == 0.11243725365550578

