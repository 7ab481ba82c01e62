"""Rows whose reference answer is not defined at f32, on the CPU.

A toy routed surrogate in plain ``jax.numpy``: top-1 of 4 gates over
dense experts, each with a bias of its own.  Its ``forward`` marks a row
undefined where the row's top two gate scores lie within the margin its
configuration states (``route_margin``).  Every caller's first row sits
on the features' midpoints, where the normalized row is zero and gates 0
and 1 tie exactly; the served side (the engine's apply, replaced here by
the same forward) breaks ties the other way, so those rows come back from
another expert, as a correct f32 program whose rounding differs may do.
"""
import functools
import json
import os
import pathlib
import sys
import time
import types

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
CHIP = pathlib.Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
sys.path[:0] = [str(CHIP), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import calibrate  # noqa: E402
import generate  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIG = dict(harness.load_part("configs", "binomial-mlp-5-512-512-1"),
              name="toy-route-5-4x1", arch="toy_route", route_margin=1e-6,
              check={"max_rel_err": 1e-6, "max_rows_left_out": 0.05})
CALLERS, ROWS, STEPS = 4, 64, 2
#: one tie row a caller a step, of all the rows a run compares
TIE_SHARE = 1 / ROWS


def _route(config, model, x, dot, last_wins=False):
    x_mu, x_sd, y_mu, y_sd = model["norm"]
    h = (x - x_mu) / x_sd
    s = dot(h, model["gate"]) + model["gate_bias"]
    first, second = jax.lax.top_k(s, 2)[0].T
    e = (3 - jnp.argmax(s[:, ::-1], -1)) if last_wins else jnp.argmax(s, -1)
    ys = jnp.stack([dot(h, w) for w in model["experts"]], 1)
    y = jnp.take_along_axis(ys + model["expert_bias"], e[:, None, None], 1)
    return y[:, 0] * y_sd + y_mu, first - second > config["route_margin"]


def make_weights(config, seed):
    rng = np.random.default_rng(generate.seed_words(seed))

    def normal(*shape):
        return rng.standard_normal(shape).astype(np.float32)

    return {"gate": normal(5, 4),
            "gate_bias": np.array([1, 1, 0, 0], np.float32),
            "experts": normal(4, 5, 1), "expert_bias": normal(4, 1),
            "norm": generate.norm_stats(config)}


def write_bundle(path, config, model):
    """A dense bundle of the right shapes, for the engine to load; its
    apply is replaced by ``_route``."""
    from repro.nn.layers import MLP
    from repro.nn.serialize import save_model
    net = MLP((1, 5), [], 1)
    return save_model(path, net, [{"w": np.zeros((5, 1), np.float32),
                                   "b": np.zeros((1,), np.float32)}])


def make_inputs(config, traffic, seed):
    x_mu = generate.norm_stats(config)[0]
    return [[x.at[0].set(x_mu) for x in xs]
            for xs in generate.make_inputs(config, traffic, seed)]


TOY = types.SimpleNamespace(
    make_weights=make_weights, write_bundle=write_bundle,
    make_inputs=make_inputs, forward=_route,
    flops_per_row=lambda config: 2 * 5 * 8,
    call_bytes=lambda config, rows: 4 * (5 * 8 + 8 + rows * 6))


def _cell(**check):
    cell = harness.find_cell("binomial-ranks", BENCH)
    cell["config"] = dict(CONFIG, check=check) if check else CONFIG
    cell["arch"] = TOY
    cell["traffic"] = dict(cell["traffic"], callers=CALLERS,
                           rows_per_caller=ROWS, distinct_steps=STEPS,
                           sampled_steps=STEPS)
    return cell


def _serve_routed(monkeypatch, cell, fault=None):
    """The engine's apply becomes the toy's forward over the run's own
    weights, ties broken the other way, with ``fault`` applied."""
    from repro.core.engine import InferenceEngine
    made = []

    def made_weights(config, seed):
        made.append(make_weights(config, seed))
        return made[-1]

    cell["arch"] = types.SimpleNamespace(**dict(vars(TOY),
                                                make_weights=made_weights))

    def apply(self, x, **kw):
        y, _ = _route(cell["config"], made[-1], jnp.asarray(x),
                      reference._dot, last_wins=True)
        return y if fault is None else fault(y)

    monkeypatch.setattr(InferenceEngine, "apply_batched", apply)


def _run(cell):
    return harness.run_cell(cell, seed=2 ** 33 + 11, seconds=0.3,
                            trace=False, t_start=time.perf_counter(),
                            devices=jax.devices())


# (the configuration's check, a fault in the served rows, correct)
CASES = {
    # the tie rows come back from another expert and are left out
    "tie_rows_left_out": ({"max_rel_err": 1e-6, "max_rows_left_out": 0.05},
                          None, True),
    # a defined row altered is still caught
    "defined_row_altered": ({"max_rel_err": 1e-6,
                             "max_rows_left_out": 0.05},
                            lambda y: y.at[1].add(0.01), False),
    # more rows left out than the configuration allows
    "share_above_the_bound": ({"max_rel_err": 1e-6,
                               "max_rows_left_out": TIE_SHARE / 2},
                              None, False),
    # a configuration that states no bound may leave out no row
    "no_bound_stated": ({"max_rel_err": 1e-6}, None, False),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_rows_left_out_are_counted_and_bounded(monkeypatch, case):
    check, fault, correct = CASES[case]
    cell = _cell(**check)
    _serve_routed(monkeypatch, cell, fault)
    out = _run(cell)
    assert out["correct"] is correct, out["checks"]
    left_out = out["checks"]["rows_left_out"]
    assert left_out == {"value": TIE_SHARE,
                        "limit": check.get("max_rows_left_out", 0)}
    gap = out["checks"]["max_rel_err"]["value"]
    assert (gap > 1e-6) is (fault is not None)


def test_with_no_margin_the_tie_rows_read_far_off(monkeypatch):
    """The rows left out above do differ: with every row defined, the
    tie rows alone fail the comparison by far."""
    cell = _cell(max_rel_err=1e-6, max_rows_left_out=0.05)
    cell["config"] = dict(cell["config"], route_margin=-1.0)
    _serve_routed(monkeypatch, cell)
    out = _run(cell)
    assert not out["correct"]
    assert out["checks"]["rows_left_out"]["value"] == 0.0
    assert out["checks"]["max_rel_err"]["value"] > 1e-3


def test_the_control_fails_the_limit_on_the_defined_rows():
    """``calibrate``'s control reading takes the rows the ``highest``
    pass defines, and the 3-pass control fails the limit there, while
    the served forward (ties broken the other way) reads inside it."""
    cell = _cell()
    forward = functools.partial(TOY.forward, CONFIG)
    limit = CONFIG["check"]["max_rel_err"]
    for seed in (1, 2, 3):
        assert calibrate.control_reading(cell, forward, seed) > limit
        model = make_weights(CONFIG, seed)
        x = np.concatenate([np.asarray(a) for a in make_inputs(
            CONFIG, cell["traffic"], seed)[0]])
        ref, = reference.run(forward, model, [x])
        served, _ = jax.jit(functools.partial(
            _route, CONFIG, dot=reference._dot, last_wins=True))(model, x)
        gap, left_out, rows = reference.compare([(np.asarray(served), ref)])
        assert gap < limit / 4
        assert (left_out, rows) == (CALLERS, CALLERS * ROWS)
