"""A chain of dense layers with one activation between them: the HPAC-ML
surrogates of Binomial Options and miniBUDE.

The configuration names this module with ``"arch": "mlp"`` and gives
``widths`` (inputs, each hidden layer, outputs) and ``activation``.  The
served bundle is the program's own ``MLP``, which the engine runs in the
``fused_mlp`` kernel on a TPU.

Counts are the algorithm's: a dense layer of fan-in ``a`` and width ``b``
costs ``2 a b`` FLOPs a row (one multiply and one add per weight); bias,
activation and normalization are left out.  Bytes are what one call must
move at least: every weight and bias once, every input and output row
once, in f32.  Padding of a batch or of a width is never counted.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

import generate
import work

_ACTS = {"relu": lambda h: jnp.maximum(h, 0.0)}


def _widths(config) -> tuple:
    return tuple(int(w) for w in config["widths"])


@functools.partial(jax.jit, static_argnums=0)
def _weights(widths, words):
    key = jax.random.wrap_key_data(words, impl="threefry2x32")
    layers = []
    for i, (a, b) in enumerate(zip(widths[:-1], widths[1:])):
        kw, kb = jax.random.split(jax.random.fold_in(key, i))
        w = jax.random.normal(kw, (a, b), jnp.float32) * math.sqrt(2.0 / a)
        layers.append((w, jax.random.normal(kb, (b,), jnp.float32) * 0.1))
    return layers


def make_weights(config, seed: int) -> dict:
    """{"layers": [(w [a, b], b [b]), ...], "norm": (x_mu, x_sd, y_mu,
    y_sd)}, f32 on the host: He-normal weights and N(0, 0.1) biases, made
    in one jitted call on the default device."""
    layers = _weights(_widths(config), jnp.asarray(generate.seed_words(seed)))
    return {"layers": jax.device_get(layers),
            "norm": generate.norm_stats(config)}


def write_bundle(path, config, model) -> str:
    """The weights as a model bundle the program loads by path, with the
    normalization entries a trained bundle carries."""
    from repro.nn.layers import MLP, Dense
    from repro.nn.serialize import save_model
    widths = config["widths"]
    net = MLP((1, widths[0]), widths[1:-1], widths[-1],
              act=config["activation"])
    it = iter(model["layers"])
    params = []
    for layer in net.layers:
        if isinstance(layer, Dense):
            w, b = next(it)
            params.append({"w": w, "b": b})
        else:
            params.append({})
    extra = {k: np.asarray(v).tolist()
             for k, v in zip(("x_mu", "x_sd", "y_mu", "y_sd"), model["norm"])}
    return save_model(path, net, params, extra=extra)


def make_inputs(config, traffic, seed: int):
    """Every feature uniform over its range (``generate.make_inputs``)."""
    return generate.make_inputs(config, traffic, seed)


def forward(config, model, x, dot):
    """Normalize the inputs, the chain of dense layers with the
    activation between them, denormalize the outputs.  ``x``: [rows, in]
    f32; returns [rows, out] f32."""
    act = _ACTS[config["activation"]]
    x_mu, x_sd, y_mu, y_sd = model["norm"]
    h = (x - x_mu) / x_sd
    layers = model["layers"]
    for i, (w, b) in enumerate(layers):
        h = dot(h, w) + b
        if i + 1 < len(layers):
            h = act(h)
    return h * y_sd + y_mu


def flops_per_row(config) -> int:
    widths = _widths(config)
    return 2 * sum(a * b for a, b in zip(widths[:-1], widths[1:]))


def call_bytes(config, rows: int) -> int:
    """Least bytes one call over ``rows`` rows moves to and from HBM."""
    widths = _widths(config)
    params = sum(a * b + b for a, b in zip(widths[:-1], widths[1:]))
    return work.F32_BYTES * (params + rows * (widths[0] + widths[-1]))
