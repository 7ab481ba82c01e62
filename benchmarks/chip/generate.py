"""Everything a run makes from its seed: the surrogate's weights, in one
jitted call on the device, and the callers' inputs, drawn on the host in
one block and placed on the device in one transfer.

The seed may be any whole number, wider than 32 bits too: it is hashed
(``numpy.random.SeedSequence``) to two 32-bit words, which key JAX's
threefry generator for the weights, and to a stream of its own for the
inputs.  The same seed gives the same weights and rows; every seed gives
the same sizes, so the work of a run does not depend on it.  Shapes and
ranges come from the configuration and traffic files.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def _seed_sequence(seed: int) -> np.random.SeedSequence:
    return np.random.SeedSequence(int(seed) % 2 ** 64)


def seed_words(seed: int) -> np.ndarray:
    return _seed_sequence(seed).generate_state(2, np.uint32)


@functools.partial(jax.jit, static_argnums=0)
def _weights(widths, words):
    key = jax.random.wrap_key_data(words, impl="threefry2x32")
    layers = []
    for i, (a, b) in enumerate(zip(widths[:-1], widths[1:])):
        kw, kb = jax.random.split(jax.random.fold_in(key, i))
        w = jax.random.normal(kw, (a, b), jnp.float32) * math.sqrt(2.0 / a)
        layers.append((w, jax.random.normal(kb, (b,), jnp.float32) * 0.1))
    return layers


def make_weights(widths, seed: int):
    """[(w [a, b], b [b]), ...] f32 on the default device: He-normal
    weights and N(0, 0.1) biases."""
    return _weights(tuple(int(w) for w in widths),
                    jnp.asarray(seed_words(seed)))


def feature_ranges(config):
    lo = np.array([f["lo"] for f in config["features"]], np.float32)
    hi = np.array([f["hi"] for f in config["features"]], np.float32)
    return lo, hi


def make_inputs(config, traffic, seed: int):
    """``inputs[s][c]``: caller ``c``'s rows [rows_per_caller, features]
    at distinct step ``s``, each feature uniform over its range, on the
    default device."""
    lo, hi = feature_ranges(config)
    steps, callers = int(traffic["distinct_steps"]), int(traffic["callers"])
    rng = np.random.default_rng(_seed_sequence(seed).spawn(1)[0])
    u = rng.random((steps, callers, int(traffic["rows_per_caller"]),
                    lo.shape[0]), dtype=np.float32)
    x = lo + u * (hi - lo)
    return jax.device_put([[x[s, c] for c in range(callers)]
                           for s in range(steps)])


def norm_stats(config):
    """(x_mu, x_sd, y_mu, y_sd) f32: the mean and spread of a uniform
    feature, as a bundle trained on that range carries, and the output
    statistics the configuration states.  Fixed by the configuration and
    not by the seed, so that the served program is the same for every
    seed and comes from the compile cache."""
    lo, hi = feature_ranges(config)
    return (((lo + hi) / 2).astype(np.float32),
            ((hi - lo) / math.sqrt(12.0)).astype(np.float32),
            np.array([config["output_norm"]["mu"]], np.float32),
            np.array([config["output_norm"]["sd"]], np.float32))
