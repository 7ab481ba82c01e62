"""What every architecture makes from its seed alike: the seed's words,
the callers' inputs with each feature uniform over its range, and the
normalization a bundle trained on those ranges carries.  An
architecture's weights are its own (``archs/<arch>.py``), made in one
jitted call on the device and keyed by ``seed_words``.

The seed may be any whole number, wider than 32 bits too: it is hashed
(``numpy.random.SeedSequence``) to two 32-bit words, which key JAX's
threefry generator for the weights, and to a stream of its own for the
inputs.  The same seed gives the same weights and rows; every seed gives
the same sizes, so the work of a run does not depend on it.  Shapes and
ranges come from the configuration and traffic files.
"""
from __future__ import annotations

import math

import jax
import numpy as np


def _seed_sequence(seed: int) -> np.random.SeedSequence:
    return np.random.SeedSequence(int(seed) % 2 ** 64)


def seed_words(seed: int) -> np.ndarray:
    return _seed_sequence(seed).generate_state(2, np.uint32)


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
