"""Pure-jnp oracle for fused_mlp."""
from __future__ import annotations

import jax
import jax.numpy as jnp

_ACTS = {
    "relu": lambda x: jnp.maximum(x, 0.0),
    "gelu": jax.nn.gelu,
    "tanh": jnp.tanh,
    "silu": jax.nn.silu,
    "sigmoid": jax.nn.sigmoid,
    "identity": lambda x: x,
}


def fused_mlp_ref(x, weights, biases, acts):
    h = x.astype(jnp.float32)
    for w, b, a in zip(weights, biases, acts):
        # the kernel contracts at HIGHEST; so must its oracle, or on a TPU
        # it would run one bf16 pass and stop being the f32 ground truth
        h = _ACTS[a](jnp.dot(h, w, precision=jax.lax.Precision.HIGHEST)
                     + b)
    return h.astype(x.dtype)
