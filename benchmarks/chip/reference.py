"""The plain reference of a served surrogate, its control, and the
comparison that decides ``correct``.

The reference is the architecture's forward pass written out in
``jax.numpy`` (``forward`` of ``archs/<arch>.py``).  Every product runs
through the ``dot`` this module hands it, in f32 at ``highest`` matmul
precision, so on a TPU it is f32 arithmetic and not one bf16 pass.  It
imports nothing of the program and is given the weights the benchmark
made from the seed, never a bundle read back.

The control is the same forward pass one precision step lower: each f32
product split into three bf16 passes (high x high + high x low + low x
high), what a matmul at ``high`` precision computes.  It is the step a
later change to the served kernel would be tempted to take, and the
comparison's limit sits between the two (``PERF.md`` gives the readings).
The reference and its control are one code path; only ``dot`` differs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BLOCK_ROWS = 65536


def _dot(a, b):
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _bf16(a):
    # an explicit rounding op: written as two converts, the round trip
    # read like a single bf16 pass on a TPU (XLA may drop such a pair)
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _bf16_parts(a):
    hi = _bf16(a)
    return hi, _bf16(a - hi)


def _dot_3pass(a, b):
    """f32 product from three bf16 passes; products of bf16 values are
    exact in f32, so only the dropped low x low term and the rounding of
    each operand to 16 mantissa bits are lost."""
    ah, al = _bf16_parts(a)
    bh, bl = _bf16_parts(b)
    return _dot(ah, bh) + (_dot(ah, bl) + _dot(al, bh))


#: ``highest``: the reference; ``3pass``: the control
DOTS = {"highest": _dot, "3pass": _dot_3pass}


def run(forward, model, xs, *, precision="highest",
        block_rows=BLOCK_ROWS) -> list:
    """The reference (or, at ``precision="3pass"``, the control) over each
    array of ``xs``, in blocks of rows so that it fits beside whatever
    else is resident; one array of outputs for each.  ``forward(model, x,
    dot)`` is an architecture's forward pass with its configuration bound
    (``functools.partial(arch.forward, config)``), compiled once for the
    call."""
    f = jax.jit(functools.partial(forward, dot=DOTS[precision]))
    outs = []
    with jax.default_matmul_precision("highest"):
        for x in xs:
            x = np.asarray(x, np.float32)
            outs.append(np.concatenate(
                [np.asarray(f(model, jnp.asarray(x[i:i + block_rows])))
                 for i in range(0, x.shape[0], block_rows)], axis=0))
    return outs


def max_rel_err(served, ref) -> float:
    """The number compared: the widest gap between a served value and the
    reference's, over the largest reference magnitude.  A non-finite
    served value reads as infinitely far off."""
    served = np.asarray(served, np.float64)
    ref = np.asarray(ref, np.float64)
    if served.shape != ref.shape:
        raise ValueError(f"served rows {served.shape} vs reference rows "
                         f"{ref.shape}")
    if not np.all(np.isfinite(served)):
        return float("inf")
    return float(np.abs(served - ref).max() / np.abs(ref).max())
