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

Where an architecture's answer for a row is not defined at f32 (a
routing decision that rounding can flip), its ``forward`` says so with a
mask, computed in the reference's own ``highest`` pass; the comparison
leaves those rows out of the gap and counts them (``compare``).
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
    else is resident; for each, what ``forward`` returns over all its rows:
    the outputs, or ``(outputs, defined)`` (``split``).  ``forward(model,
    x, dot)`` is an architecture's forward pass with its configuration
    bound (``functools.partial(arch.forward, config)``), compiled once for
    the call.  The weights are placed on the device once for the call,
    every block reads that one copy, and it is released when the call
    ends."""
    f = jax.jit(functools.partial(forward, dot=DOTS[precision]))
    outs = []
    with jax.default_matmul_precision("highest"):
        placed = jax.device_put(model)
        for x in xs:
            x = np.asarray(x, np.float32)
            blocks = [jax.device_get(f(placed,
                                       jax.device_put(x[i:i + block_rows])))
                      for i in range(0, x.shape[0], block_rows)]
            outs.append(jax.tree.map(lambda *b: np.concatenate(b, axis=0),
                                     *blocks))
    return outs


def split(out):
    """``(rows, defined)`` of what ``run`` returned for one array:
    ``defined`` is one boolean a row, False where the architecture says
    the reference's answer is not defined at f32 (a routing decision
    within rounding of a tie).  A plain array defines every row."""
    if isinstance(out, tuple):
        rows, defined = out
        return rows, np.asarray(defined, bool).reshape(-1)
    return out, np.ones(out.shape[0], bool)


def max_rel_err(served, ref, defined=None) -> float:
    """The number compared: the widest gap between a served value and the
    reference's, over the largest reference magnitude, both taken on the
    rows ``defined`` (every row where it is None).  A non-finite served
    value, in any row, reads as infinitely far off; with no row defined
    there is no gap."""
    served = np.asarray(served, np.float64)
    ref = np.asarray(ref, np.float64)
    if served.shape != ref.shape:
        raise ValueError(f"served rows {served.shape} vs reference rows "
                         f"{ref.shape}")
    if not np.all(np.isfinite(served)):
        return float("inf")
    if defined is not None:
        served, ref = served[defined], ref[defined]
        if not ref.size:
            return 0.0
    return float(np.abs(served - ref).max() / np.abs(ref).max())


def compare(pairs) -> tuple:
    """``(gap, left_out, rows)`` over ``pairs`` of (served rows, what
    ``run`` returned at ``highest`` for the same inputs): the widest
    ``max_rel_err`` of a pair on the rows the reference defines, the rows
    it leaves out, and all rows.  Where no row at all is defined, nothing
    was compared and the gap is infinite."""
    gap, left_out, rows = 0.0, 0, 0
    for served, out in pairs:
        ref, defined = split(out)
        gap = max(gap, max_rel_err(served, ref, defined))
        rows += defined.size
        left_out += int(defined.size - defined.sum())
    return (float("inf") if left_out == rows else gap), left_out, rows
