"""Tensor map: memory concretization (paper §IV-A, Fig. 4).

Applying a functor to application memory runs the paper's four compiler
steps, implemented here as runtime functions over JAX arrays:

  1. symbolic shape extraction — per RHS slice, the base-pointer offset and
     element count relative to the mapped ranges;
  2. symbolic shape resolution — the window shape each slice resolves to;
  3. tensor wrapping — lightweight window views (``lax.slice``, no copies
     until XLA decides layout);
  4. tensor composition — flatten + stack the per-slice views into the LHS
     tensor (app -> tensor direction only).

Direction ``to`` maps application memory -> tensor space (gather);
``from`` maps tensor space -> application memory (window writes).  This
jnp implementation is the path every region runs, on every backend.
``repro.kernels.stencil_gather`` is a Pallas kernel for the same stencil
gather (bit-exact against its jnp oracle), but no region calls it yet.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import Sequence, Union

import jax
import jax.numpy as jnp

from repro.core.functor import SSlice, TensorFunctor


def _normalize_ranges(functor: TensorFunctor, ranges) -> dict:
    syms = functor.sweep_symbols
    if isinstance(ranges, dict):
        out = {}
        for k, v in ranges.items():
            if isinstance(v, range):
                out[k] = (v.start, v.stop, v.step)
            else:
                t = tuple(v)
                out[k] = t if len(t) == 3 else (t[0], t[1], 1)
        return out
    out = {}
    for s, v in zip(syms, ranges):
        t = tuple(v) if not isinstance(v, range) else (v.start, v.stop, v.step)
        out[s] = t if len(t) == 3 else (t[0], t[1], 1)
    return out


@dataclass(frozen=True)
class SliceDescriptor:
    """One RHS slice after extraction/resolution (paper's runtime struct)."""
    offsets: tuple          # per-dim start offset at the sweep origin
    window_shape: tuple     # per-dim window extent (sweep dims) or 1
    sweep_dims: tuple       # which array dim each sweep symbol drives (or None)
    elem_offsets: tuple     # per-feature additional offsets within the slice
    steps: tuple            # per-dim stride (sweep step * symbol coeff)


def symbolic_shape_extraction(group: Sequence[SSlice], ranges: dict):
    """Offsets + element counts for one RHS slice group."""
    offsets, elem_axes = [], []
    for d, s in enumerate(group):
        syms = s.start.symbols
        if len(syms) > 1:
            raise ValueError("an s-slice may use at most one s-constant")
        base = {n: ranges[n][0] for n in syms}
        offsets.append(s.start.evaluate(base))
        elem_axes.append(s.n_elements())
    return tuple(offsets), tuple(elem_axes)


def symbolic_shape_resolution(group: Sequence[SSlice], ranges: dict):
    """Window shape + sweep-dim mapping + strides for one slice group."""
    shape, sweep_dims, steps = [], [], []
    for s in group:
        syms = s.start.symbols
        if syms:
            name = syms[0]
            coeff = dict(s.start.coeffs)[name]
            lo, hi, st = ranges[name]
            n = max(0, -(-(hi - lo) // st))
            shape.append(n)
            sweep_dims.append(name)
            steps.append(st * coeff)
        else:
            shape.append(1)
            sweep_dims.append(None)
            steps.append(1)
    return tuple(shape), tuple(sweep_dims), tuple(steps)


def tensor_wrapping(group: Sequence[SSlice], ranges: dict) -> SliceDescriptor:
    offsets, elem_axes = symbolic_shape_extraction(group, ranges)
    shape, sweep_dims, steps = symbolic_shape_resolution(group, ranges)
    elem_offsets = tuple(itertools.product(
        *[range(0, n * max(1, s.step), max(1, s.step)) if n > 1 else (0,)
          for n, s in zip(elem_axes, group)]))
    return SliceDescriptor(offsets, shape, sweep_dims, elem_offsets, steps)


def _gather_group(array, desc: SliceDescriptor):
    """All shifted windows for one slice group -> [sweep..., n_elem]."""
    views = []
    for eo in desc.elem_offsets:
        starts, limits, strides = [], [], []
        for d in range(len(desc.offsets)):
            start = desc.offsets[d] + eo[d]
            extent = desc.window_shape[d]
            step = desc.steps[d] if desc.sweep_dims[d] is not None else 1
            starts.append(start)
            limits.append(start + (extent - 1) * abs(step) + 1 if extent > 1
                          else start + 1)
            strides.append(abs(step) if extent > 1 else 1)
        v = jax.lax.slice(array, starts, limits, strides)
        views.append(v.reshape([s for s in v.shape if s != 1] or [1]))
    return jnp.stack(views, axis=-1)


class TensorMap:
    """A functor applied to concrete memory over concrete ranges."""

    def __init__(self, functor: TensorFunctor, array, ranges,
                 direction: str = "to"):
        assert direction in ("to", "from")
        self.functor = functor
        self.array = array
        self.ranges = _normalize_ranges(functor, ranges)
        self.direction = direction
        self.descriptors = [tensor_wrapping(g, self.ranges)
                            for g in functor.rhs]

    # ------------------------------------------------------ to tensor -----
    def to_tensor(self, array=None):
        """Tensor composition: app memory -> LHS-shaped tensor."""
        array = self.array if array is None else array
        parts = [_gather_group(array, d) for d in self.descriptors]
        t = jnp.concatenate(parts, axis=-1)
        return self._compose_lhs(t)

    def _lhs_dims(self):
        sweep, feat = [], []
        for s in self.functor.lhs:
            if s.start.symbols:
                name = s.start.symbols[0]
                lo, hi, st = self.ranges[name]
                sweep.append(max(0, -(-(hi - lo) // st)))
            else:
                feat.append(s.n_elements())
        return sweep, feat

    def _compose_lhs(self, t):
        sweep, feat = self._lhs_dims()
        want_feat = 1
        for f in feat:
            want_feat *= f
        if t.shape[-1] != want_feat:
            raise ValueError(
                f"functor {self.functor.name}: LHS declares {want_feat} "
                f"features, RHS provides {t.shape[-1]}")
        return t.reshape(tuple(sweep) + tuple(feat) if feat else tuple(sweep)
                         + (1,))[..., 0] if not feat else \
            t.reshape(tuple(sweep) + tuple(feat))

    @property
    def tensor_shape(self):
        sweep, feat = self._lhs_dims()
        return tuple(sweep) + tuple(feat if feat else ())

    # ---------------------------------------------------- from tensor -----
    def from_tensor(self, tensor, array=None):
        """Write the tensor back through the functor windows (scatter)."""
        array = self.array if array is None else array
        sweep, feat = self._lhs_dims()
        flat = tensor.reshape(tuple(sweep) + (-1,))
        fidx = 0
        out = array
        for desc in self.descriptors:
            for eo in desc.elem_offsets:
                starts = [desc.offsets[d] + eo[d]
                          for d in range(len(desc.offsets))]
                piece = flat[..., fidx]
                shape = [desc.window_shape[d] for d in range(len(starts))]
                piece = piece.reshape(shape)
                out = jax.lax.dynamic_update_slice(
                    out, piece.astype(out.dtype), tuple(starts))
                fidx += 1
        return out

    def min_array_shape(self):
        """Smallest app-memory shape the windows cover (template synth)."""
        nd = len(self.descriptors[0].offsets)
        hi = [0] * nd
        for desc in self.descriptors:
            for eo in desc.elem_offsets:
                for d in range(nd):
                    step = abs(desc.steps[d]) if desc.sweep_dims[d] else 1
                    end = (desc.offsets[d] + eo[d]
                           + (desc.window_shape[d] - 1) * step + 1)
                    hi[d] = max(hi[d], end)
        return tuple(hi)

    def identity_shape(self):
        """The one array shape at which this map is the identity, or None.

        At that shape ``to_tensor`` returns the array bit for bit and
        ``from_tensor`` overwrites all of it with the tensor: one RHS slice
        group, every offset 0, the sweep dims ahead of the feature dims,
        each a step-1 window over its whole range, feature elements 0..F-1
        in order, and an LHS of the same shape.
        """
        if len(self.descriptors) != 1:
            return None
        desc = self.descriptors[0]
        shape = self.min_array_shape()
        sweep = [s for s in desc.sweep_dims if s is not None]
        n = len(sweep)
        if (shape != self.tensor_shape or 0 in shape or any(desc.offsets)
                or len(set(sweep)) != n
                or None in desc.sweep_dims[:n]
                or any(st != 1 for st in desc.steps[:n])):
            return None
        in_order = tuple(itertools.product(
            *[range(e) if d >= n else (0,) for d, e in enumerate(shape)]))
        return shape if desc.elem_offsets == in_order else None

    def __repr__(self):
        return (f"TensorMap({self.functor.name}, dir={self.direction}, "
                f"ranges={self.ranges}, tensor_shape={self.tensor_shape})")
