"""Shared kernel registry: one declaration per Pallas kernel, one dispatcher.

Every kernel package used to hand-roll the same wrapper four times: check
``jax.default_backend()``, honor ``force_kernel``, run the Pallas kernel
(interpret mode off-TPU) or the jnp oracle, and — for fused_mlp only —
consult the autotune cache.  This module factors that control plane into
a :class:`KernelSpec` each package registers once:

  * **tunable params** with candidate ladders (``batch_tile`` for
    fused_mlp, ``block_q``/``block_kv`` for flash attention,
    ``block_h``/``block_w`` for stencil gather; rwkv6 has none — its
    grid is fixed by the problem shape);
  * a **VMEM cost model** (``fits``) the dispatcher and the tuner share,
    budgeted against the *actual device* (:func:`device_vmem_budget`)
    rather than a hardcoded constant;
  * the **jitted ref oracle** every tuned candidate is validated against
    (``tol=None`` demands bit-identity; flash attention declares a f32
    tolerance because the online-softmax block order legitimately
    changes rounding);
  * an **interpret fallback**: off-TPU the kernel path runs only under
    ``force_kernel`` (Pallas interpret mode), everything else takes the
    oracle.

The four ``*_op`` wrappers become thin shims over :func:`dispatch`,
which resolves tunable params at trace time: explicit caller overrides
win, then validated winners from the kernel-namespaced
:class:`repro.tune.cache.TuneCache`, then the spec defaults — any value
is re-checked against the cost model so a cache written on a roomier
device can never overflow this one.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax

from repro.obs import TRACER
from repro.obs import metrics as _m

_DISPATCHES = _m.counter(
    "repro_kernel_dispatch_total",
    "kernel dispatches by resolved-params provenance and precision tier",
    ("kernel", "provenance", "tier"))

# ------------------------------------------------------------ VMEM budget ---
# Budget the kernels' cost models (``fits``) may spend, by ``device_kind``.
# The models count each weight once and each activation tile twice; the
# compiler double-buffers every input.  For "TPU v5 lite" (v5e) the
# compiler accepts the f32 fused MLP up to 5-1920-1920-1 at tile 128 and
# refuses 5-2048-2048-1 at tile 8, which fits a 32 MiB kernel limit; 12
# MiB of modelled bytes stays inside it with room for Mosaic's own
# scratch.  ``tests/test_tpu_compile.py`` compiles the largest net each
# model admits at this budget.  A kind missing here is an error: a guess
# would only show up as a compile refusal on the chip.
_VMEM_BUDGET_BY_KIND = {"TPU v5 lite": 12 * 2 ** 20}
_OFF_TPU_BUDGET = 12 * 2 ** 20  # interpret mode: keep the old constant


def _vmem_budget_for_kind(device_kind: str) -> int:
    """Usable VMEM budget for a TPU ``device_kind`` string ("TPU v5
    lite", ...); raises ``ValueError`` for a kind with no entry."""
    try:
        return _VMEM_BUDGET_BY_KIND[device_kind]
    except KeyError:
        raise ValueError(
            f"no VMEM budget for TPU device kind {device_kind!r}; known: "
            f"{sorted(_VMEM_BUDGET_BY_KIND)}") from None


@functools.lru_cache(maxsize=None)
def _device_vmem_budget_cached(backend: str, device_kind: str) -> int:
    if backend != "tpu":
        return _OFF_TPU_BUDGET
    return _vmem_budget_for_kind(device_kind)


def device_vmem_budget() -> int:
    """VMEM byte budget of the backend this process dispatches to.

    Keyed by the device's kind (VMEM size is a property of the TPU
    generation, not exposed by ``memory_stats()``, which reports HBM);
    off-TPU — where kernels only ever run in interpret mode — the old
    12 MiB constant is kept so tuner decisions stay deterministic in CI.
    """
    backend = jax.default_backend()
    kind = jax.devices()[0].device_kind if backend == "tpu" else ""
    return _device_vmem_budget_cached(backend, kind)


# ------------------------------------------------------------- KernelSpec ---
@dataclasses.dataclass(frozen=True)
class TunableParam:
    """One tunable kernel parameter and its sweep ladder."""

    name: str
    default: int
    ladder: Tuple[int, ...]


@dataclasses.dataclass
class KernelSpec:
    """Declaration the registry dispatches and the tuner sweeps.

    The call protocol splits a kernel invocation into a static
    ``problem`` dict (shapes, dtype name, config like ``acts`` or
    ``causal`` — everything that keys the tune cache and synthesizes
    sweep inputs) and the positional ``arrays`` tuple:

      * ``inspect(*args, **kwargs) -> (problem, arrays)`` — from an op
        call (arrays may be tracers: only shape/dtype are read);
      * ``run_call(problem, arrays, params, interpret)`` — the Pallas
        kernel with resolved tunables;
      * ``ref_call(problem, arrays)`` — the jnp oracle;
      * ``make_call(problem, rng) -> arrays`` — synthetic inputs for a
        sweep of the same problem;
      * ``cache_key(problem, backend) -> str`` — tune-cache key; and
        ``cache_keys`` (optional) for ordered lookup fallbacks (e.g.
        fused_mlp tries the exact batch before the pow2 bucket);
      * ``candidates(problem) -> [param dicts]`` — defaults first;
      * ``fits(problem, params, budget=None) -> bool`` — VMEM cost
        model (None budget = :func:`device_vmem_budget`);
      * ``supports(problem) -> bool`` — whether the kernel path applies
        at all (fused_mlp: the net must fit VMEM);
      * ``tol`` — (rtol, atol) validation tolerance, None = bit-exact;
      * ``tier`` — precision tier ("f32" default, "int8" for the
        quantized variants).  An int8 variant validates against its own
        int8-*simulating* oracle at a tolerance sized to one requant
        step; accuracy-vs-f32 is the quant gate's concern
        (:mod:`repro.quant.gate`), measured on real calibration rows.
    """

    name: str
    params: Tuple[TunableParam, ...]
    inspect: Callable
    run_call: Callable
    ref_call: Callable
    make_call: Callable
    cache_key: Callable
    candidates: Callable
    fits: Optional[Callable] = None
    supports: Optional[Callable] = None
    cache_keys: Optional[Callable] = None
    tol: Optional[Tuple[float, float]] = None
    tier: str = "f32"
    default_problems: Tuple[dict, ...] = ()

    def defaults(self) -> Dict[str, int]:
        return {p.name: p.default for p in self.params}

    def lookup_keys(self, problem: dict, backend: str) -> List[str]:
        if self.cache_keys is not None:
            return list(self.cache_keys(problem, backend))
        return [self.cache_key(problem, backend)]


# --------------------------------------------------------------- registry ---
_SPECS: Dict[str, KernelSpec] = {}


def register(spec: KernelSpec) -> KernelSpec:
    _SPECS[spec.name] = spec
    return spec


def get_spec(name: str) -> KernelSpec:
    ensure_builtin_specs()
    try:
        return _SPECS[name]
    except KeyError:
        raise KeyError(f"unknown kernel {name!r}; registered: "
                       f"{sorted(_SPECS)}") from None


def all_specs() -> List[KernelSpec]:
    ensure_builtin_specs()
    return [_SPECS[k] for k in sorted(_SPECS)]


_BUILTIN_OPS = ("repro.kernels.fused_mlp.ops",
                "repro.kernels.fused_mlp.int8",
                "repro.kernels.flash_attention.ops",
                "repro.kernels.flash_attention.int8",
                "repro.kernels.stencil_gather.ops",
                "repro.kernels.rwkv6_chunk.ops")


def ensure_builtin_specs() -> None:
    """Import the kernel packages so their specs self-register."""
    import importlib
    for mod in _BUILTIN_OPS:
        importlib.import_module(mod)


# --------------------------------------------------------------- dispatch ---
def tuned_params(spec: KernelSpec, problem: dict) -> Dict[str, int]:
    """Validated tune-cache winner for ``problem``, or {} when untuned.

    Runs at trace time (the op shims call it while the engine's apply is
    being traced).  The cache reads a missing or corrupt file as a miss,
    so the defaults apply; any other error is a fault and propagates.
    """
    if not spec.params:
        return {}
    from repro.tune.cache import best_params
    return best_params(spec.name,
                       spec.lookup_keys(problem, jax.default_backend())) or {}


def resolve_params_info(spec: KernelSpec, problem: dict,
                        overrides: Optional[dict] = None
                        ) -> Tuple[Dict[str, int], str]:
    """Merge explicit overrides > tuned winners > spec defaults, then
    re-check the result against the VMEM cost model — a tuned (or
    caller-supplied) config that would overflow *this* device's budget
    falls back to the defaults.

    Returns ``(params, provenance)``; the provenance string (one of
    ``explicit``/``tuned``/``default``/``default:vmem-fallback``, the
    first two mixed as ``explicit+tuned``) is what the obs layer records
    per dispatch, so a trace shows whether a kernel ran its sweep winner
    or silently fell back.
    """
    overrides = {k: v for k, v in (overrides or {}).items() if v is not None}
    tuned = None
    params: Dict[str, int] = {}
    sources = set()
    for p in spec.params:
        if p.name in overrides:
            params[p.name] = int(overrides[p.name])
            sources.add("explicit")
            continue
        if tuned is None:
            tuned = tuned_params(spec, problem)
        if p.name in tuned:
            params[p.name] = int(tuned[p.name])
            sources.add("tuned")
        else:
            params[p.name] = p.default
            sources.add("default")
    provenance = "+".join(s for s in ("explicit", "tuned", "default")
                          if s in sources) or "default"
    if spec.fits is not None and params and not spec.fits(problem, params):
        params = spec.defaults()
        provenance = "default:vmem-fallback"
    return params, provenance


def resolve_params(spec: KernelSpec, problem: dict,
                   overrides: Optional[dict] = None) -> Dict[str, int]:
    return resolve_params_info(spec, problem, overrides)[0]


def quantized_variant(spec: KernelSpec) -> Optional[KernelSpec]:
    """The registered int8 twin of a base spec (``<name>_int8``), or
    None when the kernel has no quantized variant."""
    ensure_builtin_specs()
    return _SPECS.get(spec.name + "_int8")


def select_tier_spec(spec: KernelSpec, problem: Optional[dict] = None, *,
                     gated: bool, explicit: Optional[str] = None
                     ) -> Tuple[KernelSpec, str]:
    """Precision-tier resolution for one dispatch site.

    Extends the param-provenance order to tiers — **explicit >
    tuned-quantized-if-gated > tuned > default**:

      * ``explicit`` pins the tier: ``"f32"`` (REPRO_QUANT=never) always
        serves the base spec, ``"int8"`` (REPRO_QUANT=force, the CI
        fail-path drill) serves the variant whenever it exists and
        supports the problem — the gate verdict is bypassed;
      * otherwise the int8 variant serves only when the bundle's
        accuracy gate passed (``gated=True``) *and* the variant's own
        ``supports`` accepts the problem;
      * anything else falls through to the base spec, whose params then
        resolve tuned-before-default as always.

    Returns ``(spec_to_dispatch, tier)``.
    """
    if explicit == "f32":
        return spec, spec.tier
    q = quantized_variant(spec)
    if q is None or (explicit != "int8" and not gated):
        return spec, spec.tier
    if problem is not None and q.supports is not None \
            and not q.supports(problem):
        return spec, spec.tier
    return q, q.tier


def dispatch(spec: KernelSpec, problem: dict, arrays: tuple, *,
             force_kernel: bool = False, overrides: Optional[dict] = None):
    """The shared on-TPU / ``force_kernel`` / interpret-fallback branch.

    On TPU (or under ``force_kernel``, which runs the Pallas kernel in
    interpret mode off-TPU) the kernel path runs with trace-time
    resolved tunables; otherwise the jnp oracle serves the call.
    """
    from repro.resilience.faults import FAULTS
    if FAULTS.enabled:
        # dispatch runs at jit trace time, so a raise here surfaces as a
        # compile failure on the serve path (once per shape, not per call)
        FAULTS.fire("kernel.dispatch", key=spec.name)
    on_tpu = jax.default_backend() == "tpu"
    use_kernel = force_kernel or on_tpu
    if use_kernel and spec.supports is not None:
        use_kernel = bool(spec.supports(problem))
    if not use_kernel:
        _DISPATCHES.inc(1, kernel=spec.name, provenance="ref",
                        tier=spec.tier)
        if TRACER.enabled:
            TRACER.instant("kernel.dispatch", cat="kernel",
                           args={"kernel": spec.name, "path": "ref",
                                 "tier": spec.tier})
        return spec.ref_call(problem, arrays)
    params, provenance = resolve_params_info(spec, problem, overrides)
    # dispatch() runs at jit trace time, so this lands once per compiled
    # shape, not once per serving call — an instant, not a span, because
    # kernel wall time belongs to XLA's own profile
    _DISPATCHES.inc(1, kernel=spec.name, provenance=provenance,
                    tier=spec.tier)
    if TRACER.enabled:
        TRACER.instant("kernel.dispatch", cat="kernel",
                       args={"kernel": spec.name, "params": dict(params),
                             "provenance": provenance, "tier": spec.tier,
                             "interpret": not on_tpu})
    return spec.run_call(problem, arrays, params, interpret=not on_tpu)


# ------------------------------------------------------------ shared bits ---
def round_up(n: int, m: int) -> int:
    return n + (-n % m)


def tile_bytes(rows: int, cols: int, dtype_bytes: int = 4) -> int:
    """Bytes one [rows, cols] buffer occupies in VMEM after (sublane,
    lane) register-layout padding — (8, 128) for f32."""
    sublane = max(8 * 4 // dtype_bytes, 8)
    return round_up(rows, sublane) * round_up(cols, 128) * dtype_bytes


def ladder_candidates(spec_params: Sequence[TunableParam],
                      clip: Optional[Dict[str, int]] = None,
                      fits: Optional[Callable] = None) -> List[dict]:
    """Cartesian product of the params' ladders, defaults-first, each
    axis clipped to ``clip[name]`` (inclusive), filtered by ``fits``.

    Defaults-first matters: the sweep measures ``candidates[0]`` as the
    baseline every winner's speedup is reported against, and ties keep
    the default.
    """
    clip = clip or {}
    axes: List[List[int]] = []
    for p in spec_params:
        hi = clip.get(p.name)
        vals = [p.default]
        for v in p.ladder:
            if v == p.default or (hi is not None and v > hi):
                continue
            vals.append(int(v))
        axes.append(vals)
    combos: List[dict] = [{}]
    for p, vals in zip(spec_params, axes):
        combos = [dict(c, **{p.name: v}) for c in combos for v in vals]
    # the all-defaults combo is first by construction; drop dupes, keep order
    seen, out = set(), []
    for c in combos:
        key = tuple(sorted(c.items()))
        if key in seen:
            continue
        seen.add(key)
        if fits is None or fits(c):
            out.append(c)
    return out
