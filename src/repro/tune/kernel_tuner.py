"""Measurement-driven autotuning for every registered Pallas kernel.

Each kernel declares its tunables via a
:class:`repro.kernels.registry.KernelSpec` (candidate ladders, VMEM cost
model, jitted ref oracle); :func:`sweep` measures every candidate that
fits the device's VMEM budget, validates each against the oracle
(bit-for-bit where the spec declares ``tol=None`` — fused_mlp,
stencil_gather — or to the spec's tolerance where the block structure
legitimately changes rounding, e.g. flash attention's online softmax),
and persists winners in the kernel-namespaced on-disk
:class:`repro.tune.cache.TuneCache` the registry dispatch consults at
trace time.

Entry points:

  * :func:`sweep` — one (kernel, problem) cell: measure, pick, store.
  * :func:`sweep_fused_mlp` — the historical fused_mlp-shaped wrapper.
  * :func:`autotune` — warm-up over the shapes an engine bundle serves
    (the buckets ``InferenceEngine.apply_batched`` can produce), or over
    explicit widths.  Call it once at deploy; the cache makes it free
    afterwards.
  * :func:`autotune_registered` — pre-populate every registered kernel's
    representative problems (what ``dryrun --tune`` runs at deploy).

Measurements run whatever path the op would take on this backend: the
compiled Pallas kernel on TPU, interpret mode elsewhere (slower in
absolute terms, but the grid/tile tradeoff ranks the same way: fewer,
fatter tiles amortize per-step overhead until VMEM or padding waste
pushes back).
"""
from __future__ import annotations

import time
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist.buckets import bucket_for
from repro.kernels import registry
from repro.kernels.fused_mlp.ops import DEFAULT_TILE, candidate_tiles
from repro.tune.cache import TuneCache, _dtype_name, default_cache

__all__ = ["DEFAULT_TILE", "autotune", "autotune_registered",
           "candidate_tiles", "serve_buckets", "sweep", "sweep_fused_mlp",
           "widths_from_spec"]


def widths_from_spec(spec: dict) -> Optional[List[int]]:
    """Dense widths of a pure-MLP bundle spec, or None if not pure-MLP.

    Mirrors the adapter logic in ``fused_mlp_from_spec``: flatten folds
    trailing dims into the feature dim, acts don't change widths.
    """
    in_shape = spec.get("in_shape") or ()
    feat = 1
    for d in in_shape[1:]:
        feat *= int(d)
    widths = [feat]
    for layer in spec.get("layers", ()):
        kind = layer.get("kind")
        if kind == "dense":
            widths.append(int(layer["features"]))
        elif kind in ("act", "flatten"):
            continue
        else:
            return None  # conv/pool/... : not the fused kernel's shape
    return widths if len(widths) > 1 else None


def _acts_for(n_layers: int, acts=None) -> tuple:
    if acts is not None:
        return tuple(acts)
    return ("relu",) * (n_layers - 1) + ("identity",)


def _measure_us(fn, reps: int, warmup: int) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn())
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)) * 1e6


def _outputs_match(spec, out, ref) -> bool:
    """Spec-declared comparison: bit-identity unless the spec carries a
    tolerance (an output may be a pytree, e.g. rwkv6's (o, state))."""
    a_leaves = jax.tree.leaves(out)
    b_leaves = jax.tree.leaves(ref)
    if len(a_leaves) != len(b_leaves):
        return False
    for a, b in zip(a_leaves, b_leaves):
        a, b = np.asarray(a), np.asarray(b)
        if spec.tol is None:
            if not np.array_equal(a, b):
                return False
        else:
            rtol, atol = spec.tol
            if not np.allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32),
                               rtol=rtol, atol=atol):
                return False
    return True


def sweep(kernel, problem: dict, *, reps: int = 5, warmup: int = 2,
          cache: Optional[TuneCache] = None, seed: int = 0,
          force: bool = False) -> dict:
    """Measure every candidate config for one (kernel, problem) cell.

    Returns (and persists) the record the registry dispatch will
    consult.  Candidates whose output fails the spec's oracle check are
    disqualified — a tuned config must never change serving results.
    The spec's defaults are always ``candidates[0]``, so the winner's
    ``speedup_x`` is measured against the exact config dispatch would
    use untuned.
    """
    spec = registry.get_spec(kernel) if isinstance(kernel, str) else kernel
    problem = dict(problem)
    problem["dtype"] = _dtype_name(problem.get("dtype", "float32"))
    cache = cache or default_cache(spec.name)
    backend = jax.default_backend()
    key = spec.cache_key(problem, backend)
    if not force:
        cached = cache.get(key)
        if cached is not None:
            return cached

    rng = np.random.default_rng(seed)
    arrays = spec.make_call(problem, rng)
    # jitted oracle: the serving path always runs compiled, and XLA's
    # eager-vs-compiled ops round differently — compare like with like
    ref = jax.jit(lambda *a: spec.ref_call(problem, a))(*arrays)
    ref = jax.tree.map(np.asarray, ref)
    interpret = backend != "tpu"
    defaults = spec.defaults()

    swept = []
    for params in spec.candidates(problem):
        fn = jax.jit(lambda *a, _p=dict(params): spec.run_call(
            problem, a, _p, interpret=interpret))
        entry = {"params": dict(params)}
        try:
            out = fn(*arrays)
            entry["exact"] = _outputs_match(spec, out, ref)
            entry["us"] = round(
                _measure_us(lambda: fn(*arrays), reps, warmup), 2)
        except Exception as e:  # a config the backend rejects is skipped
            entry.update(us=None, exact=False,
                         error=f"{type(e).__name__}: {e}"[:200])
        swept.append(entry)

    valid = [s for s in swept if s["exact"]]
    default = next((s for s in swept
                    if s["params"] == defaults and s["us"]), None)
    if valid:
        best = min(valid, key=lambda s: s["us"])
        default_us = default["us"] if default else best["us"]
        rec = {"params": dict(best["params"]), "us": best["us"],
               "default_us": default_us,
               "speedup_x": round(default_us / best["us"], 3)
               if best["us"] else 1.0,
               "exact": True, "backend": backend, "swept": swept,
               "tuned_at": time.time()}
    else:  # nothing validated: record the failure so we don't re-sweep,
        # but the dispatch path will refuse to serve it (exact=False)
        rec = {"params": dict(defaults), "us": None,
               "default_us": default["us"] if default else None,
               "speedup_x": 1.0, "exact": False, "backend": backend,
               "swept": swept, "tuned_at": time.time()}
    rec.update(rec["params"])  # flattened winner params (legacy readers)
    cache.put(key, rec)
    return rec


def sweep_fused_mlp(widths: Sequence[int], bucket: int, *,
                    dtype=jnp.float32, acts=None, reps: int = 5,
                    warmup: int = 2, cache: Optional[TuneCache] = None,
                    seed: int = 0, force: bool = False) -> dict:
    """One fused_mlp (widths, bucket) cell through the generic sweep."""
    widths = tuple(int(w) for w in widths)
    problem = {"widths": widths, "acts": _acts_for(len(widths) - 1, acts),
               "batch": int(bucket), "dtype": _dtype_name(dtype)}
    return sweep("fused_mlp", problem, reps=reps, warmup=warmup,
                 cache=cache, seed=seed, force=force)


def serve_buckets(min_bucket: int = 8, max_batch_rows: int = 1024,
                  n_shards: int = 1) -> List[int]:
    """The batch buckets ``apply_batched`` can actually dispatch for a
    flush policy: powers of two from the (shard-raised) floor up to the
    bucket covering max_batch_rows."""
    lo = bucket_for(1, min_bucket, n_shards)
    hi = bucket_for(max_batch_rows, min_bucket, n_shards)
    out, b = [], lo
    while b < hi:
        out.append(b)
        b *= 2
    out.append(hi)
    return out


def autotune(target, buckets: Optional[Sequence[int]] = None, *,
             dtype=jnp.float32, policy=None, n_shards: int = 1,
             reps: int = 5, warmup: int = 2,
             cache: Optional[TuneCache] = None,
             force: bool = False, verbose: bool = False) -> List[dict]:
    """Warm the fused_mlp tune cache for everything an engine will serve.

    ``target`` is a bundle path (widths derived from its spec.json) or
    an explicit widths sequence.  ``buckets`` defaults to the serve-path
    buckets for ``policy`` (a FlushPolicy, or the default policy).
    Returns the per-bucket records; after this, every
    ``InferenceEngine.apply_batched`` shape hits a tuned tile.
    """
    if isinstance(target, (list, tuple)):
        widths = [int(w) for w in target]
    else:
        import json
        import pathlib
        spec = json.loads(
            (pathlib.Path(str(target)) / "spec.json").read_text())
        widths = widths_from_spec(spec)
        if widths is None:
            raise ValueError(f"bundle {target!r} is not a pure MLP; "
                             "fused_mlp autotuning does not apply")
    if buckets is None:
        if policy is None:
            from repro.serve.queue import FlushPolicy
            policy = FlushPolicy()
        buckets = serve_buckets(policy.min_bucket, policy.max_batch_rows,
                                n_shards)
    buckets = set(int(b) for b in buckets)
    if n_shards > 1:
        # under shard_map the kernel sees the *per-shard* batch; warm
        # those shapes too so the sharded path hits tuned tiles
        buckets |= {b // n_shards for b in buckets
                    if b % n_shards == 0 and b // n_shards >= 1}
    recs = []
    for b in sorted(buckets):
        rec = sweep_fused_mlp(widths, b, dtype=dtype, reps=reps,
                              warmup=warmup, cache=cache, force=force)
        recs.append(rec)
        if verbose:
            print(f"[tune] widths={widths} bucket={b}: "
                  f"tile={rec['params'].get('batch_tile')} "
                  f"{rec['us']}us vs default {rec['default_us']}us "
                  f"({rec['speedup_x']}x) exact={rec['exact']}",
                  flush=True)
    return recs


def autotune_registered(kernels: Optional[Sequence[str]] = None, *,
                        reps: int = 5, warmup: int = 2,
                        force: bool = False,
                        verbose: bool = False) -> List[dict]:
    """Pre-populate every registered kernel's representative problems.

    Kernels with no tunable params (rwkv6_chunk) are skipped — there is
    nothing to pick.  ``dryrun --tune`` calls this after the
    bundle-aware fused_mlp warm-up so a deploy tunes the whole kernel
    surface, not just the surrogate MLP.
    """
    recs = []
    names = list(kernels) if kernels else [
        s.name for s in registry.all_specs()]
    for name in names:
        spec = registry.get_spec(name)
        if not spec.params:
            continue
        for problem in spec.default_problems:
            rec = sweep(spec, problem, reps=reps, warmup=warmup, force=force)
            recs.append(rec)
            if verbose:
                print(f"[tune] {spec.name} {spec.cache_key(dict(problem), jax.default_backend())}: "
                      f"params={rec['params']} {rec['us']}us vs default "
                      f"{rec['default_us']}us ({rec['speedup_x']}x) "
                      f"exact={rec['exact']}", flush=True)
    return recs
