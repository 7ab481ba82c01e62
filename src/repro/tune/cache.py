"""On-disk autotune cache: measured kernel configs, kernel-namespaced.

One JSON file per registered kernel under ``artifacts/tune/``
(``fused_mlp.json``, ``flash_attention.json``, ``stencil_gather.json``,
...), schema 2:

    {"schema": 2, "kernel": "<name>", "entries": {key: record}}

Keys are kernel-defined problem strings (``KernelSpec.cache_key``; for
fused_mlp the historical ``"<w0-w1-...>|<dtype>|<backend>|b<bucket>"``
format is preserved).  Records carry the measured winner:

    {"params": {"batch_tile": 64}, "us": float, "default_us": float,
     "speedup_x": float, "exact": bool, "swept": [...]}

plus — for fused_mlp back-compat — the winner's params flattened at the
top level (``"batch_tile": 64``).

**Migration:** schema-1 files were a flat ``{key: record}`` dict with no
envelope and per-record ``batch_tile`` instead of ``params``.  The first
load of a legacy file lifts it into the schema-2 layout (adding
``params`` to each record) and rewrites the file atomically, so deployed
caches and the CI ``actions/cache`` entry survive the registry refactor;
a read-only filesystem just keeps serving the migrated view from memory.

Lookups sit on the trace-time hot path (the registry dispatch consults
the cache while the engine's apply is being traced), so the file is
parsed once and memoized; an mtime fingerprint re-reads it when another
process (``tune.sweep`` warm-up, ``dryrun --tune``) rewrites it.  Writes
are atomic (tmp + rename) so a crashed sweep never leaves a torn file.
"""
from __future__ import annotations

import json
import os
import pathlib
import tempfile
import threading
from typing import Dict, Iterable, List, Optional, Sequence

import numpy as np

from repro.dist.buckets import bucket_size

ART = pathlib.Path(__file__).resolve().parents[3] / "artifacts" / "tune"

SCHEMA = 2


def _dtype_name(dtype) -> str:
    """Canonical dtype spelling: jnp.float32 (a type), np.float32, and an
    array's ``.dtype`` must all key identically — str() on the raw type
    yields "<class ...>" and would split the cache between the tuner
    (stores types) and the serving path (looks up array dtypes)."""
    try:
        return str(np.dtype(dtype))
    except TypeError:
        return str(dtype)


def shape_key(widths: Iterable[int], dtype, backend: str, bucket: int) -> str:
    """The fused_mlp cache key (kept byte-identical to the schema-1
    format so legacy entries keep hitting after migration)."""
    w = "-".join(str(int(v)) for v in widths)
    return f"{w}|{_dtype_name(dtype)}|{backend}|b{int(bucket)}"


def batch_keys(widths: Iterable[int], dtype, backend: str,
               batch: int) -> List[str]:
    """A fused_mlp call's lookup chain: the exact batch first — serve-path
    dispatches (and the per-shard batches inside ``fused_mlp_sharded``)
    arrive already bucket-shaped, including the non-power-of-two buckets
    a shard-count rounding produces — then the power-of-two bucket, which
    covers eager calls of arbitrary size."""
    batch = int(batch)
    return [shape_key(widths, dtype, backend, b)
            for b in dict.fromkeys((batch, bucket_size(batch)))]


def _migrate_record(rec: dict) -> dict:
    """Schema-1 records carried the winner as a bare ``batch_tile``."""
    if isinstance(rec, dict) and "params" not in rec and "batch_tile" in rec:
        rec = dict(rec, params={"batch_tile": rec["batch_tile"]})
    return rec


class TuneCache:
    """Persistent measured-config store for one kernel family."""

    def __init__(self, kernel: str = "fused_mlp", path=None):
        self.kernel = kernel
        self.path = pathlib.Path(path) if path is not None else (
            ART / f"{kernel}.json")
        self._lock = threading.Lock()
        self._mem: Dict[str, dict] = {}
        self._fingerprint = None  # (mtime_ns, size) of the last read

    # ---------------------------------------------------------- storage ---
    def _file_fingerprint(self):
        try:
            st = os.stat(self.path)
            return (st.st_mtime_ns, st.st_size)
        except OSError:
            return None

    def _refresh_locked(self) -> None:
        fp = self._file_fingerprint()
        if fp == self._fingerprint:
            return
        self._fingerprint = fp
        if fp is None:
            self._mem = {}
            return
        try:
            data = json.loads(self.path.read_text())
        except (OSError, ValueError):
            # a torn/corrupt cache is a cache miss, never a crash
            self._mem = {}
            return
        if not isinstance(data, dict):
            self._mem = {}
            return
        if data.get("schema") == SCHEMA:
            ent = data.get("entries")
            self._mem = ent if isinstance(ent, dict) else {}
            return
        # schema-1 legacy: a flat {key: record} dict — lift it into the
        # namespaced layout and persist the migration atomically
        self._mem = {k: _migrate_record(v) for k, v in data.items()
                     if isinstance(v, dict)}
        try:
            self._save_locked()
        except OSError:
            pass  # read-only checkout: serve the migrated view from memory

    def _save_locked(self) -> None:
        self.path.parent.mkdir(parents=True, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=str(self.path.parent),
                                   prefix=self.path.name, suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump({"schema": SCHEMA, "kernel": self.kernel,
                           "entries": self._mem}, f, indent=1,
                          sort_keys=True)
            os.replace(tmp, self.path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        self._fingerprint = self._file_fingerprint()

    # -------------------------------------------------------------- api ---
    def get(self, key: str) -> Optional[dict]:
        """Record for a kernel-defined cache key, or None."""
        with self._lock:
            self._refresh_locked()
            return self._mem.get(key)

    def put(self, key: str, record: dict) -> None:
        with self._lock:
            self._refresh_locked()  # merge with concurrent writers' entries
            self._mem[key] = record
            self._save_locked()

    def lookup(self, widths, dtype, backend: str,
               bucket: int) -> Optional[dict]:
        """fused_mlp-shaped convenience lookup (legacy API)."""
        return self.get(shape_key(widths, dtype, backend, bucket))

    def store(self, widths, dtype, backend: str, bucket: int,
              record: dict) -> None:
        self.put(shape_key(widths, dtype, backend, bucket), record)

    def entries(self) -> Dict[str, dict]:
        with self._lock:
            self._refresh_locked()
            return dict(self._mem)

    def clear(self) -> None:
        with self._lock:
            self._mem = {}
            if self.path.exists():
                self.path.unlink()
            self._fingerprint = None


# process-wide default caches (what the serving hot path consults)
_default: Dict[str, TuneCache] = {}
_default_lock = threading.Lock()


def default_cache(kernel: str = "fused_mlp") -> TuneCache:
    with _default_lock:
        c = _default.get(kernel)
        if c is None:
            c = _default[kernel] = TuneCache(kernel)
        return c


def _record_params(rec: Optional[dict]) -> Optional[Dict[str, int]]:
    """Validated winner params of a record, or None.

    Only validated winners are served — the kernel must never pick up a
    config that failed the oracle check.  Schema-1 records that reached
    memory without migration still resolve via ``batch_tile``.
    """
    if not isinstance(rec, dict) or not rec.get("exact", False):
        return None
    params = rec.get("params")
    if params is None and "batch_tile" in rec:
        params = {"batch_tile": rec["batch_tile"]}
    if not isinstance(params, dict) or not params:
        return None
    try:
        return {k: int(v) for k, v in params.items()}
    except (TypeError, ValueError):
        return None


def best_params(kernel: str, keys: Sequence[str]) -> Optional[Dict[str, int]]:
    """First validated winner along ``keys`` (ordered lookup fallbacks,
    e.g. fused_mlp's exact-batch-then-pow2-bucket chain), or None.

    Outcomes publish to the obs metrics layer: sustained misses mean the
    serving shapes have drifted away from what the sweep tuned, and the
    per-key miss counter is the signal the planned online re-sweep will
    trigger from.
    """
    from repro.obs import metrics as _m
    cache = default_cache(kernel)
    for key in keys:
        params = _record_params(cache.get(key))
        if params is not None:
            _m.counter("repro_tune_cache_lookups_total",
                       "tune-cache lookups by outcome",
                       ("kernel", "outcome")).inc(
                1, kernel=kernel, outcome="hit")
            return params
    _m.counter("repro_tune_cache_lookups_total",
               "tune-cache lookups by outcome",
               ("kernel", "outcome")).inc(1, kernel=kernel, outcome="miss")
    if keys:
        # the most specific key is the serving shape that went untuned —
        # exactly what a drift-triggered re-sweep needs to know
        _m.counter("repro_tune_cache_miss_keys_total",
                   "tune-cache lookup chains that missed, by leading key",
                   ("kernel", "key")).inc(1, kernel=kernel, key=keys[0])
    return None


def best_tile(widths, dtype, backend: str, batch: int) -> Optional[int]:
    """Tuned ``batch_tile`` for a fused_mlp call (looked up along
    :func:`batch_keys`), or None when untuned."""
    params = best_params("fused_mlp", batch_keys(widths, dtype, backend,
                                                 batch))
    if params is None:
        return None
    tile = params.get("batch_tile")
    return int(tile) if tile and tile > 0 else None
