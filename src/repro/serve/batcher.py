"""Batcher: coalesce queued region requests into bucket-shaped mega-batches.

The queue hands the batcher a FIFO run of requests for one bundle path;
the batcher concatenates their rows, dispatches them through the
engine's :meth:`InferenceEngine.apply_batched` (which pads to the
power-of-two bucket, places the batch over the ``data`` axis of the
active mesh, and slices the padding back off), then scatters per-request
row slices into the callers' futures.

Row-wise surrogates make this exact rather than approximate: each output
row depends only on its input row, so a request's rows come back
bit-identical to what a synchronous ``MLRegion._infer`` of the same
inputs produces, regardless of which mega-batch they rode in (asserted
by tests/test_serve.py).
"""
from __future__ import annotations

import itertools
import time
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import TRACER
from repro.obs import metrics as _metrics
from repro.resilience.breaker import BREAKERS
from repro.resilience.faults import FAULTS
from repro.resilience.retry import DEFAULT_RETRY, RetryPolicy
from repro.serve.scratch import ScratchPool
from repro.serve.stats import ServeStats

# process-wide dispatch sequence: ties a request's spans to the batch
# that served it in a trace without threading ids through call sites
_BATCH_IDS = itertools.count()

_RETRIES = _metrics.counter(
    "repro_resilience_retries_total",
    "dispatch attempts retried after a transient failure", ("key",))
_SPLITS = _metrics.counter(
    "repro_resilience_split_retries_total",
    "batches bisected to isolate a poisoned request", ("key",))
_NONFINITE = _metrics.counter(
    "repro_resilience_nonfinite_total",
    "output rows screened as NaN/Inf before scatter", ("key",))


class NonFiniteOutput(RuntimeError):
    """A request's output rows contained NaN/Inf and were withheld.

    Screened before scatter: non-finite surrogate output is a failure
    (the caller falls back to the accurate path via its future's
    exception), never a silently returned value.
    """

    def __init__(self, key: str, rows: int):
        super().__init__(f"non-finite surrogate output for {key!r} "
                         f"({rows} rows withheld)")
        self.key, self.rows = key, rows


def bucket_size(n: int, min_bucket: int = 8) -> int:
    """Smallest power-of-two >= max(n, min_bucket).

    Power-of-two buckets bound the jit cache to log2(max batch) shapes
    per sharding context.
    """
    b = max(int(min_bucket), 1)
    while b < n:
        b <<= 1
    return b


def bucket_for(n: int, min_bucket: int, n_shards: int = 1) -> int:
    """Dispatch bucket: power-of-two floor, rounded up to a multiple of
    the data-shard count — a bucket smaller than (or not dividing) the
    shard count would make `spec_for` drop the data axis and silently
    replicate the whole batch on every device.
    """
    b = bucket_size(n, max(min_bucket, n_shards))
    if n_shards > 1 and b % n_shards:
        b += -b % n_shards
    return b


class Batcher:
    """Stateless dispatch: concat -> padded apply -> scatter.

    ``engine_for`` maps a queue key (bundle path) to an engine-like
    object exposing ``apply_batched``; the default resolves through the
    process-wide :class:`InferenceEngine` cache, so retrained bundles
    are picked up between batches exactly like synchronous serving.
    """

    def __init__(self, *, min_bucket: int = 8,
                 engine_for: Optional[Callable] = None,
                 scratch: Optional[ScratchPool] = None,
                 retry: Optional[RetryPolicy] = None):
        self.min_bucket = min_bucket
        self.scratch = scratch or ScratchPool()
        self.retry = retry or DEFAULT_RETRY
        # ServeQueue attaches its TenantBoard here so per-request
        # outcomes (served rows + latencies, drops) land on the tenant
        # that submitted them; None = tenancy-free queue, zero overhead
        self.tenancy = None
        if engine_for is None:
            def engine_for(key):
                from repro.core.engine import InferenceEngine
                return InferenceEngine.get(key)
        self._engine_for = engine_for

    @staticmethod
    def _device_resident(x) -> bool:
        """True when ``x`` is a committed, fully-addressable jax.Array on
        a non-host backend.  There the mega-batch should assemble with an
        on-device concat — the host scratch gather would be a D2H
        round-trip per request followed by one H2D of the whole batch.
        On CPU the pooled host gather IS the fast path (measured in PR 3),
        so plain numpy inputs and CPU arrays keep using it."""
        if not isinstance(x, jax.Array):
            return False
        try:
            if not x.is_fully_addressable:
                return False
            dev = next(iter(x.devices()))
        except Exception:
            return False
        return dev.platform != "cpu"

    def _gather(self, requests, n: int, bucket: int):
        """Assemble the mega-batch.

        A lone request rides through untouched (the engine pads it).
        Device-resident inputs concatenate on device — no D2H round-trip;
        the concat output is batcher-owned and safe to donate.  Host
        inputs gather into a pooled scratch buffer already padded to the
        bucket, so the engine skips its own concat+pad.
        """
        if len(requests) == 1:
            return requests[0].x, False
        feat = tuple(requests[0].x.shape[1:])
        if all(self._device_resident(r.x) for r in requests):
            parts = [r.x for r in requests]
            if bucket > n:
                parts.append(jnp.zeros((bucket - n,) + feat,
                                       requests[0].x.dtype))
            return jnp.concatenate(parts, axis=0), True
        buf = self.scratch.take((bucket,) + feat,
                                np.dtype(requests[0].x.dtype))
        off = 0
        for r in requests:
            buf[off:off + r.n] = np.asarray(r.x)
            off += r.n
        buf[off:] = 0  # zero padding: same rows a jnp pad would produce
        return jnp.asarray(buf), True

    def _to_host(self, Y, *, rows=None) -> np.ndarray:
        """One device->host gather of rows ``[rows[0], rows[1])`` (default:
        all) landed in a pooled scratch buffer (per-shard zero-copy reads
        on host-mesh arrays).  Futures get row views of the buffer; the
        pool will not reuse it while any view is alive.

        Only *addressable* shards can be read, and that is now enforced:
        if the local shards do not cover every requested element, this
        raises instead of returning a buffer whose missing rows are
        uninitialized pool memory.  Multi-process dispatches must either
        ask only for the rows this host owns (``dispatch_pod`` passes its
        slab range) or gather explicitly before landing.
        """
        try:
            shards = list(Y.addressable_shards)
        except Exception:  # plain numpy/eager arrays: everything is local
            arr = np.asarray(Y)
            return arr if rows is None else arr[rows[0]:rows[1]]
        n_rows = int(Y.shape[0])
        start, stop = (0, n_rows) if rows is None else \
            (int(rows[0]), int(rows[1]))
        out = self.scratch.take((stop - start,) + tuple(Y.shape[1:]),
                                np.dtype(Y.dtype))
        filled = 0
        for s in shards:
            if getattr(s, "replica_id", 0) != 0:
                continue
            idx = tuple(s.index)
            i0 = idx[0] if idx else slice(None)
            s0 = 0 if i0.start is None else int(i0.start)
            e0 = n_rows if i0.stop is None else int(i0.stop)
            lo, hi = max(s0, start), min(e0, stop)
            if lo >= hi:
                continue
            block = np.asarray(s.data)[lo - s0:hi - s0]
            out[(slice(lo - start, hi - start),) + idx[1:]] = block
            filled += block.size
        if filled != out.size:
            raise RuntimeError(
                f"_to_host: addressable shards cover {filled}/{out.size} "
                f"elements of rows [{start}, {stop}) of a {Y.shape} "
                f"output — the rest is owned by other processes.  A "
                f"multi-process dispatch must read only its own slab "
                f"(ServeQueue.pod_flush / Batcher.dispatch_pod) or "
                f"gather the array before landing it.")
        return out

    @staticmethod
    def _request_ctx(requests):
        """Install the submitters' ShardCtx around the batched apply.

        Sharding contexts are thread-local; a deadline/max-batch flush
        runs on the dispatcher thread, which would otherwise serve the
        mega-batch unsharded.  The submit-time ctx governs even when it
        is None (a no-mesh submit flushed inline from inside someone
        else's ``use_mesh`` must not pick up that ambient mesh, or the
        engine's bucket would diverge from the one stats recorded).
        Requests queued under different meshes never coalesce
        meaningfully, so the first request's ctx speaks for the batch
        (they arrived FIFO on one key).
        """
        from repro.dist.sharding import use_mesh
        ctx = requests[0].ctx
        if ctx is None:
            return use_mesh(None)
        return use_mesh(ctx.mesh, ctx.multi_pod)

    def _fail_all(self, requests, exc, stats, reason, busy_s, *,
                  record_breaker_key=None):
        for r in requests:
            r.future.set_exception(exc)
        self._note_dropped(requests)
        stats.on_failure(requests=len(requests),
                         rows=sum(r.n for r in requests), reason=reason,
                         busy_s=busy_s)
        if record_breaker_key is not None:
            BREAKERS.record_failure(record_breaker_key)

    # ------------------------------------------------ tenant attribution ---
    def _note_dropped(self, requests) -> None:
        board = self.tenancy
        if board is None or not requests:
            return
        agg = {}
        for r in requests:
            t = getattr(r, "tenant", None)
            if t is not None:
                c = agg.setdefault(t, [0, 0])
                c[0] += 1
                c[1] += r.n
        for t, (n_req, n_rows) in agg.items():
            board.on_dropped(t, n_req, n_rows)

    def _note_served(self, requests, bad, lats) -> None:
        """Attribute a scattered batch's outcomes per tenant.  ``lats``
        aligns with the non-``bad`` requests in order (exactly how the
        scatter loops build it)."""
        board = self.tenancy
        if board is None or not requests:
            return
        self._note_dropped([r for i, r in enumerate(requests) if i in bad])
        li = 0
        agg = {}
        for i, r in enumerate(requests):
            if i in bad:
                continue
            lat = lats[li]
            li += 1
            t = getattr(r, "tenant", None)
            if t is None:
                continue
            c = agg.setdefault(t, [0, []])
            c[0] += r.n
            c[1].append(lat)
        for t, (rows, ls) in agg.items():
            board.on_served(t, rows, ls)

    @staticmethod
    def _screen_nonfinite(requests, Y) -> tuple:
        """Indices of requests whose output rows contain NaN/Inf.

        Cheap whole-batch check first; the per-request scan only runs
        when the batch is known dirty, so the healthy path pays one
        vectorized ``isfinite`` reduce over host memory.
        """
        if not np.issubdtype(Y.dtype, np.inexact) \
                or bool(np.isfinite(Y).all()):
            return ()
        bad, off = [], 0
        for i, r in enumerate(requests):
            if not np.isfinite(Y[off:off + r.n]).all():
                bad.append(i)
            off += r.n
        return tuple(bad)

    def dispatch(self, key: str, requests: List, stats: ServeStats,
                 reason: str, *, _attempts: Optional[int] = None) -> None:
        """Serve one coalesced batch and resolve every request future.

        Failure handling, in order:

        1. Engine *load* failures (missing/corrupt bundle) are
           deterministic — fail the whole batch once, no retry, no split.
        2. Compute/landing failures retry up to ``retry.max_attempts``
           with capped exponential backoff (the mega-batch is re-gathered
           each attempt — a donated buffer is dead after a failed apply).
        3. A multi-request batch that exhausts its retries is bisected
           (split-retry): each half re-dispatches with a single attempt,
           recursing down to singles, so one poisoned request cannot fail
           its siblings — only the request that actually fails does.
        4. Non-finite output rows are screened before scatter and
           converted to per-request :class:`NonFiniteOutput` failures,
           never silently returned.

        Every outcome feeds the per-key circuit breaker.
        """
        if not requests:
            return
        # monotonic throughout: latencies subtract submit-time stamps
        # taken with time.monotonic(), and mixing clocks is undefined
        t0 = time.monotonic()
        tr = TRACER
        traced = tr.enabled
        bid = next(_BATCH_IDS)
        try:
            eng = self._engine_for(key)
        except Exception as e:
            # bundle-load failures are batch-independent: retrying or
            # splitting would re-fail identically request by request
            tr.instant("batch.error", cat="batch",
                       args={"key": key, "batch": bid, "error": repr(e)})
            self._fail_all(requests, e, stats, reason,
                           time.monotonic() - t0, record_breaker_key=key)
            return
        n = sum(r.n for r in requests)
        ctx = requests[0].ctx
        shards = (ctx.axis_size("data")
                  if ctx is not None and ctx.mesh is not None else 1)
        bucket = bucket_for(n, self.min_bucket, shards)
        attempts = self.retry.max_attempts if _attempts is None \
            else max(1, _attempts)
        Y = None
        last_exc: Optional[Exception] = None
        for attempt in range(attempts):
            try:
                with tr.span("batch.gather", cat="batch",
                             args={"key": key, "batch": bid, "rows": n,
                                   "bucket": bucket,
                                   "requests": len(requests)}):
                    X, owned = self._gather(requests, n, bucket)
                with tr.span("batch.apply", cat="batch",
                             args={"key": key, "batch": bid,
                                   "bucket": bucket, "reason": reason,
                                   "attempt": attempt}):
                    with self._request_ctx(requests):
                        Y = eng.apply_batched(X, min_bucket=self.min_bucket,
                                              donate=owned, prepadded=owned)
                    Y = jax.block_until_ready(Y)
                # one device->host gather for the whole mega-batch:
                # scattering zero-copy numpy row views is ~1000x cheaper
                # than slicing a mesh-sharded array once per caller (each
                # such slice is a cross-device gather of its own)
                with tr.span("batch.to_host", cat="batch",
                             args={"key": key, "batch": bid}):
                    Y = self._to_host(Y)
                break
            except Exception as e:
                Y, last_exc = None, e
                tr.instant("batch.error", cat="batch",
                           args={"key": key, "batch": bid,
                                 "attempt": attempt, "error": repr(e)})
                if attempt + 1 < attempts:
                    _RETRIES.inc(1, key=key)
                    time.sleep(self.retry.delay_for(attempt))
        if Y is None:
            if len(requests) > 1:
                # split-retry: bisect so a poisoned request fails alone;
                # children get one attempt each (the backoff budget was
                # already spent above) and recurse down to singles
                _SPLITS.inc(1, key=key)
                tr.instant("batch.split", cat="batch",
                           args={"key": key, "batch": bid,
                                 "requests": len(requests)})
                mid = len(requests) // 2
                self.dispatch(key, requests[:mid], stats, reason,
                              _attempts=1)
                self.dispatch(key, requests[mid:], stats, reason,
                              _attempts=1)
                return
            self._fail_all(requests, last_exc, stats, reason,
                           time.monotonic() - t0, record_breaker_key=key)
            return
        if FAULTS.enabled:
            rule = FAULTS.fire("batcher.scatter", key=key)
            if rule is not None and rule.mode in ("nan", "inf"):
                Y = np.array(Y)  # writable copy on the injected path only
                Y[:requests[0].n] = rule.value
        bad = self._screen_nonfinite(requests, Y)
        t1 = time.monotonic()
        off = 0
        lats = []
        bad_rows = 0
        # per-request span [enqueue, future resolved]: with queue.submit
        # it tiles the request's whole enqueue->resolve window, so
        # coverage audits close; queued time is recoverable inside it as
        # (batch.gather.ts - this span's ts).  One args dict serves every
        # request of the batch (rec() documents shared-args safety).
        rargs = sargs = None
        if traced:
            rargs = {"key": key, "batch": bid, "reason": reason}
            sargs = {"key": key, "batch": bid, "requests": len(requests)}
        with tr.span("batch.scatter", cat="batch", args=sargs):
            for i, r in enumerate(requests):
                if i in bad:
                    r.future.set_exception(NonFiniteOutput(key, r.n))
                    bad_rows += r.n
                    off += r.n
                    continue
                r.future.set_result(Y[off:off + r.n])
                off += r.n
                lats.append(t1 - r.t_enqueue)
                if traced:
                    tr.rec("serve.request", "serve", r.t_enqueue,
                           time.monotonic(), r.trace, rargs)
        self._note_served(requests, bad, lats)
        if bad:
            _NONFINITE.inc(bad_rows, key=key)
            tr.instant("batch.nonfinite", cat="batch",
                       args={"key": key, "batch": bid,
                             "requests": len(bad), "rows": bad_rows})
            stats.on_failure(requests=len(bad), rows=bad_rows,
                             reason=reason, busy_s=0.0)
            BREAKERS.record_failure(key)
        else:
            BREAKERS.record_success(key)
        if len(bad) < len(requests):
            stats.on_batch(requests=len(requests) - len(bad),
                           rows=n - bad_rows, bucket=bucket, reason=reason,
                           busy_s=t1 - t0, latencies_s=lats)
            # drift re-sweep trigger: a sustained bucket with no tune
            # entry enqueues a background sweep of that exact cell.
            # Lazy import + disabled fast path keep this a no-op unless
            # REPRO_RESWEEP is on.
            from repro.tune.resweep import get_resweeper
            rs = get_resweeper()
            if rs.enabled:
                rs.observe(eng, bucket, stats)

    @staticmethod
    def _dtype_from_num(num: int):
        """np.dtype for a type number gathered from a pod peer.

        Type numbers are the only dtype spelling that travels through an
        integer all-gather; builtins have stable numbers, and extension
        dtypes (bfloat16) get consistent ones on identical software
        stacks (CI pins the stack)."""
        for name in ("float32", "float64", "float16", "int8", "int16",
                     "int32", "int64", "uint8", "uint16", "uint32",
                     "uint64", "bool_", "complex64", "complex128"):
            dt = np.dtype(getattr(np, name))
            if dt.num == num:
                return dt
        try:
            import ml_dtypes
            dt = np.dtype(ml_dtypes.bfloat16)
            if dt.num == num:
                return dt
        except ImportError:
            pass
        raise ValueError(f"dispatch_pod: unknown dtype num {num} gathered "
                         f"from a pod peer")

    def _slab_layout(self, requests, eng, agreed_num: int = -1):
        """(feature shape, dtype) of one slab row.

        From the local requests when this host has any; an idle host
        derives the feature shape from the engine's bundle spec and the
        dtype from the pod-agreed type number — every process must hand
        ``make_array_from_process_local_data`` the same dtype or the
        global array's avals diverge across the pod."""
        if requests:
            return (tuple(requests[0].x.shape[1:]),
                    np.dtype(requests[0].x.dtype))
        dtype = (self._dtype_from_num(agreed_num) if agreed_num >= 0
                 else np.dtype(np.float32))
        return tuple(eng.spec["in_shape"][1:]), dtype

    def dispatch_pod(self, key: str, requests: List, stats: ServeStats, *,
                     ctx=None, reason: str = "pod") -> None:
        """Serve one cross-host mega-batch (collective).

        Every process in the pod must call this at the same point for the
        same key — it contains collectives.  The hosts agree on a common
        per-host slab via an all-gather of their pending row counts; each
        host assembles its slab (its callers' rows + zero padding, sized
        ``bucket_for(max(counts))`` so slabs match), the slabs form one
        global batch whose leading dim is sharded over ``("pod", "data")``
        (``ShardCtx.make_global``), and after the batched apply each host
        reads back *only its own slab* — which is addressable by
        construction, so no cross-host result gather ever happens.

        A host with nothing pending still participates (zero slab, no
        futures): collectives cannot be skipped unilaterally.  ``ctx``
        overrides the serving ShardCtx for exactly that case — with no
        local requests there is no submit-time ctx to recover.
        """
        from repro.dist.sharding import current_ctx, use_mesh
        from repro.launch import multihost
        t0 = time.monotonic()
        tr = TRACER
        traced = tr.enabled
        bid = next(_BATCH_IDS)
        if ctx is None:
            ctx = requests[0].ctx if requests else current_ctx()
        local_n = sum(r.n for r in requests)
        my_num = int(np.dtype(requests[0].x.dtype).num) if requests else -1
        # pod.agree: the count/dtype all-gather is where a straggling
        # host shows up — every peer's span stretches to the slowest one
        with tr.span("pod.agree", cat="pod",
                     args={"key": key, "batch": bid, "local_rows": local_n}):
            gathered = multihost.allgather_ints([local_n, my_num])
        counts, dtype_nums = gathered[:, 0], gathered[:, 1]
        total = int(counts.sum())
        if total == 0:
            return
        pid, nproc = multihost.process_index(), len(counts)
        try:
            if nproc > 1 and (ctx is None or ctx.mesh is None):
                raise RuntimeError(
                    "dispatch_pod: cross-process serving needs a pod mesh "
                    "— submit under use_mesh(make_pod_mesh(), "
                    "multi_pod=True) or pass ctx=")
            # hosts with rows must agree on the row dtype; idle hosts
            # adopt it so every process assembles the same global aval
            active = {int(c) for c, k in zip(dtype_nums, counts) if k > 0}
            if len(active) > 1:
                raise ValueError(
                    f"dispatch_pod: pod hosts submitted mixed row dtypes "
                    f"for {key!r} (type nums {sorted(active)})")
            eng = self._engine_for(key)
            feat, dtype = self._slab_layout(requests, eng,
                                            next(iter(active), -1))
            local_shards = (ctx.local_axis_size("data")
                            if ctx is not None and ctx.mesh is not None
                            else 1)
            per_slab = bucket_for(int(counts.max()), self.min_bucket,
                                  local_shards)
            bucket = per_slab * nproc
            slab = self.scratch.take((per_slab,) + feat, dtype)
            off = 0
            for r in requests:
                slab[off:off + r.n] = np.asarray(r.x)
                off += r.n
            slab[off:] = 0
            if ctx is not None and ctx.mesh is not None:
                X = ctx.make_global(slab, ("data",) + (None,) * len(feat),
                                    global_shape=(bucket,) + feat)
            else:
                X = jnp.asarray(slab)
            with tr.span("batch.apply", cat="pod",
                         args={"key": key, "batch": bid, "bucket": bucket,
                               "pid": pid, "nproc": nproc,
                               "local_rows": local_n, "total_rows": total}):
                with (use_mesh(ctx.mesh, ctx.multi_pod) if ctx is not None
                      else use_mesh(None)):
                    Y = eng.apply_batched(X, min_bucket=self.min_bucket,
                                          prepadded=True)
                Y = jax.block_until_ready(Y)
            if requests:
                base = pid * per_slab
                with tr.span("batch.to_host", cat="pod",
                             args={"key": key, "batch": bid}):
                    Yh = self._to_host(Y, rows=(base, base + local_n))
        except Exception as e:
            tr.instant("batch.error", cat="pod",
                       args={"key": key, "batch": bid, "error": repr(e)})
            for r in requests:
                r.future.set_exception(e)
            self._note_dropped(requests)
            stats.on_failure(requests=len(requests), rows=local_n,
                             reason=reason, busy_s=time.monotonic() - t0)
            BREAKERS.record_failure(key)
            if nproc > 1:
                # pod-fatal: a host that bails after the count all-gather
                # (bundle read failure, bad dtype, ...) has already
                # diverged from the collective schedule its peers are
                # entering — swallowing the error here would leave them
                # hung in the apply.  Fail loudly so the driver/harness
                # tears the pod down.
                raise
            return
        bad = self._screen_nonfinite(requests, Yh) if requests else ()
        t1 = time.monotonic()
        off = 0
        lats = []
        bad_rows = 0
        rargs = {"key": key, "batch": bid, "reason": reason,
                 "pid": pid, "nproc": nproc} if traced else None
        for i, r in enumerate(requests):
            if i in bad:
                r.future.set_exception(NonFiniteOutput(key, r.n))
                bad_rows += r.n
                off += r.n
                continue
            r.future.set_result(Yh[off:off + r.n])
            off += r.n
            lats.append(t1 - r.t_enqueue)
            if traced:
                tr.rec("serve.request", "serve", r.t_enqueue,
                       time.monotonic(), r.trace, rargs)
        self._note_served(requests, bad, lats)
        if bad:
            _NONFINITE.inc(bad_rows, key=key)
            stats.on_failure(requests=len(bad), rows=bad_rows,
                             reason=reason, busy_s=0.0)
            BREAKERS.record_failure(key)
        else:
            BREAKERS.record_success(key)
        if not requests or len(bad) < len(requests):
            stats.on_batch(requests=len(requests) - len(bad),
                           rows=local_n - bad_rows, bucket=bucket,
                           reason=reason, busy_s=t1 - t0, latencies_s=lats,
                           remote_rows=total - local_n)
