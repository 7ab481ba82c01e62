"""Batcher: coalesce queued region requests into bucket-shaped mega-batches.

The queue hands the batcher a FIFO run of requests for one bundle path;
the batcher concatenates their rows, dispatches them through the
engine's :meth:`InferenceEngine.apply_batched` (which pads to the
power-of-two bucket, places the batch over the ``data`` axis of the
active mesh, and slices the padding back off), then lands the output
(split on its device where every request asked for device rows, else
read back to a host buffer) and scatters per-request rows into the
callers' futures.

Row-wise surrogates make this exact rather than approximate: each output
row depends only on its input row, so a request's rows come back
bit-identical to what a synchronous ``MLRegion._infer`` of the same
inputs produces, regardless of which mega-batch they rode in (asserted
by tests/test_serve.py).
"""
from __future__ import annotations

import dataclasses
import itertools
import math
import time
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist.buckets import bucket_for
from repro.dist.sharding import ShardCtx, current_ctx, use_mesh
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
_LANDED = _metrics.counter(
    "repro_batch_land_total",
    "served batches by where their rows landed: split on the device, or "
    "read back to a host buffer", ("key", "route"))


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


@dataclasses.dataclass
class _Batch:
    """One batch as an entry point planned it for :meth:`Batcher._serve`.

    ``assemble(engine)`` returns ``(X, prepadded)``; ``rows`` is the row
    range of the applied batch to read back (None: all of it).  A local
    batch retries ``attempts`` times and then bisects (``split``); a
    collective gets one attempt and no split, since no host can retry or
    bisect it alone, and across processes its failure is ``pod_fatal``.
    """

    key: str
    requests: List
    stats: ServeStats
    reason: str
    bid: int
    t0: float
    bucket: int
    ctx: Optional[ShardCtx]
    assemble: Callable[..., Tuple[object, bool]]
    attempts: int
    split: bool = True
    pod_fatal: bool = False
    rows: Optional[Tuple[int, int]] = None
    remote_rows: int = 0


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
        # one compiled split per batch layout (see _split), shared by
        # every region that serves through this batcher
        self._splits = {}
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

        Returns ``(X, prepadded)``.  A lone request rides through
        untouched (the engine pads it).  Device-resident inputs
        concatenate on device — no D2H round-trip.  Host inputs gather
        into a pooled scratch buffer already padded to the bucket, so the
        engine skips its own concat+pad.
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
        return jnp.asarray(self._stage(requests, bucket, feat,
                                       requests[0].x.dtype)), True

    def _stage(self, requests, rows: int, feat, dtype) -> np.ndarray:
        """The requests' rows in a pooled host buffer of ``rows`` rows."""
        buf = self.scratch.take((rows,) + feat, np.dtype(dtype))
        off = 0
        for r in requests:
            buf[off:off + r.n] = np.asarray(r.x)
            off += r.n
        buf[off:] = 0  # zero padding: same rows a jnp pad would produce
        return buf

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
    def _device_landing(requests, Y):
        """The one landing (``repro.core.region.Landing``: device,
        committed, shape, dtype) every request asked for, when ``Y``
        can be split into it on the device: equal request sizes, ``Y``
        fully addressable on that one device and committed alike, and
        each request's rows of the landing's size.  Else None: the batch
        is read back to the host."""
        land = requests[0].land if requests else None
        if land is None or not isinstance(Y, jax.Array):
            return None
        n = requests[0].n
        if any(r.land != land or r.n != n for r in requests):
            return None
        if (not Y.is_fully_addressable or Y.devices() != {land.device}
                or Y.committed != land.committed
                or math.prod(land.shape) != n * math.prod(Y.shape[1:])):
            return None
        return land

    def _split(self, Y, land, count: int, n: int, start: int):
        """``count`` requests' rows of ``n`` from row ``start`` of ``Y``,
        each at the landing's shape and dtype, and one flag a request
        saying its rows are all finite: one compiled program, cached per
        layout, whose outputs stay where ``Y`` is."""
        layout = (Y.shape, Y.dtype, start, count, n, land)
        fn = self._splits.get(layout)
        if fn is None:
            inexact = jnp.issubdtype(Y.dtype, jnp.inexact)

            def split(y):
                y = y[start:start + count * n]
                parts = tuple(
                    p.reshape(land.shape).astype(
                        y.dtype if land.dtype is None else land.dtype)
                    for p in jnp.split(y, count))
                ok = (jnp.isfinite(y.reshape(count, -1)).all(axis=1)
                      if inexact else jnp.ones((count,), bool))
                return parts, ok

            fn = self._splits[layout] = jax.jit(split)
        return fn(Y)

    def _fail_all(self, b: _Batch, exc: Exception) -> None:
        for r in b.requests:
            r.future.set_exception(exc)
        self._note_dropped(b.requests)
        b.stats.on_failure(requests=len(b.requests),
                           rows=sum(r.n for r in b.requests),
                           reason=b.reason, busy_s=time.monotonic() - b.t0)
        BREAKERS.record_failure(b.key)
        if b.pod_fatal:
            # a host that bails after the count all-gather (bundle read
            # failure, bad dtype, ...) has already diverged from the
            # collective schedule its peers are entering — swallowing the
            # error here would leave them hung in the apply.  Fail loudly
            # so whatever launched the pod tears it down.
            raise exc

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
           with capped exponential backoff (the assembled mega-batch is
           reused; only a failed assembly is redone).
        3. A multi-request batch that exhausts its retries is bisected
           (split-retry): each half re-dispatches with a single attempt,
           recursing down to singles, so one poisoned request cannot fail
           its siblings — only the request that actually fails does.
        4. Non-finite output rows are screened before scatter and
           converted to per-request :class:`NonFiniteOutput` failures,
           never silently returned.

        Every outcome feeds the per-key circuit breaker.  The first
        request's submit-time ShardCtx serves the batch (requests arrive
        FIFO on one key; different meshes never coalesce meaningfully).
        """
        if not requests:
            return
        # monotonic throughout: latencies subtract submit-time stamps
        # taken with time.monotonic(), and mixing clocks is undefined
        t0 = time.monotonic()
        n = sum(r.n for r in requests)
        ctx = requests[0].ctx
        shards = (ctx.axis_size("data")
                  if ctx is not None and ctx.mesh is not None else 1)
        bucket = bucket_for(n, self.min_bucket, shards)
        self._serve(_Batch(
            key, requests, stats, reason, next(_BATCH_IDS), t0, bucket, ctx,
            lambda eng: self._gather(requests, n, bucket),
            attempts=(self.retry.max_attempts if _attempts is None
                      else max(1, _attempts))))

    def _serve(self, b: _Batch) -> None:
        """Assemble, apply, land, screen, scatter and account one batch:
        the one routine behind :meth:`dispatch` and :meth:`dispatch_pod`."""
        tr = TRACER
        traced = tr.enabled
        key, requests, bid = b.key, b.requests, b.bid
        try:
            eng = self._engine_for(key)
        except Exception as e:
            # bundle-load failures are batch-independent: retrying or
            # splitting would re-fail identically request by request
            tr.instant("batch.error", cat="batch",
                       args={"key": key, "batch": bid, "error": repr(e)})
            self._fail_all(b, e)
            return
        n = sum(r.n for r in requests)
        X = Y = parts = None
        last_exc: Optional[Exception] = None
        for attempt in range(b.attempts):
            try:
                if X is None:
                    with tr.span("batch.gather", cat="batch",
                                 args={"key": key, "batch": bid, "rows": n,
                                       "bucket": b.bucket,
                                       "requests": len(requests)}):
                        X, prepadded = b.assemble(eng)
                with tr.span("batch.apply", cat="batch",
                             args={"key": key, "batch": bid,
                                   "bucket": b.bucket, "reason": b.reason,
                                   "attempt": attempt}):
                    # sharding contexts are thread-local: the planned ctx
                    # governs even when None, so a flush on the dispatcher
                    # thread or inside someone else's use_mesh applies
                    # with the mesh the bucket was sized for
                    with (use_mesh(None) if b.ctx is None
                          else use_mesh(b.ctx.mesh, b.ctx.multi_pod)):
                        Y = eng.apply_batched(X, min_bucket=self.min_bucket,
                                              prepadded=prepadded)
                    Y = jax.block_until_ready(Y)
                # land the batch.  Device route: when every request wants
                # its rows on Y's device, one program splits them there
                # and only a flag a request comes back.  Host route: one
                # device->host gather for the whole mega-batch; scattering
                # zero-copy numpy row views is ~1000x cheaper than slicing
                # a mesh-sharded array once per caller (each such slice is
                # a cross-device gather of its own)
                land = self._device_landing(requests, Y)
                route = "host" if land is None else "device"
                with tr.span("batch.to_host", cat="batch",
                             args={"key": key, "batch": bid,
                                   "route": route}):
                    if land is None:
                        Y = self._to_host(Y, rows=b.rows)
                    else:
                        parts, ok = self._split(
                            Y, land, len(requests), requests[0].n,
                            b.rows[0] if b.rows else 0)
                        ok = np.array(ok)
                break
            except Exception as e:
                Y, parts, last_exc = None, None, e
                tr.instant("batch.error", cat="batch",
                           args={"key": key, "batch": bid,
                                 "attempt": attempt, "error": repr(e)})
                if attempt + 1 < b.attempts:
                    _RETRIES.inc(1, key=key)
                    time.sleep(self.retry.delay_for(attempt))
        if Y is None:
            if b.split and len(requests) > 1:
                # split-retry: bisect so a poisoned request fails alone;
                # children get one attempt each (the backoff budget was
                # already spent above) and recurse down to singles
                _SPLITS.inc(1, key=key)
                tr.instant("batch.split", cat="batch",
                           args={"key": key, "batch": bid,
                                 "requests": len(requests)})
                mid = len(requests) // 2
                self.dispatch(key, requests[:mid], b.stats, b.reason,
                              _attempts=1)
                self.dispatch(key, requests[mid:], b.stats, b.reason,
                              _attempts=1)
                return
            self._fail_all(b, last_exc)
            return
        _LANDED.inc(1, key=key, route=route)
        if FAULTS.enabled and requests:
            rule = FAULTS.fire("batcher.scatter", key=key)
            if rule is not None and rule.mode in ("nan", "inf"):
                # poison the first request's rows where they landed, on
                # the injected path only
                if parts is None:
                    Y = np.array(Y)  # writable copy
                    Y[:requests[0].n] = rule.value
                else:
                    parts = (jnp.full_like(parts[0], rule.value),) + \
                        parts[1:]
                    ok[0] = bool(jnp.isfinite(parts[0]).all())
        bad = (self._screen_nonfinite(requests, Y) if parts is None
               else tuple(int(i) for i in np.flatnonzero(~ok)))
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
            rargs = {"key": key, "batch": bid, "reason": b.reason}
            sargs = {"key": key, "batch": bid, "requests": len(requests)}
        with tr.span("batch.scatter", cat="batch", args=sargs):
            for i, r in enumerate(requests):
                if i in bad:
                    r.future.set_exception(NonFiniteOutput(key, r.n))
                    bad_rows += r.n
                    off += r.n
                    continue
                r.future.set_result(Y[off:off + r.n] if parts is None
                                    else parts[i])
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
            b.stats.on_failure(requests=len(bad), rows=bad_rows,
                               reason=b.reason, busy_s=0.0)
            BREAKERS.record_failure(key)
        else:
            BREAKERS.record_success(key)
        # an idle pod host still accounts the collective it joined
        if not requests or len(bad) < len(requests):
            b.stats.on_batch(requests=len(requests) - len(bad),
                             rows=n - bad_rows, bucket=b.bucket,
                             reason=b.reason, busy_s=t1 - b.t0,
                             latencies_s=lats, remote_rows=b.remote_rows)
            # drift re-sweep trigger: a sustained bucket with no tune
            # entry enqueues a background sweep of that exact cell.
            # Lazy import + disabled fast path keep this a no-op unless
            # REPRO_RESWEEP is on.
            from repro.tune.resweep import get_resweeper
            rs = get_resweeper()
            if rs.enabled:
                rs.observe(eng, b.bucket, b.stats)

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
        from repro.launch import multihost
        t0 = time.monotonic()
        bid = next(_BATCH_IDS)
        if ctx is None:
            ctx = requests[0].ctx if requests else current_ctx()
        local_n = sum(r.n for r in requests)
        my_num = int(np.dtype(requests[0].x.dtype).num) if requests else -1
        # pod.agree: the count/dtype all-gather is where a straggling
        # host shows up — every peer's span stretches to the slowest one
        with TRACER.span("pod.agree", cat="pod",
                         args={"key": key, "batch": bid,
                               "local_rows": local_n}):
            gathered = multihost.allgather_ints([local_n, my_num])
        counts, dtype_nums = gathered[:, 0], gathered[:, 1]
        total = int(counts.sum())
        if total == 0:
            return
        pid, nproc = multihost.process_index(), len(counts)
        meshed = ctx is not None and ctx.mesh is not None
        per_slab = bucket_for(int(counts.max()), self.min_bucket,
                              ctx.local_axis_size("data") if meshed else 1)
        bucket = per_slab * nproc

        def assemble(eng):
            if nproc > 1 and not meshed:
                raise RuntimeError(
                    "dispatch_pod: cross-process serving needs a pod mesh "
                    "— submit under use_mesh(make_pod_mesh(), "
                    "multi_pod=True) or pass ctx=")
            # hosts with rows must agree on the row dtype; an idle host
            # adopts it and takes its feature shape from the bundle spec,
            # or the global array's avals diverge across the pod
            active = {int(c) for c, k in zip(dtype_nums, counts) if k > 0}
            if len(active) > 1:
                raise ValueError(
                    f"dispatch_pod: pod hosts submitted mixed row dtypes "
                    f"for {key!r} (type nums {sorted(active)})")
            if requests:
                feat, dtype = requests[0].x.shape[1:], requests[0].x.dtype
            else:
                feat = tuple(eng.spec["in_shape"][1:])
                dtype = self._dtype_from_num(active.pop())
            slab = self._stage(requests, per_slab, feat, dtype)
            if meshed:
                return ctx.make_global(slab, ("data",) + (None,) * len(feat),
                                       global_shape=(bucket,) + feat), True
            return jnp.asarray(slab), True

        base = pid * per_slab
        self._serve(_Batch(
            key, requests, stats, reason, bid, t0, bucket, ctx, assemble,
            attempts=1, split=False, pod_fatal=nproc > 1,
            rows=(base, base + local_n), remote_rows=total - local_n))
