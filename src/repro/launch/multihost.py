"""Multi-process pod bootstrap + local test harness.

Everything before this module ran the ``pod`` mesh axis as a fiction:
``dryrun --smoke`` forces 512 host devices in *one* process and calls it
a pod.  This module makes the axis real:

``bootstrap()``
    Environment-driven wrapper around ``jax.distributed.initialize``.
    Launchers (SLURM scripts, k8s pods, :func:`spawn_local_pod`) export
    ``REPRO_COORDINATOR`` / ``REPRO_NUM_PROCESSES`` / ``REPRO_PROCESS_ID``
    (+ optional ``REPRO_LOCAL_DEVICES`` host-device partitioning) and
    every process calls ``bootstrap()`` before touching jax state.  On
    CPU it enables the Gloo cross-process collectives the backend needs
    (without them every multi-process computation fails with
    "Multiprocess computations aren't implemented on the CPU backend").

``spawn_local_pod(n, target)``
    CPU-local test harness: forks ``n`` fresh processes on this machine
    (spawn, never fork — jax is multithreaded), each bootstrapping into
    one pod process with ``devices_per_host`` forced host-platform
    devices, and runs ``target`` ("pkg.mod:fn") in all of them.  This is
    what the multi-process CI lane and tests/test_multihost.py drive:
    real ``jax.distributed`` process groups, real cross-host collectives,
    one machine.

``allgather_counts`` / ``barrier``
    The two collectives the serve path needs: agreeing on per-host row
    counts before assembling a cross-host mega-batch
    (``Batcher.dispatch_pod``), and synchronizing bundle rewrites between
    batches (the NAS-retrain-under-load scenario in
    ``benchmarks/multihost_bench.py``).

No jax import at module level: children of :func:`spawn_local_pod`
import this module *before* their env is final, and the parent harness
must be able to drive pods without initializing a backend of its own.
"""
from __future__ import annotations

import dataclasses
import importlib
import os
import socket
import tempfile
import threading
import time
import traceback
from typing import Any, Dict, List, Optional, Sequence

ENV_COORDINATOR = "REPRO_COORDINATOR"
ENV_NUM_PROCESSES = "REPRO_NUM_PROCESSES"
ENV_PROCESS_ID = "REPRO_PROCESS_ID"
ENV_LOCAL_DEVICES = "REPRO_LOCAL_DEVICES"
ENV_POD_WATCHDOG = "REPRO_POD_WATCHDOG_S"


def pod_watchdog_s() -> float:
    """Collective watchdog budget for one guarded ``pod_flush`` round."""
    raw = os.environ.get(ENV_POD_WATCHDOG, "")
    try:
        return float(raw) if raw else 30.0
    except ValueError:
        return 30.0

_HOST_DEVICE_FLAG = "--xla_force_host_platform_device_count"


@dataclasses.dataclass(frozen=True)
class PodInfo:
    """What bootstrap() resolved: this process's place in the pod."""

    process_id: int = 0
    num_processes: int = 1
    coordinator: Optional[str] = None

    @property
    def is_multiprocess(self) -> bool:
        return self.num_processes > 1


def _env_int(value, name: str, default: Optional[int]) -> Optional[int]:
    if value is not None:
        return int(value)
    raw = os.environ.get(name)
    return int(raw) if raw else default


def _enable_cpu_collectives() -> None:
    """Switch the CPU client to Gloo collectives (idempotent, pre-init).

    Harmless on TPU/GPU — the flag only affects CPU client creation —
    and guarded so jax versions without the option degrade to their
    default instead of crashing the bootstrap.
    """
    import jax
    try:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except (AttributeError, ValueError):  # pre-gloo jax or renamed flag
        pass


def bootstrap(coordinator: Optional[str] = None,
              num_processes: Optional[int] = None,
              process_id: Optional[int] = None,
              local_devices: Optional[int] = None) -> PodInfo:
    """Join the pod described by args/env; single-process is a no-op.

    Must run before anything initializes a jax backend (first device
    query / computation): ``XLA_FLAGS`` partitioning and the distributed
    client cannot be installed afterwards.  Safe to call again once
    initialized — an already-joined pod is returned as-is.
    """
    coordinator = coordinator or os.environ.get(ENV_COORDINATOR)
    num_processes = _env_int(num_processes, ENV_NUM_PROCESSES, 1)
    process_id = _env_int(process_id, ENV_PROCESS_ID, 0)
    local_devices = _env_int(local_devices, ENV_LOCAL_DEVICES, None)
    if local_devices:
        flags = os.environ.get("XLA_FLAGS", "")
        if _HOST_DEVICE_FLAG not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} {_HOST_DEVICE_FLAG}={local_devices}".strip())

    if num_processes <= 1:
        return PodInfo(0, 1, None)

    import jax
    from jax._src import distributed as _dist
    if getattr(_dist.global_state, "client", None) is None:
        if not coordinator:
            raise RuntimeError(
                f"bootstrap: {num_processes} processes requested but no "
                f"coordinator address (set {ENV_COORDINATOR} or pass "
                f"coordinator=)")
        # only flip the collectives flag once we are certain to join a
        # pod: a gloo CPU client without a distributed runtime fails to
        # initialize, which would poison this process's backend
        _enable_cpu_collectives()
        jax.distributed.initialize(coordinator_address=coordinator,
                                   num_processes=num_processes,
                                   process_id=process_id)
    return PodInfo(jax.process_index(), jax.process_count(), coordinator)


# ----------------------------------------------------------- pod state -----

def is_multiprocess() -> bool:
    import jax
    return jax.process_count() > 1


def process_index() -> int:
    import jax
    return jax.process_index()


def process_count() -> int:
    import jax
    return jax.process_count()


def allgather_ints(values: Sequence[int]):
    """Every process's ``values`` as an int64 array [process_count, k].

    The serve path's agreement primitive: every host learns every host's
    pending row count (and row dtype), so all of them derive the same
    per-host slab and global bucket for a cross-host mega-batch.
    Collective — every process must call it at the same point with the
    same ``k``.  Single-process: ``[values]`` without touching the
    collectives stack.
    """
    import numpy as np
    vals = np.asarray([int(v) for v in values], np.int64).reshape(1, -1)
    if not is_multiprocess():
        return vals
    from jax.experimental import multihost_utils
    g = multihost_utils.process_allgather(vals[0].astype(np.int32))
    return np.asarray(g).reshape(process_count(), -1).astype(np.int64)


def allgather_counts(n: int):
    """Per-process values of ``n`` as an int64 array of len process_count."""
    return allgather_ints([n])[:, 0]


def allgather_bytes(data: bytes) -> List[bytes]:
    """Every process's ``data`` blob, ordered by process id.

    Variable-length payloads over the int collective the pod already
    has: the hosts agree on lengths first (one :func:`allgather_ints`),
    pad to the max, gather the padded byte matrix as int32, and slice
    each row back to its real length.  This is the transport under
    ``repro.obs.pod_snapshot`` — spans/metrics serialize to JSON bytes
    and ride it across the pod.  Collective (same contract as
    ``allgather_ints``); single-process returns ``[data]``.
    """
    import numpy as np
    if not is_multiprocess():
        return [bytes(data)]
    lengths = allgather_ints([len(data)])[:, 0]
    m = int(lengths.max())
    if m == 0:
        return [b""] * len(lengths)
    padded = np.zeros((m,), np.int32)
    padded[:len(data)] = np.frombuffer(bytes(data), np.uint8)
    from jax.experimental import multihost_utils
    g = np.asarray(multihost_utils.process_allgather(padded))
    g = g.reshape(process_count(), m).astype(np.uint8)
    return [g[i, :int(lengths[i])].tobytes() for i in range(len(lengths))]


def barrier(tag: str = "repro-pod") -> None:
    """Block until every pod process reaches this point (no-op solo)."""
    if not is_multiprocess():
        return
    from jax.experimental import multihost_utils
    multihost_utils.sync_global_devices(tag)


# ------------------------------------------------------------ pod health ---

class PodHealth:
    """Dropout bookkeeping for this process's view of the pod.

    Heartbeats piggyback on the ``pod_flush`` transport: every guarded
    flush round calls :meth:`beat`, which bumps the local round counter
    and best-effort publishes ``repro_hb_<pid>_<round>`` through the
    coordinator's key-value store (per-round keys sidestep overwrite
    semantics).  When the collective watchdog fires, :meth:`check_round`
    names the peers whose beat for that round never landed — a host that
    dropped *before* its flush never wrote one — and
    :meth:`mark_degraded` latches local-only serving (gauge
    ``repro_pod_degraded``; healthz reports ``pod:host-<k>``).

    :meth:`try_rejoin` runs a barrier under a timeout and clears the
    degraded latch when every peer answers.  Caveat: after a *torn*
    collective (the watchdog abandoned a live Gloo op to a zombie
    thread) the transport's op sequence numbers may have diverged, so a
    true rejoin generally needs the returning host to restart; the
    barrier succeeding is evidence of health, not a transport repair.

    All jax access is lazy — this module must import jax-free.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._round = 0
        self.degraded = False
        self.degraded_at: Optional[float] = None  # monotonic stamp
        self.offenders: tuple = ()

    @staticmethod
    def _kv_client():
        try:
            from jax._src import distributed as _dist
            return getattr(_dist.global_state, "client", None)
        except Exception:
            return None

    def beat(self) -> int:
        """Start a flush round: bump the counter, publish the heartbeat."""
        with self._lock:
            self._round += 1
            rid = self._round
        client = self._kv_client()
        if client is not None:
            try:
                client.key_value_set(f"repro_hb_{process_index()}_{rid}",
                                     str(time.time()))
            except Exception:
                pass  # heartbeat is best-effort; the watchdog still works
        return rid

    def check_round(self, round_id: int) -> tuple:
        """Peers with no heartbeat for ``round_id`` (empty when the KV
        store is unavailable — degrade generically, name nobody)."""
        client = self._kv_client()
        if client is None or not hasattr(client, "key_value_try_get"):
            return ()
        me = process_index()
        offenders = []
        for k in range(process_count()):
            if k == me:
                continue
            try:
                v = client.key_value_try_get(f"repro_hb_{k}_{round_id}")
            except Exception:  # NOT_FOUND surfaces as an error status
                v = None
            if not v:
                offenders.append(k)
        return tuple(offenders)

    def mark_degraded(self, offenders: Sequence[int] = ()) -> None:
        from repro.obs import metrics as _metrics
        with self._lock:
            already = self.degraded
            self.degraded = True
            if self.degraded_at is None:
                self.degraded_at = time.monotonic()
            self.offenders = tuple(sorted(set(self.offenders)
                                          | set(offenders)))
        _metrics.gauge("repro_pod_degraded",
                       "1 while this host serves local-only").set(1)
        _metrics.counter("repro_pod_watchdog_trips_total",
                         "pod watchdog timeouts").inc(1)
        if not already:
            _metrics.warn_once(
                "pod-degraded",
                f"pod degraded to local-only serving (offenders: "
                f"{list(self.offenders) or 'unknown'})")

    def try_rejoin(self, timeout_s: float = 10.0, *,
                   barrier_fn=None) -> bool:
        """Probe the pod with a barrier under ``timeout_s``; clear the
        degraded latch when every peer answers.  Returns success."""
        fn = barrier_fn or (lambda: barrier("repro-pod-rejoin"))
        done = threading.Event()
        ok: Dict[str, bool] = {}

        def run():
            try:
                fn()
                ok["ok"] = True
            except Exception:
                ok["ok"] = False
            finally:
                done.set()

        t = threading.Thread(target=run, daemon=True,
                             name="repro-pod-rejoin")
        t.start()
        if not (done.wait(timeout_s) and ok.get("ok")):
            return False
        from repro.obs import metrics as _metrics
        with self._lock:
            self.degraded = False
            self.degraded_at = None
            self.offenders = ()
        _metrics.gauge("repro_pod_degraded",
                       "1 while this host serves local-only").set(0)
        return True

    def reset(self) -> None:
        """Forget all state (tests)."""
        with self._lock:
            self._round = 0
            self.degraded = False
            self.degraded_at = None
            self.offenders = ()

    def snapshot(self) -> dict:
        with self._lock:
            return {"round": self._round, "degraded": self.degraded,
                    "offenders": list(self.offenders)}


#: process-wide pod health (what pod_flush and healthz consult)
POD_HEALTH = PodHealth()


# ----------------------------------------------------- local pod harness ---

class PodWorkerError(RuntimeError):
    """One or more spawn_local_pod workers failed; message carries all
    per-process tracebacks."""


def _free_port() -> int:
    s = socket.socket()
    try:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]
    finally:
        s.close()


def _pod_child(conn, env: Dict[str, str], target: str,
               args: tuple, kwargs: dict) -> None:
    """Spawn-side entry: env first, then bootstrap, then the target.

    Top-level so the spawn pickler can import it by reference; the env
    update happens before any jax import, which is why this module must
    stay jax-free at import time.
    """
    os.environ.update(env)
    try:
        from repro.launch.multihost import bootstrap
        bootstrap()
        mod_name, _, fn_name = target.partition(":")
        fn = getattr(importlib.import_module(mod_name), fn_name)
        conn.send(("ok", fn(*args, **(kwargs or {}))))
    except BaseException:
        try:
            conn.send(("err", traceback.format_exc()))
        except Exception:
            pass
    finally:
        conn.close()


def spawn_local_pod(n: int, target: str, args: tuple = (), *,
                    kwargs: Optional[dict] = None, devices_per_host: int = 1,
                    timeout_s: float = 300.0,
                    extra_env: Optional[Dict[str, str]] = None) -> List[Any]:
    """Run ``target`` ("pkg.mod:fn") in ``n`` fresh pod processes.

    Each child gets ``devices_per_host`` forced host-platform CPU
    devices, joins one ``jax.distributed`` process group over localhost,
    and runs the target with ``args``/``kwargs``.  Returns the targets'
    return values ordered by process id (results must pickle).  Raises
    :class:`PodWorkerError` with every failing process's traceback, or
    ``TimeoutError`` if any child outlives ``timeout_s`` (stragglers are
    killed — a hung collective must not hang CI).
    """
    import multiprocessing as mp
    if n < 1:
        raise ValueError(f"spawn_local_pod needs n >= 1, got {n}")
    port = _free_port()
    ctx = mp.get_context("spawn")
    procs, conns = [], []
    for pid in range(n):
        env = {
            ENV_COORDINATOR: f"127.0.0.1:{port}",
            ENV_NUM_PROCESSES: str(n),
            ENV_PROCESS_ID: str(pid),
            ENV_LOCAL_DEVICES: str(devices_per_host),
            # a CPU drill: a child never takes the parent's accelerator
            "JAX_PLATFORMS": "cpu",
            # children build their own device view; never inherit the
            # parent's partitioning (dryrun forces 512 devices at import)
            "XLA_FLAGS": f"{_HOST_DEVICE_FLAG}={devices_per_host}",
        }
        env.update(extra_env or {})
        parent_conn, child_conn = ctx.Pipe(duplex=False)
        p = ctx.Process(target=_pod_child,
                        args=(child_conn, env, target, tuple(args),
                              dict(kwargs or {})),
                        name=f"repro-pod-{pid}", daemon=True)
        p.start()
        child_conn.close()
        procs.append(p)
        conns.append(parent_conn)

    from multiprocessing import connection as mp_connection
    results: List[Any] = [None] * n
    errors: List[str] = []
    # one shared deadline (sequential per-process timeouts would stack to
    # n * timeout_s and outlive the CI job's own limit), collected
    # round-robin: a fast failure in any process surfaces immediately
    # instead of hiding behind an earlier pid's hung collective — once a
    # failure lands, surviving peers (likely hung in the now-peerless
    # collective) get a short grace, not the whole budget
    deadline = time.monotonic() + timeout_s
    fail_grace_s = 15.0
    by_conn = {conn: pid for pid, conn in enumerate(conns)}
    pending = dict(enumerate(zip(procs, conns)))
    while pending:
        left = deadline - time.monotonic()
        if left <= 0:
            break
        ready = mp_connection.wait(
            [c for _, c in pending.values()], timeout=left)
        for conn in ready:
            pid = by_conn[conn]
            p, _ = pending.pop(pid)
            try:
                status, payload = conn.recv()
            except (EOFError, OSError):  # a crash, not a hang
                p.join(timeout=5)
                errors.append(f"--- process {pid} exited {p.exitcode} "
                              f"with no result ---")
                continue
            if status == "ok":
                results[pid] = payload
            else:
                errors.append(f"--- process {pid} ---\n{payload}")
        if errors:
            deadline = min(deadline, time.monotonic() + fail_grace_s)
    timed_out = sorted(pending)
    for p in procs:
        p.join(timeout=5 if not timed_out else 0.5)
        if p.is_alive():
            p.terminate()
            p.join(timeout=5)
    if errors:
        if timed_out:
            errors.append(f"--- processes {timed_out} still pending "
                          f"{fail_grace_s}s after the first failure "
                          f"(killed) ---")
        raise PodWorkerError("spawn_local_pod worker failure:\n"
                             + "\n".join(errors))
    if timed_out:
        raise TimeoutError(
            f"spawn_local_pod: processes {timed_out} produced no result "
            f"within {timeout_s}s (killed)")
    return results


# -------------------------------------------------------------- CI smoke ---

def _write_smoke_bundle(path: str, widths=(32, 32)):
    import jax
    from repro.nn import MLP
    from repro.nn.serialize import save_model
    net = MLP((1, 5), list(widths), 1)
    params = net.init(jax.random.PRNGKey(7))
    return save_model(path, net, params)


def _smoke_worker(tmp: str, callers_per_host: int = 3,
                  rows_per_caller: int = 5) -> Dict[str, Any]:
    """One pod process of the cross-host serve round-trip.

    Every host submits its callers' rows to the *same* queue key, all
    hosts pod_flush collectively, and each host checks its callers'
    results bit-identical to single-process (eager, mesh-less) serving
    of the same rows.
    """
    import jax
    import numpy as np

    from repro.core.engine import InferenceEngine
    from repro.dist.sharding import use_mesh
    from repro.launch.mesh import make_pod_mesh
    from repro.serve import FlushPolicy, ServeQueue

    pid, nproc = jax.process_index(), jax.process_count()
    bundle = os.path.join(tmp, "surrogate")
    if pid == 0:
        _write_smoke_bundle(bundle)
    barrier("smoke-bundle-ready")

    # every host sees the same deterministic global caller set and owns
    # a contiguous slice of it
    rng = np.random.default_rng(1234)
    full = rng.standard_normal(
        (nproc * callers_per_host * rows_per_caller, 5)).astype(np.float32)
    mine = full.reshape(nproc, callers_per_host, rows_per_caller, 5)[pid]

    mesh = make_pod_mesh()
    queue = ServeQueue(FlushPolicy(max_batch_rows=1 << 30))  # explicit only
    with use_mesh(mesh, multi_pod=True):
        futs = [queue.submit(bundle, mine[c]) for c in range(callers_per_host)]
        queue.pod_flush(bundle)
    got = [np.asarray(f.result(timeout=120)) for f in futs]

    # single-process reference: the same engine serving eagerly, no mesh
    eng = InferenceEngine.get(bundle)
    ref = [np.asarray(eng(mine[c])) for c in range(callers_per_host)]
    equal = all(np.array_equal(g, r) for g, r in zip(got, ref))

    snap = queue.stats(bundle).snapshot()
    out = {
        "pid": pid,
        "nproc": nproc,
        "equal": bool(equal),
        "local_rows": int(callers_per_host * rows_per_caller),
        "bucket": int(snap["bucket_rows"]),
        "pod_batches": int(snap["pod_batches"]),
        "remote_rows": int(snap["remote_rows"]),
        "global_devices": jax.device_count(),
    }
    from repro.obs import SHADOW, TRACER, pod_snapshot
    if SHADOW.enabled:
        # quality pass: every host shadow-scores its own served rows
        # against the eager single-process reference it already computed
        # (bit-identical -> the drift alert must stay OK; cross-host
        # state rides the pod snapshot below)
        SHADOW.set_budget(bundle, 0.05)
        for c in range(callers_per_host):
            SHADOW.submit(bundle,
                          pred=lambda g=got[c]: g,
                          ref=lambda r=ref[c]: r,
                          region="pod-smoke", rows=rows_per_caller)
        SHADOW.flush(60.0)
        out["quality_state"] = SHADOW.state(bundle)
    if TRACER.enabled:
        # flight-recorder pass: all-gather every host's spans/metrics
        # (collective, so it must run before the final barrier on every
        # host) — each worker returns the merged pod view, letting the
        # parent write one trace artifact without its own jax runtime
        out["obs"] = pod_snapshot()
    barrier("smoke-done")
    return out


def run_smoke(processes: int = 2, devices_per_host: int = 2,
              tmpdir: Optional[str] = None,
              timeout_s: float = 420.0,
              obs_out: Optional[str] = None,
              shadow_rate: Optional[float] = None) -> List[Dict[str, Any]]:
    """The multi-process CI smoke: spawn_local_pod driving a cross-host
    serve round-trip.  Raises on any correctness failure; returns the
    per-process summaries.

    ``obs_out`` turns the pod into a flight recorder: children run with
    tracing on, every host's spans/metrics are all-gathered in-pod
    (``obs.pod_snapshot``), and the merged Chrome trace lands at
    ``obs_out`` (open in Perfetto; each host is one pid track).

    ``shadow_rate`` enables shadow quality scoring in every child
    (defaults to 1.0 when the flight recorder is on); the smoke then
    also requires every host's drift alert to report OK — the served
    rows are bit-identical to the accurate reference, so anything else
    is a monitor bug.
    """
    tmp = tmpdir or tempfile.mkdtemp(prefix="repro_pod_smoke_")
    if shadow_rate is None and obs_out:
        shadow_rate = 1.0
    extra_env: Dict[str, str] = {}
    if obs_out:
        extra_env["REPRO_TRACE"] = "1"
    if shadow_rate:
        extra_env["REPRO_SHADOW_RATE"] = str(shadow_rate)
    res = spawn_local_pod(processes, "repro.launch.multihost:_smoke_worker",
                          (tmp,), devices_per_host=devices_per_host,
                          timeout_s=timeout_s,
                          extra_env=extra_env or None)
    failures = []
    for r in res:
        if not r["equal"]:
            failures.append(f"p{r['pid']}: results diverge from "
                            f"single-process serving")
        if r["pod_batches"] < 1:
            failures.append(f"p{r['pid']}: no pod batch dispatched")
        if processes > 1 and r["remote_rows"] <= 0:
            failures.append(f"p{r['pid']}: mega-batch carried no remote "
                            f"rows — it did not span the pod axis")
        if r["bucket"] <= r["local_rows"]:
            failures.append(f"p{r['pid']}: global bucket {r['bucket']} "
                            f"does not exceed local rows {r['local_rows']}")
        if shadow_rate and r.get("quality_state") != "OK":
            failures.append(
                f"p{r['pid']}: drift alert {r.get('quality_state')!r} on "
                f"bit-identical served rows (expected OK)")
    for r in res:
        q = f" quality={r['quality_state']}" if "quality_state" in r else ""
        print(f"[pod-smoke] p{r['pid']}/{r['nproc']} "
              f"devices={r['global_devices']} bucket={r['bucket']} "
              f"remote_rows={r['remote_rows']} equal={r['equal']}{q}",
              flush=True)
    if failures:
        raise PodWorkerError("pod smoke FAILED:\n" + "\n".join(failures))
    if obs_out:
        # process 0's gathered snapshots already hold every host's view;
        # the merge is jax-free so the parent harness can write it
        from repro.obs import merge_pod_trace, pod_quality_report
        snapshots = (res[0] or {}).get("obs") or []
        merged = merge_pod_trace(snapshots, obs_out)
        print(f"[pod-smoke] obs: merged {len(merged)} events from "
              f"{len(snapshots)} hosts -> {obs_out}", flush=True)
        if shadow_rate:
            print("[pod-smoke] cross-host surrogate quality:", flush=True)
            print(pod_quality_report(snapshots), flush=True)
    print(f"[pod-smoke] OK: {processes} processes, cross-host mega-batch, "
          f"bit-identical to single-process serving", flush=True)
    return res


# ------------------------------------------------------ host-drop drill ---

def _host_drop_worker(tmp: str, callers_per_host: int = 2,
                      rows_per_caller: int = 4) -> Dict[str, Any]:
    """One pod process of the chaos host-drop drill.

    Launched with ``REPRO_FAULTS="pod.flush:drop:pid=1,stall=<s>"`` and a
    short ``REPRO_POD_WATCHDOG_S``: host 1 stalls at ``pod_flush`` entry
    — *before* writing its heartbeat, so it looks exactly like a dropped
    host — and host 0's watchdog must fire, degrade to local-only
    dispatch, and still resolve every future bit-identically.  Host 1,
    on waking, either completes a late pod batch with host 0's abandoned
    collective thread or degrades locally itself; both are correct, and
    first-wins futures keep either race winner exact.

    Two latencies are measured separately because they are bounded by
    different mechanisms.  *Time-to-degrade* (watchdog fires, healthz
    flips, later flushes go local-only) is bounded by the watchdog.
    *Drain time* for the batch that was in flight when the host dropped
    is bounded by the collective transport, not the watchdog: on
    backends with FIFO per-device execution streams (XLA CPU) the torn
    collective pins the devices, so the survivor's local re-dispatch
    executes only once the transport gives up (peer timeout) or the
    straggler limps back — zero requests lost either way.
    """
    import jax
    import numpy as np

    from repro.core.engine import InferenceEngine
    from repro.dist.sharding import use_mesh
    from repro.launch.mesh import make_pod_mesh
    from repro.serve import FlushPolicy, ServeQueue

    pid, nproc = jax.process_index(), jax.process_count()
    bundle = os.path.join(tmp, "surrogate")
    if pid == 0:
        _write_smoke_bundle(bundle)
    barrier("drill-bundle-ready")

    rng = np.random.default_rng(99)
    full = rng.standard_normal(
        (nproc * callers_per_host * rows_per_caller, 5)).astype(np.float32)
    mine = full.reshape(nproc, callers_per_host, rows_per_caller, 5)[pid]

    mesh = make_pod_mesh()
    queue = ServeQueue(FlushPolicy(max_batch_rows=1 << 30))  # explicit only
    t0 = time.monotonic()
    with use_mesh(mesh, multi_pod=True):
        futs = [queue.submit(bundle, mine[c])
                for c in range(callers_per_host)]
        queue.pod_flush(bundle)
    elapsed = time.monotonic() - t0

    got = [np.asarray(f.result(timeout=120)) for f in futs]
    eng = InferenceEngine.get(bundle)
    ref = [np.asarray(eng(mine[c])) for c in range(callers_per_host)]
    equal = all(np.array_equal(g, r) for g, r in zip(got, ref))

    from repro.obs.server import ObsServer
    _, health = ObsServer().health()
    # no rejoin drill here: after a torn Gloo collective only a process
    # restart truly rejoins (see PodHealth.try_rejoin caveat) — the unit
    # tests cover the rejoin state machine with a stubbed barrier
    degrade_latency = (POD_HEALTH.degraded_at - t0
                       if POD_HEALTH.degraded_at is not None else None)
    return {
        "pid": pid, "nproc": nproc, "equal": bool(equal),
        "resolved": sum(1 for f in futs if f.done()),
        "submitted": len(futs),
        "elapsed_s": float(elapsed),
        "degrade_latency_s": (float(degrade_latency)
                              if degrade_latency is not None else None),
        "degraded": bool(POD_HEALTH.degraded),
        "offenders": list(POD_HEALTH.offenders),
        "critical": list(health["critical"]),
        "watchdog_s": pod_watchdog_s(),
    }


def run_host_drop_drill(processes: int = 2, devices_per_host: int = 2,
                        tmpdir: Optional[str] = None,
                        timeout_s: float = 240.0, stall_s: float = 15.0,
                        watchdog_s: float = 2.0) -> List[Dict[str, Any]]:
    """The chaos-lane drill: drop host 1 mid-flush, require the survivor
    to *degrade* within the watchdog (healthz flips, later flushes go
    local-only) and to *drain* the in-flight batch with zero lost
    requests.  The drain itself is transport-bound, not watchdog-bound —
    see ``_host_drop_worker`` — so it is only required to complete
    promptly once the dropped host's stall ends, never to beat it."""
    if processes < 2:
        raise ValueError("host-drop drill needs >= 2 processes")
    tmp = tmpdir or tempfile.mkdtemp(prefix="repro_pod_drill_")
    extra_env = {
        "REPRO_FAULTS": f"pod.flush:drop:pid=1,stall={stall_s}",
        ENV_POD_WATCHDOG: str(watchdog_s),
    }
    res = spawn_local_pod(
        processes, "repro.launch.multihost:_host_drop_worker", (tmp,),
        devices_per_host=devices_per_host,
        timeout_s=timeout_s, extra_env=extra_env)
    failures = []
    for r in res:
        if r["resolved"] != r["submitted"]:
            failures.append(f"p{r['pid']}: lost "
                            f"{r['submitted'] - r['resolved']} requests")
        if not r["equal"]:
            failures.append(f"p{r['pid']}: results diverge from eager "
                            f"serving")
    r0 = res[0]
    if not r0["degraded"]:
        failures.append("p0: survivor never degraded — the watchdog did "
                        "not fire")
    else:
        if r0["offenders"] and r0["offenders"] != [1]:
            failures.append(f"p0: offenders {r0['offenders']} "
                            f"(expected [1])")
        if r0["offenders"] and "pod:host-1" not in r0["critical"]:
            failures.append(f"p0: healthz critical {r0['critical']} does "
                            f"not name pod:host-1")
        lat = r0["degrade_latency_s"]
        # watchdog + heartbeat/thread spin-up slack; far under the stall
        if lat is None or lat >= min(watchdog_s + 5.0, stall_s):
            failures.append(
                f"p0: degrade latency {lat if lat is None else round(lat, 1)}s"
                f" — the watchdog ({watchdog_s}s) did not flip the pod to "
                f"local-only before the {stall_s}s stall ended")
    if r0["elapsed_s"] >= stall_s + 10.0:
        failures.append(
            f"p0: pod_flush took {r0['elapsed_s']:.1f}s — the in-flight "
            f"batch did not drain promptly after the {stall_s}s stall "
            f"released the transport")
    for r in res:
        lat = r["degrade_latency_s"]
        print(f"[host-drop] p{r['pid']}/{r['nproc']} "
              f"resolved={r['resolved']}/{r['submitted']} "
              f"equal={r['equal']} degraded={r['degraded']} "
              f"degrade_latency="
              f"{'-' if lat is None else format(lat, '.1f') + 's'} "
              f"offenders={r['offenders']} "
              f"flush={r['elapsed_s']:.1f}s", flush=True)
    if failures:
        raise PodWorkerError("host-drop drill FAILED:\n"
                             + "\n".join(failures))
    print(f"[host-drop] OK: host 1 dropped {stall_s}s, survivor flipped "
          f"local-only in {r0['degrade_latency_s']:.1f}s "
          f"(watchdog {watchdog_s}s), zero requests lost", flush=True)
    return res


def main() -> None:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--smoke", action="store_true",
                    help="spawn_local_pod cross-host serve round-trip")
    ap.add_argument("--host-drop-drill", action="store_true",
                    help="chaos drill: drop one host mid-pod_flush and "
                         "require degrade-within-watchdog, zero lost "
                         "requests")
    ap.add_argument("--processes", type=int, default=2)
    ap.add_argument("--devices-per-host", type=int, default=2)
    ap.add_argument("--obs", default=None, metavar="PATH",
                    help="flight recorder: run the pod with tracing on "
                         "and write the merged Chrome trace to PATH")
    ap.add_argument("--shadow-rate", type=float, default=None,
                    help="shadow-score this fraction of served requests "
                         "in every pod process (default 1.0 with --obs)")
    args = ap.parse_args()
    if args.smoke:
        run_smoke(processes=args.processes,
                  devices_per_host=args.devices_per_host,
                  obs_out=args.obs,
                  shadow_rate=args.shadow_rate)
        return
    if args.host_drop_drill:
        run_host_drop_drill(processes=args.processes,
                            devices_per_host=args.devices_per_host)
        return
    ap.error("nothing to do (pass --smoke or --host-drop-drill)")


if __name__ == "__main__":
    main()
