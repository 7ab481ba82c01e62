"""From a profiler trace to device busy and idle time, the time of each
device program, and the idle gaps named by what the host was doing.

Two steps, so that a recorded trace can be checked on the CPU:

- :func:`extract` reads an ``.xplane.pb`` (``jax.profiler.ProfileData``)
  into plain lists: the host's events of every thread, and per TPU the
  ops and the programs (XLA modules) it ran, each as ``[name, t0_ns,
  t1_ns]`` on the profiler's one clock;
- :func:`reduce` takes the window from the host event named
  ``bench.window`` and, inside it: the union of each device's op
  intervals (busy), the gaps between them, each gap's time charged to the
  innermost host event around its middle, and the seconds per op name
  (every op in ``ops``, the longest in ``device_ops``) and per program.
  Device numbers are the mean over the devices traced.
"""
from __future__ import annotations

import bisect
import gzip
import pathlib
import re

WINDOW_EVENT = "bench.window"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_OPS_LINE = "XLA Ops"
_MODULES_LINE = "XLA Modules"
TOP = 10


def load(path):
    """ProfileData from an ``.xplane.pb``, gzipped or not."""
    from jax.profiler import ProfileData
    path = pathlib.Path(path)
    if path.suffix == ".gz":
        return ProfileData.from_serialized_xspace(
            gzip.decompress(path.read_bytes()))
    return ProfileData.from_file(str(path))


_HLO_OP = re.compile(r"^(%\S+) = .*?\s([a-z][\w-]*)\(")


def _short(name: str) -> str:
    """An op event is named by its whole HLO instruction; keep the
    instruction's name and opcode (``%fusion.3 fusion``)."""
    m = _HLO_OP.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name


def _events(line, name=lambda n: n):
    return [[name(e.name), float(e.start_ns),
             float(e.start_ns + e.duration_ns)] for e in line.events]


def extract(profile) -> dict:
    host, devices = [], {}
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(_events(line))
        elif _DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            if _OPS_LINE in lines:
                devices[plane.name] = {
                    "ops": _events(lines[_OPS_LINE], _short),
                    "modules": (_events(lines[_MODULES_LINE])
                                if _MODULES_LINE in lines else [])}
    return {"host": host, "devices": devices}


def _clip(events, t0, t1):
    return [[n, max(a, t0), min(b, t1)] for n, a, b in events
            if b > t0 and a < t1]


def _union(events):
    """Sorted disjoint busy intervals of ``events``."""
    out = []
    for _, a, b in sorted(events, key=lambda e: e[1]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


class _HostIndex:
    """Innermost host event around an instant: of the events that contain
    it, the one that started last."""

    def __init__(self, host):
        self._ev = sorted(((a, b, n) for n, a, b in host if b > a),
                          key=lambda e: e[0])
        self._starts = [e[0] for e in self._ev]

    def name_at(self, t) -> str:
        i = bisect.bisect_right(self._starts, t)
        while i > 0:
            i -= 1
            a, b, n = self._ev[i]
            if b > t:
                return n
        return "(no host event)"


def window_of(ex: dict):
    wins = [(a, b) for n, a, b in ex["host"] if n == WINDOW_EVENT]
    if len(wins) != 1:
        raise ValueError(f"expected one {WINDOW_EVENT!r} host event, "
                         f"found {len(wins)}")
    return wins[0]


def _top(totals: dict, n_dev: int):
    return [[k, v / n_dev] for k, v in
            sorted(totals.items(), key=lambda kv: -kv[1])[:TOP]]


def reduce(ex: dict) -> dict:
    t0, t1 = window_of(ex)
    devs = sorted(ex["devices"])
    if not devs:
        raise ValueError("the trace holds no TPU device ops")
    host = _HostIndex(_clip(ex["host"], t0, t1))
    busy, op_s, idle_by_host, modules = [], {}, {}, {}
    for d in devs:
        ops = _clip(ex["devices"][d]["ops"], t0, t1)
        spans = _union(ops)
        busy.append(sum(b - a for a, b in spans))
        for n, a, b in ops:
            op_s[n] = op_s.get(n, 0.0) + (b - a)
        edges = [t0] + [x for ab in spans for x in ab] + [t1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                n = host.name_at((a + b) / 2)
                idle_by_host[n] = idle_by_host.get(n, 0.0) + (b - a)
        for n, a, b in _clip(ex["devices"][d]["modules"], t0, t1):
            m = modules.setdefault(n, [0, 0.0])
            m[0] += 1
            m[1] += (b - a) * 1e-9
    n_dev = len(devs)
    return {
        "window_s": (t1 - t0) * 1e-9,
        "busy_s": sum(busy) / n_dev * 1e-9,
        "devices": n_dev,
        "modules": modules,
        # every op name, so that a reader can sum one kernel's instances
        "ops": {k: v / n_dev * 1e-9 for k, v in op_s.items()},
        "device_ops": [[k, v * 1e-9] for k, v in _top(op_s, n_dev)],
        "idle_gaps": [[k, v * 1e-9] for k, v in _top(idle_by_host, n_dev)],
    }


def module_time(reduced: dict, prefix: str):
    """(calls, seconds) of the device programs whose name starts with
    ``prefix``, summed over the devices traced."""
    calls, secs = 0, 0.0
    for name, (c, s) in reduced["modules"].items():
        if name.startswith(prefix):
            calls += c
            secs += s
    return calls, secs
