"""From a profiler trace to the device numbers of a run.

``jax.profiler`` writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
:func:`load` keeps what the reduction needs, on the trace's one clock
(nanoseconds; device planes are already on the host's time base):

* device operations: events of the ``XLA Ops`` line of each
  ``/device:TPU:<i>`` plane, as ``(name, start, duration)``;
* host events: every event of the ``/host:CPU`` plane, which holds the
  benchmark's ``jax.profiler.TraceAnnotation`` spans (``bench:...``);
* the window: the ``bench:window`` span the benchmark opens around its
  measured window.

:func:`reduce` turns that into busy seconds (union of the device
operations' intervals inside the window, averaged over the devices
traced), idle share, time in operations whose text holds a marker (a
kernel's time), the operations that took most time, and the idle gaps
by what the host was doing in them.
"""
from __future__ import annotations

import collections
import glob
import gzip
import heapq
import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench:window"
#: marker of a Pallas (Mosaic) kernel in an XLA op's text
PALLAS_MARKER = 'custom_call_target="tpu_custom_call"'
#: ``%name = <result type> <opcode>(``; a tuple type holds spaces
_HLO = re.compile(r"^%?[\w.\-]+ = (.+?) ([a-z][\w\-]*)\(")


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise FileNotFoundError(f"{log_dir}: {len(paths)} xplane files")
    return paths[0]


def load(xplane_path: str) -> dict:
    """The device operations, host events and window of one trace."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    devices, host = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = [(e.name, e.start_ns, e.duration_ns)
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            devices.append(ops)
        elif plane.name == "/host:CPU":
            host += [(e.name, e.start_ns, e.duration_ns)
                     for line in plane.lines for e in line.events]
    windows = [(s, s + d) for n, s, d in host if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"{xplane_path}: {len(windows)} {WINDOW_SPAN} spans")
    return {"devices": devices, "host": host, "window": list(windows[0])}


def save(trace: dict, path: str) -> None:
    """Write a loaded trace as gzipped JSON (what the tests read)."""
    with gzip.open(path, "wt") as f:
        json.dump(trace, f)


def read_saved(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """``(start, end)`` pairs cut to ``[lo, hi]``; empty ones dropped."""
    out = []
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((s, e))
    return out


def union(intervals) -> list[tuple[float, float]]:
    """The disjoint, sorted union of ``(start, end)`` pairs."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    """The parts of ``[lo, hi]`` that the disjoint sorted ``busy``
    intervals leave uncovered."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def op_kind(name: str) -> str:
    """A device operation's opcode and result type (``fusion
    f32[32,1000]``), or its name where the text is not an HLO
    instruction; Pallas kernels read ``tpu_custom_call <type>``."""
    m = _HLO.match(name)
    if not m:
        return name[:120]
    typ, opcode = m.groups()
    if PALLAS_MARKER in name:
        opcode = "tpu_custom_call"
    return f"{opcode} {typ}"[:120]


def host_activity(host, points) -> list[str]:
    """For each time in ``points`` (ascending), the innermost host event
    running then (the shortest that covers it), or ``idle host``."""
    events = sorted(host, key=lambda h: h[1])
    active: list = []  # heap of (duration, end, name)
    out, j = [], 0
    for t in points:
        while j < len(events) and events[j][1] <= t:
            name, s, d = events[j]
            heapq.heappush(active, (d, s + d, name))
            j += 1
        while active and active[0][1] <= t:
            heapq.heappop(active)
        out.append(active[0][2] if active else "idle host")
    return out


def reduce(trace: dict, markers: dict | None = None, top: int = 10) -> dict:
    """Busy and idle time of the traced window.

    ``markers`` maps a label to a substring of an operation's text; the
    result's ``kernel_s[label]`` sums the device time of the operations
    that hold it (intervals clipped to the window, averaged over the
    devices).  ``device_ops`` and ``idle_gaps`` are ``[name, seconds]``
    lists, longest first, at most ``top`` each."""
    lo, hi = trace["window"]
    window_s = (hi - lo) / 1e9
    n_dev = max(len(trace["devices"]), 1)
    busy_ns = 0.0
    by_kind: collections.Counter = collections.Counter()
    kernel_ns = {label: 0.0 for label in (markers or {})}
    gap_list = []
    for ops in trace["devices"]:
        kept = []
        for name, s, d in ops:
            cut = clip([(s, s + d)], lo, hi)
            if not cut:
                continue
            (a, b), = cut
            kept.append((a, b))
            by_kind[op_kind(name)] += b - a
            for label, marker in (markers or {}).items():
                if marker in name:
                    kernel_ns[label] += b - a
        busy = union(kept)
        busy_ns += sum(e - s for s, e in busy)
        gap_list += gaps(busy, lo, hi)
    by_host: collections.Counter = collections.Counter()
    host = [h for h in trace["host"] if h[0] != WINDOW_SPAN]
    gap_list.sort()
    names = host_activity(host, [(s + e) / 2 for s, e in gap_list])
    for (s, e), name in zip(gap_list, names):
        by_host[name] += e - s
    busy_s = busy_ns / n_dev / 1e9
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "kernel_s": {k: v / n_dev / 1e9 for k, v in kernel_ns.items()},
        "device_ops": [[k, v / n_dev / 1e9]
                       for k, v in by_kind.most_common(top)],
        "idle_gaps": [[k, v / n_dev / 1e9]
                      for k, v in by_host.most_common(top)],
    }
