"""Reduce a ``jax.profiler`` trace to device busy time, idle gaps and the
device operations that took most time.

Busy time is the union of the intervals in which an operation ran on a
device (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), clipped to
the traced window and averaged over the devices.  Each idle gap is labelled
with the harness's own host span (``jax.profiler.TraceAnnotation`` named
``bench.<what>``) that covers the gap's midpoint, or ``other``.

The device planes run on a clock of their own, a millisecond or two from
the host's.  Each program the host enqueues (``DoEnqueueProgram``) shows
once on the device (``XLA Modules``); where the counts agree, the k-th of
each are paired and the median of (enqueue end - module start) moves the
device onto the host's clock, so that no module starts before the host
enqueued it.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ENQUEUE = "DoEnqueueProgram"
HOST_PREFIX = "bench."
WINDOW_SPAN = "bench.window"    # the harness's span around the traced window
TOP = 10

Interval = Tuple[float, float]      # seconds on the trace's clock


def find_xplane(trace_dir: str) -> str:
    """The one ``.xplane.pb`` file a profiler session wrote under
    ``trace_dir``."""
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {found}")
    return found[0]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge overlapping intervals; sorted, disjoint."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def collect(profile) -> Tuple[Dict[str, List[Tuple[str, float, float]]],
                              List[Tuple[str, float, float]]]:
    """``({device plane: [(op, start_s, end_s)]}, [(host span, start_s,
    end_s)])`` from a ``jax.profiler.ProfileData``, device times moved onto
    the host's clock."""
    devices: Dict[str, List[Tuple[str, float, float]]] = {}
    modules: Dict[str, List[float]] = {}
    host: List[Tuple[str, float, float]] = []
    enqueued: List[float] = []
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                               for e in line.events)
                elif line.name == MODULES_LINE:
                    modules.setdefault(plane.name, []).extend(
                        e.start_ns * 1e-9 for e in line.events)
        else:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append((e.name, e.start_ns * 1e-9,
                                     e.end_ns * 1e-9))
                    elif e.name == ENQUEUE:
                        enqueued.append(e.end_ns * 1e-9)
    for name, ops in devices.items():
        shift = clock_shift(sorted(enqueued), sorted(modules.get(name, [])))
        devices[name] = [(n, a + shift, b + shift) for n, a, b in ops]
    return devices, host


def clock_shift(enqueued: Sequence[float], starts: Sequence[float]) -> float:
    """Seconds to add to device times: the median of (k-th enqueue end -
    k-th module start), or 0 where the counts differ (one device of
    several, or a trace cut mid-program)."""
    if not starts or len(enqueued) != len(starts):
        return 0.0
    d = sorted(e - s for e, s in zip(enqueued, starts))
    return d[len(d) // 2]


def reduce(devices, host, window: Optional[Interval] = None) -> dict:
    """Busy seconds (mean over devices), the traced window, the top device
    operations by total time and the longest idle gaps with their host
    label.  ``window`` defaults to the harness's ``bench.window`` span, or
    to the extent of its host spans where there is none.
    """
    devices = {k: v for k, v in devices.items() if v}   # the chips in use
    if not devices:
        raise RuntimeError("the trace holds no device operations")
    if window is None:
        if not host:
            raise RuntimeError("the trace holds no bench.* host spans to "
                               "take its window from")
        spans = [(s, e) for name, s, e in host if name == WINDOW_SPAN] \
            or [(s, e) for _, s, e in host]
        window = (min(s for s, _ in spans), max(e for _, e in spans))
    lo, hi = window
    busy, gaps = [], []
    per_op: Dict[str, float] = defaultdict(float)
    for ops in devices.values():
        merged = union(clip([(s, e) for _, s, e in ops], lo, hi))
        busy.append(sum(b - a for a, b in merged))
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps.extend((edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
        for name, s, e in ops:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                per_op[name] += e - s
    n = len(devices)
    labelled = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]:
        mid = (a + b) / 2
        # the innermost harness span over the midpoint names the gap
        over = [(e - s, name) for name, s, e in host
                if s <= mid <= e and name != WINDOW_SPAN]
        label = min(over)[1][len(HOST_PREFIX):] if over else "other"
        labelled.append([label, b - a])
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "busy_s": sum(busy) / n,
        "window_s": hi - lo,
        "device_ops": [[name, t / n] for name, t in top],
        "idle_gaps": labelled,
    }


def read(trace_dir: str, window: Optional[Interval] = None) -> dict:
    """``reduce`` of the profiler session written under ``trace_dir``."""
    from jax.profiler import ProfileData
    profile = ProfileData.from_file(find_xplane(trace_dir))
    devices, host = collect(profile)
    return reduce(devices, host, window)
