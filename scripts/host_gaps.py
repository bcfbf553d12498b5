#!/usr/bin/env python3
"""Split a benchmark cell's device idle time by what the host was doing.

Drives one cell of ``BENCHMARK.json`` the way ``bench/run.py`` does (the
same set-up, engine, clients and ramp), with the program's ``obs`` tracer
in profiler mode (``trace.enable(profiler=True)``), so that the
``serve.*`` spans of ``ContinuousEngine.step`` land in the ``jax.profiler``
trace beside the harness's ``bench.*`` spans and the device's operations.

    python3 scripts/host_gaps.py --workload qwen3-1.7b.chat --seed 7 \\
        --seconds 24 [--cost-windows 3 --cost-seconds 40] [--out r.json]

The profiled window is reduced as ``bench/devtrace.py`` reduces a traced
run (busy time, the ``bench.window`` span, devices moved onto the host's
clock), except that every idle gap is labelled, not the ten longest, and
the label is the innermost ``bench.*`` or ``serve.*`` span over the gap's
midpoint (harness labels lose their ``bench.`` prefix, program labels keep
their full name).  Idle time labelled ``ce_step`` or ``serve.step``, the
parents' own time, is split further by the spans the midpoint lies
between.  From the same trace: the host's own time a step
(``serve.step`` minus its ``serve.decode_sync`` child) and the blocking
device-to-host reads a decode step, two in each ``serve.decode_sync`` and
two in each ``serve.first_token``.  From the program's ``Request`` stamps:
the queue wait of the requests whose first token came in the window.

``--cost-windows N`` first measures N pairs of windows, tracer off and in
profiler mode with no profiler session, in the order off on on off off
on ...: the tracer's cost in ``out_tok_s``.  Prints one JSON object.
"""
from __future__ import annotations

import argparse
import bisect
import importlib
import json
import shutil
import statistics
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT / "bench", ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import devtrace  # noqa: E402
from stats import percentile  # noqa: E402

PROGRAM_PREFIX = "serve."
STEP, SYNC, FIRST = "serve.step", "serve.decode_sync", "serve.first_token"
PARENTS = ("bench.ce_step", STEP)   # spans whose own time is split further
READS = {SYNC: 2, FIRST: 2}     # blocking device->host reads in each span
LONG_GAP_S = 0.05

Span = Tuple[str, float, float]     # name, start_s, end_s on the host clock


def collect(profile) -> Tuple[dict, List[Span]]:
    """``devtrace.collect``'s device operations and ``bench.*`` spans, with
    the program's ``serve.*`` spans added to the host spans."""
    devices, host = devtrace.collect(profile)
    for plane in profile.planes:
        if plane.name.startswith(devtrace.DEVICE_PREFIX):
            continue
        for line in plane.lines:
            host.extend((e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                        for e in line.events
                        if e.name.startswith(PROGRAM_PREFIX))
    return devices, host


def idle_gaps(devices: dict, lo: float, hi: float) -> List[Tuple[float,
                                                                 float]]:
    """Every idle interval of every device in ``[lo, hi]``."""
    gaps = []
    for ops in devices.values():
        merged = devtrace.union(devtrace.clip([(s, e) for _, s, e in ops],
                                              lo, hi))
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps.extend((edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
    return gaps


def label_points(points: Sequence[float], host: Sequence[Span]
                 ) -> List[str]:
    """For each point, the shortest span over it (``bench/devtrace.py``'s
    rule, ``start <= t <= end``), the window span left out, or ``other``."""
    spans = [(s, e, name) for name, s, e in host
             if name != devtrace.WINDOW_SPAN]
    by_start = sorted(range(len(spans)), key=lambda i: spans[i][0])
    by_end = sorted(range(len(spans)), key=lambda i: spans[i][1])
    active: Dict[int, Tuple[float, str]] = {}
    i = j = 0
    out: List[Optional[str]] = [None] * len(points)
    for k in sorted(range(len(points)), key=lambda k: points[k]):
        t = points[k]
        while i < len(by_start) and spans[by_start[i]][0] <= t:
            s, e, name = spans[by_start[i]]
            active[by_start[i]] = (e - s, name)
            i += 1
        while j < len(by_end) and spans[by_end[j]][1] < t:
            active.pop(by_end[j], None)
            j += 1
        out[k] = min(active.values())[1] if active else "other"
    return [_short(name) for name in out]


def _short(name: str) -> str:
    return name[len(devtrace.HOST_PREFIX):] \
        if name.startswith(devtrace.HOST_PREFIX) else name


def between(points: Sequence[float], host: Sequence[Span]) -> List[str]:
    """For each point, ``"<last span ended before> -> <next span to
    start>"`` among the spans that are no window or ``PARENTS``: where in a
    parent's own time the point lies."""
    leaves = [(s, e, n) for n, s, e in host
              if n not in PARENTS and n != devtrace.WINDOW_SPAN]
    ends = sorted((e, n) for s, e, n in leaves)
    starts = sorted((s, n) for s, e, n in leaves)
    end_t = [e for e, _ in ends]
    start_t = [s for s, _ in starts]
    out = []
    for t in points:
        k = bisect.bisect_right(end_t, t) - 1
        m = bisect.bisect_left(start_t, t)
        prev = _short(ends[k][1]) if k >= 0 else "start"
        nxt = _short(starts[m][1]) if m < len(starts) else "end"
        out.append(f"{prev} -> {nxt}")
    return out


def host_steps(host: Sequence[Span], lo: float, hi: float) -> dict:
    """Steps, their host time and their reads, from the ``serve.*`` spans
    that start in ``[lo, hi]``."""
    inside = [(n, s, e) for n, s, e in host if lo <= s <= hi]
    steps = sorted((s, e) for n, s, e in inside if n == STEP)
    starts = [s for s, _ in steps]
    sync = [0.0] * len(steps)
    n_sync = 0
    for n, s, e in inside:
        if n != SYNC:
            continue
        n_sync += 1
        k = bisect.bisect_right(starts, s) - 1
        if k >= 0 and e <= steps[k][1]:
            sync[k] += e - s
    reads = sum(READS.get(n, 0) for n, _, _ in inside)
    own = [(e - s) - w for (s, e), w in zip(steps, sync)]
    return {
        "steps": len(steps),
        "decode_steps": n_sync,
        "admissions": sum(n == FIRST for n, _, _ in inside),
        "host_step_ms": statistics.median(own) * 1e3 if own else None,
        "reads_per_decode_step": reads / n_sync if n_sync else None,
    }


def reduce(devices: dict, host: Sequence[Span]) -> dict:
    """Idle seconds by label over the ``bench.window`` span, the gaps of
    ``LONG_GAP_S`` and more with their label, and ``host_steps``."""
    devices = {k: v for k, v in devices.items() if v}
    if not devices:
        raise RuntimeError("the trace holds no device operations")
    windows = [(s, e) for n, s, e in host if n == devtrace.WINDOW_SPAN]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {devtrace.WINDOW_SPAN} span, "
                           f"found {len(windows)}")
    lo, hi = windows[0]
    gaps = idle_gaps(devices, lo, hi)
    labels = label_points([(a + b) / 2 for a, b in gaps], host)
    idle: Dict[str, float] = {}
    for (a, b), label in zip(gaps, labels):
        idle[label] = idle.get(label, 0.0) + (b - a) / len(devices)
    total = sum(idle.values())
    own = [k for k, label in enumerate(labels)
           if label in map(_short, PARENTS)]
    where = between([(gaps[k][0] + gaps[k][1]) / 2 for k in own], host)
    in_parents: Dict[str, float] = {}
    for k, w in zip(own, where):
        key = f"{labels[k]}: {w}"
        in_parents[key] = in_parents.get(key, 0.0) \
            + (gaps[k][1] - gaps[k][0]) / len(devices)
    return {
        "window_s": hi - lo,
        "idle_s": total,
        "n_gaps": len(gaps),
        "idle_by_label_s": dict(sorted(idle.items(), key=lambda kv: -kv[1])),
        "parent_idle_between_s": dict(sorted(in_parents.items(),
                                             key=lambda kv: -kv[1])),
        "long_gaps": sorted(([label, b - a] for (a, b), label
                             in zip(gaps, labels) if b - a >= LONG_GAP_S),
                            key=lambda g: -g[1]),
        **host_steps(host, lo, hi),
    }


def read(xplane: str) -> dict:
    from jax.profiler import ProfileData
    return reduce(*collect(ProfileData.from_file(xplane)))


# ----------------------------------------------------------------- driving

def queue_waits_ms(requests, t0: float, t1: float) -> List[float]:
    """``t_admitted - t_arrival`` of the requests whose first token came in
    ``[t0, t1]`` (``time.monotonic``), in ms."""
    return [(r.t_admitted - r.t_arrival) * 1e3 for r in requests
            if r.t_first_token is not None and t0 <= r.t_first_token <= t1]


def _window(sc, seconds: float, tracer: bool, profiled: bool = False
            ) -> dict:
    from repro.obs import trace as obs_trace
    ce = sc.ce
    n_done, n_steps = len(ce.finished), ce.n_decode_steps
    if tracer:
        obs_trace.enable(profiler=True)
    m0 = time.monotonic()
    try:
        ph = sc.measure(seconds, annotate=profiled)
    finally:
        m1 = time.monotonic()
        obs_trace.disable()
    reqs = ce.finished[n_done:] + [c.req for c in sc.clients]
    waits = queue_waits_ms(reqs, m0, m1)
    return {"tracer": tracer, "profiled": profiled, "seconds": ph.seconds,
            "steps": ph.steps, "decode_steps": ce.n_decode_steps - n_steps,
            "out_tok_s": ph.tokens / ph.seconds,
            "ttft_p95_ms": percentile(ph.ttft, 95) * 1e3,
            "itl_p95_ms": percentile(ph.itl, 95) * 1e3,
            "first_tokens": len(waits),
            "queue_wait_p95_ms": percentile(waits, 95)}


def drive(workload: str, seed: int, seconds: float, cost_windows: int,
          cost_seconds: float) -> dict:
    import jax
    import cell as cells
    import run as bench_run
    from serve_cell import ServedCell
    from repro.launch.compile_cache import setup_compile_cache

    cell = cells.load_cell(workload)
    devices = bench_run.require_chip(cell.chips)
    print(f"compile cache: {setup_compile_cache()}", file=sys.stderr)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    ref = importlib.import_module(f"reference.{cell.dims.family}")
    t = time.perf_counter()
    sc = ServedCell(cell, seed, ref)
    sc.build()
    sc.start()
    sc.ramp()
    out = {"device": devices[0].device_kind, "workload": workload,
           "seed": seed, "setup_s": time.perf_counter() - t, "windows": []}
    for k in range(2 * cost_windows):
        on = k % 4 in (1, 2)                    # off on on off off on ...
        out["windows"].append(_window(sc, cost_seconds, on))
        print(json.dumps(out["windows"][-1]), file=sys.stderr, flush=True)
    tdir = tempfile.mkdtemp(prefix="host-gaps-")
    try:
        jax.profiler.start_trace(tdir)
        try:
            out["profiled"] = _window(sc, seconds, True, profiled=True)
        finally:
            jax.profiler.stop_trace()
        out["profiled"].update(read(devtrace.find_xplane(tdir)))
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    sc.release()
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, default=24.0,
                   help="length of the profiled window")
    p.add_argument("--cost-windows", type=int, default=0)
    p.add_argument("--cost-seconds", type=float, default=40.0)
    p.add_argument("--out", help="also write the result to this file")
    a = p.parse_args(argv)
    text = json.dumps(drive(a.workload, a.seed, a.seconds, a.cost_windows,
                            a.cost_seconds))
    if a.out:
        Path(a.out).parent.mkdir(parents=True, exist_ok=True)
        Path(a.out).write_text(text + "\n")
    print(text, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
