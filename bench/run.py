"""Run one benchmark cell once on the chip and print its result.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic, metrics and limits are found by name
from ``BENCHMARK.json`` (see ``bench/cell.py``).  Before any work the run
fails, printing no result, unless JAX's first device is a TPU and there are
as many as the cell asks for.  Set-up (weights from the seed, compression,
load, and a closed-loop ramp that compiles every shape the traffic uses) is
timed from process start.  Then the closed loop is measured for
``--seconds``: with ``--trace 0`` the whole window gives the cell's
end-to-end metrics; with ``--trace 1`` the first 40% runs with the
program's fenced ``obs`` spans and the rest under ``jax.profiler``, and they
give its per-layer metrics.  Afterwards the program is released and the
float32 reference checks a sample of the served tokens (``bench/check.py``).

The last lines on standard error are the numbers compared, each beside its
limit; the last line on standard output is one JSON object.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import cell as cells  # noqa: E402

TRACE_OBS_SHARE = 0.4       # --trace 1: share of the window under obs spans


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def require_chip(chips: int):
    """JAX's devices, if they are at least ``chips`` TPUs; else exit."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench/run.py: JAX's first device is "
                         f"{devs[0].platform!r} ({devs[0].device_kind}), not "
                         f"a TPU; nothing was run")
    if len(devs) < chips:
        raise SystemExit(f"bench/run.py: the cell needs {chips} TPUs, JAX "
                         f"sees {len(devs)}; nothing was run")
    return devs


def within(gap, limit) -> bool:
    """The test ``correct`` applies to a widest gap: a number, at most the
    limit.  No number (a token off the vocabulary) or no limit fails."""
    return (gap is not None and limit is not None and math.isfinite(gap)
            and gap <= limit)


def _phase_spans(tracer) -> dict:
    out: dict = {}
    for e in tracer.events:
        out.setdefault(e.name, []).append(e.dur_us * 1e-6)
    return out


def run(cell, seed: int, seconds: float, trace: bool, devices, *,
        compile_cache: bool = True, peaks: dict = None,
        control: bool = False) -> dict:
    """One run of ``cell``; returns the result object.  ``peaks`` defaults
    to the row of ``bench/peaks.json`` for the device.  ``control`` also
    reads the float8 control's gap on the same requests
    (``checks["widest_gap_control"]``; ``bench/control.py``)."""
    import jax
    from compiles import CompileLog
    from serve_cell import ServedCell
    import check
    if compile_cache:
        from repro.launch.compile_cache import setup_compile_cache
        log(f"compile cache: {setup_compile_cache()}")
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compiles = CompileLog().register()
    ref = importlib.import_module(f"reference.{cell.dims.family}")

    sc = ServedCell(cell, seed, ref)
    sc.build()
    sc.start()
    ramp_s = sc.ramp()
    setup = dict(sc.setup, ramp_s=ramp_s,
                 setup_s=time.perf_counter() - T_START)
    log(f"set-up: init {setup['init_s']:.2f}s, compress "
        f"{setup['compress_s']:.2f}s, load {setup['load_s']:.2f}s, ramp "
        f"{ramp_s:.2f}s; setup_s {setup['setup_s']:.2f}; {compiles.n} "
        f"compiles ({compiles.secs:.1f}s), {compiles.cache_hits} "
        f"persistent-cache hits")
    compiles.mark()

    spans = device = None
    if not trace:
        window = sc.measure(seconds)
        phases = [window]
    else:
        from repro.obs import trace as obs_trace
        obs_trace.enable(sync=True)
        first = sc.measure(seconds * TRACE_OBS_SHARE)
        spans = _phase_spans(obs_trace.disable())
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
        try:
            jax.profiler.start_trace(tdir)
            window = sc.measure(seconds * (1 - TRACE_OBS_SHARE),
                                annotate=True)
            jax.profiler.stop_trace()
            import devtrace
            device = devtrace.read(tdir)
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        phases = [first, window]
    in_window = compiles.since_mark
    stats = devices[0].memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    sent = sum(p.sent for p in phases)
    failed = sum(p.failed for p in phases)
    done = [r for p in phases for r in p.done]
    log(f"window: {window.seconds:.3f}s, {window.steps} steps, "
        f"{window.decode_steps} decode steps, {window.tokens} tokens, "
        f"{len(window.ttft)} first tokens, {sent} requests sent, "
        f"{len(done)} ended; {in_window} compiles inside the window; "
        f"peak_bytes_in_use {peak}")
    sc.release()

    mix, d = cell.mix, cell.dims
    picked = check.sample(done, seed, int(mix["check_tokens"]),
                          int(mix["check_requests"]))
    t = time.perf_counter()
    bits = int(cell.raw["compression"]["bits"])
    weights = ref.reference_weights(d, seed, bits)
    seq_len = -(-int(mix["max_len"]) // ref.Q_BLOCK) * ref.Q_BLOCK
    sampled = [done[i] for i in picked]
    gap, n_cmp = check.widest_gap(ref, d, weights, sampled, seq_len,
                                  int(mix["output"]["max"]))
    gap_control = None
    if control:
        gap_control, _ = check.widest_gap(ref, d, weights, sampled, seq_len,
                                          int(mix["output"]["max"]),
                                          control=True)
    del weights
    log(f"check: {len(picked)} requests, {n_cmp} served tokens against the "
        f"float32 reference in {time.perf_counter() - t:.1f}s")
    limit = cell.limits.get("widest_gap", {}).get("limit")
    correct = bool(picked) and within(gap, limit)

    view = types.SimpleNamespace(
        setup=setup, window=window, spans=spans, device=device,
        peak_bytes=peak, dims=d, mix=mix,
        peaks=peaks or cells.peaks(devices[0].device_kind, cell.root))
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = cells.reader(m["name"], cell.root)(view)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": sent, "failed": failed,
              "metrics": metrics, "device": dev}
    if device is not None:
        dev["busy_s"] = device["busy_s"]
        dev["window_s"] = device["window_s"]
        result["breakdown"] = {"device_ops": device["device_ops"],
                               "idle_gaps": device["idle_gaps"]}
    result["checks"] = {"widest_gap": {
        "value": gap if math.isfinite(gap) else None, "limit": limit}}
    if control:
        result["checks"]["widest_gap_control"] = {"value": gap_control,
                                                  "limit": limit}
    return result


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = p.parse_args(argv)
    cell = cells.load_cell(a.workload)
    devices = require_chip(cell.chips)
    log(f"device: {devices[0].device_kind} x{len(devices)}; workload "
        f"{cell.name}, seed {a.seed}, {a.seconds}s, trace {a.trace}")
    result = run(cell, a.seed, a.seconds, bool(a.trace), devices)
    for name, c in result["checks"].items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
