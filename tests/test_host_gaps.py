"""scripts/host_gaps.py's reduction: device idle time split by the
innermost host span, on hand-made events and on the small profiler trace
recorded on one v5e that ``bench/tests`` reads."""
import importlib.util
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
RECORDED = ROOT / "bench" / "tests" / "data" / "v5e_steps.xplane.pb"


@pytest.fixture(scope="module")
def host_gaps():
    path = list(sys.path)
    spec = importlib.util.spec_from_file_location(
        "host_gaps", ROOT / "scripts" / "host_gaps.py")
    mod = importlib.util.module_from_spec(spec)
    try:
        spec.loader.exec_module(mod)
    finally:
        sys.path[:] = path
    return mod


def _nested_step(t0):
    """One 10 ms harness step at ``t0``: ``bench.ce_step`` > ``serve.step``
    > its children, then ``bench.stamp``."""
    return [("bench.ce_step", t0, t0 + 0.010),
            ("serve.step", t0 + 0.0005, t0 + 0.0095),
            ("serve.decode_dispatch", t0 + 0.001, t0 + 0.002),
            ("serve.decode_sync", t0 + 0.002, t0 + 0.007),
            ("serve.commit", t0 + 0.007, t0 + 0.009),
            ("bench.stamp", t0 + 0.010, t0 + 0.011)]


def test_gap_takes_the_innermost_span_over_its_midpoint(host_gaps):
    host = [("bench.window", 0.0, 0.030)] + _nested_step(0.0) \
        + _nested_step(0.012)
    ops = {"/device:TPU:0": [
        ("fusion", 0.0015, 0.0065),     # the first step's decode
        ("fusion", 0.0100, 0.0135),     # the second step's, early
        ("fusion", 0.0140, 0.0180)]}
    r = host_gaps.reduce(ops, host)
    assert r["window_s"] == pytest.approx(0.030)
    assert r["n_gaps"] == 4
    by = r["idle_by_label_s"]
    # 6.5-10.0 ms: midpoint 8.25 under ce_step > serve.step > serve.commit
    assert by["serve.commit"] == pytest.approx(0.0035)
    # 0-1.5 ms: midpoint under serve.step alone; 13.5-14.0: decode_dispatch
    assert by["serve.step"] == pytest.approx(0.0015)
    assert by["serve.decode_dispatch"] == pytest.approx(0.0005)
    # 18-30 ms: midpoint 24 ms after every step: the window alone
    assert by["other"] == pytest.approx(0.012)
    assert r["idle_s"] == pytest.approx(0.030 - 0.0125)
    assert r["long_gaps"] == []
    # serve.step's own idle time lies before the step's first child
    assert r["parent_idle_between_s"] == {
        "serve.step: start -> serve.decode_dispatch": pytest.approx(0.0015)}


def test_parent_own_time_is_split_by_the_spans_around_it(host_gaps):
    host = [("bench.ce_step", 0.0, 1.0), ("serve.step", 0.0, 1.0),
            ("serve.admit_chunk", 0.1, 0.2), ("serve.admit_chunk", 0.4, 0.5),
            ("serve.first_token", 0.7, 0.8)]
    assert host_gaps.between([0.3, 0.6, 0.9], host) == [
        "serve.admit_chunk -> serve.admit_chunk",
        "serve.admit_chunk -> serve.first_token",
        "serve.first_token -> end"]


def test_harness_label_loses_its_prefix_and_long_gaps_are_listed(host_gaps):
    host = [("bench.window", 0.0, 1.0), ("bench.ce_step", 0.0, 0.2),
            ("bench.stamp", 0.2, 0.9)]
    ops = {"/device:TPU:0": [("a", 0.0, 0.2), ("b", 0.9, 1.0)]}
    r = host_gaps.reduce(ops, host)
    assert r["idle_by_label_s"] == {"stamp": pytest.approx(0.7)}
    assert r["long_gaps"] == [["stamp", pytest.approx(0.7)]]
    assert r["steps"] == 0 and r["host_step_ms"] is None


def test_host_step_time_and_reads_per_decode_step(host_gaps):
    host = [("bench.window", 0.0, 0.030)] + _nested_step(0.0) \
        + _nested_step(0.012) + [("serve.first_token", 0.0121, 0.0124)]
    r = host_gaps.host_steps(host, 0.0, 0.030)
    assert (r["steps"], r["decode_steps"], r["admissions"]) == (2, 2, 1)
    # each 9 ms step less its 5 ms decode_sync
    assert r["host_step_ms"] == pytest.approx(4.0)
    # 2 reads in each decode_sync, 2 in the first token: 6 over 2 steps
    assert r["reads_per_decode_step"] == pytest.approx(3.0)


def test_label_points_at_span_edges_and_outside(host_gaps):
    host = [("bench.ce_step", 1.0, 2.0), ("serve.step", 1.2, 1.8)]
    assert host_gaps.label_points([0.5, 1.0, 1.2, 1.9, 2.0, 2.5], host) == \
        ["other", "ce_step", "serve.step", "ce_step", "ce_step", "other"]


def test_recorded_v5e_trace(host_gaps):
    """Five 0.18 ms programs, each followed by 5 ms of host work under
    ``bench.stamp``: the long idle time is the stamps', and idle plus the
    union of the device's operations fill the window."""
    import devtrace
    from jax.profiler import ProfileData
    devices, host = host_gaps.collect(ProfileData.from_file(str(RECORDED)))
    r = host_gaps.reduce(devices, host)
    busy = devtrace.reduce(devices, host)["busy_s"]
    assert r["idle_s"] + busy == pytest.approx(r["window_s"])
    assert r["idle_by_label_s"]["stamp"] > 5 * 0.005
    assert max(r["idle_by_label_s"], key=r["idle_by_label_s"].get) == \
        "stamp"
    assert r["steps"] == 0
