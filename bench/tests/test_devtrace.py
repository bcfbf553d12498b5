"""The trace reduction, on a small profiler trace recorded on one v5e
(``record_trace.py``: five steps of two 0.09 ms fusions, each followed by
5 ms of host work under ``bench.stamp``) and on hand-made events."""
import pytest

from conftest import BENCH

import devtrace

RECORDED = BENCH / "tests" / "data" / "v5e_steps.xplane.pb"


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData
    return devtrace.collect(ProfileData.from_file(str(RECORDED)))


def test_recorded_trace_busy_and_window(recorded):
    devices, host = recorded
    assert list(devices) == ["/device:TPU:0"]
    r = devtrace.reduce(devices, host)
    # five programs of about 0.181 ms each, all inside the bench.window span
    assert 5 * 0.170e-3 < r["busy_s"] < 5 * 0.190e-3
    assert 0.030 < r["window_s"] < 0.036
    assert all("fusion" in name or "copy" in name
               for name, _ in r["device_ops"])
    assert abs(sum(t for _, t in r["device_ops"]) - r["busy_s"]) < 1e-5


def test_recorded_trace_gaps_are_the_host_stamps(recorded):
    """Once the device is moved onto the host's clock, the five long idle
    gaps fall in the 5 ms host sleeps."""
    r = devtrace.reduce(*recorded)
    longest = r["idle_gaps"][:5]
    assert [label for label, _ in longest] == ["stamp"] * 5
    assert all(0.005 < s < 0.0075 for _, s in longest)


def test_union_clip_and_labels():
    ops = {"/device:TPU:0": [("a", 0.0, 1.0), ("b", 0.5, 2.0), ("c", 3.0, 4.0)],
           "/device:TPU:1": [("a", 0.0, 4.0)]}
    host = [("bench.window", 0.0, 5.0), ("bench.ce_step", 2.0, 3.5),
            ("bench.stamp", 2.4, 2.6)]
    r = devtrace.reduce(ops, host)
    assert r["window_s"] == 5.0
    assert r["busy_s"] == (3.0 + 4.0) / 2          # mean over the devices
    gaps = {tuple(g) for g in r["idle_gaps"]}
    # 2.0-3.0 has its midpoint in the innermost span, bench.stamp
    assert ("stamp", 1.0) in gaps
    # 4.0-5.0 lies outside every span but the window
    assert ("other", 1.0) in gaps
    assert r["device_ops"][0] == ["a", (1.0 + 4.0) / 2]


def test_clock_shift():
    assert devtrace.clock_shift([1.0, 2.0, 3.0], [0.9, 1.8, 2.9]) == \
        pytest.approx(0.1)
    assert devtrace.clock_shift([1.0, 2.0], [0.9]) == 0.0


def test_empty_trace_is_an_error():
    with pytest.raises(RuntimeError):
        devtrace.reduce({"/device:TPU:0": []}, [("bench.window", 0, 1)])
