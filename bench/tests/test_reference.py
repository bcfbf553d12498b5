"""The reference against the served path, on each configuration reduced
to CPU size: sound runs pass, a token altered where it is produced fails,
and so does the float8 control put in the program's place.

TINY_LIMIT is this size's limit, set the way the cells' limits are
(PERF.md): CPU readings over seeds 1-6, 9 and 2**33+11 of both tiny cells
gave at most 0.0093 for the program (float32-resident weights, against the
exactly dequantized reference) and at least 0.065 for the control.
"""
import pytest

TINY_LIMIT = 0.03
CONFIGS = ["qwen3-1.7b", "untied"]


@pytest.mark.parametrize("config", CONFIGS)
def test_served_tokens_agree_with_the_reference(run_tiny, config):
    res = run_tiny(config, 2 ** 33 + 11, limit=TINY_LIMIT)
    gap = res["checks"]["widest_gap"]
    assert res["correct"], gap
    assert 0.0 <= gap["value"] <= TINY_LIMIT
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"out_tok_s", "ttft_p95_ms", "itl_p95_ms",
                                   "setup_s"}      # no peak on the CPU
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("config", CONFIGS)
def test_control_fails_the_limit(run_tiny, config):
    import jax
    import run as bench_run
    from conftest import CPU_PEAKS, tiny_cell
    res = bench_run.run(tiny_cell(config, TINY_LIMIT), 5, 1.0, False,
                        jax.devices(), compile_cache=False, peaks=CPU_PEAKS,
                        control=True)
    assert res["correct"]
    assert res["checks"]["widest_gap"]["value"] <= TINY_LIMIT
    # the control, judged by the test that decides ``correct``
    assert not bench_run.within(res["checks"]["widest_gap_control"]["value"],
                                TINY_LIMIT)


def test_altered_token_is_not_correct(run_tiny, monkeypatch):
    """Every decode step's sampled ids come out one higher."""
    from repro.serving.batching import engine as batching_engine
    real = batching_engine._sample_slots

    def altered(logits, keys, temps, vocab):
        tok, keys = real(logits, keys, temps, vocab)
        return (tok + 1) % vocab, keys

    monkeypatch.setattr(batching_engine, "_sample_slots", altered)
    res = run_tiny("qwen3-1.7b", 7, limit=TINY_LIMIT)
    assert not res["correct"]
    assert res["checks"]["widest_gap"]["value"] > 10 * TINY_LIMIT


def test_traced_run_reports_per_layer_metrics(run_tiny, monkeypatch):
    """--trace 1 on the CPU, with the trace reduction stubbed (a CPU trace
    has no TPU plane): every per-layer metric is reported, and the device
    busy time and window ride in ``device``."""
    import devtrace
    stub = {"busy_s": 0.3, "window_s": 0.9, "device_ops": [["fusion", 0.3]],
            "idle_gaps": [["ce_step", 0.01]]}
    monkeypatch.setattr(devtrace, "read", lambda _dir: stub)
    res = run_tiny("untied", 9, trace=True, limit=TINY_LIMIT)
    assert res["correct"]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(m) == {"init_s", "compress_s", "load_s", "slot_occupancy",
                      "prefill_share", "mfu", "decode_hbm_share",
                      "device_idle_share"}
    assert 0 < m["slot_occupancy"] <= 100 and 0 < m["prefill_share"] < 100
    assert abs(m["device_idle_share"] - 100 * (1 - 0.3 / 0.9)) < 1e-9
    assert res["device"]["busy_s"] == 0.3
    assert res["breakdown"]["device_ops"] == [["fusion", 0.3]]
