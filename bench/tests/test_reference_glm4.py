"""GLM-4's block (``family: glm4``) through the benchmark on the CPU, at a
tiny size: the served tokens agree with ``reference/glm4.py``, the float8
control fails the same limit, and so does a token altered at sampling.

The tiny cell keeps every setting of ``configs/glm4-9b.json`` but its
sizes: 16 query heads per KV head, a 16-dim head whose first 8 dims rotate,
and an FFN width that is no power of two.  TINY_LIMIT is set the way the
cells' limits are (PERF.md): CPU readings over seeds 1-14, 2**33+11,
2**33+15, 3100001501 and 3100001577 gave at most 0.0039 for the program
(float32-resident weights, against the exactly dequantized reference) and
at least 0.036 for the control; the limit lies between them, 1.5 times the
one and 6 times below the other.
"""
import copy
import json

from conftest import BENCH, CPU_PEAKS, ROOT, TINY_MIX

TINY_LIMIT = 0.006
SIZES = {"hidden_size": 128, "ffn_hidden_size": 208,
         "num_attention_heads": 16, "multi_query_group_num": 1,
         "kv_channels": 16, "num_layers": 2, "padded_vocab_size": 500}


def glm4_tiny_cell(limit=TINY_LIMIT):
    import cell as cells
    import modelcfg
    raw = json.loads((BENCH / "configs" / "glm4-9b.json").read_text())
    raw.update(SIZES)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return cells.Cell(name="glm4-9b.tiny", chips=1, config_name="glm4-9b",
                      traffic_name="tiny", raw=raw, dims=modelcfg.dims(raw),
                      mix=copy.deepcopy(TINY_MIX),
                      end_to_end=bench["end_to_end"],
                      per_layer=bench["per_layer"],
                      limits={"widest_gap": {"limit": limit}}, root=ROOT)


def run_glm4(seed, *, control=False, seconds=1.5):
    import jax
    import run as bench_run
    return bench_run.run(glm4_tiny_cell(), seed, seconds, False,
                         jax.devices(), compile_cache=False, peaks=CPU_PEAKS,
                         control=control)


def test_tiny_cell_is_glm4_block():
    from serve_cell import arch_config
    cfg = arch_config(glm4_tiny_cell())
    assert cfg.family == "dense"
    assert (cfg.qkv_bias, cfg.rotary, cfg.rope_dim, cfg.norm_eps) == \
        (True, "partial_interleaved", 8, 1.5625e-7)
    assert cfg.n_heads // cfg.n_kv_heads == 16 and not cfg.tie_embeddings


def test_served_tokens_agree_with_the_reference():
    res = run_glm4(2 ** 33 + 11)
    gap = res["checks"]["widest_gap"]
    assert res["correct"], gap
    assert 0.0 <= gap["value"] <= TINY_LIMIT
    assert res["attempted"] > 0 and res["failed"] == 0


def test_control_fails_the_limit():
    import run as bench_run
    res = run_glm4(5, control=True, seconds=1.0)
    assert res["correct"]
    assert not bench_run.within(res["checks"]["widest_gap_control"]["value"],
                                TINY_LIMIT)


def test_altered_token_is_not_correct(monkeypatch):
    """Every decode step's sampled ids come out one higher."""
    from repro.serving.batching import engine as batching_engine
    real = batching_engine._sample_slots

    def altered(logits, keys, temps, vocab):
        tok, keys = real(logits, keys, temps, vocab)
        return (tok + 1) % vocab, keys

    monkeypatch.setattr(batching_engine, "_sample_slots", altered)
    res = run_glm4(7)
    assert not res["correct"]
    assert res["checks"]["widest_gap"]["value"] > 10 * TINY_LIMIT

