"""Shared fixtures: the benchmark's modules on the path, and tiny cells
made from the real configuration files for CPU runs."""
from __future__ import annotations

import copy
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# tiny cell -> (configuration file, published key -> the value a CPU run
# takes); "untied" gives the same block an untied head, no qk-norm, 8 query
# heads per KV head and an FFN that is no power of two, as GLM-4 has
TINY = {
    "qwen3-1.7b": ("qwen3-1.7b", {
        "hidden_size": 128, "intermediate_size": 256,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
        "num_hidden_layers": 2, "vocab_size": 512}),
    "untied": ("qwen3-1.7b", {
        "hidden_size": 128, "intermediate_size": 208,
        "num_attention_heads": 8, "num_key_value_heads": 1, "head_dim": 16,
        "num_hidden_layers": 2, "vocab_size": 500,
        "tie_word_embeddings": False, "qk_norm": False}),
}

TINY_MIX = {"clients": 4, "slots": 4, "prefill_chunk": 16,
            "admit_chunks_per_step": 2, "max_len": 512,
            "prompt": {"median": 24, "sigma": 0.693, "min": 8, "max": 64},
            "output": {"median": 10, "sigma": 0.693, "min": 4, "max": 24},
            "check_tokens": 60, "check_requests": 4}

CPU_PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}


def tiny_cell(config: str, limit=None):
    """The tiny cell ``config`` of ``TINY`` at CPU size: every setting of
    its configuration file that ``TINY`` leaves, small widths, a small
    closed loop."""
    import cell as cells
    import modelcfg
    file, sizes = TINY[config]
    raw = json.loads((BENCH / "configs" / f"{file}.json").read_text())
    raw.update(sizes)
    dims = modelcfg.dims(raw)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    limits = {} if limit is None else {"widest_gap": {"limit": limit}}
    return cells.Cell(name=f"{config}.tiny", chips=1, config_name=file,
                      traffic_name="tiny", raw=raw, dims=dims,
                      mix=copy.deepcopy(TINY_MIX),
                      end_to_end=bench["end_to_end"],
                      per_layer=bench["per_layer"], limits=limits, root=ROOT)


@pytest.fixture
def run_tiny():
    """``run_tiny(config, seed, trace=False, limit=None) -> result``."""
    import jax
    import run as bench_run

    def go(config, seed, *, trace=False, limit=None, seconds=1.5):
        return bench_run.run(tiny_cell(config, limit), seed, seconds, trace,
                             jax.devices(), compile_cache=False,
                             peaks=CPU_PEAKS)
    return go


@pytest.fixture
def run_tiny_cell():
    """A started, ramped ``ServedCell`` of the tiny Qwen3 cell."""
    from reference import dense
    from serve_cell import ServedCell
    sc = ServedCell(tiny_cell("qwen3-1.7b"), 3, dense)
    sc.build()
    sc.start()
    sc.ramp()
    yield sc
    sc.release()
