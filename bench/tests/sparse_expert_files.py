"""A reduced sparse-expert configuration, written as new files into a copy
of the benchmark: a configuration file with Qwen2-MoE's published keys, the
adapter beside it and entries in ``BENCHMARK.json``."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

from conftest import BENCH, ROOT

NAME = "tiny-moe"
WORKLOAD = f"{NAME}.chat"

# Qwen1.5-MoE-A2.7B's keys at CPU size: 8 routed experts of the published
# 8 all held here, 2 a token, a shared expert twice a routed one's width
RAW = {
    "source": "https://huggingface.co/Qwen/Qwen1.5-MoE-A2.7B",
    "family": "moe",
    "model_type": "qwen2_moe",
    "hidden_size": 64,
    "intermediate_size": 128,
    "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 64,
    "num_experts": 8,
    "num_experts_per_tok": 2,
    "norm_topk_prob": False,
    "num_attention_heads": 4,
    "num_key_value_heads": 4,
    "num_hidden_layers": 2,
    "vocab_size": 512,
    "rope_theta": 1000000,
    "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False,
    "published": {"num_experts": 8},
}

ADAPTER = '''"""Qwen2-MoE's block: the sizes, program settings and work counts its
routed and shared experts add."""
import dataclasses

import modelcfg
import work

CALLS = []          # the work counts asked of this adapter, by name


def dims(raw):
    d = modelcfg.dims(raw)
    return dataclasses.replace(
        d, d_ff=int(raw["moe_intermediate_size"]),
        family_sizes=modelcfg.family_sizes({
            "experts": int(raw["published"]["num_experts"]),
            "experts_held": int(raw["num_experts"]),
            "top_k": int(raw["num_experts_per_tok"]),
            "expert_d_ff": int(raw["moe_intermediate_size"]),
            "shared_d_ff": int(raw["shared_expert_intermediate_size"]),
            "shared_gate": 1,
            "norm_topk_prob": bool(raw["norm_topk_prob"])}))


def arch_kwargs(raw):
    return {"moe": {"num_experts": int(raw["num_experts"]),
                    "top_k": int(raw["num_experts_per_tok"]),
                    "shared_experts": int(raw["shared_expert_intermediate_size"])
                    // int(raw["moe_intermediate_size"])}}


def matmul_flops(d):
    CALLS.append("matmul_flops")
    return work.sparse_expert_matmul_flops(d)


def decode_weight_bytes(d, stored, active):
    CALLS.append("decode_weight_bytes")
    return work.sparse_expert_decode_weight_bytes(d, stored, active)
'''


def copy_bench(root: Path) -> dict:
    """A copy of the benchmark under ``root``; returns every file's bytes."""
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    return {p.relative_to(root): p.read_bytes()
            for p in root.rglob("*") if p.is_file()}


def add_sparse_expert_config(root: Path) -> None:
    """The configuration, its adapter and their entries, as new files and
    new entries only."""
    configs = root / "bench" / "configs"
    (configs / f"{NAME}.json").write_text(json.dumps(dict(
        RAW, **{k: json.loads((configs / "qwen3-1.7b.json").read_text())[k]
                for k in ("compression", "serving")})))
    (configs / f"{NAME}.py").write_text(ADAPTER)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": NAME, "source": RAW["source"],
                             "file": f"bench/configs/{NAME}.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": WORKLOAD, "config": NAME,
                               "traffic": "chat", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def unchanged(root: Path, before: dict) -> bool:
    """Whether every file of ``before`` is still there, byte for byte;
    ``BENCHMARK.json`` takes new entries and is left out."""
    after = {p.relative_to(root): p.read_bytes() for p in root.rglob("*")
             if p.is_file() and "__pycache__" not in p.parts}
    return all(after.get(k) == v for k, v in before.items()
               if k != Path("BENCHMARK.json"))
