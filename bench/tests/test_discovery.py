"""A configuration, a traffic mix and a metric added under new names are
found by name, with no edit to any file the benchmark already has; so is a
sparse-expert block's adapter, with its sizes, program settings and work
counts."""
import json
import shutil

import pytest
from conftest import BENCH, ROOT

import cell as cells
import sparse_expert_files as moe_files
import work


def test_new_files_are_found_by_name(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p.relative_to(tmp_path): p.read_bytes()
              for p in (tmp_path / "bench").rglob("*") if p.is_file()}
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())

    # new files only
    raw = json.loads((BENCH / "configs" / "qwen3-1.7b.json").read_text())
    raw["num_hidden_layers"] = 8
    (tmp_path / "bench" / "configs" / "new-model.json").write_text(
        json.dumps(raw))
    mix = json.loads((BENCH / "traffic" / "chat.json").read_text())
    mix["clients"] = 8
    (tmp_path / "bench" / "traffic" / "new-mix.json").write_text(
        json.dumps(mix))
    (tmp_path / "bench" / "metrics" / "new_metric.py").write_text(
        "def read(run):\n    return 2.0 * run.window.tokens\n")
    # new entries in BENCHMARK.json
    bench["configs"].append(dict(bench["configs"][0], name="new-model",
                                 file="bench/configs/new-model.json"))
    bench["workloads"].append({"name": "new-model.new-mix",
                               "config": "new-model", "traffic": "new-mix",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "new_metric", "unit": "%",
                               "better": "higher", "source": "host_clock",
                               "layer": "scheduler", "moves": "out_tok_s",
                               "workloads": ["new-model.new-mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    c = cells.load_cell("new-model.new-mix", root=tmp_path)
    assert c.dims.layers == 8 and c.mix["clients"] == 8
    assert "new_metric" in [m["name"] for m in c.per_layer]
    old = cells.load_cell("qwen3-1.7b.chat", root=tmp_path)
    assert "new_metric" not in [m["name"] for m in old.per_layer]

    class Window:
        tokens = 21
    read = cells.reader("new_metric", root=tmp_path)
    assert read(type("Run", (), {"window": Window})) == 42.0

    after = {p.relative_to(tmp_path): p.read_bytes()
             for p in (tmp_path / "bench").rglob("*") if p.is_file()
             and "__pycache__" not in p.parts}
    assert {k: v for k, v in after.items() if k in before} == before


def test_a_sparse_expert_block_is_new_files_only(tmp_path):
    from repro.configs.base import MoEConfig
    from serve_cell import arch_config
    before = moe_files.copy_bench(tmp_path)
    moe_files.add_sparse_expert_config(tmp_path)

    c = cells.load_cell(moe_files.WORKLOAD, root=tmp_path)
    cfg = arch_config(c)
    assert cfg.family == "moe" and cfg.d_ff == 32 and cfg.n_kv_heads == 4
    assert cfg.moe == MoEConfig(num_experts=8, top_k=2, shared_experts=2)
    assert c.dims.size("experts_held") == 8 and c.dims.size("top_k") == 2
    assert c.dims.size("norm_topk_prob") is False
    hash(c.dims)            # a static argument of the references' jit

    # the family's counts, not the dense block's
    assert work.matmul_flops(c.dims, c.adapter) == \
        work.sparse_expert_matmul_flops(c.dims) != work.matmul_flops(c.dims)
    stored = {name: 800 for name in work.EXPERT_TENSORS}
    stored.update({"embed": 1024, "lm_head": 512, "layers/wq": 64})
    assert work.decode_weight_bytes(c.dims, stored, 1, c.adapter) == \
        work.sparse_expert_decode_weight_bytes(c.dims, stored, 1) < \
        work.decode_weight_bytes(c.dims, stored, 1)
    assert c.adapter.CALLS == ["matmul_flops", "decode_weight_bytes"]
    assert moe_files.unchanged(tmp_path, before)


@pytest.mark.parametrize("workload", ["qwen3-1.7b.chat", "glm4-9b.longctx"])
def test_existing_configs_reach_the_program_as_before(workload):
    """No adapter: the ``ArchConfig`` of the sizes ``Dims`` states, as
    before configurations could declare more."""
    import modelcfg
    from repro.configs.base import ArchConfig
    from serve_cell import arch_config
    c = cells.load_cell(workload)
    d = c.dims
    assert c.adapter is None and d.family_sizes == ()
    assert d == modelcfg.dims(c.raw)
    assert arch_config(c) == ArchConfig(
        name=c.config_name, family=d.family, n_layers=d.layers,
        d_model=d.d_model, n_heads=d.heads, n_kv_heads=d.kv_heads,
        d_ff=d.d_ff, vocab=d.vocab, head_dim=d.head_dim, qk_norm=d.qk_norm,
        rope_theta=d.rope_theta, tie_embeddings=d.tie,
        source=c.raw.get("source", ""))


@pytest.mark.parametrize("declared, error", [
    ({"n_experts": 8}, "takes no"),             # not an ArchConfig field
    ({"n_layers": 3}, "comes from"),            # Dims sets it
    ({"frontend": {"a": 1}}, "no dataclass"),   # a dict for a plain field
])
def test_a_wrong_program_setting_is_an_error(tmp_path, declared, error):
    from serve_cell import arch_config
    moe_files.copy_bench(tmp_path)
    moe_files.add_sparse_expert_config(tmp_path)
    c = cells.load_cell(moe_files.WORKLOAD, root=tmp_path)
    c.adapter = type("Adapter", (), {
        "arch_kwargs": staticmethod(lambda raw: declared)})
    with pytest.raises(TypeError, match=error):
        arch_config(c)


def test_every_metric_and_cell_has_its_files():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").exists(), m["name"]
    for w in bench["workloads"]:
        c = cells.load_cell(w["name"])
        assert c.end_to_end and c.per_layer
    assert cells.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_unknown_device_is_an_error():
    try:
        cells.peaks("cpu")
    except KeyError:
        return
    raise AssertionError("a device missing from the peaks table passed")
