"""A configuration, a traffic mix and a metric added under new names are
found by name, with no edit to any file the benchmark already has."""
import json
import shutil

from conftest import BENCH, ROOT

import cell as cells


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
