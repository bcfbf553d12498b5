"""Find everything one cell needs by the names ``BENCHMARK.json`` gives.

A cell (an entry of ``workloads``) names a configuration and a traffic mix;
each lives in a file of its own, and so does every metric's reader:

- ``configs[*].file``              the configuration (``bench/configs/``)
- the ``.py`` beside that file      where the block needs it, the family's
                                   sizes, program settings and work counts
                                   (``bench/modelcfg.py``)
- ``bench/traffic/<traffic>.json`` the traffic mix and the server's slots,
                                   chunk and cache length
- ``bench/metrics/<metric>.py``    ``read(run) -> float | None``
- ``bench/limits/<workload>.json`` the limit of each number ``correct``
                                   compares, with the readings it was set from
- ``bench/peaks.json``             the chip's peaks, by ``device_kind``

A new cell, mix, metric or configuration is a new file and a new entry;
no file that exists needs an edit.  That holds for a block ``Dims`` cannot
state too (sparse experts, say): its adapter brings the family's sizes into
``Dims.family_sizes``, the program's ``ArchConfig`` settings and its own
count of operations and bytes.  A family with no reference module yet
(``bench/reference/<family>.py``) brings that as a new file as well.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional

import modelcfg

ROOT = Path(__file__).resolve().parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    raw: dict                   # the configuration file's contents
    dims: modelcfg.Dims
    mix: dict                   # the traffic file's contents
    end_to_end: List[dict]      # the metric entries this cell reports
    per_layer: List[dict]
    limits: Dict[str, dict]     # number compared -> {"limit": ..., ...}
    root: Path
    adapter: Optional[ModuleType] = None    # the configuration's ``.py``


def _reported(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    raw, dims, adapter = modelcfg.load(root / conf["file"])
    mix = json.loads((root / "bench" / "traffic" / f"{w['traffic']}.json")
                     .read_text())
    lim_file = root / "bench" / "limits" / f"{workload}.json"
    limits = json.loads(lim_file.read_text()) if lim_file.exists() else {}
    return Cell(name=workload, chips=int(w["chips"]), config_name=conf["name"],
                traffic_name=w["traffic"], raw=raw, dims=dims, mix=mix,
                end_to_end=[m for m in bench["end_to_end"]
                            if _reported(m, workload)],
                per_layer=[m for m in bench["per_layer"]
                           if _reported(m, workload)],
                limits=limits, root=root, adapter=adapter)


def reader(metric: str, root: Path = ROOT) -> Callable[[object],
                                                       Optional[float]]:
    """The ``read`` function of ``bench/metrics/<metric>.py``."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str, root: Path = ROOT) -> dict:
    """The chip's published peaks; a chip not in the table is an error."""
    table = json.loads((root / "bench" / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (known: {sorted(table['devices'])})")
    return table["devices"][device_kind]
