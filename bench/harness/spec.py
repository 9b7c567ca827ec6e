"""The cell's files, found by the names in ``BENCHMARK.json``.

A cell (an entry of ``workloads``) names a configuration, whose file is
given in ``configs``, and a traffic mix, read from
``<bench>/workloads/<traffic>.json``.  Its correctness limits are in
``<bench>/limits/<cell>.json``, and each per-layer metric is read by
``<bench>/metrics/<metric>.py``.  Nothing here names a cell, a
configuration or a metric: a new one is a new file and a new entry.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List

BENCH = Path(__file__).resolve().parents[1]


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    limits: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    peaks: Dict
    bench: Path


def _reports(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load(cell_name: str, bench: Path = BENCH) -> Cell:
    root = bench.parent
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if cell_name not in cells:
        raise SystemExit(f"no workload {cell_name!r} in BENCHMARK.json")
    w = cells[cell_name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    e2e = [m for m in spec["end_to_end"] if _reports(m, cell_name)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if m["moves"] in e2e_names and _reports(m, cell_name)]
    read = lambda p: json.loads(p.read_text())
    return Cell(name=cell_name, chips=w["chips"],
                config=read(root / conf["file"]),
                traffic=read(bench / "workloads" / f"{w['traffic']}.json"),
                limits=read(bench / "limits" / f"{cell_name}.json"),
                end_to_end=e2e, per_layer=per_layer,
                peaks=read(bench / "peaks.json"), bench=bench)


def reader(bench: Path, metric: str):
    """The ``read(run)`` function of one per-layer metric."""
    path = bench / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
