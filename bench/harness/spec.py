"""The cell's files, found by the names in ``BENCHMARK.json``.

A cell (an entry of ``workloads``) names a configuration, whose file is
given in ``configs``, and a traffic mix, read from
``<bench>/workloads/<traffic>.json``.  Its correctness limits are in
``<bench>/limits/<cell>.json``, and each per-layer metric is read by
``<bench>/metrics/<metric>.py``.  Nothing here names a cell, a
configuration, a metric or a piece of code that runs one: a new one is a
new file and a new entry.

The code of a cell is found by name the same way, each piece a module of
its own (``module``):

* the traffic file's ``driver`` names ``<bench>/drivers/<driver>.py``,
  which exports ``Driver(cfg, traffic, dataset, devices)``: the program's
  training loop, built once.  ``start(weight_key, batch_seed, rng_seed)``
  gives it fresh weights and batch order; ``steps()`` yields the step's
  metrics (with ``"loss"``) once per step, and records the first
  ``harness.proof.PROOF_STEPS`` in ``proof`` for the reference
  (``losses``, ``batches``, ``rngs``, ``params0``, ``grads1``,
  ``params1``, ``params``, ``table``: what ``harness/check.py`` reads);
  ``block()`` waits for the device; ``lower_temp_bytes()`` is the
  compiled step's temp memory; ``close()`` frees it.  It has ``devices``,
  ``h2d_bytes`` (host bytes put on the device per step, 0 where none) and
  ``spans``: while a list, each step appends ``(start, end, name)`` by
  ``time.perf_counter`` (``bench.batch``, ``bench.step``).
* the configuration's ``dataset`` names ``<bench>/datasets/<dataset>.py``,
  which exports ``build(cfg, cache)``: the cell's data, built from the
  configuration or read back from the directory ``cache``, as an object
  with ``n`` (examples), ``j_max`` (segments an example) and ``stats()``
  (a dict the per-layer readers read); and ``SOURCES``, the files whose
  text is part of its cache key.
* the configuration's ``reference`` names
  ``<bench>/references/<reference>.py``, the plain reference, which
  imports nothing of the program.  It exports ``make_step(cfg, traffic,
  dtype, fault=None, shards=1)``, ``run(cfg, traffic, weight_key, n, j_max,
  batches, rngs, dtype=float32, fault=None, step=None)`` (a record like
  the driver's ``proof``; ``batches`` and ``rngs`` are the driver's
  recorded trees, passed through whole), ``FAULTS`` (the faults
  ``make_step`` can plant, for ``readings.py``) and
  ``train_flops_per_graph(cfg, traffic, stats)`` (the useful FLOPs of one
  example's step, for ``mfu.train``).
"""
from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List

BENCH = Path(__file__).resolve().parents[1]
_MODULES: Dict[Path, object] = {}


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

    @property
    def driver(self):
        return module(self.bench, "drivers", self.traffic["driver"])

    @property
    def dataset(self):
        return module(self.bench, "datasets", self.config["dataset"])

    @property
    def reference(self):
        return module(self.bench, "references", self.config["reference"])


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


def module(bench: Path, kind: str, name: str):
    """The module ``<bench>/<kind>/<name>.py``, loaded once per path."""
    path = (bench / kind / f"{name}.py").resolve()
    if path not in _MODULES:
        if not path.is_file():
            raise SystemExit(f"no {kind} module {name!r} ({path})")
        ident = re.sub(r"\W", "_", f"bench_{kind}_{name}_{len(_MODULES)}")
        spec = importlib.util.spec_from_file_location(ident, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


def reader(bench: Path, metric: str):
    """The ``read(run)`` function of one per-layer metric."""
    return module(bench, "metrics", metric).read
