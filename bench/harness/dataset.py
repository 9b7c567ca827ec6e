"""The cell's segmented dataset: generated from its configuration, segmented
and padded by the program's own ``graphs/batching.py::segment_dataset`` (as
``graphs/experiment.py`` calls it), and cached on disk.

The generator is the benchmark's copy (``bench/data/synthetic.py``).  The
cache file's name hashes the data parameters and the source of every module
that shapes the arrays, so that a change to the partitioner or the padding
is measured on freshly segmented data.
"""
from __future__ import annotations

import hashlib
import json
import os
from pathlib import Path
from typing import Dict

import numpy as np

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
SOURCES = [BENCH / "data" / "synthetic.py",
           BENCH / "harness" / "dataset.py",
           ROOT / "src/repro/graphs/partition.py",
           ROOT / "src/repro/graphs/batching.py"]
FIELDS = ("x", "edges", "edge_valid", "node_valid", "seg_valid", "labels")


def _generate(data: Dict):
    from data.synthetic import GENERATORS
    kw = {k: (tuple(v) if isinstance(v, list) else v)
          for k, v in data.items()
          if k not in ("generator", "max_seg_nodes", "partition")}
    return GENERATORS[data["generator"]](**kw)


def cache_key(data: Dict) -> str:
    h = hashlib.sha256(json.dumps(data, sort_keys=True).encode())
    for p in SOURCES:
        h.update(p.read_bytes())
    return h.hexdigest()[:20]


def for_config(cfg: Dict, cache: Path):
    """The dataset of configuration ``cfg``."""
    return load({**cfg["data"], "n_graphs": cfg["n_graphs"],
                 "n_feat": cfg["n_feat"]}, cache)


def load(data: Dict, cache: Path):
    """The program's ``SegmentedDataset`` cached in directory ``cache``,
    built on a miss."""
    from repro.graphs import batching as Bt
    path = cache / f"{cache_key(data)}.npz"
    if path.exists():
        with np.load(path) as z:
            return Bt.SegmentedDataset(*(z[f] for f in FIELDS),
                                       int(z["j_max"]), int(z["m_max"]),
                                       int(z["e_max"]))
    ds = Bt.segment_dataset(_generate(data), data["max_seg_nodes"],
                            method=data["partition"], seed=data["seed"])
    cache.mkdir(parents=True, exist_ok=True)
    tmp = path.with_suffix(".tmp.npz")
    np.savez(tmp, **{f: getattr(ds, f) for f in FIELDS},
             j_max=ds.j_max, m_max=ds.m_max, e_max=ds.e_max)
    os.replace(tmp, path)
    return ds
