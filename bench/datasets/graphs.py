"""A graph configuration's segmented dataset: generated by the benchmark's
copy of a generator (``bench/data/<generator>.py``, named by the
configuration's ``data``), segmented and padded by the program's own
``graphs/batching.py::segment_dataset`` (as ``graphs/experiment.py`` calls
it), and cached on disk.

The cache file's name hashes the data parameters and the source of every
module that shapes the arrays (``SOURCES`` and the generator's own), so
that a change to the partitioner or the padding is measured on freshly
segmented data.  ``graphs_shared.py`` pads to the serve ladder instead.
"""
from __future__ import annotations

import hashlib
import json
import os
from pathlib import Path
from typing import Dict, List

import numpy as np

from harness import spec as SPEC
from repro.graphs import batching as Bt
from repro.graphs import partition

BENCH = Path(__file__).resolve().parents[1]
SOURCES = [Path(__file__).resolve(), Path(partition.__file__),
           Path(Bt.__file__)]
FIELDS = ("x", "edges", "edge_valid", "node_valid", "seg_valid", "labels")


class GraphData:
    """The program's ``SegmentedDataset`` (``segmented``), with what the
    harness and the readers read of it."""

    def __init__(self, segmented):
        self.segmented = segmented
        self.n, self.j_max = segmented.n, segmented.j_max
        self._stats = None

    def stats(self) -> Dict[str, float]:
        """Fill of the padded node slots, and the mean valid nodes, edges
        and segments a sampled segment and a graph have (per graph, then
        over graphs, as uniform sampling weighs them)."""
        if self._stats is None:
            ds = self.segmented
            sv = ds.seg_valid > 0
            nodes = ds.node_valid.sum(-1)
            edges = ds.edge_valid.sum(-1)
            per_graph = sv.sum(-1)
            mean = lambda a: float(np.mean((a * sv).sum(-1)
                                           / np.maximum(per_graph, 1)))
            self._stats = {
                "seg_fill": float(nodes[sv].sum() / (sv.sum() * ds.m_max)),
                "nodes": mean(nodes), "edges": mean(edges),
                "segments": float(per_graph.mean())}
        return self._stats


def generator(data: Dict):
    return SPEC.module(BENCH, "data", data["generator"])


def params(cfg: Dict) -> Dict:
    """The data parameters the cache key and the segmentation read."""
    return {**cfg["data"], "n_graphs": cfg["n_graphs"],
            "n_feat": cfg["n_feat"]}


def generate(data: Dict):
    kw = {k: (tuple(v) if isinstance(v, list) else v)
          for k, v in data.items()
          if k not in ("generator", "max_seg_nodes", "partition")}
    return generator(data).generate(**kw)


def cache_key(data: Dict, sources: List[Path]) -> str:
    h = hashlib.sha256(json.dumps(data, sort_keys=True).encode())
    for p in sources + generator(data).SOURCES:
        h.update(p.read_bytes())
    return h.hexdigest()[:20]


def segment(data: Dict):
    return Bt.segment_dataset(generate(data), data["max_seg_nodes"],
                              method=data["partition"], seed=data["seed"])


def build(cfg: Dict, cache: Path) -> GraphData:
    return GraphData(load(params(cfg), cache, segment, SOURCES))


def load(data: Dict, cache: Path, segment_fn, sources: List[Path]):
    """The program's ``SegmentedDataset`` cached in directory ``cache``,
    built by ``segment_fn(data)`` on a miss."""
    path = cache / f"{cache_key(data, sources)}.npz"
    if path.exists():
        with np.load(path) as z:
            return Bt.SegmentedDataset(*(z[f] for f in FIELDS),
                                       int(z["j_max"]), int(z["m_max"]),
                                       int(z["e_max"]))
    ds = segment_fn(data)
    cache.mkdir(parents=True, exist_ok=True)
    tmp = path.with_suffix(".tmp.npz")
    np.savez(tmp, **{f: getattr(ds, f) for f in FIELDS},
             j_max=ds.j_max, m_max=ds.m_max, e_max=ds.e_max)
    os.replace(tmp, path)
    return ds
