"""A graph configuration's segmented dataset padded to the serve ladder's
shapes: ``graphs.py`` with the program's
``dist/pipeline.py::segment_dataset_shared`` (as ``launch/train_dist.py``
calls it) in place of ``segment_dataset``.
"""
from __future__ import annotations

from pathlib import Path
from typing import Dict

from harness import spec as SPEC
from repro.dist import pipeline as DP
from repro.serve import buckets

BENCH = Path(__file__).resolve().parents[1]
GRAPHS = SPEC.module(BENCH, "datasets", "graphs")
SOURCES = GRAPHS.SOURCES + [Path(__file__).resolve(), Path(DP.__file__),
                            Path(buckets.__file__)]


def segment(data: Dict):
    ds, _ = DP.segment_dataset_shared(GRAPHS.generate(data),
                                      data["max_seg_nodes"],
                                      method=data["partition"],
                                      seed=data["seed"])
    return ds


def build(cfg: Dict, cache: Path):
    return GRAPHS.GraphData(GRAPHS.load(GRAPHS.params(cfg), cache, segment,
                                        SOURCES))
