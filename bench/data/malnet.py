"""Synthetic MalNet-like call graphs, the benchmark's own copy of the
program's ``graphs/data.py::make_malnet_like``.

A graph's label is the type most of its communities have (ties go to the
smaller type): a property of the whole graph, which no single segment
shows.
"""
from __future__ import annotations

from pathlib import Path
from typing import List, Tuple

import numpy as np

from data.communities import Graph, community_graph

SOURCES = [Path(__file__).resolve(),
           Path(__file__).resolve().with_name("communities.py")]


def make_malnet_like(n_graphs: int, n_classes: int, n_feat: int,
                     comm_range: Tuple[int, int],
                     comm_size_range: Tuple[int, int],
                     seed: int) -> List[Graph]:
    rng = np.random.default_rng(seed)
    graphs = []
    for _ in range(n_graphs):
        n_comm = int(rng.integers(*comm_range))
        x, e, types, _ = community_graph(
            rng, n_comm, comm_size_range, n_classes, n_feat, p_in=2.0,
            p_out_edges=max(2, n_comm // 2))
        label = int(np.argmax(np.bincount(types, minlength=n_classes)))
        graphs.append(Graph(x, e, label))
    return graphs


generate = make_malnet_like
