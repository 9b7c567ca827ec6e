"""Synthetic TpuGraphs-like examples, the benchmark's own copy of the
program's ``graphs/data.py::make_tpugraphs_like``.

Each (graph, configuration) pair is one example; its target runtime is a
sum of per-community costs scaled by the configuration scalar, which is
broadcast into the last feature column.
"""
from __future__ import annotations

from pathlib import Path
from typing import List, Tuple

import numpy as np

from data.communities import Graph, community_graph

SOURCES = [Path(__file__).resolve(),
           Path(__file__).resolve().with_name("communities.py")]


def make_tpugraphs_like(n_graphs: int, n_feat: int, n_types: int,
                        comm_range: Tuple[int, int],
                        comm_size_range: Tuple[int, int], n_configs: int,
                        seed: int) -> List[Graph]:
    """``n_graphs // n_configs`` graphs, each under ``n_configs``
    configurations; targets normalised to zero mean and unit spread, as the
    program's driver does before training on them."""
    rng = np.random.default_rng(seed)
    base_cost = rng.uniform(0.5, 2.0, size=n_types)
    graphs = []
    for _ in range(n_graphs // n_configs):
        n_comm = int(rng.integers(*comm_range))
        x, e, types, comm = community_graph(
            rng, n_comm, comm_size_range, n_types, n_feat, p_in=2.0,
            p_out_edges=max(2, n_comm // 2))
        sizes = np.bincount(comm, minlength=len(types)).astype(np.float32)
        for k in range(n_configs):
            cfgval = k / max(n_configs - 1, 1)
            runtime = float(np.sum(base_cost[types] * np.sqrt(sizes)
                                   * (1 + 0.3 * cfgval * types / n_types)))
            xc = x.copy()
            xc[:, -1] = cfgval
            graphs.append(Graph(xc, e, runtime + float(rng.normal(0, 0.01))))
    lab = np.asarray([g.label for g in graphs], np.float32)
    mu, sd = lab.mean(), lab.std() + 1e-6
    for g in graphs:
        g.label = float((g.label - mu) / sd)
    return graphs


generate = make_tpugraphs_like
