"""Synthetic TpuGraphs-like graphs, the benchmark's own copy.

Copied from the program's ``graphs/data.py`` so that the benchmark's inputs
stay fixed whatever a later change does to the program's generator.  Each
graph is a union of communities with a random spanning tree and ``p_in``
extra random edges per node inside each community, a few edges between
communities, and noisy one-hot features of the community's type.

Each (graph, configuration) pair is one example; its target runtime is a
sum of per-community costs scaled by the configuration scalar, which is
broadcast into the last feature column.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Tuple

import numpy as np


@dataclass
class Graph:
    x: np.ndarray          # (n_nodes, n_feat) float32
    edges: np.ndarray      # (n_edges, 2) int32, both directions present
    label: float           # class id or runtime


def _community_graph(rng: np.random.Generator, n_comm: int, comm_size_rng,
                     n_types: int, n_feat: int, p_in: float,
                     p_out_edges: int):
    sizes = [int(rng.integers(*comm_size_rng)) for _ in range(n_comm)]
    types = rng.integers(0, n_types, size=n_comm)
    n = sum(sizes)
    x = np.zeros((n, n_feat), np.float32)
    comm = np.zeros((n,), np.int32)
    edges = []
    offset = 0
    for c, (sz, t) in enumerate(zip(sizes, types)):
        idx = np.arange(offset, offset + sz)
        comm[idx] = c
        feats = rng.normal(0, 0.4, size=(sz, n_feat)).astype(np.float32)
        feats[:, t % n_feat] += 1.0
        x[idx] = feats
        for i in range(1, sz):
            j = int(rng.integers(0, i))
            edges.append((idx[i], idx[j]))
        for _ in range(int(p_in * sz)):
            a, b = rng.integers(0, sz, 2)
            if a != b:
                edges.append((idx[a], idx[b]))
        offset += sz
    for _ in range(p_out_edges):
        a = int(rng.integers(0, n))
        b = int(rng.integers(0, n))
        if comm[a] != comm[b]:
            edges.append((a, b))
    e = np.asarray(edges, np.int32)
    e = np.concatenate([e, e[:, ::-1]], axis=0)
    return x, e, types, comm


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
        x, e, types, comm = _community_graph(
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


GENERATORS = {"tpugraphs": make_tpugraphs_like}
