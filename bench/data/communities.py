"""Community graphs, the building block of the benchmark's synthetic
generators (``tpugraphs.py``, ``malnet.py``).

Copied from the program's ``graphs/data.py`` so that the benchmark's inputs
stay fixed whatever a later change does to the program's generator.  Each
graph is a union of communities with a random spanning tree and ``p_in``
extra random edges per node inside each community, a few edges between
communities, and noisy one-hot features of the community's type.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass
class Graph:
    x: np.ndarray          # (n_nodes, n_feat) float32
    edges: np.ndarray      # (n_edges, 2) int32, both directions present
    label: float           # class id or runtime


def community_graph(rng: np.random.Generator, n_comm: int, comm_size_rng,
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
