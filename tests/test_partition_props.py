"""Partitioner invariants, property-style across all four PARTITIONERS.

For random graphs (including DIRECTED edge lists — edges are no longer
assumed pre-symmetrized after the _adjacency fix) every partitioner must:
  * cover every node in >= 1 segment,
  * respect the max_size cap on every segment,
  * be deterministic under a fixed seed,
  * return int32 node ids within range.
Plus the specific regressions: BFS coverage on purely-directed star/chain
graphs, and louvain's BFS fallback when networkx is missing.
"""
import sys

import numpy as np

from hypothesis import given, settings, strategies as st
from repro.graphs.partition import (PARTITIONERS, bfs_partition,
                                    louvain_partition, partition_graph)


def _random_graph(n, avg_deg, seed, directed=True):
    rng = np.random.default_rng(seed)
    m = max(1, int(n * avg_deg / 2))
    edges = rng.integers(0, n, (m, 2)).astype(np.int64)
    edges = edges[edges[:, 0] != edges[:, 1]]
    if len(edges) == 0:
        edges = np.asarray([[0, min(1, n - 1)]], np.int64)
    if not directed:
        edges = np.concatenate([edges, edges[:, ::-1]], axis=0)
    return edges


@settings(max_examples=12, deadline=None)
@given(method=st.sampled_from(sorted(PARTITIONERS)),
       n=st.integers(2, 40),
       avg_deg=st.integers(1, 6),
       max_size=st.integers(2, 12),
       seed=st.integers(0, 10_000),
       directed=st.booleans())
def test_partitioner_invariants(method, n, avg_deg, max_size, seed, directed):
    edges = _random_graph(n, avg_deg, seed, directed)
    segs = partition_graph(n, edges, max_size, method, seed)
    assert len(segs) >= 1
    covered = set()
    for s in segs:
        assert s.dtype == np.int32
        assert len(s) >= 1
        assert len(s) <= max_size, f"{method} violated the max_size cap"
        assert (s >= 0).all() and (s < n).all()
        covered.update(int(u) for u in s)
    assert covered == set(range(n)), \
        f"{method} left nodes uncovered: {set(range(n)) - covered}"
    # determinism under a fixed seed
    again = partition_graph(n, edges, max_size, method, seed)
    assert len(again) == len(segs)
    assert all((a == b).all() for a, b in zip(segs, again))


def test_bfs_covers_directed_star():
    """Regression: with a one-directional edge list (hub -> leaves) the old
    _adjacency only walked forward edges; leaves whose only edge POINTS AT
    them were reachable, but a sink-only hub (leaves -> hub) never expanded.
    Both orientations must now grow identical locality regions."""
    n = 9
    hub_out = np.asarray([[0, i] for i in range(1, n)])   # hub -> leaves
    hub_in = hub_out[:, ::-1].copy()                      # leaves -> hub
    for edges in (hub_out, hub_in):
        segs = bfs_partition(n, edges, max_size=n, seed=0)
        assert sorted(int(u) for s in segs for u in s) == list(range(n))
        # the star is one connected region — a single BFS from any seed
        # should reach everything through the symmetrized adjacency
        assert len(segs) == 1


def test_bfs_directed_chain_locality():
    """A directed path 0->1->...->k must form contiguous BFS regions from
    either end (symmetrized adjacency), not one region per stranded node."""
    k = 12
    edges = np.asarray([[i, i + 1] for i in range(k)])
    segs = bfs_partition(k + 1, edges, max_size=4, seed=3)
    assert sorted(int(u) for s in segs for u in s) == list(range(k + 1))
    assert all(len(s) <= 4 for s in segs)
    # locality: every segment of a path graph spans a contiguous id range
    for s in segs:
        lo, hi = int(min(s)), int(max(s))
        assert hi - lo == len(s) - 1


def test_louvain_falls_back_to_bfs_without_networkx(monkeypatch):
    """louvain must degrade to the BFS partitioner instead of raising
    ImportError at call time when networkx is absent."""
    edges = _random_graph(20, 3, seed=4, directed=False)
    monkeypatch.setitem(sys.modules, "networkx", None)  # import -> ImportError
    segs = louvain_partition(20, edges, max_size=6, seed=4)
    expect = bfs_partition(20, edges, max_size=6, seed=4)
    assert len(segs) == len(expect)
    assert all((a == b).all() for a, b in zip(segs, expect))
    covered = {int(u) for s in segs for u in s}
    assert covered == set(range(20))
