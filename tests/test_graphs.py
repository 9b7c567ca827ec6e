"""Graph substrate: partitioners, padded batching invariants, GNN encoders."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.graphs import data as D
from repro.graphs import partition as P
from repro.graphs import batching as Bt
from repro.graphs.gnn import GNNConfig, gnn_init, make_encode_fn

HSET = settings(max_examples=6, deadline=None)


def _graph(seed=0, n_graphs=3):
    return D.make_malnet_like(n_graphs=n_graphs, seed=seed)


@pytest.mark.parametrize("method", list(P.PARTITIONERS))
def test_partitioners_cover_all_nodes_and_respect_cap(method):
    g = _graph()[0]
    segs = P.partition_graph(len(g.x), g.edges, 48, method)
    covered = set()
    for s in segs:
        assert len(s) <= 48, f"{method} exceeded max size"
        covered.update(int(u) for u in s)
    assert covered == set(range(len(g.x))), f"{method} lost nodes"


def test_bfs_partition_preserves_locality_better_than_random():
    """Locality metric: fraction of edges kept inside segments — the paper's
    Table 6 mechanism (random edge-cut destroys structure)."""
    g = _graph(seed=3)[0]

    def kept_fraction(method):
        segs = P.partition_graph(len(g.x), g.edges, 48, method)
        assign = {}
        for si, s in enumerate(segs):
            for u in s:
                assign.setdefault(int(u), si)
        kept = sum(1 for a, b in g.edges if assign[int(a)] == assign[int(b)])
        return kept / len(g.edges)

    assert kept_fraction("bfs") > kept_fraction("random") + 0.2


@given(max_seg=st.sampled_from([32, 48, 64]), seed=st.integers(0, 100))
@HSET
def test_segment_dataset_masks_consistent(max_seg, seed):
    graphs = _graph(seed=seed, n_graphs=2)
    ds = Bt.segment_dataset(graphs, max_seg_nodes=max_seg)
    # segment validity implies node validity; edges index only valid nodes
    for gi in range(ds.n):
        for j in range(ds.j_max):
            if ds.seg_valid[gi, j] == 0:
                assert ds.node_valid[gi, j].sum() == 0
                continue
            nv = int(ds.node_valid[gi, j].sum())
            ev = ds.edge_valid[gi, j] > 0
            if ev.any():
                assert ds.edges[gi, j][ev].max() < nv
    # every graph's nodes are covered across segments
    for gi, g in enumerate(graphs):
        total_nodes = int(ds.node_valid[gi].sum())
        assert total_nodes >= len(g.x)  # >= because vertex-cut may duplicate


def test_padding_invariance_of_encoder():
    """Adding pad rows/edges must not change the segment embedding."""
    graphs = _graph(seed=1, n_graphs=1)
    ds_small = Bt.segment_dataset(graphs, max_seg_nodes=48)
    ds_big = Bt.segment_dataset(graphs, max_seg_nodes=48,
                                e_max=ds_small.e_max + 37)
    cfg = GNNConfig(backbone="sage", n_feat=graphs[0].x.shape[1], hidden=16)
    params = gnn_init(jax.random.key(0), cfg)
    enc = make_encode_fn(cfg)
    flat = lambda ds: {k: jnp.asarray(v[0]) for k, v in ds.seg_inputs(np.asarray([0])).items()}
    e1, _ = enc(params, flat(ds_small))
    e2, _ = enc(params, flat(ds_big))
    np.testing.assert_allclose(np.asarray(e1), np.asarray(e2), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("backbone", ["gcn", "sage", "gps"])
def test_gnn_backbones_finite_and_shaped(backbone):
    graphs = _graph(seed=2, n_graphs=2)
    ds = Bt.segment_dataset(graphs, max_seg_nodes=48)
    cfg = GNNConfig(backbone=backbone, n_feat=graphs[0].x.shape[1], hidden=32)
    params = gnn_init(jax.random.key(0), cfg)
    enc = make_encode_fn(cfg)
    seg = {k: jnp.asarray(v.reshape((-1,) + v.shape[2:]))
           for k, v in ds.seg_inputs(np.arange(2)).items()}
    emb, aux = enc(params, seg)
    assert emb.shape == (2 * ds.j_max, 32)
    assert bool(jnp.isfinite(emb).all())


def test_malnet_label_requires_global_information():
    """No single community determines the majority label in general —
    sanity-check the dataset actually exercises GST's aggregation."""
    graphs = D.make_malnet_like(n_graphs=40, seed=0)
    disagree = 0
    for g in graphs:
        types = g.meta["types"]
        if any(int(t) != g.label for t in types):
            disagree += 1
    assert disagree > len(graphs) // 2


def test_tpugraphs_runtime_is_segment_decomposable():
    graphs = D.make_tpugraphs_like(n_graphs=8, seed=0)
    assert all(isinstance(g.label, float) for g in graphs)
    # same graph, different configs -> different runtimes (ranking signal)
    labels = [g.label for g in graphs[:4]]
    assert len(set(np.round(labels, 6))) > 1
