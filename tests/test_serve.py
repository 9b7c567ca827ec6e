"""Serving-subsystem invariants (serve/: engine, buckets, cache, traffic).

* streaming-vs-one-shot encoder parity for every GNN variant, Pallas and
  reference paths (acceptance: atol 1e-5; empirically bit-exact),
* the constant-memory contract via buffer-size accounting: the streaming
  scan's largest intermediate does not grow with the number of chunks,
  while the one-shot encoder's grows with the segment count,
* cache properties: hit returns the bit-identical embedding, eviction
  respects capacity with LRU order, and a full-hit request launches zero
  encode kernels,
* engine-vs-offline parity on traffic spanning multiple buckets.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gst as G
from repro.graphs import data as D
from repro.graphs.batching import segment_dataset
from repro.graphs.gnn import GNNConfig, encode_segments, gnn_init
from repro.graphs.partition import partition_graph
from repro.kernels.ops import max_intermediate_bytes
from repro.serve import (
    BucketSpec,
    SegmentCache,
    ServeConfig,
    ServeEngine,
    TrafficConfig,
    graph_to_chunks,
    make_request_stream,
    make_stream_encoder,
)
from repro.serve.engine import SEG_KEYS

# gps has no fused kernel path (falls back to reference inside
# encode_segments), so the pallas axis only applies to gcn/sage
ENCODER_VARIANTS = [("gcn", False), ("gcn", True),
                    ("sage", False), ("sage", True), ("gps", False)]

HID = 16


def _graph(seed=0):
    return D.make_malnet_like(n_graphs=2, comm_range=(6, 9),
                              comm_size_range=(14, 26), seed=seed)[seed % 2]


def _setup(backbone, use_pallas, head_mode="mlp", seed=0):
    cfg = GNNConfig(backbone=backbone, n_feat=8, hidden=HID,
                    use_pallas=use_pallas)
    key = jax.random.key(seed)
    params = gnn_init(key, cfg)
    head = G.head_init(jax.random.fold_in(key, 1), HID, 3, head_mode)
    return cfg, params, head


def _one_shot(cfg, params, head, chunks, head_mode="mlp", agg="mean"):
    """Reference: encode ALL segments in one flat batch, mask-pool, head."""
    flat = {k: jnp.asarray(chunks[k].reshape((-1,) + chunks[k].shape[2:]))
            for k in SEG_KEYS}
    h = encode_segments(params, cfg, flat)
    w = jnp.asarray(chunks["seg_valid"].reshape(-1))
    if head_mode == "segment_sum":
        scal = G.head_apply(head, h, "segment_sum")
        s = jnp.sum(scal * w)
        return s / jnp.maximum(w.sum(), 1.0) if agg == "mean" else s
    pooled = (h * w[:, None]).sum(0) / jnp.maximum(w.sum(), 1.0)
    return G.head_apply(head, pooled, "mlp")


# ---------------------------------------------------------------------------
# streaming encoder
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backbone,use_pallas", ENCODER_VARIANTS)
def test_streaming_matches_one_shot(backbone, use_pallas):
    cfg, params, head = _setup(backbone, use_pallas)
    spec = BucketSpec(m_max=32, e_max=256, batch=4)
    chunks = graph_to_chunks(_graph(0), spec, chunk=4)
    assert chunks["seg_valid"].shape[0] > 1, "graph must span multiple chunks"
    stream = make_stream_encoder(cfg)
    pred, _ = stream(params, head, {k: jnp.asarray(v) for k, v in chunks.items()})
    ref = _one_shot(cfg, params, head, chunks)
    np.testing.assert_allclose(np.asarray(pred), np.asarray(ref), atol=1e-5)


def test_streaming_matches_one_shot_segment_sum_head():
    cfg, params, head = _setup("sage", False, head_mode="segment_sum")
    spec = BucketSpec(m_max=32, e_max=256, batch=4)
    chunks = graph_to_chunks(_graph(1), spec, chunk=4)
    stream = make_stream_encoder(cfg, head_mode="segment_sum", agg="sum")
    pred, _ = stream(params, head, {k: jnp.asarray(v) for k, v in chunks.items()})
    ref = _one_shot(cfg, params, head, chunks, head_mode="segment_sum", agg="sum")
    np.testing.assert_allclose(np.asarray(pred), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_streaming_constant_memory(use_pallas):
    """Buffer-size accounting: the scan's largest live buffer is bounded by
    one chunk and does NOT grow with the chunk count; the one-shot encoder's
    grows with the total segment count."""
    cfg, params, head = _setup("sage", use_pallas)
    spec = BucketSpec(m_max=16, e_max=64, batch=4)
    chunk = 4
    big = D.make_malnet_like(n_graphs=1, comm_range=(10, 11),
                             comm_size_range=(14, 16), seed=3)[0]
    chunks_big = graph_to_chunks(big, spec, chunk=chunk)
    small = D.make_malnet_like(n_graphs=1, comm_range=(3, 4),
                               comm_size_range=(14, 16), seed=4)[0]
    chunks_small = graph_to_chunks(small, spec, chunk=chunk)
    c_small, c_big = chunks_small["seg_valid"].shape[0], chunks_big["seg_valid"].shape[0]
    assert c_big > c_small >= 1

    stream = make_stream_encoder(cfg)
    dev = lambda ch: {k: jnp.asarray(v) for k, v in ch.items()}
    m_small = max_intermediate_bytes(lambda c: stream(params, head, c),
                                     dev(chunks_small))
    m_big = max_intermediate_bytes(lambda c: stream(params, head, c),
                                   dev(chunks_big))
    assert m_big == m_small, (
        f"streaming peak buffer grew with chunk count: {m_small} -> {m_big}")

    flat = {k: jnp.asarray(chunks_big[k].reshape((-1,) + chunks_big[k].shape[2:]))
            for k in SEG_KEYS}
    m_one_shot = max_intermediate_bytes(
        lambda f: encode_segments(params, cfg, f), flat)
    assert m_big < m_one_shot, (
        f"one-shot ({m_one_shot}b) should dwarf streaming ({m_big}b) "
        f"for a {c_big}-chunk graph")


# ---------------------------------------------------------------------------
# cache properties
# ---------------------------------------------------------------------------


def test_cache_hit_returns_bit_identical_embedding():
    cache = SegmentCache(capacity=8, d_h=HID)
    rng = np.random.default_rng(0)
    keys = [bytes([i]) * 4 for i in range(5)]
    embs = jnp.asarray(rng.normal(size=(5, HID)), jnp.float32)
    cache.put(keys, embs)
    slots = [cache.get(k) for k in keys]
    assert all(s is not None for s in slots)
    got = np.asarray(cache.gather(slots))
    assert np.array_equal(got, np.asarray(embs)), "hit must be bit-identical"


def test_cache_eviction_respects_capacity_lru():
    cache = SegmentCache(capacity=4, d_h=HID)
    rng = np.random.default_rng(1)
    keys = [bytes([i]) * 4 for i in range(10)]
    for k in keys:
        cache.put([k], jnp.asarray(rng.normal(size=(1, HID)), jnp.float32))
        assert len(cache) <= 4
    assert cache.evictions == 6
    # LRU: only the 4 most recently inserted survive
    assert [cache.peek(k) is not None for k in keys] == [False] * 6 + [True] * 4
    st = cache.stats()
    assert st["size"] == 4 and st["capacity"] == 4


def test_cache_lru_refresh_on_hit():
    cache = SegmentCache(capacity=2, d_h=HID)
    e = jnp.ones((1, HID), jnp.float32)
    cache.put([b"a"], e)
    cache.put([b"b"], 2 * e)
    assert cache.get(b"a") is not None   # refresh 'a' -> 'b' becomes LRU
    cache.put([b"c"], 3 * e)
    assert cache.peek(b"a") is not None
    assert cache.peek(b"b") is None
    assert cache.peek(b"c") is not None


def test_cache_age_counters_advance():
    cache = SegmentCache(capacity=4, d_h=HID)
    e = jnp.ones((1, HID), jnp.float32)
    cache.put([b"old"], e)
    for i in range(3):
        cache.put([bytes([i])], e)
    st = cache.stats()
    assert st["age_max_steps"] == 3 and st["age_mean_steps"] > 0


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------


def _engine(use_pallas=False, cache_enabled=True, backbone="sage"):
    cfg = ServeConfig(backbone=backbone, hidden=32, use_pallas=use_pallas,
                      max_seg_nodes=32, cache_capacity=128,
                      cache_enabled=cache_enabled, stream_chunk=4)
    return ServeEngine(cfg, seed=0)


def _offline_ref(engine, g):
    """One-shot batch encode with training-style padding (graphs/batching)."""
    segs = partition_graph(len(g.x), g.edges, engine.cfg.max_seg_nodes,
                           engine.cfg.partition, engine.cfg.partition_seed)
    ds = segment_dataset([g], engine.cfg.max_seg_nodes,
                         method=engine.cfg.partition,
                         seed=engine.cfg.partition_seed)
    si = {k: jnp.asarray(v[0]) for k, v in ds.seg_inputs(np.array([0])).items()}
    h = encode_segments(engine.params, engine.gnn_cfg, si)[:len(segs)]
    return np.asarray(G.head_apply(engine.head, h.mean(axis=0), "mlp"))


@pytest.mark.parametrize("use_pallas", [False, True])
def test_engine_matches_one_shot_across_buckets(use_pallas):
    """Requests with mixed graph sizes span several buckets of the ladder;
    every prediction must match the one-shot batch encoder (atol 1e-5)."""
    engine = _engine(use_pallas=use_pallas)
    tc = TrafficConfig(n_unique=4, n_requests=6, duplicate_rate=0.5,
                       comm_range=(1, 6), comm_size_range=(6, 28), seed=2)
    stream = make_request_stream(tc)
    results = engine.process(stream, window=3)
    assert len({bi for items in map(engine._segment_request, stream[:4])
                for _, bi, _ in items}) > 1, "traffic must span buckets"
    for g, r in zip(stream, results):
        np.testing.assert_allclose(r.pred, _offline_ref(engine, g), atol=1e-5)


def test_engine_full_hit_launches_zero_encode_kernels():
    engine = _engine()
    g = _graph(0)
    r1 = engine.process([g], window=1)[0]
    launches_before = engine.stats.encode_launches
    pallas_before = engine.stats.pallas_launches
    r2 = engine.process([g], window=1)[0]
    assert engine.stats.encode_launches == launches_before, \
        "full cache hit must not launch the encoder"
    assert engine.stats.pallas_launches == pallas_before
    assert r2.n_cache_hits == r2.n_segments
    assert np.array_equal(r1.pred, r2.pred), \
        "hit-path prediction must be bit-identical"


def test_engine_hit_slot_survives_same_window_eviction_pressure():
    """Regression: a window whose hits coexist with >= capacity new misses
    must NOT evict the hit slots before the gather — the hit request's
    prediction must equal the cache-off reference exactly."""
    tc = TrafficConfig(n_unique=4, n_requests=4, duplicate_rate=0.0,
                       comm_range=(4, 7), comm_size_range=(10, 24), seed=7)
    pool = make_request_stream(tc)
    g0, rest = pool[0], pool[1:]

    def tiny_engine(cache_enabled):
        cfg = ServeConfig(backbone="sage", hidden=32, max_seg_nodes=32,
                          cache_capacity=2, cache_enabled=cache_enabled,
                          stream_chunk=4)
        return ServeEngine(cfg, seed=0)

    eng = tiny_engine(True)
    ref = tiny_engine(False)
    eng.process([g0], window=1)          # g0's segments (partially) cached
    preds = eng.process([g0] + rest, window=4)
    ref_preds = ref.process([g0] + rest, window=4)
    for p, r in zip(preds, ref_preds):
        np.testing.assert_array_equal(p.pred, r.pred)


def test_cache_flush_keeps_jitted_ops_and_empties_contents():
    cache = SegmentCache(capacity=4, d_h=HID)
    cache.put([b"k"], jnp.ones((1, HID), jnp.float32))
    assert cache.get(b"k") is not None
    update_fn = cache._update
    cache.flush()
    assert len(cache) == 0 and cache.hits == 0 and cache.step == 0
    assert cache._update is update_fn, "flush must keep compile caches"
    assert cache.get(b"k") is None
    cache.put([b"k2"], jnp.ones((1, HID), jnp.float32))
    assert cache.get(b"k2") is not None


def test_engine_cache_disabled_always_encodes():
    engine = _engine(cache_enabled=False)
    g = _graph(0)
    engine.process([g], window=1)
    n1 = engine.stats.encoded_segments
    launches1 = engine.stats.encode_launches
    engine.process([g], window=1)
    assert engine.stats.encoded_segments == 2 * n1
    # with no cache, the repeat launches the encoder again
    assert launches1 > 0
    assert engine.stats.encode_launches == 2 * launches1
    assert engine.stats.cache == {}


def test_engine_streaming_prediction_matches_process():
    """The constant-memory path and the bucketed path agree when the graph's
    segments all land in the catch-all bucket."""
    cfg = ServeConfig(backbone="sage", hidden=32, max_seg_nodes=32,
                      ladder=(BucketSpec(32, 256, 8),), stream_chunk=4,
                      cache_capacity=64)
    engine = ServeEngine(cfg, seed=0)
    g = _graph(1)
    pred = engine.process([g], window=1)[0].pred
    sp = engine.predict_streaming(g)
    np.testing.assert_allclose(sp, pred, atol=1e-5)


def test_traffic_duplicate_rate_controls_hit_rate():
    tc_dup = TrafficConfig(n_unique=4, n_requests=24, duplicate_rate=0.8, seed=5)
    tc_uniq = TrafficConfig(n_unique=24, n_requests=24, duplicate_rate=0.0, seed=5)
    e1, e2 = _engine(), _engine()
    e1.process(make_request_stream(tc_dup), window=4)
    e2.process(make_request_stream(tc_uniq), window=4)
    hr1 = e1.stats.cache["hit_rate"]
    hr2 = e2.stats.cache["hit_rate"]
    assert hr1 > 0.5
    assert hr1 > hr2
    assert e1.stats.encoded_segments < e1.stats.n_segments


# ---------------------------------------------------------------------------
# traffic repeat sampling (ISSUE 10 bugfix) + catch-all truncation counting
# ---------------------------------------------------------------------------


def _repeat_counts(popularity: float, seed=11, n=3000):
    tc = TrafficConfig(n_unique=4, n_requests=n, duplicate_rate=0.6,
                       popularity=popularity, seed=seed)
    stream = make_request_stream(tc)
    ids = {}
    counts = np.zeros(4, np.int64)
    for g in stream:
        gi = ids.setdefault(id(g), len(ids))
        counts[gi] += 1
    return counts


def test_traffic_repeats_uniform_over_distinct():
    """popularity=0 (the default): repeats spread evenly over distinct
    seen graphs.  The pre-fix stream sampled the seen list WITH
    duplicates — a Polya urn where every repeat compounded — so its
    counts were heavily skewed; uniform sampling keeps max/min tight."""
    counts = _repeat_counts(0.0)
    assert counts.min() > 0
    assert counts.max() / counts.min() < 1.3


def test_traffic_popularity_knob_restores_skew():
    """popularity=1 is the explicit rich-get-richer (old) behavior; it
    must be visibly more skewed than the uniform default on the same
    seed, and the skew must grow with the exponent."""
    flat = _repeat_counts(0.0)
    rich = _repeat_counts(1.0)
    richer = _repeat_counts(3.0)

    def spread(c):
        return c.max() / max(c.min(), 1)

    assert spread(rich) > spread(flat)
    assert spread(richer) > spread(rich)


def test_truncation_counts_math():
    from repro.serve.buckets import truncation_counts
    spec = BucketSpec(32, 256, 8)
    assert truncation_counts(40, 300, spec) == (8, 44)
    assert truncation_counts(32, 256, spec) == (0, 0)   # exact fit
    assert truncation_counts(5, 7, spec) == (0, 0)      # under: never negative


def test_serve_stats_truncation_in_summary():
    from repro.serve.engine import ServeStats
    s = ServeStats()
    assert s.summary()["truncated_nodes"] == 0
    assert s.summary()["truncated_edges"] == 0
    s.truncated_nodes += 3
    s.truncated_edges += 1
    out = s.summary()
    assert out["truncated_nodes"] == 3 and out["truncated_edges"] == 1
