"""Distributed GST subsystem (src/repro/dist/).

Contract under test (ISSUE 3):
  * ring lookup / write-back over the row-sharded table ≡ the dense
    single-device table ops, BIT-exact (pure row selection, no reductions)
  * shard_map train/refresh/finetune steps for ALL SEVEN variants track the
    single-device oracle over >= 5 steps: identical segment sampling and
    table bookkeeping, params/losses equal up to cross-shard reduction
    order (bitwise at 1 shard, <= a few ulps at 8)
  * the async double-buffered feeder delivers the exact same batches as
    the synchronous feeder on the same trace, and surfaces producer errors
  * train-side padding comes from the serve bucket ladder, so a segment's
    serving-cache fingerprint is identical when padded by either side

Runs at whatever device count the host exposes: tier-1 sees 1 device
(degenerate mesh, bitwise parity); the CI dist-smoke job re-runs this file
under XLA_FLAGS=--xla_force_host_platform_device_count=8.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import dist as DT
from repro.core import embedding_table as tbl
from repro.core import gst as G
from repro.core.embedding_table import init_table
from repro.dist import pipeline as DP
from repro.dist.exchange import RingExchange
from repro.graphs import data as D
from repro.graphs.gnn import GNNConfig, gnn_init, make_encode_fn
from repro.optim import make_optimizer
from repro.serve.buckets import default_ladder, pad_to_bucket, segment_fingerprint

N_DEV = jax.device_count()
SHARD_COUNTS = [d for d in (1, 2, 4, 8) if d <= N_DEV]
HID = 8


def _tree_max_diff(a, b):
    diffs = jax.tree_util.tree_map(
        lambda x, y: float(np.max(np.abs(np.asarray(x) - np.asarray(y)))), a, b)
    return max(jax.tree_util.tree_leaves(diffs), default=0.0)


def _tree_bitwise(a, b):
    eq = jax.tree_util.tree_map(
        lambda x, y: bool((np.asarray(x) == np.asarray(y)).all()), a, b)
    return all(jax.tree_util.tree_leaves(eq))


@pytest.fixture(scope="module")
def dataset():
    graphs = D.make_malnet_like(n_graphs=16, seed=0)
    ds, spec = DP.segment_dataset_shared(graphs, 16, seed=0)
    return ds


def _state(ds, head_out=5):
    cfg = GNNConfig(backbone="sage", n_feat=ds.x.shape[-1], hidden=HID)
    enc = make_encode_fn(cfg)
    key = jax.random.key(0)
    bb = gnn_init(key, cfg)
    head = G.head_init(jax.random.fold_in(key, 1), HID, head_out, "mlp")
    opt = make_optimizer("adam", lr=5e-3)
    return enc, opt, G.TrainState(bb, head, opt.init((bb, head)),
                                  init_table(ds.n, ds.j_max, HID),
                                  jnp.zeros((), jnp.int32))


def _batch(ds, ids):
    return jax.tree_util.tree_map(jnp.asarray, DP._assemble(ds, ids))


# ---------------------------------------------------------------------------
# sharded table: ring ops ≡ dense ops, bit-exact
# ---------------------------------------------------------------------------


def _random_table(n, J, d, seed=0):
    rng = np.random.default_rng(seed)
    return tbl.EmbeddingTable(
        emb=jnp.asarray(rng.normal(size=(n, J, d)), jnp.float32),
        age=jnp.asarray(rng.integers(0, 9, (n, J)), jnp.int32),
        initialized=jnp.asarray(rng.integers(0, 2, (n, J)), bool))


@pytest.mark.parametrize("n_shards", SHARD_COUNTS)
def test_ring_lookup_bit_exact(n_shards):
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P

    n, J, d, B = 21, 3, 4, 8  # n deliberately not divisible by the shards
    table = _random_table(n, J, d)
    ids = jnp.asarray(np.random.default_rng(1).permutation(n)[:B], jnp.int32)
    ctx = DT.make_context(DT.make_dist_mesh(n_shards), n)
    dev = DT.device_table(ctx, table)
    ring = RingExchange(axis_name=DT.AXIS, num_shards=ctx.num_shards,
                        rows=ctx.rows_per_shard)
    f = shard_map(
        ring.lookup, mesh=ctx.mesh,
        in_specs=(tbl.EmbeddingTable(P(DT.AXIS), P(DT.AXIS), P(DT.AXIS)),
                  P(DT.AXIS)),
        out_specs=(P(DT.AXIS), P(DT.AXIS)), check_rep=False)
    emb_d, init_d = jax.jit(f)(dev, jax.device_put(
        ids, DT.batch_sharding(ctx)))
    emb, init = tbl.lookup(table, ids)
    assert (np.asarray(emb_d) == np.asarray(emb)).all()
    assert (np.asarray(init_d) == np.asarray(init)).all()


@pytest.mark.parametrize("n_shards", SHARD_COUNTS)
def test_ring_update_sampled_bit_exact(n_shards):
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P

    n, J, d, B, S = 21, 3, 4, 8, 2
    rng = np.random.default_rng(2)
    table = _random_table(n, J, d)
    ids = jnp.asarray(rng.permutation(n)[:B], jnp.int32)
    sidx = jnp.asarray(rng.integers(0, J, (B, S)), jnp.int32)
    h = jnp.asarray(rng.normal(size=(B, S, d)), jnp.float32)
    step = jnp.asarray(7, jnp.int32)
    ctx = DT.make_context(DT.make_dist_mesh(n_shards), n)
    tspec = tbl.EmbeddingTable(P(DT.AXIS), P(DT.AXIS), P(DT.AXIS))
    ring = RingExchange(axis_name=DT.AXIS, num_shards=ctx.num_shards,
                        rows=ctx.rows_per_shard)
    f = shard_map(
        ring.update_sampled, mesh=ctx.mesh,
        in_specs=(tspec, P(DT.AXIS), P(DT.AXIS), P(DT.AXIS), P()),
        out_specs=tspec, check_rep=False)
    bsh = DT.batch_sharding(ctx)
    got = jax.jit(f)(DT.device_table(ctx, table), jax.device_put(ids, bsh),
                     jax.device_put(sidx, bsh), jax.device_put(h, bsh), step)
    want = tbl.update_sampled(table, ids, sidx, h, step)
    got = DT.host_table(ctx, got)
    assert (np.asarray(got.emb) == np.asarray(want.emb)).all()
    assert (np.asarray(got.age) == np.asarray(want.age)).all()
    assert (np.asarray(got.initialized) == np.asarray(want.initialized)).all()


def test_exchange_bytes_accounting():
    def ring(num_shards):   # the f32 payload
        return RingExchange(axis_name=DT.AXIS, num_shards=num_shards, rows=1)

    assert ring(1).lookup_bytes(8, 4, 16) == 0
    assert ring(1).update_sampled_bytes(8, 1, 16) == 0
    # lookup: D hops of the (ids, emb, init) buffer (answers must come home)
    assert ring(4).lookup_bytes(2, 3, 8) == 4 * 2 * (4 + 3 * 8 * 4 + 3)
    # writes: D-1 hops of the (ids, seg_idx, h_new) buffer (no homecoming)
    assert ring(4).update_sampled_bytes(2, 1, 8) == 3 * 2 * (4 + 4 + 8 * 4)
    assert ring(4).train_step_bytes(2, 3, 1, 8, use_table=False) == 0
    assert ring(4).train_step_bytes(2, 3, 1, 8, use_table=True) == \
        ring(4).lookup_bytes(2, 3, 8) + ring(4).update_sampled_bytes(2, 1, 8)


# ---------------------------------------------------------------------------
# train step: dist ≡ single-device oracle, all seven variants, 5 steps
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("variant", list(G.VARIANTS))
def test_train_step_parity_all_variants(dataset, variant):
    ds = dataset
    n_shards = SHARD_COUNTS[-1]
    enc, opt, state0 = _state(ds)
    batch = _batch(ds, DP.epoch_ids(ds, 8, rng=np.random.default_rng(0),
                                    shuffle=False)[0])
    rng = jax.random.PRNGKey(3)
    var = G.VARIANTS[variant]

    oracle = jax.jit(G.make_train_step(enc, opt, var, keep_prob=0.5))
    s1 = state0
    for _ in range(5):
        s1, m1 = oracle(s1, batch, rng)

    ctx = DT.make_context(DT.make_dist_mesh(n_shards), ds.n)
    dstep = DT.make_dist_train_step(enc, opt, var, ctx=ctx, keep_prob=0.5,
                                    donate=False)
    s2 = DT.device_state(ctx, state0)
    b2 = DT.shard_batch(ctx, batch)
    for _ in range(5):
        s2, m2 = dstep(s2, b2, rng)

    t2 = DT.host_table(ctx, s2.table)
    # bookkeeping is pure row selection — identical segment sampling means
    # identical ages and init flags, bit for bit
    assert (np.asarray(s1.table.age) == np.asarray(t2.age)).all()
    assert (np.asarray(s1.table.initialized) ==
            np.asarray(t2.initialized)).all()
    tol = 0.0 if ctx.num_shards == 1 else 1e-5
    assert _tree_max_diff(s1.table.emb, t2.emb) <= tol
    assert _tree_max_diff((s1.backbone, s1.head),
                          jax.device_get((s2.backbone, s2.head))) <= tol
    assert abs(float(m1["loss"]) - float(m2["loss"])) <= tol
    if ctx.num_shards == 1:  # degenerate mesh: the whole step is bitwise
        assert _tree_bitwise((s1.backbone, s1.head),
                             jax.device_get((s2.backbone, s2.head)))


def test_refresh_step_bit_exact(dataset):
    ds = dataset
    enc, opt, state0 = _state(ds)
    batch = _batch(ds, DP.epoch_ids(ds, 8, rng=np.random.default_rng(0),
                                    shuffle=False)[0])
    s1 = jax.jit(G.make_refresh_step(enc))(state0, batch)
    ctx = DT.make_context(DT.make_dist_mesh(SHARD_COUNTS[-1]), ds.n)
    s2 = DT.make_dist_refresh_step(enc, ctx=ctx, donate=False)(
        DT.device_state(ctx, state0), DT.shard_batch(ctx, batch))
    t2 = DT.host_table(ctx, s2.table)
    # refresh is encode + row writes, no cross-row reductions: bit-exact
    assert (np.asarray(s1.table.emb) == np.asarray(t2.emb)).all()
    assert (np.asarray(s1.table.initialized) ==
            np.asarray(t2.initialized)).all()


def test_finetune_step_parity(dataset):
    ds = dataset
    enc, opt, state0 = _state(ds)
    batch = _batch(ds, DP.epoch_ids(ds, 8, rng=np.random.default_rng(0),
                                    shuffle=False)[0])
    state0 = jax.jit(G.make_refresh_step(enc))(state0, batch)
    ft_opt = make_optimizer("adam", lr=1e-3)
    s1 = state0._replace(opt_state=ft_opt.init(state0.head))
    step1 = jax.jit(G.make_finetune_step(ft_opt))
    for _ in range(5):
        s1, m1 = step1(s1, batch)

    ctx = DT.make_context(DT.make_dist_mesh(SHARD_COUNTS[-1]), ds.n)
    s2 = DT.device_state(ctx, state0._replace(
        opt_state=ft_opt.init(state0.head)))
    step2 = DT.make_dist_finetune_step(ft_opt, ctx=ctx, donate=False)
    b2 = DT.shard_batch(ctx, batch)
    for _ in range(5):
        s2, m2 = step2(s2, b2)
    tol = 0.0 if ctx.num_shards == 1 else 1e-5
    assert _tree_max_diff(s1.head, jax.device_get(s2.head)) <= tol
    assert abs(float(m1["loss"]) - float(m2["loss"])) <= tol


def test_eval_step_parity(dataset):
    ds = dataset
    enc, opt, state0 = _state(ds)
    batch = _batch(ds, DP.epoch_ids(ds, 8, rng=np.random.default_rng(0),
                                    shuffle=False)[0])
    m1 = jax.jit(G.make_eval_step(enc))(state0, batch)
    ctx = DT.make_context(DT.make_dist_mesh(SHARD_COUNTS[-1]), ds.n)
    m2 = DT.make_dist_eval_step(enc, ctx=ctx)(
        DT.device_state(ctx, state0), DT.shard_batch(ctx, batch))
    tol = 0.0 if ctx.num_shards == 1 else 1e-5
    assert abs(float(m1["loss"]) - float(m2["loss"])) <= tol


def test_donated_dist_step_frees_input_table(dataset):
    ds = dataset
    enc, opt, state0 = _state(ds)
    batch = _batch(ds, DP.epoch_ids(ds, 8, rng=np.random.default_rng(0),
                                    shuffle=False)[0])
    ctx = DT.make_context(DT.make_dist_mesh(SHARD_COUNTS[-1]), ds.n)
    step = DT.make_dist_train_step(enc, opt, G.VARIANTS["gst_efd"], ctx=ctx,
                                   keep_prob=0.5)  # donate=True default
    state = DT.device_state(ctx, state0)
    emb0 = state.table.emb
    state, _ = step(state, DT.shard_batch(ctx, batch), jax.random.PRNGKey(0))
    if not emb0.is_deleted():
        pytest.skip("backend does not implement input-output aliasing")
    assert state.table.emb.shape == emb0.shape  # scatter landed in place


def test_dist_step_kernel_launch_contract(dataset):
    """The batched Pallas kernels run per-shard UNCHANGED: the dist step's
    jaxpr (counted through the shard_map sub-jaxpr) contains exactly the
    same number of pallas_call launches as the single-device step — data
    parallelism adds collectives, never extra kernel launches."""
    from repro.kernels.ops import count_pallas_calls

    ds = dataset
    cfg = GNNConfig(backbone="sage", n_feat=ds.x.shape[-1], hidden=HID,
                    use_pallas=True)
    enc = make_encode_fn(cfg)
    key = jax.random.key(0)
    bb = gnn_init(key, cfg)
    head = G.head_init(jax.random.fold_in(key, 1), HID, 5, "mlp")
    opt = make_optimizer("adam", lr=5e-3)
    state = G.TrainState(bb, head, opt.init((bb, head)),
                         init_table(ds.n, ds.j_max, HID),
                         jnp.zeros((), jnp.int32))
    batch = _batch(ds, DP.epoch_ids(ds, 8, rng=np.random.default_rng(0),
                                    shuffle=False)[0])
    var = G.VARIANTS["gst_efd"]
    sstep = jax.jit(G.make_train_step(enc, opt, var, keep_prob=0.5,
                                      use_pallas=True))
    n_single = count_pallas_calls(
        lambda s, b: sstep(s, b, jax.random.PRNGKey(0)), state, batch)

    ctx = DT.make_context(DT.make_dist_mesh(SHARD_COUNTS[-1]), ds.n)
    dstep = DT.make_dist_train_step(enc, opt, var, ctx=ctx, keep_prob=0.5,
                                    use_pallas=True, donate=False)
    sd = DT.device_state(ctx, state)
    bd = DT.shard_batch(ctx, batch)
    n_dist = count_pallas_calls(
        lambda s, b: dstep(s, b, jax.random.PRNGKey(0)), sd, bd)
    assert n_single > 0
    assert n_dist == n_single


def test_batch_size_must_divide_shards(dataset):
    ds = dataset
    ctx = DT.make_context(DT.make_dist_mesh(SHARD_COUNTS[-1]), ds.n)
    if ctx.num_shards == 1:
        pytest.skip("any batch divides one shard")
    batch = _batch(ds, np.arange(ctx.num_shards + 1))
    with pytest.raises(ValueError, match="must divide"):
        DT.shard_batch(ctx, batch)


# ---------------------------------------------------------------------------
# async host→device pipeline
# ---------------------------------------------------------------------------


def _put_identity(b):
    return jax.tree_util.tree_map(jnp.asarray, b)


def test_async_feeder_delivers_sync_trace(dataset):
    ds = dataset
    sched = DP.epoch_ids(ds, 4, rng=np.random.default_rng(5))
    sync = list(DP.make_feeder("sync", ds, sched, _put_identity))
    asyn = list(DP.make_feeder("async", ds, sched, _put_identity, depth=2))
    assert len(sync) == len(asyn) == len(sched)
    for b1, b2 in zip(sync, asyn):
        assert _tree_bitwise(b1, b2)
        assert b1.batch_pos is not None  # per-row RNG positions travel along


def test_feeder_stats_populated(dataset):
    ds = dataset
    sched = DP.epoch_ids(ds, 4, rng=np.random.default_rng(5), shuffle=False)
    feeder = DP.make_feeder("async", ds, sched, _put_identity)
    n = sum(1 for _ in feeder)
    assert feeder.stats.batches == n == len(sched)
    assert len(feeder.stats.blocked_per_batch) == n
    assert feeder.stats.host_blocked_ms >= 0.0


def test_async_feeder_shuts_down_when_abandoned(dataset):
    """Breaking out of the consumer loop mid-epoch must stop the producer
    thread (no forever-blocked daemon pinning device batches)."""
    ds = dataset
    sched = DP.epoch_ids(ds, 4, rng=np.random.default_rng(5))
    feeder = DP.make_feeder("async", ds, sched, _put_identity, depth=1)
    it = iter(feeder)
    next(it)
    it.close()  # what an exception in the consumer's for-loop triggers
    feeder._thread.join(timeout=5.0)
    assert not feeder._thread.is_alive()


def test_async_feeder_is_single_shot(dataset):
    ds = dataset
    sched = DP.epoch_ids(ds, 4, rng=np.random.default_rng(5))
    feeder = DP.make_feeder("async", ds, sched, _put_identity)
    assert sum(1 for _ in feeder) == len(sched)
    with pytest.raises(RuntimeError, match="single-shot"):
        next(iter(feeder))  # would otherwise hang on the drained queue


def test_async_feeder_propagates_producer_errors(dataset):
    ds = dataset
    sched = DP.epoch_ids(ds, 4, rng=np.random.default_rng(5))

    def bad_put(b):
        raise RuntimeError("device_put exploded")

    with pytest.raises(RuntimeError, match="device_put exploded"):
        list(DP.make_feeder("async", ds, sched, bad_put))


def test_epoch_ids_drop_last_and_determinism(dataset):
    ds = dataset
    a = DP.epoch_ids(ds, 8, rng=np.random.default_rng(9))
    b = DP.epoch_ids(ds, 8, rng=np.random.default_rng(9))
    assert all((x == y).all() for x, y in zip(a, b))
    assert all(len(x) == 8 for x in a)


# ---------------------------------------------------------------------------
# shared train/serve padding policy
# ---------------------------------------------------------------------------


def test_train_padding_comes_from_serve_ladder():
    graphs = D.make_malnet_like(n_graphs=4, seed=1)
    ds, spec = DP.segment_dataset_shared(graphs, 32, seed=1)
    ladder = default_ladder(32)
    assert spec in ladder
    assert ds.m_max == spec.m_max and ds.e_max == spec.e_max


def test_segment_fingerprint_matches_across_train_and_serve():
    """Same-rung invariant: a segment padded by the training pipeline to
    the shared bucket spec is byte-identical (same fingerprint) to that
    segment padded by the serving side FOR THE SAME RUNG.  Serving routes
    smaller segments to smaller rungs — those get their own addresses, by
    design (training uses one static shape)."""
    graphs = D.make_malnet_like(n_graphs=2, seed=2)
    g = graphs[0]
    _, spec = DP.segment_dataset_shared(graphs, 32, seed=2)
    node_ids = np.arange(min(10, len(g.x)), dtype=np.int32)
    from repro.graphs.batching import pad_segment
    x, e, ev, nv = pad_segment(g, node_ids, spec.m_max, spec.e_max)
    train_side = {"x": x, "edges": e, "edge_valid": ev, "node_valid": nv}
    serve_side = pad_to_bucket(g, node_ids, spec)
    assert segment_fingerprint(train_side, 0) == \
        segment_fingerprint(serve_side, 0)
