"""Lookahead prefetch lane (ISSUE 9): hide the exchange behind compute.

Contract under test (dist/exchange.py + dist/train.py + dist/pipeline.py):
  * ``prefetch_lookup`` is the strategy's lookup verbatim — same
    collectives, same bytes, just dispatched as its own jitted program
    while the previous step runs;
  * every strategy's fused ``update_sampled_patch`` applies the sampled
    write-back exactly like ``update_sampled`` AND repairs the next
    batch's prefetched buffer so it equals a lookup of the POST-write
    table, bit-exact at f32, across adversarial overlap schedules
    (all-overlap | zero-overlap | partial) and shard counts;
  * ring/alltoall patch for free (0 extra wire bytes — asserted against
    the jaxpr); bucketed pays exactly its analytic ``patch_bytes``;
  * end to end, prefetched training is BIT-exact vs the inline dist
    oracle at f32 (params, table emb, ages, init) for all 7 GST
    variants x 3 strategies;
  * ragged/sentinel next batches read zeros and are never patched;
  * ``PrefetchLane`` dispatches each item once, before the previous
    item's step launches, and propagates errors/close;
  * ``TieredStore`` lookahead pinning keeps prefetched batches resident
    (release frees them; exhaustion raises, not corrupts);
  * the obs gate requires the ``exchange.prefetch.*`` families whenever
    a stream advertises the lane.

Runs at whatever device count the host exposes: tier-1 sees 1 device;
the exchange-matrix CI prefetch leg re-runs under
XLA_FLAGS=--xla_force_host_platform_device_count=8.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.shard_map import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import dist as DT
from repro.core import embedding_table as tbl
from repro.core import gst as G
from repro.core.embedding_table import init_table
from repro.dist import exchange as EX
from repro.dist import pipeline as DP
from repro.graphs import data as D
from repro.graphs.gnn import GNNConfig, gnn_init, make_encode_fn
from repro.obs.gate import main as gate_main
from repro.obs.metrics import MetricsRegistry
from repro.obs.staleness import record_prefetch_exchange
from repro.optim import make_optimizer

N_DEV = jax.device_count()
SHARD_COUNTS = [d for d in (1, 2, 4, 8) if d <= N_DEV]
# the ISSUE's adversarial grid: shards {2, 8} (intersected with the host)
MULTI_SHARDS = [d for d in (2, 8) if d <= N_DEV] or [1]
STRATEGIES = list(EX.EXCHANGES)
OVERLAPS = ("all", "none", "partial")
HID = 8

N_ROWS, J, DH = 64, 2, 4
B_GLOBAL = 8


def _random_table(n, J, d, seed=0):
    rng = np.random.default_rng(seed)
    return tbl.EmbeddingTable(
        emb=jnp.asarray(rng.normal(size=(n, J, d)), jnp.float32),
        age=jnp.asarray(rng.integers(0, 9, (n, J)), jnp.int32),
        initialized=jnp.asarray(rng.integers(0, 2, (n, J)), bool))


def _ctx(n_shards, n_rows=N_ROWS, **kw):
    return DT.make_context(DT.make_dist_mesh(n_shards), n_rows, **kw)


def _tspec():
    return tbl.EmbeddingTable(P(DT.AXIS), P(DT.AXIS), P(DT.AXIS))


def _put(ctx, x):
    return jax.device_put(x, NamedSharding(ctx.mesh, P(DT.AXIS)))


def _exchange(name, ctx, cap=None, patch_cap=None, dtype="f32"):
    return EX.make_exchange(name, axis_name=DT.AXIS,
                            num_shards=ctx.num_shards,
                            rows=ctx.rows_per_shard, cap=cap,
                            payload_dtype=dtype, patch_cap=patch_cap)


def _overlap_ids(mode, rng):
    """(cur_ids, next_ids): unique global batches with controlled overlap."""
    pool = rng.permutation(N_ROWS).astype(np.int32)
    cur = pool[:B_GLOBAL]
    if mode == "all":
        nxt = rng.permutation(cur)
    elif mode == "none":
        nxt = pool[B_GLOBAL:2 * B_GLOBAL]
    else:
        nxt = rng.permutation(np.concatenate(
            [cur[:B_GLOBAL // 2], pool[B_GLOBAL:B_GLOBAL + B_GLOBAL // 2]]))
    return cur, nxt.astype(np.int32)


def _payloads_sampled(ids, S=1):
    rng = np.random.default_rng(7)
    key = rng.normal(size=(N_ROWS + 1, S, DH)).astype(np.float32)
    sidx = (ids[:, None] + np.arange(S)[None, :]) % J
    return sidx.astype(np.int32), key[ids]


def _patch_callable(ex, with_dest):
    """update_sampled_patch flattened for shard_map (tuple args unpacked)."""
    if with_dest:
        def f(table, ids, sidx, h, step, pe, pi, nids, dest):
            t, (e, i) = ex.update_sampled_patch(table, ids, sidx, h, step,
                                                (pe, pi), nids, dest)
            return t, e, i
        return f

    def f(table, ids, sidx, h, step, pe, pi, nids):
        t, (e, i) = ex.update_sampled_patch(table, ids, sidx, h, step,
                                            (pe, pi), nids)
        return t, e, i
    return f


def _patch_specs(with_dest):
    ins = [_tspec(), P(DT.AXIS), P(DT.AXIS), P(DT.AXIS), P(),
           P(DT.AXIS), P(DT.AXIS), P(DT.AXIS)]
    if with_dest:
        ins.append(P(DT.AXIS))
    return tuple(ins), (_tspec(), P(DT.AXIS), P(DT.AXIS))


def _run_patch(ctx, ex, table, ids, sidx, h, step, pref, next_ids,
               dest=None):
    with_dest = dest is not None
    in_specs, out_specs = _patch_specs(with_dest)
    f = shard_map(_patch_callable(ex, with_dest), mesh=ctx.mesh,
                  in_specs=in_specs, out_specs=out_specs, check_rep=False)
    args = [DT.device_table(ctx, table), _put(ctx, jnp.asarray(ids)),
            _put(ctx, jnp.asarray(sidx)), _put(ctx, jnp.asarray(h)), step,
            _put(ctx, pref[0]), _put(ctx, pref[1]),
            _put(ctx, jnp.asarray(next_ids))]
    if with_dest:
        args.append(_put(ctx, jnp.asarray(dest)))
    got_t, got_e, got_i = jax.jit(f)(*args)
    return DT.host_table(ctx, got_t), np.asarray(got_e), np.asarray(got_i)


# ---------------------------------------------------------------------------
# fused-op parity: write-back == dense oracle AND the patched buffer ==
# a lookup of the post-write table, bit-exact, every overlap schedule
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("overlap", OVERLAPS)
@pytest.mark.parametrize("n_shards", MULTI_SHARDS + ([1] if 1 in SHARD_COUNTS else []))
@pytest.mark.parametrize("strategy", STRATEGIES)
def test_update_sampled_patch_parity(strategy, n_shards, overlap):
    ctx = _ctx(n_shards)
    rng = np.random.default_rng(11)
    ids, next_ids = _overlap_ids(overlap, rng)
    sidx, h = _payloads_sampled(ids)
    table = _random_table(N_ROWS, J, DH)
    step = jnp.asarray(5, jnp.int32)
    cap = EX.required_capacity(ids, num_shards=n_shards,
                               rows=ctx.rows_per_shard)
    pcap = EX.required_patch_capacity(ids, next_ids, num_shards=n_shards,
                                      rows=ctx.rows_per_shard)
    ex = _exchange(strategy, ctx, cap=cap, patch_cap=pcap)
    pref = tbl.lookup(table, jnp.asarray(next_ids))
    dest = EX.consumer_shards(ids, next_ids, num_shards=n_shards,
                              rows=ctx.rows_per_shard) \
        if strategy == "bucketed" else None

    got_t, got_e, got_i = _run_patch(ctx, ex, table, ids, sidx, h, step,
                                     pref, next_ids, dest)
    want_t = tbl.update_sampled(table, jnp.asarray(ids), jnp.asarray(sidx),
                                jnp.asarray(h), step)
    # the table write is update_sampled verbatim
    for a, b in zip(got_t, want_t):
        assert (np.asarray(a) == np.asarray(b)).all()
    # the patched buffer equals a fresh lookup of the POST-write table —
    # the invariant that makes the next prefetched step read-correct
    want_e, want_i = tbl.lookup(want_t, jnp.asarray(next_ids))
    assert (got_e == np.asarray(want_e)).all(), overlap
    assert (got_i == np.asarray(want_i)).all(), overlap


def test_bucketed_patch_requires_next_dest():
    ctx = _ctx(SHARD_COUNTS[-1])
    ex = _exchange("bucketed", ctx, cap=2, patch_cap=1)
    if ctx.num_shards == 1:
        pytest.skip("one shard: the local fused path needs no routing")
    with pytest.raises(ValueError, match="next_dest"):
        ids = jnp.zeros(B_GLOBAL // ctx.num_shards, jnp.int32)
        ex.update_sampled_patch(
            _random_table(N_ROWS, J, DH), ids, jnp.zeros_like(ids[:, None]),
            jnp.zeros((ids.shape[0], 1, DH)), jnp.asarray(0, jnp.int32),
            (jnp.zeros((4, J, DH)), jnp.zeros((4, J), bool)),
            jnp.zeros(4, jnp.int32))


# ---------------------------------------------------------------------------
# sentinel / ragged next batches: pad slots read zeros, never patched
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_ragged_next_batch_sentinels_never_patched(strategy):
    """Next global batch of 2·D+3 rows, sentinel-padded: real slots are
    patched exactly like the dense case, pad slots keep their prefetched
    zeros, and the table write is untouched by the padding."""
    n_shards = SHARD_COUNTS[-1]
    ctx = _ctx(n_shards)
    rng = np.random.default_rng(3)
    ids = rng.permutation(N_ROWS)[:B_GLOBAL].astype(np.int32)
    sidx, h = _payloads_sampled(ids)
    B_next = 2 * n_shards + 3 if n_shards > 1 else 5
    # overlap the current batch so real patches actually happen
    next_real = np.concatenate(
        [ids[:B_next // 2],
         np.setdiff1d(rng.permutation(N_ROWS), ids)[:B_next - B_next // 2]]
    ).astype(np.int32)[:B_next]
    next_p, n_real = EX.pad_ragged(n_shards, ctx.rows_per_shard, next_real)
    # bucket capacity must cover BOTH batches: the current batch's write
    # and the next batch's prefetched lookup (the launcher plans over
    # the whole schedule with plan_capacity)
    cap = EX.plan_capacity([ids, next_p], num_shards=n_shards,
                           rows=ctx.rows_per_shard)
    pcap = EX.required_patch_capacity(ids, next_p, num_shards=n_shards,
                                      rows=ctx.rows_per_shard)
    ex = _exchange(strategy, ctx, cap=cap, patch_cap=pcap)
    table = _random_table(N_ROWS, J, DH)
    step = jnp.asarray(4, jnp.int32)
    # prefetched buffer for the padded batch: pad rows read EXACT zeros
    look = shard_map(ex.prefetch_lookup, mesh=ctx.mesh,
                     in_specs=(_tspec(), P(DT.AXIS)),
                     out_specs=(P(DT.AXIS), P(DT.AXIS)), check_rep=False)
    pe, pi = jax.jit(look)(DT.device_table(ctx, table),
                           _put(ctx, jnp.asarray(next_p)))
    assert (np.asarray(pe)[n_real:] == 0).all()
    assert not np.asarray(pi)[n_real:].any()

    dest = EX.consumer_shards(ids, next_p, num_shards=n_shards,
                              rows=ctx.rows_per_shard) \
        if strategy == "bucketed" else None
    got_t, got_e, got_i = _run_patch(
        ctx, ex, table, ids, sidx, h, step,
        (np.asarray(pe), np.asarray(pi)), next_p, dest)
    want_t = tbl.update_sampled(table, jnp.asarray(ids), jnp.asarray(sidx),
                                jnp.asarray(h), step)
    for a, b in zip(got_t, want_t):
        assert (np.asarray(a) == np.asarray(b)).all()
    want_e, want_i = tbl.lookup(want_t, jnp.asarray(next_real))
    assert (got_e[:n_real] == np.asarray(want_e)).all()
    assert (got_i[:n_real] == np.asarray(want_i)).all()
    # sentinel pad slots: never patched, still exact zeros
    assert (got_e[n_real:] == 0).all()
    assert not got_i[n_real:].any()


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_all_sentinel_next_batch_patch_noop(strategy):
    """The epoch tail: every next id is the sentinel — the patch must be
    a pure no-op on the throwaway buffer at every strategy."""
    n_shards = SHARD_COUNTS[-1]
    ctx = _ctx(n_shards)
    rng = np.random.default_rng(5)
    ids = rng.permutation(N_ROWS)[:B_GLOBAL].astype(np.int32)
    sidx, h = _payloads_sampled(ids)
    sent = n_shards * ctx.rows_per_shard
    next_ids = np.full(B_GLOBAL, sent, np.int32)
    ex = _exchange(strategy, ctx,
                   cap=EX.required_capacity(ids, num_shards=n_shards,
                                            rows=ctx.rows_per_shard),
                   patch_cap=1)
    table = _random_table(N_ROWS, J, DH)
    zeros = (np.zeros((B_GLOBAL, J, DH), np.float32),
             np.zeros((B_GLOBAL, J), bool))
    dest = np.full(B_GLOBAL, n_shards, np.int32) \
        if strategy == "bucketed" else None
    got_t, got_e, got_i = _run_patch(ctx, ex, table, ids, sidx, h,
                                     jnp.asarray(2, jnp.int32), zeros,
                                     next_ids, dest)
    want_t = tbl.update_sampled(table, jnp.asarray(ids), jnp.asarray(sidx),
                                jnp.asarray(h), jnp.asarray(2, jnp.int32))
    for a, b in zip(got_t, want_t):
        assert (np.asarray(a) == np.asarray(b)).all()
    assert (got_e == 0).all() and not got_i.any()


# ---------------------------------------------------------------------------
# bytes: prefetch_lookup == lookup traffic; the fused patch costs exactly
# patch_bytes extra (0 for ring/alltoall) — asserted against the jaxpr
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", list(EX.PAYLOAD_DTYPES))
@pytest.mark.parametrize("strategy", STRATEGIES)
def test_prefetch_bytes_model_matches_measured(strategy, dtype):
    n_shards = SHARD_COUNTS[-1]
    ctx = _ctx(n_shards)
    B_local, S = 4, 2
    B = B_local * n_shards
    cap = 2 if n_shards > 1 else None
    pcap = 2 if n_shards > 1 else None
    ex = _exchange(strategy, ctx, cap=cap, patch_cap=pcap, dtype=dtype)
    dev = DT.device_table(ctx, _random_table(N_ROWS, J, DH))
    ids = jnp.zeros(B, jnp.int32)
    sidx = jnp.zeros((B, S), jnp.int32)
    h = jnp.zeros((B, S, DH), jnp.float32)
    step = jnp.asarray(0, jnp.int32)
    pe = jnp.zeros((B, J, DH), jnp.float32)
    pi = jnp.zeros((B, J), bool)
    dest = jnp.zeros(B, jnp.int32)

    look = shard_map(ex.prefetch_lookup, mesh=ctx.mesh,
                     in_specs=(_tspec(), P(DT.AXIS)),
                     out_specs=(P(DT.AXIS), P(DT.AXIS)), check_rep=False)
    measured_look = EX.measured_exchange_bytes(look, n_shards, dev, ids)
    assert measured_look == ex.prefetch_lookup_bytes(B_local, J, DH)
    assert measured_look == ex.lookup_bytes(B_local, J, DH)

    with_dest = strategy == "bucketed"
    in_specs, out_specs = _patch_specs(with_dest)
    patch = shard_map(_patch_callable(ex, with_dest), mesh=ctx.mesh,
                      in_specs=in_specs, out_specs=out_specs,
                      check_rep=False)
    args = (dev, ids, sidx, h, step, pe, pi, ids) + \
        ((dest,) if with_dest else ())
    measured = EX.measured_exchange_bytes(patch, n_shards, *args)
    assert measured == ex.update_sampled_patch_bytes(B_local, S, DH)
    # the surcharge over the inline write-back is exactly patch_bytes:
    # zero for ring/alltoall (fused into existing hops), the tiny
    # consumer-direct hop for bucketed
    surcharge = measured - ex.update_sampled_bytes(B_local, S, DH)
    assert surcharge == ex.patch_bytes(B_local, S, DH)
    if strategy in ("ring", "alltoall"):
        assert surcharge == 0


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_prefetch_train_step_bytes_model(strategy):
    ex = EX.make_exchange(strategy, axis_name="x", num_shards=8, rows=8,
                          cap=4, patch_cap=2)
    b, j, s, d = 16, 4, 1, 16
    assert ex.prefetch_train_step_bytes(b, j, s, d, use_table=True) == \
        ex.train_step_bytes(b, j, s, d, use_table=True) + \
        ex.patch_bytes(b, s, d)
    assert ex.prefetch_train_step_bytes(b, j, s, d, use_table=False) == 0
    if strategy != "bucketed":
        assert ex.patch_bytes(b, s, d) == 0


# ---------------------------------------------------------------------------
# host planners: consumer routing + patch capacity
# ---------------------------------------------------------------------------


def test_consumer_shards_routing():
    # 2 shards x 8 rows; next batch [1, 9, 2, 3]: positions 0-1 live on
    # shard 0, positions 2-3 on shard 1
    cur = np.asarray([1, 2, 7, 9])
    nxt = np.asarray([1, 9, 2, 3])
    dest = EX.consumer_shards(cur, nxt, num_shards=2, rows=8)
    assert dest.tolist() == [0, 1, 2, 0]    # 7 has no consumer
    # zero overlap: nobody travels
    assert (EX.consumer_shards(np.arange(4), np.arange(8, 12),
                               num_shards=2, rows=8) == 2).all()
    # ragged current batch is sentinel-padded; the pad row never matches
    d = EX.consumer_shards(np.asarray([0, 1, 2]), np.asarray([0, 1, 2, 3]),
                           num_shards=2, rows=8)
    assert d.shape[0] == 4 and d[3] == 2
    # sentinel ids in the NEXT batch are not consumers
    d = EX.consumer_shards(np.asarray([0, 16, 1, 3]),
                           np.asarray([0, 16, 3, 16]), num_shards=2, rows=8)
    assert d.tolist() == [0, 2, 2, 1]


def test_required_and_plan_patch_capacity():
    # all-overlap, contiguous halves: both of device 0's consumers live
    # on shard 0 => capacity 2
    ids = np.asarray([0, 1, 8, 9])
    assert EX.required_patch_capacity(ids, ids, num_shards=2, rows=8) == 2
    # zero overlap plans to the minimum bucket of 1
    assert EX.required_patch_capacity(np.arange(4), np.arange(8, 12),
                                      num_shards=2, rows=8) == 1
    # plan over a schedule = max over consecutive pairs only
    a, b = np.asarray([0, 1, 8, 9]), np.asarray([4, 5, 12, 13])
    assert EX.plan_patch_capacity([a, b, a], num_shards=2, rows=8) == 1
    assert EX.plan_patch_capacity([a, a, b], num_shards=2, rows=8) == 2


# ---------------------------------------------------------------------------
# end to end: prefetched training == the inline dist oracle, bit for bit
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def dataset():
    graphs = D.make_malnet_like(n_graphs=16, seed=0)
    ds, spec = DP.segment_dataset_shared(graphs, 16, seed=0)
    return ds


def _state(ds):
    cfg = GNNConfig(backbone="sage", n_feat=ds.x.shape[-1], hidden=HID)
    enc = make_encode_fn(cfg)
    key = jax.random.key(0)
    bb = gnn_init(key, cfg)
    head = G.head_init(jax.random.fold_in(key, 1), HID, 5, "mlp")
    opt = make_optimizer("adam", lr=5e-3)
    return enc, opt, G.TrainState(bb, head, opt.init((bb, head)),
                                  init_table(ds.n, ds.j_max, HID),
                                  jnp.zeros((), jnp.int32))


def _schedule(ds, mode, steps=4, seed=0):
    rng = np.random.default_rng(seed)
    if mode == "all":
        base = rng.permutation(ds.n)[:B_GLOBAL].astype(np.int32)
        return [rng.permutation(base) for _ in range(steps)]
    if mode == "none":
        a = np.arange(B_GLOBAL, dtype=np.int32)
        b = np.arange(B_GLOBAL, 2 * B_GLOBAL, dtype=np.int32)
        return [a, b] * (steps // 2)
    raise ValueError(mode)


def _mk_ctxs(ds, n_shards, strategy, sched):
    rows = _ctx(n_shards, ds.n).rows_per_shard
    cap = EX.plan_capacity(sched, num_shards=n_shards, rows=rows) \
        if strategy == "bucketed" else None
    pcap = EX.plan_patch_capacity(sched, num_shards=n_shards, rows=rows) \
        if strategy == "bucketed" else None
    mk = lambda **kw: DT.make_context(DT.make_dist_mesh(n_shards), ds.n,
                                      exchange=strategy, exchange_cap=cap,
                                      **kw)
    return mk(), mk(prefetch=True, patch_cap=pcap)


def _assemble_padded(ds, ids):
    """Like the feeder's _assemble, but sentinel-tolerant: pad rows gather
    graph 0's inputs (identical garbage on both runs) while graph_ids
    keeps the sentinel so the table ops drop their reads and writes."""
    real = np.where(ids < ds.n, ids, 0).astype(np.int32)
    return DP._assemble(ds, real)._replace(graph_ids=ids.astype(np.int32))


def _run_inline(ds, enc, opt, state0, variant, ctx, sched):
    step = DT.make_dist_train_step(enc, opt, G.VARIANTS[variant], ctx=ctx,
                                   keep_prob=0.5, donate=False)
    state = DT.device_state(ctx, state0)
    m = None
    for ids in sched:
        state, m = step(state,
                        DT.shard_batch(ctx, _assemble_padded(ds, ids)),
                        jax.random.PRNGKey(3))
    return state, m


def _run_prefetched(ds, enc, opt, state0, variant, ctx, sched):
    """The launcher's prefetch loop, driven by hand over a schedule."""
    pstep = DT.make_dist_train_step(enc, opt, G.VARIANTS[variant], ctx=ctx,
                                    keep_prob=0.5, donate=False)
    pf = DT.make_prefetch_lookup(ctx)
    bsh = DT.batch_sharding(ctx)
    sent = ctx.num_shards * ctx.table_rows
    batches = [(ids, DT.shard_batch(ctx, _assemble_padded(ds, ids)))
               for ids in sched]
    state = DT.device_state(ctx, state0)
    pref, m = None, None
    for k, (ids, b) in enumerate(batches):
        if pref is None:
            pref = pf(state.table, b.graph_ids)
        if k + 1 < len(batches):
            nids, nb = batches[k + 1]
            nxt, next_ids = pf(state.table, nb.graph_ids), nb.graph_ids
            dest = EX.consumer_shards(ids, nids, num_shards=ctx.num_shards,
                                      rows=ctx.table_rows)
        else:
            B = ids.shape[0]
            next_ids = jax.device_put(np.full(B, sent, np.int32), bsh)
            nxt = (jax.device_put(np.zeros((B, ds.j_max, HID), np.float32),
                                  bsh),
                   jax.device_put(np.zeros((B, ds.j_max), bool), bsh))
            dest = np.full(B, ctx.num_shards, np.int32)
        state, m, pref = pstep(state, b, jax.random.PRNGKey(3), pref, nxt,
                               next_ids,
                               jax.device_put(np.asarray(dest, np.int32),
                                              bsh))
    return state, m


def _assert_bit_exact(ctx_a, s_a, m_a, ctx_b, s_b, m_b):
    ta, tb = DT.host_table(ctx_a, s_a.table), DT.host_table(ctx_b, s_b.table)
    assert (np.asarray(ta.age) == np.asarray(tb.age)).all()
    assert (np.asarray(ta.initialized) ==
            np.asarray(tb.initialized)).all()
    assert (np.asarray(ta.emb) == np.asarray(tb.emb)).all()
    pa = jax.device_get((s_a.backbone, s_a.head))
    pb = jax.device_get((s_b.backbone, s_b.head))
    for x, y in zip(jax.tree_util.tree_leaves(pa),
                    jax.tree_util.tree_leaves(pb)):
        assert (np.asarray(x) == np.asarray(y)).all()
    assert float(m_a["loss"]) == float(m_b["loss"])


@pytest.mark.parametrize("strategy", STRATEGIES)
@pytest.mark.parametrize("variant", list(G.VARIANTS))
def test_prefetched_training_bit_exact_all_variants(dataset, variant,
                                                    strategy):
    ds = dataset
    if N_DEV == 1 and variant != "gst_efd":
        pytest.skip("single-device host: the degenerate mesh is covered by "
                    "the complete method; the full 7x3 matrix runs in the "
                    "exchange-matrix CI prefetch leg at 8 forced devices")
    n_shards = SHARD_COUNTS[-1]
    sched = _schedule(ds, "all", steps=4, seed=1)
    ctx_i, ctx_p = _mk_ctxs(ds, n_shards, strategy, sched)
    enc, opt, state0 = _state(ds)
    s1, m1 = _run_inline(ds, enc, opt, state0, variant, ctx_i, sched)
    s2, m2 = _run_prefetched(ds, enc, opt, state0, variant, ctx_p, sched)
    _assert_bit_exact(ctx_i, s1, m1, ctx_p, s2, m2)


@pytest.mark.parametrize("overlap", ("all", "none"))
@pytest.mark.parametrize("n_shards", MULTI_SHARDS)
@pytest.mark.parametrize("strategy", STRATEGIES)
def test_prefetched_training_adversarial_schedules(dataset, strategy,
                                                   n_shards, overlap):
    """All-overlap (every row patched every step) and zero-overlap (the
    patch must be a perfect no-op) schedules, shards {2, 8}."""
    ds = dataset
    sched = _schedule(ds, overlap, steps=4, seed=2)
    ctx_i, ctx_p = _mk_ctxs(ds, n_shards, strategy, sched)
    enc, opt, state0 = _state(ds)
    s1, m1 = _run_inline(ds, enc, opt, state0, "gst_efd", ctx_i, sched)
    s2, m2 = _run_prefetched(ds, enc, opt, state0, "gst_efd", ctx_p, sched)
    _assert_bit_exact(ctx_i, s1, m1, ctx_p, s2, m2)


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_prefetched_training_ragged_tail(dataset, strategy):
    """A ragged LAST batch (size not divisible by the shard count) rides
    the prefetch lane via pad_ragged: sentinel rows read zeros, writes
    land nowhere, the run stays bit-exact vs inline on the same padded
    schedule."""
    ds = dataset
    n_shards = SHARD_COUNTS[-1]
    if n_shards == 1:
        pytest.skip("raggedness needs a multi-shard batch split")
    rng = np.random.default_rng(9)
    rows = _ctx(n_shards, ds.n).rows_per_shard
    full = rng.permutation(ds.n)[:B_GLOBAL].astype(np.int32)
    tail = rng.permutation(ds.n)[:n_shards + 1].astype(np.int32)
    tail_p, _ = EX.pad_ragged(n_shards, rows, tail)
    sched = [full, tail_p]
    ctx_i, ctx_p = _mk_ctxs(ds, n_shards, strategy, sched)
    enc, opt, state0 = _state(ds)
    s1, m1 = _run_inline(ds, enc, opt, state0, "gst_efd", ctx_i, sched)
    s2, m2 = _run_prefetched(ds, enc, opt, state0, "gst_efd", ctx_p, sched)
    _assert_bit_exact(ctx_i, s1, m1, ctx_p, s2, m2)


# ---------------------------------------------------------------------------
# PrefetchLane mechanics
# ---------------------------------------------------------------------------


class _FakeFeeder:
    def __init__(self, items):
        self.items = list(items)
        self.closed = False
        self.stats = "the-stats"

    def __iter__(self):
        for it in self.items:
            if isinstance(it, Exception):
                raise it
            yield it

    def close(self):
        self.closed = True


def test_prefetch_lane_dispatch_order_and_pairing():
    events = []
    feeder = _FakeFeeder(["a", "b", "c"])
    lane = DP.PrefetchLane(feeder,
                           lambda it: events.append(("d", it)) or f"h:{it}")
    out = []
    for cur, cur_h, nxt, nxt_h in lane:
        events.append(("y", cur))
        out.append((cur, cur_h, nxt, nxt_h))
    # every item dispatched exactly once, BEFORE the step that runs while
    # its lookup is in flight: d(a), d(b) precede y(a)
    assert events == [("d", "a"), ("d", "b"), ("y", "a"),
                      ("d", "c"), ("y", "b"), ("y", "c")]
    assert out == [("a", "h:a", "b", "h:b"), ("b", "h:b", "c", "h:c"),
                   ("c", "h:c", None, None)]
    assert lane.prefetch_batches == 3
    assert feeder.closed
    assert lane.stats == "the-stats"


def test_prefetch_lane_single_and_empty():
    lane = DP.PrefetchLane(_FakeFeeder(["only"]), lambda it: "h")
    assert list(lane) == [("only", "h", None, None)]
    feeder = _FakeFeeder([])
    lane = DP.PrefetchLane(feeder, lambda it: pytest.fail("no dispatch"))
    assert list(lane) == []
    assert feeder.closed


def test_prefetch_lane_error_propagates_and_closes():
    feeder = _FakeFeeder(["a", RuntimeError("boom")])
    lane = DP.PrefetchLane(feeder, lambda it: "h")
    with pytest.raises(RuntimeError, match="boom"):
        list(lane)
    assert feeder.closed


# ---------------------------------------------------------------------------
# tiered-store lookahead pinning
# ---------------------------------------------------------------------------


def test_tiered_store_lookahead_pinning():
    ctx = _ctx(1, n_rows=16, device_rows=8)
    store = DT.make_dist_store(ctx, J, DH)
    try:
        store.restore(init_table(16, J, DH))
        prep_a = store.begin(np.arange(6, dtype=np.int32), pin=True)
        # pinned rows shrink the displaceable pool: 6 pinned + 6 new > 8
        with pytest.raises(RuntimeError, match="lookahead pinning"):
            store.begin(np.arange(6, 12, dtype=np.int32))
        # releasing the pin frees the tier again
        store.release(prep_a)
        store.begin(np.arange(6, 12, dtype=np.int32))
    finally:
        store.close()


def test_tiered_store_unpinned_begins_unaffected():
    ctx = _ctx(1, n_rows=16, device_rows=8)
    store = DT.make_dist_store(ctx, J, DH)
    try:
        store.restore(init_table(16, J, DH))
        store.begin(np.arange(6, dtype=np.int32))          # no pin
        store.begin(np.arange(6, 12, dtype=np.int32))      # fine
    finally:
        store.close()


def test_device_store_accepts_pin_noop():
    ctx = _ctx(1, n_rows=16)
    store = DT.make_dist_store(ctx, J, DH)
    try:
        store.restore(init_table(16, J, DH))
        prep = store.begin(np.arange(4, dtype=np.int32), pin=True)
        store.release(prep)     # base release: no-op, never raises
    finally:
        store.close()


# ---------------------------------------------------------------------------
# observability: recorder families + the CI gate contract
# ---------------------------------------------------------------------------


def test_record_prefetch_exchange_families():
    reg = MetricsRegistry()
    record_prefetch_exchange("ring", "f32", 1234, 3, registry=reg)
    record_prefetch_exchange("ring", "f32", 1234, 0, registry=reg)
    snap = reg.snapshot()
    assert snap["exchange.prefetch.bytes.ring.f32"]["value"] == 2468
    hist = snap["exchange.prefetch.patched_rows"]
    assert hist["count"] == 2


def _gate_stream(tmp_path, metrics, name="s.jsonl"):
    p = tmp_path / name
    p.write_text(json.dumps({"type": "summary", "metrics": metrics}) + "\n")
    return str(p)


_BASE_METRICS = {"staleness.row_age": {"p99": 1.0},
                 "staleness.sed_drop_rate": 0.0}
_DIST_METRICS = {**_BASE_METRICS, "store.wb_skip_rate": 0.0,
                 "exchange.bytes.ring.f32": 10.0}
_PREFETCH_METRICS = {**_DIST_METRICS,
                     "exchange.prefetch.bytes.ring.f32": 10.0,
                     "exchange.prefetch.patched_rows": {"count": 4}}


def test_gate_requires_prefetch_families(tmp_path):
    # a stream advertising the lane with ALL its families passes
    ok = _gate_stream(tmp_path, _PREFETCH_METRICS, "ok.jsonl")
    assert gate_main(["--train-jsonl", ok]) == 0
    assert gate_main(["--train-jsonl", ok, "--expect-prefetch"]) == 0
    # half-wired lane (bytes counter without the patched-rows histogram)
    # fails even WITHOUT the flag: advertising any exchange.prefetch.*
    # metric pins the whole family set
    half = dict(_PREFETCH_METRICS)
    del half["exchange.prefetch.patched_rows"]
    bad = _gate_stream(tmp_path, half, "half.jsonl")
    assert gate_main(["--train-jsonl", bad]) == 1
    # a non-prefetch dist stream passes bare but fails the pinned flag
    plain = _gate_stream(tmp_path, _DIST_METRICS, "plain.jsonl")
    assert gate_main(["--train-jsonl", plain]) == 0
    assert gate_main(["--train-jsonl", plain, "--expect-prefetch"]) == 1
