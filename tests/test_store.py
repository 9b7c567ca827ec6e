"""Tiered embedding store (src/repro/store/) — the unified table backend.

Contract under test (ISSUE 4):
  * a TieredStore whose device tier holds ~10% of the table rows trains
    ALL SEVEN GST variants bit-identically to the device-resident oracle
    (params, table embeddings, ages, init flags, refresh behavior) —
    single-device and through the shard_map dist steps (each shard owns a
    tiered slice; ring exchange unchanged, routing on device-row ids);
  * store checkpointing (checkpoint/io.py) round-trips BOTH backends —
    host tier included — and a resumed run continues bit-exactly;
  * the serving cache layered over a TieredStore returns bit-identical
    embeddings for entries that were spilled to host RAM and faulted back;
  * eviction write-backs run asynchronously (AsyncHostWriter) and a fetch
    of a still-pending row waits for its write-back instead of reading a
    stale host copy;
  * empty row sets are no-ops on update_rows/evict_rows (no zero-size
    scatter is ever compiled).

Runs at whatever device count the host exposes: tier-1 sees 1 device; the
CI store-smoke job re-runs this file under
XLA_FLAGS=--xla_force_host_platform_device_count=8.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import dist as DT
from repro.checkpoint import load_store_checkpoint, save_store_checkpoint
from repro.core import embedding_table as tbl
from repro.core import gst as G
from repro.dist import pipeline as DP
from repro.graphs import data as D
from repro.graphs.gnn import GNNConfig, gnn_init, make_encode_fn
from repro.optim import make_optimizer
from repro.serve.cache import SegmentCache
from repro.store import (AsyncHostWriter, DeviceStore, SlotMap, TieredStore,
                         rows_per_shard)

N_DEV = jax.device_count()
DIST_SHARDS = [d for d in (1, 8) if d <= N_DEV]
HID = 8


def _tree_bitwise(a, b):
    eq = jax.tree_util.tree_map(
        lambda x, y: bool((np.asarray(x) == np.asarray(y)).all()), a, b)
    return all(jax.tree_util.tree_leaves(eq))


def _table_bitwise(a: tbl.EmbeddingTable, b: tbl.EmbeddingTable):
    return _tree_bitwise(tuple(a), tuple(b))


@pytest.fixture(scope="module")
def dataset():
    graphs = D.make_malnet_like(n_graphs=48, seed=0)
    ds, _ = DP.segment_dataset_shared(graphs, 16, seed=0)
    return ds


def _state(ds):
    cfg = GNNConfig(backbone="sage", n_feat=ds.x.shape[-1], hidden=HID)
    enc = make_encode_fn(cfg)
    key = jax.random.key(0)
    bb = gnn_init(key, cfg)
    head = G.head_init(jax.random.fold_in(key, 1), HID, 5, "mlp")
    opt = make_optimizer("adam", lr=5e-3)
    return enc, opt, G.TrainState(bb, head, opt.init((bb, head)),
                                  tbl.init_table(ds.n, ds.j_max, HID),
                                  jnp.zeros((), jnp.int32))


def _spread_batches(n, num_shards, batch, steps):
    """Batch id schedules whose rows spread evenly over the shards, so a
    device tier of batch/num_shards rows per shard suffices while every
    step still churns the LRU (each batch faults fresh rows)."""
    R = rows_per_shard(n, num_shards)
    per = batch // num_shards
    assert per >= 1 and per <= R
    out = []
    for t in range(steps):
        ids = [min(s * R + (t * per + j) % R, n - 1)
               for s in range(num_shards) for j in range(per)]
        assert len(set(ids)) == len(ids)
        out.append(np.asarray(ids, np.int64))
    return out


def _batch(ds, ids):
    return jax.tree_util.tree_map(jnp.asarray, DP._assemble(ds, ids))


# ---------------------------------------------------------------------------
# single-device: TieredStore at ~10% device capacity == oracle, 7 variants
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("variant", list(G.VARIANTS))
def test_tiered_train_bit_identical_all_variants(dataset, variant):
    ds = dataset
    B, steps = 4, 6
    cap = max(-(-ds.n // 10), B)          # ~10% of rows, >= one batch
    assert cap < ds.n // 2, "capacity must really be a small fraction"
    enc, opt, state0 = _state(ds)
    var = G.VARIANTS[variant]
    rng = jax.random.PRNGKey(3)
    scheds = _spread_batches(ds.n, 1, B, steps)

    step = G.make_train_step(enc, opt, var, keep_prob=0.5)
    oracle = jax.jit(step)
    s1 = state0
    for ids in scheds:
        s1, m1 = oracle(s1, _batch(ds, ids), rng)

    store = TieredStore(ds.n, ds.j_max, HID, device_rows=cap)
    tiered = jax.jit(step)   # same step body, smaller table shape
    s2 = state0._replace(table=store.init_device_table())
    for ids in scheds:
        table, slots = store.prepare(s2.table, ids)
        s2 = s2._replace(table=table)
        s2, m2 = tiered(s2, _batch(ds, ids)._replace(
            graph_ids=jnp.asarray(slots)), rng)

    # the full logical table — embeddings, ages, init flags — is bitwise
    # identical to the oracle's, as are params and metrics
    assert _table_bitwise(s1.table, store.snapshot(s2.table))
    assert _tree_bitwise((s1.backbone, s1.head), (s2.backbone, s2.head))
    assert float(m1["loss"]) == float(m2["loss"])
    if var.use_table:
        assert store.counters.evictions > 0, \
            "capacity below the working set must actually churn the tier"
    store.close()


def test_tiered_refresh_and_finetune_bit_identical(dataset):
    """Algorithm 2's refresh + head-finetune phases through the store."""
    ds = dataset
    B = 4
    cap = max(-(-ds.n // 10), B)
    enc, opt, state0 = _state(ds)
    scheds = _spread_batches(ds.n, 1, B, 12)   # covers every row
    refresh = jax.jit(G.make_refresh_step(enc))
    ft_opt = make_optimizer("adam", lr=1e-3)
    ft = jax.jit(G.make_finetune_step(ft_opt))

    s1 = state0
    for ids in scheds:
        s1 = refresh(s1, _batch(ds, ids))
    s1 = s1._replace(opt_state=ft_opt.init(s1.head))
    for ids in scheds[:4]:
        s1, m1 = ft(s1, _batch(ds, ids))

    store = TieredStore(ds.n, ds.j_max, HID, device_rows=cap)
    s2 = state0._replace(table=store.init_device_table())
    for ids in scheds:
        table, slots = store.prepare(s2.table, ids)
        s2 = s2._replace(table=table)
        s2 = refresh(s2, _batch(ds, ids)._replace(graph_ids=jnp.asarray(slots)))
    s2 = s2._replace(opt_state=ft_opt.init(s2.head))
    for ids in scheds[:4]:
        table, slots = store.prepare(s2.table, ids)
        s2 = s2._replace(table=table)
        s2, m2 = ft(s2, _batch(ds, ids)._replace(graph_ids=jnp.asarray(slots)))

    assert _table_bitwise(s1.table, store.snapshot(s2.table))
    assert _tree_bitwise(s1.head, s2.head)
    assert float(m1["loss"]) == float(m2["loss"])
    store.close()


# ---------------------------------------------------------------------------
# dist: each shard owns a tiered slice; ring exchange unchanged
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("variant", list(G.VARIANTS))
def test_dist_tiered_parity_all_variants(dataset, variant):
    """shard_map steps over per-shard tiered slices track the single-device
    dense oracle: ages/init/refresh bit-exact, params/loss bitwise at 1
    shard and <= a few ulps at 8 (cross-shard pmean order, same tolerance
    as tests/test_dist.py)."""
    ds = dataset
    n_shards = DIST_SHARDS[-1]
    B, steps = 8, 5
    enc, opt, state0 = _state(ds)
    var = G.VARIANTS[variant]
    rng = jax.random.PRNGKey(3)
    scheds = _spread_batches(ds.n, n_shards, B, steps)

    oracle = jax.jit(G.make_train_step(enc, opt, var, keep_prob=0.5))
    s1 = state0
    for ids in scheds:
        s1, m1 = oracle(s1, _batch(ds, ids), rng)

    # device tier: exactly one batch row per shard — the smallest legal
    # tier, ~B/n of the table
    ctx = DT.make_context(DT.make_dist_mesh(n_shards), ds.n,
                          device_rows=B)
    store = DT.make_dist_store(ctx, ds.j_max, HID)
    assert isinstance(store, TieredStore)
    dstep = DT.make_dist_train_step(enc, opt, var, ctx=ctx, keep_prob=0.5,
                                    donate=False)
    s2 = DT.device_state(ctx, state0, store=store)
    for ids in scheds:
        host = DP._assemble(ds, ids)
        prep = store.begin(np.asarray(host.graph_ids))
        b2 = DT.shard_batch(ctx, host._replace(graph_ids=prep.slots))
        s2 = s2._replace(table=store.commit(s2.table, prep))
        s2, m2 = dstep(s2, b2, rng)

    t2 = store.snapshot(s2.table)
    assert (np.asarray(s1.table.age) == np.asarray(t2.age)).all()
    assert (np.asarray(s1.table.initialized) ==
            np.asarray(t2.initialized)).all()
    tol = 0.0 if ctx.num_shards == 1 else 1e-5
    emb_diff = float(np.max(np.abs(np.asarray(s1.table.emb) -
                                   np.asarray(t2.emb))))
    assert emb_diff <= tol
    diffs = jax.tree_util.tree_map(
        lambda x, y: float(np.max(np.abs(np.asarray(x) - np.asarray(y)))),
        (s1.backbone, s1.head), jax.device_get((s2.backbone, s2.head)))
    assert max(jax.tree_util.tree_leaves(diffs)) <= tol
    assert abs(float(m1["loss"]) - float(m2["loss"])) <= tol
    store.close()


def test_dist_context_table_rows():
    mesh = DT.make_dist_mesh(1)
    dense = DT.make_context(mesh, 40)
    assert dense.table_rows == dense.rows_per_shard == 40
    assert isinstance(DT.make_dist_store(dense, 2, 4), DeviceStore)
    tiered = DT.make_context(mesh, 40, device_rows=8)
    assert tiered.table_rows == 8 and tiered.rows_per_shard == 40
    assert isinstance(DT.make_dist_store(tiered, 2, 4), TieredStore)


# ---------------------------------------------------------------------------
# checkpointing: save/restore both backends, host tier included
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["device", "tiered"])
def test_checkpoint_roundtrip_resumes_bit_exact(dataset, backend, tmp_path):
    ds = dataset
    B = 4
    enc, opt, state0 = _state(ds)
    scheds = _spread_batches(ds.n, 1, B, 6)
    step = jax.jit(G.make_train_step(enc, opt, G.VARIANTS["gst_efd"],
                                     keep_prob=0.5))
    rng = jax.random.PRNGKey(7)

    def make_store():
        if backend == "tiered":
            return TieredStore(ds.n, ds.j_max, HID, device_rows=B + 1)
        return DeviceStore(ds.n, ds.j_max, HID)

    def run(store, state, sched):
        for ids in sched:
            table, slots = store.prepare(state.table, ids)
            state = state._replace(table=table)
            state, _ = step(state, _batch(ds, ids)._replace(
                graph_ids=jnp.asarray(slots)), rng)
        return state

    # uninterrupted reference: 6 steps
    ref_store = make_store()
    ref = run(ref_store, state0._replace(table=ref_store.init_device_table()),
              scheds)

    # interrupted run: 3 steps -> checkpoint -> fresh store -> 3 more
    st1 = make_store()
    s = run(st1, state0._replace(table=st1.init_device_table()), scheds[:3])
    path = save_store_checkpoint(
        str(tmp_path), 3, st1, s.table,
        extra={"backbone": s.backbone, "head": s.head,
               "opt_state": s.opt_state, "step": s.step})
    st1.close()

    st2 = make_store()
    table, extra = load_store_checkpoint(
        path, st2, extra_like={"backbone": s.backbone, "head": s.head,
                               "opt_state": s.opt_state, "step": s.step})
    resumed = G.TrainState(extra["backbone"], extra["head"],
                           extra["opt_state"], table, extra["step"])
    resumed = run(st2, resumed, scheds[3:])

    assert _table_bitwise(ref_store.snapshot(ref.table),
                          st2.snapshot(resumed.table))
    assert _tree_bitwise((ref.backbone, ref.head),
                         (resumed.backbone, resumed.head))
    ref_store.close()
    st2.close()


def test_checkpoint_with_pending_writebacks_flushes_and_resumes(tmp_path):
    """Checkpointing while AsyncHostWriter still has eviction write-backs
    in flight: ``snapshot`` must flush them before merging tiers, so the
    file carries the evicted rows' content and a resumed run continues
    bit-exactly vs the uninterrupted one."""
    import threading

    n, J, d = 6, 1, 4
    rng_vals = np.random.default_rng(11)
    vals = rng_vals.normal(size=(32, 1, 1, d)).astype(np.float32)
    sched = [0, 1, 2, 3, 4, 5, 0, 1, 2, 3, 4, 5]  # C=2 -> constant churn

    def make_store():
        return TieredStore(n, J, d, device_rows=2)

    def run(store, table, oracle, steps):
        for t in steps:
            row = sched[t]
            table, slots = store.prepare(table, np.asarray([row]))
            z = jnp.zeros((1, 1), jnp.int32)
            table = tbl.update_sampled(table, jnp.asarray(slots), z,
                                       jnp.asarray(vals[t]), t)
            oracle = tbl.update_sampled(oracle, jnp.asarray([row]), z,
                                        jnp.asarray(vals[t]), t)
        return table, oracle

    # uninterrupted reference
    ref = make_store()
    ref_table, oracle = run(ref, ref.init_device_table(),
                            tbl.init_table(n, J, d), range(len(sched)))

    # interrupted run: half the steps, then BLOCK the write-back lane and
    # trigger one more eviction so its write-back is genuinely pending at
    # save time
    st1 = make_store()
    table1, _ = run(st1, st1.init_device_table(), tbl.init_table(n, J, d),
                    range(6))
    gate = threading.Event()
    st1._writer.submit(lambda: gate.wait(timeout=10.0))
    table1, slots = st1.prepare(table1, np.asarray([sched[6]]))  # evicts
    assert st1._writer.pending >= 1
    threading.Timer(0.2, gate.set).start()
    path = save_store_checkpoint(str(tmp_path), 6, st1, table1)
    assert st1._writer.pending == 0          # snapshot flushed the lane
    st1.close()

    # resume: finish the schedule on a fresh store.  Step 6's prepare ran
    # before the save but its update didn't — replay from step 6.
    st2 = make_store()
    table2, extra = load_store_checkpoint(path, st2)
    table2, oracle2 = run(st2, table2, tbl.init_table(n, J, d),
                          range(6, len(sched)))
    st2.flush_writebacks()
    snap_ref = ref.snapshot(ref_table)
    snap_res = st2.snapshot(table2)
    assert _table_bitwise(snap_ref, snap_res)
    assert _tree_bitwise(tuple(snap_ref), tuple(oracle))
    ref.close()
    st2.close()


def test_snapshot_restore_preserves_host_tier():
    """Rows living ONLY in the host tier at save time must round-trip."""
    rng = np.random.default_rng(0)
    store = TieredStore(12, 2, 4, device_rows=3)
    table = store.init_device_table()
    for t in range(8):
        ids = rng.permutation(12)[:3]
        table, slots = store.prepare(table, ids)
        table = tbl.update_sampled(
            table, jnp.asarray(slots), jnp.zeros((3, 1), jnp.int32),
            jnp.asarray(rng.normal(size=(3, 1, 4)), jnp.float32), t)
    snap = store.snapshot(table)
    assert np.asarray(snap.initialized).any()
    store2 = TieredStore(12, 2, 4, device_rows=3)
    table2 = store2.restore(snap)
    assert store2.occupancy() == 0          # residency reset, data in host
    table2, slots = store2.prepare(table2, np.arange(3))
    e2, _ = tbl.lookup(table2, jnp.asarray(slots))
    e1, _ = tbl.lookup(jax.tree_util.tree_map(jnp.asarray, snap),
                       jnp.arange(3))
    assert np.array_equal(np.asarray(e1), np.asarray(e2))
    store.close()
    store2.close()


# ---------------------------------------------------------------------------
# serving over the shared store
# ---------------------------------------------------------------------------


def test_serve_cache_over_tiered_store_bit_identical():
    """Entries spilled to the host tier fault back bit-identically; the
    keying layer's capacity is the TOTAL (both-tier) row count."""
    rng = np.random.default_rng(0)
    store = TieredStore(32, 1, HID, device_rows=8)
    cache = SegmentCache(32, HID, store=store)
    keys = [bytes([i]) * 4 for i in range(24)]
    embs = rng.normal(size=(24, HID)).astype(np.float32)
    for i in range(0, 24, 6):
        cache.put(keys[i:i + 6], embs[i:i + 6])
    assert len(cache) == 24                  # all keys live
    assert store.occupancy() == 8           # only a tier's worth on device
    slots = [cache.get(k) for k in keys]
    assert all(s is not None for s in slots)
    got = np.asarray(cache.gather(slots[:8]))
    assert np.array_equal(got, embs[:8]), "spill+refault must be bit-exact"
    assert store.counters.evictions > 0
    assert cache.stats()["store"]["backend"] == "TieredStore"
    store.close()


def test_serve_engine_with_device_row_cap_matches_uncapped():
    from repro.serve import ServeConfig, ServeEngine, TrafficConfig, \
        make_request_stream

    tc = TrafficConfig(n_unique=6, n_requests=12, duplicate_rate=0.5,
                       comm_range=(2, 5), comm_size_range=(8, 20), seed=3)
    stream = make_request_stream(tc)

    def engine(table_device_rows):
        cfg = ServeConfig(backbone="sage", hidden=32, max_seg_nodes=32,
                          cache_capacity=128, stream_chunk=4,
                          table_device_rows=table_device_rows)
        return ServeEngine(cfg, seed=0)

    full = engine(None)
    capped = engine(8)
    p1 = full.process(stream, window=4)
    p2 = capped.process(stream, window=4)
    for a, b in zip(p1, p2):
        assert np.array_equal(a.pred, b.pred), \
            "device-row cap must not change a single prediction bit"
    st = capped.stats.summary()["cache"]["store"]
    assert st["backend"] == "TieredStore"
    assert st["evictions"] > 0, "the cap must actually spill"
    full.close()
    capped.close()


# ---------------------------------------------------------------------------
# write-back machinery
# ---------------------------------------------------------------------------


def test_pending_writeback_blocks_refetch():
    """Evict a row and fault it straight back: the fetch must wait for the
    async write-back so the host tier is never read stale."""
    rng = np.random.default_rng(0)
    store = TieredStore(4, 1, 4, device_rows=1)
    table = store.init_device_table()
    vals = {}
    for t, row in enumerate([0, 1, 0, 1, 0, 1]):
        table, slots = store.prepare(table, np.asarray([row]))
        v = rng.normal(size=(1, 1, 4)).astype(np.float32)
        vals[row] = v
        table = tbl.update_sampled(table, jnp.asarray(slots),
                                   jnp.zeros((1, 1), jnp.int32),
                                   jnp.asarray(v), t)
        # the OTHER row's last value must have survived the round trip
        other = 1 - row
        if other in vals:
            table, oslots = store.prepare(table, np.asarray([other]))
            e, _ = tbl.lookup(table, jnp.asarray(oslots))
            assert np.array_equal(np.asarray(e), vals[other])
            table, slots = store.prepare(table, np.asarray([row]))
    assert store.counters.evictions >= 4
    store.close()


def test_async_writer_propagates_thunk_errors():
    w = AsyncHostWriter()

    def boom():
        raise RuntimeError("writeback exploded")

    w.submit(boom)
    with pytest.raises(RuntimeError, match="writeback exploded"):
        w.flush()
    w.close()


def test_commit_order_enforced():
    store = TieredStore(8, 1, 2, device_rows=2)
    table = store.init_device_table()
    p1 = store.begin(np.asarray([0]))
    p2 = store.begin(np.asarray([1]))
    with pytest.raises(RuntimeError, match="commit order"):
        store.commit(table, p2)
    table = store.commit(table, p1)
    store.commit(table, p2)
    store.close()


def test_capacity_exhaustion_raises_before_mutating():
    store = TieredStore(8, 1, 2, device_rows=2)
    with pytest.raises(RuntimeError, match="device tier exhausted"):
        store.begin(np.arange(5))
    with pytest.raises(IndexError, match="outside table"):
        store.begin(np.asarray([0, 99]))
    # the failed begins must not have reserved slots or consumed tickets —
    # the store stays fully usable
    assert store.occupancy() == 0
    table = store.init_device_table()
    table, slots = store.prepare(table, np.asarray([0, 1]))
    assert store.occupancy() == 2
    store.close()


def test_failed_writeback_raises_instead_of_hanging():
    """A write-back that dies (host tier unwritable) must surface as an
    error on the next fetch of the evicted row, not spin forever."""
    store = TieredStore(4, 1, 2, device_rows=1)
    table = store.init_device_table()
    table, _ = store.prepare(table, np.asarray([0]))
    store._host.emb.setflags(write=False)   # break the host tier
    table, _ = store.prepare(table, np.asarray([1]))   # evicts row 0
    with pytest.raises(RuntimeError, match="write-back failed"):
        store.prepare(table, np.asarray([0]))          # refetch row 0
    store._host.emb.setflags(write=True)
    store._writer._exc = None   # drop the writer's copy of the failure
    store.close()


# ---------------------------------------------------------------------------
# satellite: empty row sets are no-ops; slot machinery basics
# ---------------------------------------------------------------------------


def test_update_evict_rows_empty_noop():
    table = tbl.init_table(4, 1, 2)
    empty = jnp.zeros((0,), jnp.int32)
    assert tbl.update_rows(table, empty, jnp.zeros((0, 2)), 0) is table
    assert tbl.evict_rows(table, empty) is table


# ---------------------------------------------------------------------------
# delta-gated write-back (ISSUE 6): evictions of rows that barely moved
# skip the device->host emb copy; ages/init always land
# ---------------------------------------------------------------------------


def test_delta_gate_admission_rules():
    from repro.store import delta_gate
    old = np.zeros((4, 1, 3), np.float32)
    new = old.copy()
    new[0] += 0.5            # moved past the threshold
    new[1] += 0.09           # moved, but under it
    init_old = np.ones((4, 1), bool)
    init_new = init_old.copy()
    init_new[2, 0] = False   # bookkeeping flip on an otherwise static row
    admit = delta_gate(new, old, init_new, init_old, 0.1)
    # movement >= threshold admits (inclusive); an init flip forces
    # admission regardless of movement; static rows are skipped
    assert admit.tolist() == [True, False, True, False]
    new[1] += 0.01           # exactly at the threshold now
    assert delta_gate(new, old, init_new, init_old, 0.1).tolist() == \
        [True, True, True, False]


def test_tiered_delta_gate_skips_static_rows():
    store = TieredStore(4, 1, 4, device_rows=1, wb_threshold=0.5)
    table = store.init_device_table()
    v = np.full((1, 1, 4), 2.0, np.float32)

    def residency(store, table, val, t):
        """Row 0 faults in, is written ``val`` at step ``t``, and is
        evicted by row 1."""
        table, slots = store.prepare(table, np.asarray([0]))
        table = tbl.update_sampled(table, jnp.asarray(slots),
                                   jnp.zeros((1, 1), jnp.int32),
                                   jnp.asarray(val), t)
        table, _ = store.prepare(table, np.asarray([1]))
        store.flush_writebacks()
        return table

    # first residency: the init flip (False -> True) forces admission even
    # though the gate is on — first writes always reach the host tier
    table = residency(store, table, v, 0)
    assert store.counters.wb_skipped_rows == 0
    assert np.array_equal(store._host.emb[0], v[0])

    # second residency: a sub-threshold nudge — the eviction skips the
    # host emb write (stale by < wb_threshold) but still lands the age.
    # (Refetching row 0 evicts the never-written row 1, whose delta is 0
    # and init unchanged — also skipped, hence the count of 2.)
    table = residency(store, table, v + 0.1, 7)
    assert store.counters.wb_skipped_rows == 2
    assert store.counters.wb_skipped_bytes == 2 * 1 * 4 * 4
    assert np.array_equal(store._host.emb[0], v[0])      # stale, bounded
    assert store._host.age[0, 0] == 7                    # bookkeeping exact

    # third residency: movement past the threshold is admitted (the
    # static row 1 eviction in between is skipped again)
    table = residency(store, table, v + 3.0, 9)
    assert store.counters.wb_skipped_rows == 3
    assert np.array_equal(store._host.emb[0], v[0] + 3.0)
    assert store.stats()["wb_threshold"] == 0.5

    # the skipped bytes never crossed: the same trace through the ungated
    # store migrates exactly them more
    ungated = TieredStore(4, 1, 4, device_rows=1)
    table = ungated.init_device_table()
    for val, t in ((v, 0), (v + 0.1, 7), (v + 3.0, 9)):
        table = residency(ungated, table, val, t)
    gated_bytes = store.stats()["migration_bytes"]
    assert gated_bytes == (ungated.stats()["migration_bytes"]
                           - store.counters.wb_skipped_bytes)
    assert gated_bytes < ungated.stats()["migration_bytes"]
    ungated.close()
    store.close()


def test_tiered_gate_off_by_default_and_counts_zero():
    store = TieredStore(4, 1, 4, device_rows=1)
    assert store.wb_threshold == 0.0
    table = store.init_device_table()
    for t, row in enumerate([0, 1, 0, 1]):               # churn the tier
        table, slots = store.prepare(table, np.asarray([row]))
    store.flush_writebacks()
    # gate off: every eviction writes through, nothing is ever skipped
    assert store.counters.evictions >= 2
    assert store.counters.wb_skipped_rows == 0
    assert store.counters.wb_skipped_bytes == 0
    assert store.stats()["wb_skipped_rows"] == 0
    store.close()

    # a tier that holds every row migrates nothing once each row is in
    store = TieredStore(4, 1, 4, device_rows=4)
    table = store.init_device_table()
    table, _ = store.prepare(table, np.arange(4))
    warm = store.stats()["migration_bytes"]
    for row in [0, 1, 0, 3, 2, 1]:
        table, _ = store.prepare(table, np.asarray([row]))
    store.flush_writebacks()
    assert store.counters.evictions == 0
    assert store.stats()["migration_bytes"] == warm
    store.close()


def test_cache_gather_empty_returns_empty():
    store = TieredStore(8, 1, HID, device_rows=3)
    cache = SegmentCache(8, HID, store=store)
    out = np.asarray(cache.gather([]))
    assert out.shape == (0, HID)
    cache.close()


def test_cache_over_trainer_shaped_store():
    """A store with trainer geometry (j_max > 1) backs the cache: entries
    live in segment-slot 0 of each row, spill/refault stays bit-exact."""
    rng = np.random.default_rng(0)
    store = TieredStore(16, 3, HID, device_rows=4)   # j_max=3, like training
    cache = SegmentCache(16, HID, store=store)
    keys = [bytes([i]) * 4 for i in range(12)]
    embs = rng.normal(size=(12, HID)).astype(np.float32)
    for i in range(0, 12, 4):
        cache.put(keys[i:i + 4], embs[i:i + 4])
    slots = [cache.get(k) for k in keys]
    got = np.asarray(cache.gather(slots[:4]))
    assert np.array_equal(got, embs[:4])
    assert store.counters.evictions > 0
    cache.flush()
    assert len(cache) == 0
    cache.put([keys[0]], embs[:1])
    assert np.array_equal(np.asarray(cache.gather([cache.get(keys[0])])),
                          embs[:1])
    cache.close()


def test_slotmap_lru_and_pinning():
    m = SlotMap(2)
    s_a, ev = m.reserve("a")
    s_b, _ = m.reserve("b")
    assert ev is None and {s_a, s_b} == {0, 1}
    assert m.get("a") == s_a                   # touch: b becomes LRU
    s_c, ev = m.reserve("c")
    assert ev == ("b", s_b) and s_c == s_b
    # pinned keys are never displaced
    slot, ev = m.reserve("d", pinned={"a", "c"})
    assert slot is None and ev is None
    assert m.release("a") == s_a
    slot, ev = m.reserve("d", pinned={"c"})
    assert slot == s_a and ev is None
