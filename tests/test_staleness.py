"""Staleness intelligence (ISSUE 10): age-weighted SED η, the stale-row
forecaster, and the true-age accounting underneath them.

Contract under test:
  * λ = 0 is BIT-exact to the historical Eq.-1 step — passing
    ``sed_decay=0.0`` (or ages with decay 0 at the kernel layer) traces
    the identical jaxpr at every layer: sed_eta, sed_pool dispatch,
    make_train_step, and the dist step (whose age-lookup collective is
    only injected when decay > 0)
  * λ > 0: the aged Pallas kernel matches the jnp oracle (forward + VJP),
    and the dist step with its exchange-routed ``lookup_ages`` matches
    the single-device oracle for every exchange strategy
  * RowForecaster round-trips: age-0 and never-observed rows are the
    identity; a TieredStore with the flag on but no step hints stays
    byte-identical to one with it off
  * TRUE ages: ``refresh_ages`` re-reports device-plane ages so a row
    refreshed while resident stops scoring as its stale fault-in copy —
    the freshly-refreshed row must NOT be the stale-first victim
  * StalenessProbe publishes ``staleness.effective_age`` only when a
    knob is on, and its quantiles sit strictly below raw row-age
    (age·e^{-λ·age} < age pointwise ⇒ every order statistic shrinks)

Runs at whatever device count the host exposes (tier-1: 1 device,
bitwise parity); CI dist-smoke re-runs under
XLA_FLAGS=--xla_force_host_platform_device_count=8.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import dist as DT
from repro.core import gst as G
from repro.core.embedding_table import init_table
from repro.dist import exchange as EXC
from repro.dist import pipeline as DP
from repro.dist import table as dtbl
from repro.graphs import data as D
from repro.graphs.gnn import GNNConfig, gnn_init, make_encode_fn
from repro.kernels import ref
from repro.kernels.sed_pool import sed_pool
from repro.obs.metrics import MetricsRegistry
from repro.obs.staleness import StalenessProbe
from repro.optim import make_optimizer
from repro.store import TieredStore
from repro.store.forecast import RowForecaster
from repro.store.slots import SlotMap

N_DEV = jax.device_count()
SHARD_COUNTS = [d for d in (1, 2, 4, 8) if d <= N_DEV]
HID = 8
HSET = settings(max_examples=8, deadline=None)


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


def _aged_draw(B, J, d, seed):
    rng = np.random.default_rng(seed)
    h = jnp.asarray(rng.normal(size=(B, J, d)), jnp.float32)
    valid = jnp.asarray(rng.uniform(size=(B, J)) < 0.8, jnp.float32)
    valid = valid.at[:, 0].set(1.0)
    fresh = jnp.zeros((B, J)).at[jnp.arange(B), rng.integers(0, J, B)].set(1.0)
    fresh = fresh * valid
    drop = jnp.asarray(rng.uniform(size=(B, J)) < 0.5, jnp.float32)
    ages = jnp.asarray(rng.integers(0, 25, (B, J)), jnp.float32)
    return h, valid, fresh, drop, ages


# ---------------------------------------------------------------------------
# sed_eta: the aged Eq.-1 formula and its λ=0 reduction
# ---------------------------------------------------------------------------


def test_sed_eta_decay_zero_is_bitwise_unaged():
    h, valid, fresh, drop, ages = _aged_draw(6, 9, 4, 0)
    base, ji1 = ref.sed_eta(valid, fresh, drop, 0.5, 2)
    aged, ji2 = ref.sed_eta(valid, fresh, drop, 0.5, 2, ages=ages, decay=0.0)
    assert (np.asarray(base) == np.asarray(aged)).all()
    assert (np.asarray(ji1) == np.asarray(ji2)).all()


def test_sed_eta_aged_formula_decays_stale_branch_only():
    h, valid, fresh, drop, ages = _aged_draw(6, 9, 4, 1)
    lam = 0.3
    base = np.asarray(ref.sed_eta(valid, fresh, drop, 0.5, 2)[0])
    aged = np.asarray(ref.sed_eta(valid, fresh, drop, 0.5, 2,
                                  ages=ages, decay=lam)[0])
    f = np.asarray(fresh) > 0
    # fresh branch untouched; stale branch scaled by exp(-λ·age)
    np.testing.assert_array_equal(aged[f], base[f])
    np.testing.assert_allclose(
        aged[~f], base[~f] * np.exp(-lam * np.asarray(ages))[~f],
        rtol=1e-6, atol=1e-7)
    # decay strictly shrinks any live stale weight with nonzero age
    live = (~f) & (base > 0) & (np.asarray(ages) > 0)
    assert live.any() and (aged[live] < base[live]).all()


# ---------------------------------------------------------------------------
# aged sed_pool kernel vs oracle vs VJP
# ---------------------------------------------------------------------------


@given(B=st.integers(1, 12), J=st.integers(1, 16),
       d=st.sampled_from([8, 64, 130]),
       lam=st.sampled_from([0.05, 0.2, 0.5]),
       S=st.integers(1, 3), agg=st.sampled_from(["mean", "sum"]),
       seed=st.integers(0, 10_000))
@HSET
def test_sed_pool_aged_matches_oracle(B, J, d, lam, S, agg, seed):
    S = min(S, J)
    h, valid, fresh, drop, ages = _aged_draw(B, J, d, seed)
    out = sed_pool(h, valid, fresh, drop, keep_prob=0.4, num_sampled=S,
                   agg=agg, ages=ages, decay=lam, interpret=True)
    want = ref.sed_pool_ref(h, valid, fresh, drop, 0.4, S, agg,
                            ages=ages, decay=lam)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@given(seed=st.integers(0, 10_000), agg=st.sampled_from(["mean", "sum"]))
@HSET
def test_sed_pool_aged_vjp_matches_oracle(seed, agg):
    B, J, d, lam, S = 5, 7, 16, 0.2, 2
    h, valid, fresh, drop, ages = _aged_draw(B, J, d, seed)

    def k_loss(x):
        return sed_pool(x, valid, fresh, drop, keep_prob=0.4, num_sampled=S,
                        agg=agg, ages=ages, decay=lam, interpret=True).sum()

    def o_loss(x):
        return ref.sed_pool_ref(x, valid, fresh, drop, 0.4, S, agg,
                                ages=ages, decay=lam).sum()

    np.testing.assert_allclose(np.asarray(jax.grad(k_loss)(h)),
                               np.asarray(jax.grad(o_loss)(h)),
                               rtol=1e-5, atol=1e-5)


def test_sed_pool_decay_zero_dispatches_to_unaged_kernel():
    """ages + decay=0 must route through the historical kernel (same
    jaxpr, bit-identical output) — the λ=0 reduction at the kernel layer."""
    h, valid, fresh, drop, ages = _aged_draw(6, 9, 8, 3)
    base = sed_pool(h, valid, fresh, drop, keep_prob=0.5, num_sampled=1,
                    interpret=True)
    gated = sed_pool(h, valid, fresh, drop, keep_prob=0.5, num_sampled=1,
                     ages=ages, decay=0.0, interpret=True)
    assert (np.asarray(base) == np.asarray(gated)).all()


# ---------------------------------------------------------------------------
# λ=0 bit-exactness through the full train step — all 7 variants
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("variant", list(G.VARIANTS))
def test_decay_zero_train_step_bit_exact(dataset, variant):
    ds = dataset
    enc, opt, state0 = _state(ds)
    batch = _batch(ds, DP.epoch_ids(ds, 8, rng=np.random.default_rng(0),
                                    shuffle=False)[0])
    rng = jax.random.PRNGKey(3)
    var = G.VARIANTS[variant]
    base = jax.jit(G.make_train_step(enc, opt, var, keep_prob=0.5))
    zero = jax.jit(G.make_train_step(enc, opt, var, keep_prob=0.5,
                                     sed_decay=0.0))
    s1, s2 = state0, state0
    for _ in range(3):
        s1, m1 = base(s1, batch, rng)
        s2, m2 = zero(s2, batch, rng)
    assert _tree_bitwise(s1, s2)
    assert float(m1["loss"]) == float(m2["loss"])


@pytest.mark.parametrize("variant", ["gst_ed", "gst_efd"])
def test_decay_zero_pallas_train_step_bit_exact(dataset, variant):
    ds = dataset
    cfg = GNNConfig(backbone="sage", n_feat=ds.x.shape[-1], hidden=HID,
                    use_pallas=True)
    enc = make_encode_fn(cfg)
    key = jax.random.key(0)
    bb = gnn_init(key, cfg)
    head = G.head_init(jax.random.fold_in(key, 1), HID, 5, "mlp")
    opt = make_optimizer("adam", lr=5e-3)
    state0 = G.TrainState(bb, head, opt.init((bb, head)),
                          init_table(ds.n, ds.j_max, HID),
                          jnp.zeros((), jnp.int32))
    batch = _batch(ds, DP.epoch_ids(ds, 8, rng=np.random.default_rng(0),
                                    shuffle=False)[0])
    rng = jax.random.PRNGKey(3)
    var = G.VARIANTS[variant]
    base = jax.jit(G.make_train_step(enc, opt, var, keep_prob=0.5,
                                     use_pallas=True))
    zero = jax.jit(G.make_train_step(enc, opt, var, keep_prob=0.5,
                                     use_pallas=True, sed_decay=0.0))
    s1, _ = base(state0, batch, rng)
    s2, _ = zero(state0, batch, rng)
    assert _tree_bitwise(s1, s2)


@pytest.mark.parametrize("n_shards", SHARD_COUNTS)
def test_decay_zero_dist_step_bit_exact(dataset, n_shards):
    ds = dataset
    enc, opt, state0 = _state(ds)
    batch = _batch(ds, DP.epoch_ids(ds, 8, rng=np.random.default_rng(0),
                                    shuffle=False)[0])
    rng = jax.random.PRNGKey(3)
    var = G.VARIANTS["gst_efd"]
    ctx = DT.make_context(DT.make_dist_mesh(n_shards), ds.n)
    base = DT.make_dist_train_step(enc, opt, var, ctx=ctx, keep_prob=0.5,
                                   donate=False)
    zero = DT.make_dist_train_step(enc, opt, var, ctx=ctx, keep_prob=0.5,
                                   donate=False, sed_decay=0.0)
    b = DT.shard_batch(ctx, batch)
    s1 = DT.device_state(ctx, state0)
    s2 = DT.device_state(ctx, state0)
    for _ in range(3):
        s1, m1 = base(s1, b, rng)
        s2, m2 = zero(s2, b, rng)
    assert _tree_bitwise(DT.host_table(ctx, s1.table),
                         DT.host_table(ctx, s2.table))
    assert _tree_bitwise(jax.device_get((s1.backbone, s1.head)),
                         jax.device_get((s2.backbone, s2.head)))
    assert float(m1["loss"]) == float(m2["loss"])


# ---------------------------------------------------------------------------
# λ>0: dist step (exchange-routed age lookup) vs single-device oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("exchange", ["ring", "alltoall", "bucketed"])
@pytest.mark.parametrize("variant", ["gst_ed", "gst_efd"])
def test_aged_dist_step_matches_oracle(dataset, variant, exchange):
    ds = dataset
    n_shards = SHARD_COUNTS[-1]
    enc, opt, state0 = _state(ds)
    ids = DP.epoch_ids(ds, 8, rng=np.random.default_rng(0), shuffle=False)[0]
    batch = _batch(ds, ids)
    rng = jax.random.PRNGKey(3)
    var = G.VARIANTS[variant]
    lam = 0.2

    oracle = jax.jit(G.make_train_step(enc, opt, var, keep_prob=0.5,
                                       sed_decay=lam))
    s1 = state0
    for _ in range(5):
        s1, m1 = oracle(s1, batch, rng)

    cap = None
    if exchange == "bucketed":
        cap = EXC.plan_capacity([ids], num_shards=n_shards,
                                rows=dtbl.rows_per_shard(ds.n, n_shards))
    ctx = DT.make_context(DT.make_dist_mesh(n_shards), ds.n,
                          exchange=exchange, exchange_cap=cap)
    dstep = DT.make_dist_train_step(enc, opt, var, ctx=ctx, keep_prob=0.5,
                                    donate=False, sed_decay=lam)
    s2 = DT.device_state(ctx, state0)
    b2 = DT.shard_batch(ctx, batch)
    for _ in range(5):
        s2, m2 = dstep(s2, b2, rng)

    t2 = DT.host_table(ctx, s2.table)
    # age bookkeeping is pure row selection — bit-exact at any shard count
    assert (np.asarray(s1.table.age) == np.asarray(t2.age)).all()
    assert (np.asarray(s1.table.initialized) ==
            np.asarray(t2.initialized)).all()
    tol = 0.0 if ctx.num_shards == 1 else 1e-5
    assert _tree_max_diff(s1.table.emb, t2.emb) <= tol
    assert _tree_max_diff((s1.backbone, s1.head),
                          jax.device_get((s2.backbone, s2.head))) <= tol
    assert abs(float(m1["loss"]) - float(m2["loss"])) <= tol


def test_aged_step_actually_changes_training(dataset):
    """Guards the plumbing end-to-end: with initialized stale rows of
    nonzero age, λ>0 must CHANGE the loss trajectory vs λ=0 (else the
    decay silently fell out somewhere between the flag and Eq. 1)."""
    ds = dataset
    enc, opt, state0 = _state(ds)
    batch = _batch(ds, DP.epoch_ids(ds, 8, rng=np.random.default_rng(0),
                                    shuffle=False)[0])
    rng = jax.random.PRNGKey(3)
    var = G.VARIANTS["gst_efd"]
    base = jax.jit(G.make_train_step(enc, opt, var, keep_prob=0.5))
    aged = jax.jit(G.make_train_step(enc, opt, var, keep_prob=0.5,
                                     sed_decay=0.5))
    s1, s2 = state0, state0
    for _ in range(5):
        s1, m1 = base(s1, batch, rng)
        s2, m2 = aged(s2, batch, rng)
    assert float(m1["loss"]) != float(m2["loss"])


# ---------------------------------------------------------------------------
# RowForecaster round-trips
# ---------------------------------------------------------------------------


def test_forecast_never_observed_is_identity():
    f = RowForecaster(4, 2, 3)
    emb = np.random.default_rng(0).normal(size=(2, 2, 3)).astype(np.float32)
    age = np.zeros((2, 2), np.int32)
    init = np.ones((2, 2), bool)
    out = f.apply(np.array([0, 1]), emb, age, init, now_step=10)
    assert out is emb  # untouched buffer, not even a copy
    assert f.stats() == {"observed_rows": 0, "forecast_rows": 0}


def test_forecast_age_zero_is_identity():
    f = RowForecaster(4, 1, 3)
    rng = np.random.default_rng(1)
    old = rng.normal(size=(1, 1, 3)).astype(np.float32)
    f.observe(np.array([2]), old + 1.0, old,
              age_new=np.full((1, 1), 4, np.int32),
              age_old=np.zeros((1, 1), np.int32),
              init_new=np.ones((1, 1), bool), init_old=np.ones((1, 1), bool))
    emb = rng.normal(size=(1, 1, 3)).astype(np.float32)
    # row refreshed at step 10, asked for at step 10: age 0 < min_age
    out = f.apply(np.array([2]), emb,
                  np.full((1, 1), 10, np.int32), np.ones((1, 1), bool),
                  now_step=10)
    np.testing.assert_array_equal(out, emb)


def test_forecast_extrapolates_by_exact_velocity():
    f = RowForecaster(4, 1, 3)
    old = np.zeros((1, 1, 3), np.float32)
    # one residency: drifted +4.0 over 4 steps -> velocity exactly 1.0/step
    f.observe(np.array([0]), old + 4.0, old,
              age_new=np.full((1, 1), 4, np.int32),
              age_old=np.zeros((1, 1), np.int32),
              init_new=np.ones((1, 1), bool), init_old=np.ones((1, 1), bool))
    emb = np.full((1, 1, 3), 2.0, np.float32)
    # host copy last refreshed at step 4, asked for at step 10 -> age 6
    out = f.apply(np.array([0]), emb,
                  np.full((1, 1), 4, np.int32), np.ones((1, 1), bool),
                  now_step=10)
    np.testing.assert_array_equal(out, emb + 6.0)
    # uninitialized slots never extrapolate, whatever the velocity says
    out2 = f.apply(np.array([0]), emb,
                   np.full((1, 1), 4, np.int32), np.zeros((1, 1), bool),
                   now_step=10)
    np.testing.assert_array_equal(out2, emb)
    assert f.stats()["forecast_rows"] == 1


def test_forecast_ema_blends_observations():
    f = RowForecaster(2, 1, 1, alpha=0.5)
    z = np.zeros((1, 1, 1), np.float32)
    one_step = np.full((1, 1), 1, np.int32)
    for vel in (2.0, 6.0):  # EMA(0.5): 2.0 then 0.5*2 + 0.5*6 = 4.0
        f.observe(np.array([0]), z + vel, z, age_new=one_step,
                  age_old=np.zeros((1, 1), np.int32),
                  init_new=np.ones((1, 1), bool),
                  init_old=np.ones((1, 1), bool))
    out = f.apply(np.array([0]), z, np.zeros((1, 1), np.int32),
                  np.ones((1, 1), bool), now_step=1)
    np.testing.assert_array_equal(out, z + 4.0)


def test_store_forecast_without_step_hints_is_byte_identical():
    """--stale-forecast with no step hints (the serve replay path, and any
    driver that never passes step=) must leave the store byte-identical
    to the flag being off."""
    rng = np.random.default_rng(0)
    stores = [TieredStore(6, 2, 4, device_rows=2, stale_forecast=on)
              for on in (False, True)]
    try:
        tables = [s.init_device_table() for s in stores]
        schedule = [rng.integers(0, 6, 2) for _ in range(8)]
        for t, ids in enumerate(schedule):
            for i, s in enumerate(stores):
                tables[i], slots = s.prepare(tables[i], ids)
                # a deterministic "training write" so evictions carry
                # real deltas into the forecaster's observe stream
                tables[i] = tables[i]._replace(
                    emb=tables[i].emb.at[jnp.asarray(slots)].add(0.25 * t),
                    age=tables[i].age.at[jnp.asarray(slots)].set(t),
                    initialized=tables[i].initialized
                    .at[jnp.asarray(slots)].set(True))
        snaps = [s.snapshot(t) for s, t in zip(stores, tables)]
        assert _tree_bitwise(snaps[0], snaps[1])
        fstats = stores[1].stats()["forecast"]
        assert fstats["forecast_rows"] == 0  # never activated without hints
    finally:
        for s in stores:
            s.close()


# ---------------------------------------------------------------------------
# TRUE ages: refresh_ages and the stale-first victim
# ---------------------------------------------------------------------------


def _churn(refresh: bool):
    """Cap-2 stale-first store; row 1 is refreshed WHILE resident (its
    device age plane advances to 7), row 0 is not.  Returns the set of
    resident rows after a third row faults in."""
    store = TieredStore(3, 2, 4, device_rows=2, evict_policy="stale-first")
    try:
        table = store.init_device_table()
        table, _ = store.prepare(table, np.array([0, 1]), step=0)
        table, _ = store.prepare(table, np.array([0]), step=5)
        # training writes row 1 in place: device age plane advances, but
        # the SlotMap still scores it by its stale step-0 fault-in hint
        table = table._replace(
            age=table.age.at[store.resident_slot(1)].set(7))
        if refresh:
            store.refresh_ages(table)
        table, _ = store.prepare(table, np.array([2]))
        return {r for r in range(3) if store.resident_slot(r) is not None}
    finally:
        store.close()


def test_refresh_ages_protects_refreshed_resident_row():
    # with the readback, row 1 scores its TRUE age 7 and row 0 (device
    # plane still 0) is the victim
    assert _churn(refresh=True) == {1, 2}


def test_without_refresh_ages_refreshed_row_is_wrongly_evicted():
    # the counterfactual: stale hints make the freshly-refreshed row the
    # victim — the bug refresh_ages exists to fix
    assert _churn(refresh=False) == {0, 2}


def test_refresh_ages_noop_under_lru():
    store = TieredStore(3, 2, 4, device_rows=2, evict_policy="lru")
    try:
        table = store.init_device_table()
        table, _ = store.prepare(table, np.array([0, 1]))
        table = table._replace(
            age=table.age.at[store.resident_slot(0)].set(9))
        store.refresh_ages(table)  # must not touch LRU bookkeeping
        table, _ = store.prepare(table, np.array([2]))  # LRU victim: row 0
        assert store.resident_slot(0) is None
        assert store.resident_slot(1) is not None
    finally:
        store.close()


# ---------------------------------------------------------------------------
# SlotMap age bookkeeping under churn
# ---------------------------------------------------------------------------


def test_slotmap_stale_first_victim_order():
    m = SlotMap(2, policy="stale-first")
    m.reserve("a")
    m.set_age("a", 5)
    m.reserve("b")
    m.set_age("b", 3)
    slot, ev = m.reserve("c")          # b is stalest (3 < 5)
    assert ev[0] == "b" and slot == ev[1]
    slot, ev = m.reserve("d")          # c never reported -> stalest (-1)
    assert ev[0] == "c"
    assert sorted(k for k, _ in m.items()) == ["a", "d"]


def test_slotmap_age_dropped_with_eviction():
    m = SlotMap(1, policy="stale-first")
    m.reserve("a")
    m.set_age("a", 5)
    m.reserve("b")                     # evicts a
    assert m.age_of("a") is None
    m.set_age("a", 9)                  # not mapped: must stay a no-op
    assert m.age_of("a") is None
    # re-faulting "a" must not resurrect the pre-eviction age
    m.reserve("a")
    assert m.age_of("a") is None


def test_slotmap_pinned_keys_survive_stale_first():
    m = SlotMap(2, policy="stale-first")
    m.reserve("a")
    m.set_age("a", 0)                  # stalest reported
    m.reserve("b")
    m.set_age("b", 9)
    slot, ev = m.reserve("c", pinned={"a"})
    assert ev[0] == "b"                # pin overrides staleness order
    slot, ev = m.reserve("d", pinned={"a", "c"})
    assert (slot, ev) == (None, None)  # everything pinned: no victim


def test_slotmap_ties_break_by_coldness():
    m = SlotMap(2, policy="stale-first")
    m.reserve("a")
    m.reserve("b")
    m.set_age("a", 4)
    m.set_age("b", 4)
    m.touch("a")                       # b is now the colder of the tie
    slot, ev = m.reserve("c")
    assert ev[0] == "b"


# ---------------------------------------------------------------------------
# StalenessProbe: the effective-age metric family
# ---------------------------------------------------------------------------


def _probe_ages(step=100):
    rng = np.random.default_rng(0)
    age = (step - rng.integers(0, 60, (20, 4))).astype(np.int32)
    init = np.ones((20, 4), bool)
    return age, init, step


def test_probe_effective_age_absent_by_default():
    reg = MetricsRegistry()
    age, init, step = _probe_ages()
    out = StalenessProbe(registry=reg).observe_ages(age, init, step)
    assert "effective_age_steps" not in out
    assert "staleness.effective_age" not in reg.snapshot()


def test_probe_effective_age_below_row_age_under_decay():
    reg = MetricsRegistry()
    age, init, step = _probe_ages()
    out = StalenessProbe(registry=reg, sed_decay=0.1).observe_ages(
        age, init, step)
    eff, raw = out["effective_age_steps"], out["row_age_steps"]
    # age·e^{-λ·age} < age pointwise for age>0 ⇒ every order statistic
    # shrinks — the invariant the CI gate leg asserts on real runs
    assert raw["p99"] > 0
    for q in ("p50", "p99", "max"):
        assert eff[q] < raw[q]
    assert "staleness.effective_age" in reg.snapshot()


def test_probe_forecast_zeroes_eligible_slots():
    reg = MetricsRegistry()
    age, init, step = _probe_ages()
    age = np.minimum(age, step - 1)   # every slot at least 1 step old
    out = StalenessProbe(registry=reg, forecast=True).observe_ages(
        age, init, step)
    assert out["effective_age_steps"]["max"] == 0.0
