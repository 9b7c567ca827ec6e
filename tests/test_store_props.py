"""Property tests for the tiered embedding store's tier invariants.

Random (geometry, batch-sequence) draws from hypothesis, checked after
EVERY prepare/update against a dense oracle table:

  * device-tier occupancy never exceeds the per-shard capacity, and no
    two keys ever share a slot (SlotMap internal consistency);
  * every row is authoritative in exactly one tier: resident rows answer
    from the device tier, everything else from host RAM, and the merged
    snapshot equals the oracle bit for bit;
  * lookups after ANY eviction sequence are bit-exact vs the oracle —
    residency is invisible to the training math;
  * the eviction POLICY (lru | stale-first, store/slots.py) only changes
    WHICH row migrates, never the math: the churn invariants hold under
    both, and under stale-first the stale-and-cold rows demonstrably
    leave the device tier before fresh-and-hot ones.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import embedding_table as tbl
from repro.store import SlotMap, TieredStore


def _random_ops(store, table, oracle, rng, n_steps, batch):
    """Drive identical random lookups/updates through the tiered store and
    the dense oracle; yields after each op for invariant checks."""
    n, J, d = oracle.emb.shape
    R, C = store.rows_per_shard, store.device_rows_per_shard
    for t in range(n_steps):
        # per-shard draws so one batch never needs more than C rows of a
        # shard resident (the documented capacity contract)
        ids = []
        for s in range(store.num_shards):
            lo, hi = s * R, min((s + 1) * R, n)
            if lo >= n:
                continue
            k = min(batch, C, hi - lo)
            ids.extend(rng.choice(np.arange(lo, hi), size=k, replace=False))
        ids = np.asarray(ids, np.int64)
        h = rng.normal(size=(len(ids), 1, d)).astype(np.float32)
        sidx = rng.integers(0, J, (len(ids), 1)).astype(np.int32)

        table, slots = store.prepare(table, ids)
        e_t, i_t = tbl.lookup(table, jnp.asarray(slots))
        e_o, i_o = tbl.lookup(oracle, jnp.asarray(ids))

        table = tbl.update_sampled(table, jnp.asarray(slots),
                                   jnp.asarray(sidx), jnp.asarray(h), t)
        oracle = tbl.update_sampled(oracle, jnp.asarray(ids),
                                    jnp.asarray(sidx), jnp.asarray(h), t)
        yield table, oracle, ids, slots, (e_t, i_t), (e_o, i_o)


@settings(max_examples=8, deadline=None)
@given(n=st.integers(5, 30), device_frac=st.floats(0.1, 0.9),
       num_shards=st.sampled_from([1, 2, 4]), seed=st.integers(0, 10**6),
       policy=st.sampled_from(["lru", "stale-first"]))
def test_tier_invariants_hold_under_random_churn(n, device_frac, num_shards,
                                                 seed, policy):
    rng = np.random.default_rng(seed)
    J, d = 2, 4
    store = TieredStore(n, J, d, num_shards=num_shards,
                        device_rows=max(1, int(n * device_frac)),
                        evict_policy=policy)
    table = store.init_device_table()
    oracle = tbl.init_table(n, J, d)
    C = store.device_rows_per_shard

    for table, oracle, ids, slots, got, want in _random_ops(
            store, table, oracle, rng, n_steps=12, batch=3):
        # lookup bit-exact vs oracle after any eviction sequence
        assert np.array_equal(np.asarray(got[0]), np.asarray(want[0]))
        assert np.array_equal(np.asarray(got[1]), np.asarray(want[1]))
        # occupancy never exceeds per-shard capacity; slots never shared
        resident = {}
        for s, m in enumerate(store._maps):
            assert len(m) <= C
            entries = dict(m.items())
            assert len(set(entries.values())) == len(entries)
            for row, slot in entries.items():
                assert s * store.rows_per_shard <= row \
                    < min((s + 1) * store.rows_per_shard, n)
                resident[row] = s * C + slot
        # slot ids the batch got must agree with the residency map
        for rid, slot in zip(ids, slots):
            assert resident[int(rid)] == int(slot)
        # every row in exactly one tier: the merged snapshot IS the oracle
        # (residency must be invisible), and only non-resident rows answer
        # from the host tier
        assert store.occupancy() == len(resident)

    store.flush_writebacks()
    snap = store.snapshot(table)
    for a, b in zip(snap, oracle):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    store.close()


@settings(max_examples=10, deadline=None)
@given(capacity=st.integers(1, 8), n_keys=st.integers(1, 24),
       seed=st.integers(0, 10**6))
def test_slotmap_never_leaks_or_doubles_slots(capacity, n_keys, seed):
    rng = np.random.default_rng(seed)
    m = SlotMap(capacity)
    live = {}
    for i in range(n_keys):
        key = f"k{i}"
        slot, evicted = m.reserve(key)
        assert slot is not None          # nothing pinned -> always succeeds
        if evicted is not None:
            old_key, old_slot = evicted
            assert live.pop(old_key) == old_slot == slot
        live[key] = slot
        if live and rng.random() < 0.3:  # random release
            victim = rng.choice(sorted(live))
            m.release(victim)
            del live[victim]
        assert len(m) == len(live) <= capacity
        assert len(set(live.values())) == len(live)
        for k, s in live.items():
            assert m.get(k, touch=False) == s


# ---------------------------------------------------------------------------
# staleness-aware eviction (--evict-policy=stale-first)
# ---------------------------------------------------------------------------


def _aged_store(policy):
    """A store restored from a snapshot whose per-row ages are crafted:
    rows 0-3 will fill the 4-slot device tier; rows 4-6 arrive later and
    force evictions.  Ages: 0->5, 1->1, 2->9, 3->1, 4..6->20."""
    n, J, d, C = 8, 2, 4, 4
    rng = np.random.default_rng(0)
    ages = np.array([5, 1, 9, 1, 20, 20, 20, 3])
    snap = tbl.EmbeddingTable(
        emb=rng.normal(size=(n, J, d)).astype(np.float32),
        age=np.tile(ages[:, None], (1, J)).astype(np.int32),
        initialized=np.ones((n, J), bool))
    store = TieredStore(n, J, d, device_rows=C, evict_policy=policy)
    return store, store.restore(snap), snap


def test_stale_first_evicts_stale_and_cold_rows_first():
    store, table, snap = _aged_store("stale-first")
    table, _ = store.prepare(table, np.asarray([0, 1, 2, 3]))  # tier full
    # rows 1 and 3 are equally stale (age 1); row 1 is colder (faulted
    # earlier), so it leaves first — NOT row 0, the pure-LRU victim
    table, _ = store.prepare(table, np.asarray([4]))
    assert store.resident_slot(1) is None
    assert all(store.resident_slot(r) is not None for r in (0, 2, 3, 4))
    table, _ = store.prepare(table, np.asarray([5]))
    assert store.resident_slot(3) is None                      # age 1
    table, _ = store.prepare(table, np.asarray([6]))
    assert store.resident_slot(0) is None                      # age 5
    assert store.resident_slot(2) is not None                  # fresh: 9
    # the policy never touched the math: the merged view is still the
    # restored snapshot, bit for bit, and an evicted row faults back exact
    store.flush_writebacks()
    got = store.snapshot(table)
    for a, b in zip(got, snap):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    table, slots = store.prepare(table, np.asarray([1]))
    e, i = tbl.lookup(table, jnp.asarray(slots))
    assert np.array_equal(np.asarray(e)[0], np.asarray(snap.emb)[1])
    assert np.array_equal(np.asarray(i)[0], np.asarray(snap.initialized)[1])
    store.close()


def test_stale_first_step_hint_keeps_rewritten_resident_rows():
    """A resident row a train step is about to rewrite (prepare's ``step``
    hint) must stop scoring as stale as its fault-in age — without the
    hint the stalest-at-fault-in row would be evicted even while hot."""
    store, table, _ = _aged_store("stale-first")
    table, _ = store.prepare(table, np.asarray([0, 1, 2, 3]))
    # row 1 (fault-in age 1, the stalest) is requested by a writing step
    table, _ = store.prepare(table, np.asarray([1]), step=100)
    # eviction pressure now spares it: the victim is row 3 (age 1)
    table, _ = store.prepare(table, np.asarray([4]))
    assert store.resident_slot(3) is None
    assert store.resident_slot(1) is not None
    store.close()


def test_lru_contrast_evicts_coldest_not_stalest():
    store, table, _ = _aged_store("lru")
    table, _ = store.prepare(table, np.asarray([0, 1, 2, 3]))
    table, _ = store.prepare(table, np.asarray([4]))
    assert store.resident_slot(0) is None     # coldest, despite mid age
    assert store.resident_slot(1) is not None  # stalest but newer in LRU
    store.close()


def test_slotmap_stale_first_scoring_and_pinning():
    m = SlotMap(2, policy="stale-first")
    assert m.reserve("a")[0] is not None
    m.set_age("a", 10)
    assert m.reserve("b")[0] is not None
    m.set_age("b", 2)
    slot, evicted = m.reserve("c")            # b is stalest
    assert evicted[0] == "b" and evicted[1] == slot
    # a key with NO reported age counts as stalest of all
    slot, evicted = m.reserve("d")
    assert evicted[0] == "c"
    # pinning excludes the stalest: the other key is displaced instead
    m.set_age("d", 0)
    slot, evicted = m.reserve("e", pinned={"d"})
    assert evicted[0] == "a"
    # full map, everything pinned -> (None, None)
    assert m.reserve("f", pinned={"d", "e"}) == (None, None)
    # release cleans the age bookkeeping too
    m.set_age("e", 7)
    m.release("e")
    assert m.age_of("e") is None


def test_slotmap_rejects_unknown_policy():
    with pytest.raises(ValueError, match="policy"):
        SlotMap(4, policy="freshest-first")


@settings(max_examples=6, deadline=None)
@given(n=st.integers(4, 20), seed=st.integers(0, 10**6))
def test_min_capacity_single_slot_store_stays_exact(n, seed):
    """The degenerate 1-device-row tier: every step evicts, every lookup
    faults — still bit-exact."""
    rng = np.random.default_rng(seed)
    store = TieredStore(n, 1, 3, device_rows=1)
    table = store.init_device_table()
    oracle = tbl.init_table(n, 1, 3)
    for t in range(10):
        row = int(rng.integers(n))
        h = rng.normal(size=(1, 1, 3)).astype(np.float32)
        table, slots = store.prepare(table, np.asarray([row]))
        z = jnp.zeros((1, 1), jnp.int32)
        table = tbl.update_sampled(table, jnp.asarray(slots), z,
                                   jnp.asarray(h), t)
        oracle = tbl.update_sampled(oracle, jnp.asarray([row]), z,
                                    jnp.asarray(h), t)
    snap = store.snapshot(table)
    for a, b in zip(snap, oracle):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    store.close()
