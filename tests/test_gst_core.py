"""GST core semantics: sampling, SED (Eq. 1), table staleness, variants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import gst as G
from repro.core import segment as seg
from repro.core import embedding_table as tbl

HSET = settings(max_examples=10, deadline=None)


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------


@given(B=st.integers(1, 8), J=st.integers(2, 16), S=st.integers(1, 3),
       seed=st.integers(0, 1000))
@HSET
def test_sample_segments_valid_and_distinct(B, J, S, seed):
    S = min(S, J)
    rng = np.random.default_rng(seed)
    valid = (rng.uniform(size=(B, J)) < 0.7).astype(np.float32)
    valid[:, 0] = 1.0
    n_valid = valid.sum(-1)
    idx = seg.sample_segments(jax.random.key(seed), jnp.asarray(valid), S)
    idx = np.asarray(idx)
    for b in range(B):
        chosen = idx[b]
        assert len(set(chosen.tolist())) == S  # distinct
        # only valid segments chosen while enough valid ones exist
        if n_valid[b] >= S:
            assert all(valid[b, c] == 1.0 for c in chosen)


def test_sampling_is_uniform_over_valid():
    B, J, n = 1, 5, 4000
    valid = jnp.ones((B, J)).at[0, 3].set(0.0)
    counts = np.zeros(J)
    for i in range(n):
        idx = seg.sample_segments(jax.random.key(i), valid, 1)
        counts[int(idx[0, 0])] += 1
    assert counts[3] == 0
    freq = counts[counts > 0] / n
    np.testing.assert_allclose(freq, 0.25, atol=0.03)


# ---------------------------------------------------------------------------
# SED (Eq. 1)
# ---------------------------------------------------------------------------


@given(J=st.integers(2, 12), p=st.floats(0.05, 0.95), seed=st.integers(0, 500))
@HSET
def test_sed_weights_values(J, p, seed):
    """η ∈ {p + (1-p)J/S, 0, 1} exactly as Eq. 1 prescribes."""
    B, S = 4, 1
    valid = jnp.ones((B, J))
    fresh = jnp.zeros((B, J)).at[jnp.arange(B), 0].set(1.0)
    eta, drop = seg.sed_weights(jax.random.key(seed), valid, fresh, p, S)
    eta = np.asarray(eta)
    expect_fresh = p + (1 - p) * J / S
    np.testing.assert_allclose(eta[:, 0], expect_fresh, rtol=1e-6)
    stale_vals = eta[:, 1:].reshape(-1)
    assert set(np.round(stale_vals, 6)).issubset({0.0, 1.0})


def test_sed_unbiased_fresh_expectation():
    """E[⊕ η h] == ⊕ h when stale == fresh (no staleness): the weighting
    must be an unbiased estimator of the true mean embedding."""
    rng = np.random.default_rng(0)
    B, J, d, p = 2, 6, 8, 0.35
    h = jnp.asarray(rng.normal(size=(B, J, d)), jnp.float32)
    valid = jnp.ones((B, J))
    acc = 0
    n = 3000
    for i in range(n):
        k1, k2 = jax.random.split(jax.random.key(i))
        idx = seg.sample_segments(k1, valid, 1)
        fresh = seg.sampled_mask(idx, J)
        eta, _ = seg.sed_weights(k2, valid, fresh, p, 1)
        acc = acc + seg.aggregate(h, eta, valid, "mean")
    mc = np.asarray(acc) / n
    true = np.asarray(jnp.mean(h, axis=1))
    np.testing.assert_allclose(mc, true, atol=0.05)


def test_sed_limits():
    """p=1 keeps all stale (η=1 everywhere); p=0 drops all stale (GST-One)."""
    B, J = 3, 5
    valid = jnp.ones((B, J))
    fresh = jnp.zeros((B, J)).at[:, 2].set(1.0)
    eta1, _ = seg.sed_weights(jax.random.key(0), valid, fresh, 1.0, 1)
    np.testing.assert_allclose(np.asarray(eta1), 1.0)
    eta0, _ = seg.sed_weights(jax.random.key(0), valid, fresh, 0.0, 1)
    expect = np.zeros((B, J)); expect[:, 2] = J
    np.testing.assert_allclose(np.asarray(eta0), expect)


# ---------------------------------------------------------------------------
# embedding table
# ---------------------------------------------------------------------------


def test_table_update_and_staleness_age():
    t = tbl.init_table(5, 3, 4)
    ids = jnp.asarray([1, 3])
    idx = jnp.asarray([[0], [2]])
    h = jnp.ones((2, 1, 4))
    t = tbl.update_sampled(t, ids, idx, h, jnp.asarray(7, jnp.int32))
    assert bool(t.initialized[1, 0]) and bool(t.initialized[3, 2])
    assert int(t.age[1, 0]) == 7
    assert not bool(t.initialized[0, 0])
    emb, init = tbl.lookup(t, jnp.asarray([1]))
    np.testing.assert_allclose(np.asarray(emb[0, 0]), 1.0)


def test_staleness_grows_like_paper_bound():
    """Visiting each graph once per epoch with S=1 of J segments, the oldest
    entry is ~ n·J/S iterations stale (paper §3.4)."""
    n, J, d = 8, 4, 2
    t = tbl.init_table(n, J, d)
    step = 0
    rng = np.random.default_rng(0)
    for epoch in range(40):
        for g in range(n):
            j = rng.integers(0, J)
            t = tbl.update_sampled(t, jnp.asarray([g]), jnp.asarray([[j]]),
                                   jnp.zeros((1, 1, d)), jnp.asarray(step))
            step += 1
    ages = step - np.asarray(t.age)[np.asarray(t.initialized)]
    assert ages.max() > n  # at least n-iterations stale (paper's lower bound)
    # "approximately nJ/S-iteration stale" (paper §3.4) — the bulk of entries,
    # allowing a geometric tail for the max
    assert np.quantile(ages, 0.9) < 3 * n * J
    assert ages.max() < 10 * n * J


# ---------------------------------------------------------------------------
# variant semantics
# ---------------------------------------------------------------------------


def _tiny_setup(variant, J=4, d=8, B=4, n=16):
    from repro.optim import make_optimizer

    def encode(w, seg_inputs):
        # linear "backbone": mean of tokens one-hot embedded by w
        x = jax.nn.one_hot(seg_inputs["tokens"], 16) @ w  # (N, L, d)
        return jnp.mean(x, axis=1), jnp.zeros((), jnp.float32)

    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(16, d)), jnp.float32)
    head = G.head_init(jax.random.key(1), d, 3, "mlp")
    opt = make_optimizer("adam", lr=1e-2)
    state = G.TrainState(w, head, opt.init((w, head)),
                         tbl.init_table(n, J, d), jnp.zeros((), jnp.int32))
    batch = G.GSTBatch(
        {"tokens": jnp.asarray(rng.integers(0, 16, (B, J, 5)), jnp.int32)},
        jnp.ones((B, J), jnp.float32), jnp.arange(B, dtype=jnp.int32),
        jnp.asarray(rng.integers(0, 3, B), jnp.int32))
    step = G.make_train_step(encode, opt, G.VARIANTS[variant])
    return state, batch, step, encode, opt


@pytest.mark.parametrize("variant", list(G.VARIANTS))
def test_all_variants_run_and_learn_shape(variant):
    state, batch, step, *_ = _tiny_setup(variant)
    new_state, m = jax.jit(step)(state, batch, jax.random.key(0))
    assert np.isfinite(float(m["loss"]))
    if G.VARIANTS[variant].use_table:
        assert bool(new_state.table.initialized.any())
    else:
        assert not bool(new_state.table.initialized.any())


def test_gst_equals_full_when_sampling_everything():
    """With S=J and fresh recompute, gst's loss == full's loss on the same
    batch (the stale set is empty)."""
    from repro.optim import make_optimizer
    J = 3
    state, batch, _, encode, opt = _tiny_setup("gst", J=J)
    full_step = G.make_train_step(encode, opt, G.VARIANTS["full"])
    gst_step = G.make_train_step(encode, opt, G.VARIANTS["gst"], num_sampled=J)
    _, m_full = jax.jit(full_step)(state, batch, jax.random.key(0))
    _, m_gst = jax.jit(gst_step)(state, batch, jax.random.key(0))
    np.testing.assert_allclose(float(m_full["loss"]), float(m_gst["loss"]),
                               rtol=1e-5)


def test_finetune_trains_head_only():
    state, batch, step, encode, opt = _tiny_setup("gst_efd")
    state, _ = jax.jit(step)(state, batch, jax.random.key(0))
    refresh = jax.jit(G.make_refresh_step(encode))
    state = refresh(state, batch)
    assert bool(state.table.initialized[:4].all())
    from repro.optim import make_optimizer
    ft_opt = make_optimizer("adam", lr=1e-2)
    state = state._replace(opt_state=ft_opt.init(state.head))
    ft = jax.jit(G.make_finetune_step(ft_opt))
    bb_before = state.backbone
    head_before = state.head
    state, m = ft(state, batch)
    assert np.isfinite(float(m["loss"]))
    np.testing.assert_array_equal(np.asarray(bb_before),
                                  np.asarray(state.backbone))
    diffs = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), head_before, state.head)
    assert max(jax.tree_util.tree_leaves(diffs)) > 0
