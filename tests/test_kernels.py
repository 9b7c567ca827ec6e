"""Pallas kernels vs pure-jnp oracles: fixed cases + hypothesis shape sweeps.

All kernels run in interpret mode on CPU (the kernels target TPU; interpret
executes the kernel body in Python — the assignment's validation method).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.sed_pool import sed_pool
from repro.kernels.segment_spmm import segment_spmm
from repro.kernels.swa_attention import swa_attention

HSET = settings(max_examples=8, deadline=None)


# ---------------------------------------------------------------------------
# segment_spmm
# ---------------------------------------------------------------------------


@given(m=st.sampled_from([16, 64, 128, 256]),
       d=st.sampled_from([8, 64, 130, 256]),
       e=st.integers(1, 600),
       dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
       seed=st.integers(0, 10_000))
@HSET
def test_spmm_matches_oracle(m, d, e, dtype, seed):
    rng = np.random.default_rng(seed)
    h = jnp.asarray(rng.normal(size=(m, d)), dtype)
    src = jnp.asarray(rng.integers(0, m, e), jnp.int32)
    dst = jnp.asarray(rng.integers(0, m, e), jnp.int32)
    w = jnp.asarray(rng.uniform(0, 1, e) * (rng.uniform(size=e) > 0.3), dtype)
    out = segment_spmm(h, src, dst, w, interpret=True)
    want = ref.segment_spmm_ref(h.astype(jnp.float32), src, dst,
                                w.astype(jnp.float32), m)
    tol = 1e-5 if dtype == jnp.float32 else 6e-2
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(want),
                               rtol=tol, atol=tol)


def test_spmm_zero_weights_give_zero():
    h = jnp.ones((32, 16))
    src = jnp.zeros((10,), jnp.int32)
    dst = jnp.arange(10, dtype=jnp.int32)
    out = segment_spmm(h, src, dst, jnp.zeros((10,)), interpret=True)
    assert float(jnp.abs(out).max()) == 0.0


# ---------------------------------------------------------------------------
# sed_pool
# ---------------------------------------------------------------------------


@given(B=st.integers(1, 17), J=st.integers(1, 24),
       d=st.sampled_from([8, 64, 128, 200]),
       p=st.sampled_from([0.0, 0.3, 0.5, 1.0]),
       S=st.integers(1, 3), agg=st.sampled_from(["mean", "sum"]),
       seed=st.integers(0, 10_000))
@HSET
def test_sed_pool_matches_oracle(B, J, d, p, S, agg, seed):
    rng = np.random.default_rng(seed)
    S = min(S, J)
    h = jnp.asarray(rng.normal(size=(B, J, d)), jnp.float32)
    valid = jnp.asarray(rng.uniform(size=(B, J)) < 0.8, jnp.float32)
    valid = valid.at[:, 0].set(1.0)
    fresh = jnp.zeros((B, J)).at[jnp.arange(B), rng.integers(0, J, B)].set(1.0)
    fresh = fresh * valid
    drop = jnp.asarray(rng.uniform(size=(B, J)) < 0.5, jnp.float32)
    out = sed_pool(h, valid, fresh, drop, keep_prob=p, num_sampled=S, agg=agg,
                   interpret=True)
    want = ref.sed_pool_ref(h, valid, fresh, drop, p, S, agg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_sed_pool_matches_core_composition():
    """Kernel == segment.sed_weights + segment.aggregate on the same draw."""
    from repro.core import segment as seg
    rng = np.random.default_rng(7)
    B, J, d, p = 6, 9, 32, 0.4
    h = jnp.asarray(rng.normal(size=(B, J, d)), jnp.float32)
    valid = jnp.ones((B, J))
    fresh = jnp.zeros((B, J)).at[jnp.arange(B), rng.integers(0, J, B)].set(1.0)
    key = jax.random.key(3)
    eta, drop = seg.sed_weights(key, valid, fresh, p, 1)
    via_core = seg.aggregate(h, eta, valid, "mean")
    via_kernel = sed_pool(h, valid, fresh, drop, keep_prob=p, num_sampled=1,
                          agg="mean", interpret=True)
    np.testing.assert_allclose(np.asarray(via_kernel), np.asarray(via_core),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# swa_attention
# ---------------------------------------------------------------------------


@given(B=st.integers(1, 3), S=st.sampled_from([128, 256, 512]),
       H=st.sampled_from([1, 2, 4]), D=st.sampled_from([64, 128]),
       Wb=st.sampled_from([1, 2, 4, 100]),  # window in blocks
       seed=st.integers(0, 10_000))
@HSET
def test_swa_matches_oracle(B, S, H, D, Wb, seed):
    rng = np.random.default_rng(seed)
    blk = 128
    W = min(Wb * blk, S) if Wb != 100 else S  # 100 => full-causal window
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    out = swa_attention(q, k, v, window=W, blk=blk, interpret=True)
    want = ref.swa_attention_ref(q, k, v, W)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_swa_full_window_equals_causal_attention():
    """window >= S must reproduce plain causal attention (common.sdpa)."""
    from repro.models.common import sdpa
    rng = np.random.default_rng(11)
    B, S, H, D = 2, 256, 2, 64
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    out = swa_attention(q, k, v, window=S, blk=128, interpret=True)
    want = sdpa(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_gnn_pallas_path_matches_jnp_path():
    """segment_spmm wired into the SAGE backbone (vmapped over segments)
    must reproduce the jax.ops.segment_sum path exactly."""
    import numpy as np
    from repro.graphs import data as D, batching as Bt
    from repro.graphs.gnn import GNNConfig, gnn_init, make_encode_fn
    graphs = D.make_malnet_like(n_graphs=2, seed=0)
    ds = Bt.segment_dataset(graphs, max_seg_nodes=48)
    seg = {k: jnp.asarray(v.reshape((-1,) + v.shape[2:]))
           for k, v in ds.seg_inputs(np.arange(2)).items()}
    cfg0 = GNNConfig(backbone="sage", n_feat=8, hidden=32, use_pallas=False)
    cfg1 = GNNConfig(backbone="sage", n_feat=8, hidden=32, use_pallas=True)
    params = gnn_init(jax.random.key(0), cfg0)
    e0, _ = make_encode_fn(cfg0)(params, seg)
    e1, _ = make_encode_fn(cfg1)(params, seg)
    np.testing.assert_allclose(np.asarray(e0), np.asarray(e1),
                               rtol=2e-5, atol=2e-5)
