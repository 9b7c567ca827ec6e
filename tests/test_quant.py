"""Payload pack/unpack kernels (kernels/quant.py) vs the jnp reference,
plus the stochastic-rounding statistical contracts the compressed exchange
wire format (dist/exchange.PayloadCodec) relies on.

Pallas kernels run in interpret mode on CPU, same validation method as
test_kernels.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.ops import dequantize_payload, quantize_payload
from repro.kernels.quant import (PAYLOAD_DTYPES, dequantize_rows,
                                 dequantize_rows_ref, quantize_rows,
                                 quantize_rows_ref)

HSET = settings(max_examples=8, deadline=None)

COMPRESSED = [d for d in PAYLOAD_DTYPES if d != "f32"]


def _bits(shape, seed=0):
    return jax.random.bits(jax.random.PRNGKey(seed), shape, jnp.uint32)


# ---------------------------------------------------------------------------
# pallas kernel vs jnp reference: bit parity (same rounding bits in, same
# payload out — both rounding modes, both dtypes)
# ---------------------------------------------------------------------------


@given(r=st.integers(1, 70), n=st.sampled_from([4, 32, 128, 130]),
       dtype=st.sampled_from(COMPRESSED),
       stochastic=st.booleans(), seed=st.integers(0, 10_000))
@HSET
def test_pack_pallas_matches_ref(r, n, dtype, stochastic, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(r, n)), jnp.float32) * 3.0
    bits = _bits((r, n), seed) if stochastic else None
    got = quantize_rows(x, dtype, bits, use_pallas=True, interpret=True)
    want = quantize_rows_ref(x, dtype, bits)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    # and the unpack side round-trips identically through both paths
    back = dequantize_rows(got, dtype, use_pallas=True, interpret=True)
    back_ref = dequantize_rows_ref(want, dtype)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(back_ref))


def test_ops_wrappers_roundtrip():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(8, 3, 16)),
                    jnp.float32)
    for dtype in COMPRESSED:
        parts = quantize_payload(x, dtype=dtype, use_pallas=True)
        back = dequantize_payload(parts, dtype=dtype, use_pallas=True)
        assert back.shape == x.shape and back.dtype == x.dtype
        ref_parts = quantize_rows_ref(x, dtype)
        for g, w in zip(parts, ref_parts):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# ---------------------------------------------------------------------------
# exact preservation: values the compressed grid can represent must
# round-trip bit-for-bit under BOTH rounding modes — stochastic rounding
# must never perturb a representable value (its fraction is exactly 0)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("stochastic", [False, True])
def test_bf16_preserves_representable(stochastic):
    vals = np.asarray([[0.0, -0.0, 1.0, -1.0, 0.5, -2.0, 384.0, 2.0 ** -20]],
                      np.float32)
    x = jnp.asarray(np.asarray(jnp.asarray(vals, jnp.bfloat16), np.float32))
    bits = _bits(x.shape, 3) if stochastic else None
    (q,) = quantize_rows_ref(x, "bf16", bits)
    back = np.asarray(dequantize_rows_ref((q,), "bf16"))
    np.testing.assert_array_equal(back, np.asarray(x))
    # the sign bit of -0.0 survives (round-trip is a bitcast, not math)
    assert np.signbit(back[0, 1]) and not np.signbit(back[0, 0])


@pytest.mark.parametrize("stochastic", [False, True])
def test_int8_preserves_grid_points(stochastic):
    # rows whose values all sit on the k * amax/127 grid decode exactly
    scale = 0.25
    ks = np.asarray([[-127, -64, -1, 0, 1, 3, 64, 127]], np.float32)
    x = jnp.asarray(ks * scale)
    bits = _bits(x.shape, 7) if stochastic else None
    q, s = quantize_rows_ref(x, "int8", bits)
    np.testing.assert_array_equal(np.asarray(q), ks.astype(np.int8))
    np.testing.assert_allclose(np.asarray(s), [scale], rtol=1e-6)
    back = np.asarray(dequantize_rows_ref((q, s), "int8"))
    np.testing.assert_allclose(back, np.asarray(x), rtol=1e-6)


def test_int8_zero_row_decodes_exact_zeros():
    # amax = 0 -> scale 0 -> decode is exactly 0.0: the property ragged
    # sentinel rows in the bucketed exchange depend on (int8 carries no
    # sign bit for -0.0; it maps to +0.0, documented in kernels/quant.py)
    x = jnp.zeros((3, 8), jnp.float32)
    for stochastic in (False, True):
        bits = _bits(x.shape, 11) if stochastic else None
        q, s = quantize_rows_ref(x, "int8", bits)
        assert np.all(np.asarray(q) == 0) and np.all(np.asarray(s) == 0.0)
        back = np.asarray(dequantize_rows_ref((q, s), "int8"))
        assert np.all(back == 0.0)


# ---------------------------------------------------------------------------
# stochastic rounding is unbiased: E[decode(encode(x))] == x, unlike
# round-to-nearest whose systematic bias accumulates across write-backs
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype,value,rel_tol", [
    ("bf16", 0.3, 2e-4),     # 0.3 is not bf16-representable
    ("int8", 0.35, 2e-4),    # 0.35 * 127 = 44.45 is off-grid
])
def test_stochastic_rounding_unbiased(dtype, value, rel_tol):
    n = 20_000
    x = jnp.full((n, 4), value, jnp.float32)
    # pin amax so the int8 grid does not move with the samples
    x = x.at[:, 0].set(1.0)
    parts = quantize_rows_ref(x, dtype, _bits(x.shape, 123))
    back = np.asarray(dequantize_rows_ref(parts, dtype), np.float64)
    mean = back[:, 1:].mean()
    assert abs(mean - value) < rel_tol * value, \
        f"SR mean {mean} drifted from {value}"
    # deterministic RNE is NOT an unbiased estimator here: every sample
    # lands on the same side, so the error is the full rounding offset
    det = np.asarray(dequantize_rows_ref(quantize_rows_ref(x, dtype), dtype),
                     np.float64)
    assert abs(det[:, 1:].mean() - value) > rel_tol * value


@given(dtype=st.sampled_from(COMPRESSED), seed=st.integers(0, 10_000))
@HSET
def test_error_bound_one_ulp(dtype, seed):
    # SR lands within ONE grid step of the input (RNE within half) —
    # the bound the exchange parity tests budget against
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(16, 32)), jnp.float32)
    parts = quantize_rows_ref(x, dtype, _bits(x.shape, seed))
    back = np.asarray(dequantize_rows_ref(parts, dtype))
    amax = np.abs(np.asarray(x)).max(axis=1, keepdims=True)
    step = amax * (2.0 ** -7 if dtype == "bf16" else 1.0 / 127.0)
    assert np.all(np.abs(back - np.asarray(x)) <= step + 1e-7)
