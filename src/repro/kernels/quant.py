"""Pallas TPU kernels: pack/unpack embedding rows for the compressed wire
format (dist/exchange.py ``--payload-dtype``).

Historical embeddings are approximate by design (stale snapshots the GST
paper already perturbs via SED), so the payloads that move them — exchange
hops, eviction write-backs — tolerate reduced precision (FreshGNN,
PAPERS.md).  Two row formats over a float32 source row of N elements:

  ``bf16``  round-to-nearest on the read path, STOCHASTIC rounding on the
            write path: 16 uniform random bits are added below the bf16
            mantissa boundary before truncation, so E[packed] == exact and
            repeated write round-trips stay unbiased.  Values already
            representable in bf16 (zero low mantissa bits — including
            ±0.0) are preserved exactly: the added bits can never carry.

  ``int8``  symmetric per-row scale s = max|row| / 127 (float32, rides the
            wire next to the values; 0 for all-zero rows so zero rows
            decode to exact zeros), values stochastically or RNE-rounded
            to [-127, 127].  Integer-valued rows whose scale is exactly 1
            round-trip exactly.

Both follow the segment_spmm / sed_pool pattern: a jnp reference path
(``quantize_rows_ref`` / ``dequantize_rows_ref``) is the parity oracle for
the Pallas kernels (tests/test_quant.py), the kernels run in interpret
mode off-TPU, and ``kernels/ops.py`` owns the jit'd public wrappers.
Randomness is an EXPLICIT uint32 input (callers derive it from the train
step with jax.random.bits) — no in-kernel PRNG state, so pallas and
reference paths agree bit-for-bit given the same bits.

Quantization is row-wise over the LEADING axis: x (R, ...) packs to
values (R, ...) in the target dtype plus, for int8, one f32 scale per
leading row.  Nothing here is differentiated — the exchange write path
packs ``stop_gradient``-ed embeddings and lookups enter the loss as
constants — so the kernels carry no custom VJP.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

PAYLOAD_DTYPES = ("f32", "bf16", "int8")

# row-block sizes: int8 output tiling wants 32 sublanes, the lane dim is
# padded to 128 (pallas_guide.md dtype min tiles)
ROW_BLK = 32
LANE = 128

# masks are numpy scalars: they lower to jaxpr literals, so kernel bodies
# don't capture array constants (pallas_call rejects captured ShapedArrays)
_MANT_MASK = np.uint32(0xFFFF)         # bits below the bf16 boundary
_BF16_KEEP = np.uint32(0xFFFF0000)


# ---------------------------------------------------------------------------
# shared rounding math (kernel bodies AND the jnp reference call these)
# ---------------------------------------------------------------------------


def _bf16_stochastic(x, bits):
    """f32 -> bf16 by adding 16 uniform bits below the mantissa boundary
    and truncating.  Unbiased in magnitude; exact when the low bits are
    already zero (bf16-representable values, ±0.0 included)."""
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    u = (u + (bits & _MANT_MASK)) & _BF16_KEEP
    return jax.lax.bitcast_convert_type(u, jnp.float32).astype(jnp.bfloat16)


def _uniform01(bits):
    """uint32 -> uniform [0, 1) f32 from the high 24 bits.  ``bits >> 8``
    fits in 24 bits, so the int32 hop is exact; Mosaic has no direct
    uint32 -> f32 cast."""
    hi24 = (bits >> 8).astype(jnp.int32)
    return hi24.astype(jnp.float32) * (1.0 / (1 << 24))


def _int8_quantize(x, bits):
    """x (r, n) f32 -> (values int8, scale (r, 1) f32).  ``bits`` None =
    round-to-nearest-even (read path), else stochastic (write path)."""
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)        # (r, 1)
    scale = amax * (1.0 / 127.0)
    v = x / jnp.where(scale > 0, scale, 1.0)                  # [-127, 127]
    if bits is None:
        q = jnp.round(v)
    else:
        lo = jnp.floor(v)
        q = lo + (_uniform01(bits) < (v - lo)).astype(jnp.float32)
    q = jnp.clip(q, -127.0, 127.0).astype(jnp.int8)
    return q, scale


# ---------------------------------------------------------------------------
# jnp reference (the parity oracle; also the path the exchange runs by
# default — XLA fuses the elementwise math into the surrounding step)
# ---------------------------------------------------------------------------


def _rows(x) -> Tuple[jnp.ndarray, Tuple[int, ...]]:
    shape = x.shape
    return x.reshape(shape[0], -1), shape


def quantize_rows_ref(x, dtype: str, rand_bits=None):
    """x (R, ...) f32 -> wire parts: (values,) for bf16, (values, scale)
    for int8 (scale (R,) f32).  ``rand_bits`` uint32 of x's shape turns on
    stochastic rounding (the write path); None rounds to nearest."""
    x2, shape = _rows(x)
    if dtype == "bf16":
        if rand_bits is None:
            return (x2.astype(jnp.bfloat16).reshape(shape),)
        return (_bf16_stochastic(
            x2, rand_bits.reshape(x2.shape)).reshape(shape),)
    if dtype == "int8":
        bits = None if rand_bits is None else rand_bits.reshape(x2.shape)
        q, scale = _int8_quantize(x2, bits)
        return q.reshape(shape), scale[:, 0]
    raise ValueError(f"quantize dtype {dtype!r} not in ('bf16', 'int8')")


def dequantize_rows_ref(parts, dtype: str):
    """Inverse of quantize_rows_ref: wire parts -> f32 (R, ...)."""
    if dtype == "bf16":
        (v,) = parts
        return v.astype(jnp.float32)
    if dtype == "int8":
        v, scale = parts
        return v.astype(jnp.float32) * scale.reshape(
            (-1,) + (1,) * (v.ndim - 1))
    raise ValueError(f"dequantize dtype {dtype!r} not in ('bf16', 'int8')")


# ---------------------------------------------------------------------------
# pallas kernels (grid over row blocks; each block sees whole rows so the
# per-row amax reduction stays in VMEM)
# ---------------------------------------------------------------------------


def _pack_bf16_kernel(x_ref, bits_ref, out_ref):
    out_ref[...] = _bf16_stochastic(x_ref[...], bits_ref[...])


def _pack_bf16_det_kernel(x_ref, out_ref):
    out_ref[...] = x_ref[...].astype(jnp.bfloat16)


def _pack_int8_kernel(x_ref, bits_ref, v_ref, s_ref):
    q, scale = _int8_quantize(x_ref[...], bits_ref[...])
    v_ref[...] = q
    s_ref[...] = scale


def _pack_int8_det_kernel(x_ref, v_ref, s_ref):
    q, scale = _int8_quantize(x_ref[...], None)
    v_ref[...] = q
    s_ref[...] = scale


def _unpack_bf16_kernel(v_ref, out_ref):
    out_ref[...] = v_ref[...].astype(jnp.float32)


def _unpack_int8_kernel(v_ref, s_ref, out_ref):
    out_ref[...] = v_ref[...].astype(jnp.float32) * s_ref[...]


def _pad2(x2, r_blk):
    R, N = x2.shape
    pad_r, pad_n = (-R) % r_blk, (-N) % LANE
    if pad_r or pad_n:
        x2 = jnp.pad(x2, ((0, pad_r), (0, pad_n)))
    return x2, R + pad_r, N + pad_n


def quantize_rows(x, dtype: str, rand_bits=None, *,
                  use_pallas: bool = False, interpret: bool = True,
                  r_blk: int = ROW_BLK):
    """Pack f32 rows into the compressed wire format (see module docstring
    for the formats).  Returns the wire-parts tuple of
    ``quantize_rows_ref``; ``use_pallas`` routes through the Pallas pack
    kernel (interpret mode off-TPU) instead of the fused-jnp reference."""
    if not use_pallas:
        return quantize_rows_ref(x, dtype, rand_bits)
    x2, shape = _rows(x)
    R, N = x2.shape
    r_blk = min(r_blk, max(R, 1))
    x2, Rp, Np = _pad2(x2, r_blk)
    grid = (Rp // r_blk,)
    row_spec = pl.BlockSpec((r_blk, Np), lambda rb: (rb, 0))
    bits = None
    if rand_bits is not None:
        bits, _, _ = _pad2(rand_bits.reshape(R, N), r_blk)
    if dtype == "bf16":
        out_shape = jax.ShapeDtypeStruct((Rp, Np), jnp.bfloat16)
        if bits is None:
            v = pl.pallas_call(_pack_bf16_det_kernel, grid=grid,
                               in_specs=[row_spec], out_specs=row_spec,
                               out_shape=out_shape, interpret=interpret)(x2)
        else:
            v = pl.pallas_call(_pack_bf16_kernel, grid=grid,
                               in_specs=[row_spec, row_spec],
                               out_specs=row_spec, out_shape=out_shape,
                               interpret=interpret)(x2, bits)
        return (v[:R, :N].reshape(shape),)
    if dtype == "int8":
        out_shapes = (jax.ShapeDtypeStruct((Rp, Np), jnp.int8),
                      jax.ShapeDtypeStruct((Rp, 1), jnp.float32))
        out_specs = (row_spec, pl.BlockSpec((r_blk, 1), lambda rb: (rb, 0)))
        if bits is None:
            v, s = pl.pallas_call(_pack_int8_det_kernel, grid=grid,
                                  in_specs=[row_spec], out_specs=out_specs,
                                  out_shape=out_shapes,
                                  interpret=interpret)(x2)
        else:
            v, s = pl.pallas_call(_pack_int8_kernel, grid=grid,
                                  in_specs=[row_spec, row_spec],
                                  out_specs=out_specs, out_shape=out_shapes,
                                  interpret=interpret)(x2, bits)
        return v[:R, :N].reshape(shape), s[:R, 0]
    raise ValueError(f"quantize dtype {dtype!r} not in ('bf16', 'int8')")


def dequantize_rows(parts, dtype: str, *, use_pallas: bool = False,
                    interpret: bool = True, r_blk: int = ROW_BLK):
    """Unpack wire parts back to f32 rows (inverse of ``quantize_rows``)."""
    if not use_pallas:
        return dequantize_rows_ref(parts, dtype)
    v = parts[0]
    v2, shape = _rows(v)
    R, N = v2.shape
    r_blk = min(r_blk, max(R, 1))
    v2, Rp, Np = _pad2(v2, r_blk)
    grid = (Rp // r_blk,)
    row_spec = pl.BlockSpec((r_blk, Np), lambda rb: (rb, 0))
    out_shape = jax.ShapeDtypeStruct((Rp, Np), jnp.float32)
    if dtype == "bf16":
        out = pl.pallas_call(_unpack_bf16_kernel, grid=grid,
                             in_specs=[row_spec], out_specs=row_spec,
                             out_shape=out_shape, interpret=interpret)(v2)
    elif dtype == "int8":
        s = jnp.pad(parts[1].reshape(R, 1), ((0, Rp - R), (0, 0)))
        out = pl.pallas_call(
            _unpack_int8_kernel, grid=grid,
            in_specs=[row_spec, pl.BlockSpec((r_blk, 1), lambda rb: (rb, 0))],
            out_specs=row_spec, out_shape=out_shape,
            interpret=interpret)(v2, s)
    else:
        raise ValueError(f"dequantize dtype {dtype!r} not in "
                         "('bf16', 'int8')")
    return out[:R, :N].reshape(shape)
