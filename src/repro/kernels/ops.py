"""Jit'd public wrappers over the Pallas kernels.

``interpret`` defaults to True on CPU (this container) and False on TPU —
the kernels are written for the TPU target and validated in interpret mode
against the pure-jnp oracles in ref.py.
"""
from __future__ import annotations

from functools import partial
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend import core as jcore

from repro.kernels import ref
from repro.kernels.quant import dequantize_rows as _dequantize_rows
from repro.kernels.quant import quantize_rows as _quantize_rows
from repro.kernels.sed_pool import sed_pool as _sed_pool
from repro.kernels.segment_spmm import segment_spmm as _segment_spmm
from repro.kernels.segment_spmm import segment_spmm_batched as _segment_spmm_batched
from repro.kernels.swa_attention import swa_attention as _swa_attention


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# shape-padding helpers (shared by serve/cache.py, dist/table.py, store/)
#
# Scatter/gather row sets vary per batch; padding their length to the next
# power of two keeps the jitted-shape set O(log capacity) instead of one
# compile per distinct row count.  Padding repeats the LAST entry, so a
# padded scatter writes the same (row, value) pair twice — a deterministic
# no-op — and a padded gather reads rows the caller then ignores.
# ---------------------------------------------------------------------------


def next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


def prev_pow2(n: int) -> int:
    """Largest power of two <= n (n >= 1) — chunking a pow2-padded row set
    by a non-pow2 capacity without minting new jitted shapes."""
    return 1 << (n.bit_length() - 1)


def pad_rows_pow2(rows: Sequence[int], *alongside: Sequence,
                  ) -> Tuple[np.ndarray, ...]:
    """Pad ``rows`` (and any parallel index lists) to the next power of two
    by repeating the last entry.  Returns int-typed numpy arrays ready for a
    padded scatter/gather; ``rows`` must be non-empty."""
    n = next_pow2(len(rows))
    out = []
    for seq in (rows,) + alongside:
        seq = list(seq)
        out.append(np.asarray(seq + [seq[-1]] * (n - len(seq)), np.int32))
    return tuple(out)


def pad_leading(x, target: int):
    """Zero-pad the leading axis of ``x`` to ``target`` rows (no-op when
    already there) — the block-row padding shared by the sharded table and
    the tiered store's host tier."""
    n = x.shape[0]
    if n == target:
        return x
    if isinstance(x, np.ndarray):
        pad = np.zeros((target - n,) + x.shape[1:], x.dtype)
        return np.concatenate([x, pad], axis=0)
    return jnp.concatenate(
        [x, jnp.zeros((target - n,) + x.shape[1:], x.dtype)], axis=0)


@partial(jax.jit, static_argnames=("use_pallas",))
def batched_neighbor_sum(h, src, dst, w, *, use_pallas: bool = True):
    """Batched weighted scatter-add over N segments in ONE kernel launch.

    h: (N, m, d); src/dst/w: (N, e).  The GNN hot path: every message-passing
    layer of graphs/gnn.py::_encode_batched makes exactly one call here,
    and this wrapper owns the interpret-on-CPU decision.
    """
    if use_pallas:
        return _segment_spmm_batched(h, src, dst, w,
                                     interpret=_default_interpret())
    return ref.segment_spmm_batched_ref(h, src, dst, w)


def iter_jaxpr_eqns(jaxpr):
    """Depth-first iterator over every eqn of ``jaxpr``, recursing into
    EVERY Jaxpr-valued eqn param — pjit, scan/while bodies, custom-VJP
    wrappers AND ``shard_map``.  Shared by ``count_pallas_calls`` (kernel
    launch contracts) and ``dist/exchange.py::measured_exchange_bytes``
    (collective-traffic accounting against the analytic bytes models)."""
    def subjaxprs(params):
        for v in params.values():
            vs = v if isinstance(v, (tuple, list)) else (v,)
            for u in vs:
                if isinstance(u, jcore.ClosedJaxpr):
                    yield u.jaxpr
                elif isinstance(u, jcore.Jaxpr):
                    yield u

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for sub in subjaxprs(eqn.params):
                yield from walk(sub)

    yield from walk(jaxpr)


def count_pallas_calls(fn, *args, **kwargs) -> int:
    """Number of ``pallas_call`` eqns in fn's jaxpr (recursing into sub-jaxprs).

    The fused-path contract (one batched kernel launch per message-passing
    layer rather than one per vmapped segment) is asserted with this in
    tests/test_fused_path.py and recorded by benchmarks/bench_step.py.

    The recursion (iter_jaxpr_eqns) sees through pjit, scan/while bodies,
    custom-VJP wrappers AND ``shard_map`` — the dist/ subsystem uses that
    to assert its per-shard step launches exactly the same batched kernels
    as the single-device step
    (tests/test_dist.py::test_dist_step_kernel_launch_contract).
    """
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    return sum(1 for eqn in iter_jaxpr_eqns(closed.jaxpr)
               if eqn.primitive.name == "pallas_call")


def max_intermediate_bytes(fn, *args, **kwargs) -> int:
    """Size (bytes) of the largest intermediate buffer any eqn of fn's jaxpr
    produces (recursing into sub-jaxprs: scan/while bodies, pjit calls).

    The serving engine's constant-memory contract — a lax.scan over segment
    chunks allocates one chunk's activations regardless of how many chunks
    the graph has — is asserted with this in tests/test_serve.py: the max
    live buffer must not grow with the chunk count, while the one-shot
    encoder's grows linearly with the segment count.
    """
    def subjaxprs(params):
        for v in params.values():
            vs = v if isinstance(v, (tuple, list)) else (v,)
            for u in vs:
                if isinstance(u, jcore.ClosedJaxpr):
                    yield u.jaxpr
                elif isinstance(u, jcore.Jaxpr):
                    yield u

    def nbytes(aval) -> int:
        shape = getattr(aval, "shape", None)
        dtype = getattr(aval, "dtype", None)
        if shape is None or dtype is None:
            return 0
        return int(np.prod(shape, dtype=np.int64)) * dtype.itemsize

    def walk(jaxpr) -> int:
        m = 0
        for eqn in jaxpr.eqns:
            for v in eqn.outvars:
                m = max(m, nbytes(v.aval))
            for sub in subjaxprs(eqn.params):
                m = max(m, walk(sub))
        return m

    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    return walk(closed.jaxpr)


@partial(jax.jit, static_argnames=("num_nodes", "use_pallas"))
def neighbor_aggregate(h, src, dst, edge_valid, *, num_nodes: int,
                       use_pallas: bool = True):
    """Masked neighbor mean (GNN message aggregation).

    Returns (mean (m, d), deg (m,)).  Sum runs on the MXU via segment_spmm;
    degree is a cheap O(e) reduction kept in jnp.
    """
    if use_pallas:
        s = _segment_spmm(h, src, dst, edge_valid, interpret=_default_interpret())
    else:
        s = ref.segment_spmm_ref(h, src, dst, edge_valid, num_nodes)
    deg = jax.ops.segment_sum(edge_valid, dst, num_segments=num_nodes)
    return s / jnp.maximum(deg, 1.0)[:, None], deg


@partial(jax.jit, static_argnames=("keep_prob", "num_sampled", "agg",
                                   "decay", "use_pallas"))
def sed_aggregate(h, seg_valid, fresh_mask, drop_mask, ages=None, *,
                  keep_prob: float, num_sampled: int, agg: str = "mean",
                  decay: float = 0.0, use_pallas: bool = True):
    """Fused Eq.-1 η-weighting + ⊕ pooling over segments.

    ``ages``/``decay``: optional (B, J) age-in-steps + λ for the
    staleness-decayed stale branch (ref.sed_eta); λ=0 keeps the exact
    historical 4-operand dispatch."""
    if use_pallas:
        return _sed_pool(h, seg_valid, fresh_mask, drop_mask,
                         keep_prob=keep_prob, num_sampled=num_sampled, agg=agg,
                         ages=ages, decay=decay,
                         interpret=_default_interpret())
    return ref.sed_pool_ref(h, seg_valid, fresh_mask, drop_mask, keep_prob,
                            num_sampled, agg, ages, decay)


@partial(jax.jit, static_argnames=("dtype", "use_pallas"))
def quantize_payload(x, rand_bits=None, *, dtype: str,
                     use_pallas: bool = True):
    """Pack f32 rows into the compressed exchange wire format (bf16, or
    int8 + per-leading-row f32 scale).  ``rand_bits`` (uint32, x.shape)
    turns on stochastic rounding — the write path; None rounds to nearest
    (the read path, deterministic).  Returns the wire-parts tuple."""
    return _quantize_rows(x, dtype, rand_bits, use_pallas=use_pallas,
                          interpret=_default_interpret())


@partial(jax.jit, static_argnames=("dtype", "use_pallas"))
def dequantize_payload(parts, *, dtype: str, use_pallas: bool = True):
    """Unpack compressed wire parts back to f32 rows."""
    return _dequantize_rows(tuple(parts), dtype, use_pallas=use_pallas,
                            interpret=_default_interpret())


@partial(jax.jit, static_argnames=("window", "use_pallas"))
def sliding_window_attention(q, k, v, *, window: int, use_pallas: bool = True):
    """Causal sliding-window flash attention (sub-quadratic prefill)."""
    if use_pallas:
        return _swa_attention(q, k, v, window=window,
                              interpret=_default_interpret())
    return ref.swa_attention_ref(q, k, v, window)
