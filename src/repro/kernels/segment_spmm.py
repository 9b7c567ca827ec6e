"""Pallas TPU kernel: padded-edge-list neighbor aggregation (GNN hot spot).

GPU GNN frameworks implement scatter-add with atomics.  TPU adaptation
(DESIGN.md §4.3): express gather AND scatter as **one-hot matmuls** so the
whole message-passing reduction runs on the MXU with no dynamic memory:

    G[e, n] = 1{src_e = n}            (gather matrix,  built via iota compare)
    S[e, n] = 1{dst_e = n}            (scatter matrix)
    out     = Sᵀ @ (diag(w) @ (G @ h))

The kernel is **batched**: all ``N = B·S`` padded segments of a GST batch run
in ONE ``pallas_call`` with a 3D grid ``(segment, feature block, edge block)``
— the per-segment edge windows are selected purely through BlockSpec index
maps on the padded ``(N, e)`` edge arrays, so there is a single kernel launch
per message-passing layer instead of one per vmapped segment.  The edge-block
axis is the reduction and sits innermost so consecutive grid steps revisit
the same output block (the TPU-sequential accumulation contract); the segment
and feature axes are embarrassingly parallel.

The node dimension m (= the paper's bounded segment size m_GST) stays
resident in VMEM — this is exactly why GST bounds the segment size: the
working set (m × d_blk block of h and out plus an e_blk × m one-hot tile)
fits VMEM for m ≤ 1024 at d_blk = 128.

Reverse-mode AD: ``pallas_call`` has no transpose rule, but the SpMM
transpose is itself an SpMM with src/dst swapped —

    out[n, v] = Σ_{e: dst_e = v} w_e · h[n, src_e]
    ∂L/∂h[n, u] = Σ_{e: src_e = u} w_e · g[n, dst_e]

so the backward pass is one more batched kernel launch (custom_vjp below).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


DEFAULT_E_BLK = 256
DEFAULT_D_BLK = 128
# Segments per grid step.  The per-segment compute (two e_blk×m×d_blk dots)
# is small, so several segments share one grid step to amortize the per-step
# block-shuffling overhead (dominant in interpret mode on CPU; on TPU it
# lengthens the inner unrolled loop while keeping the VMEM working set
# n_blk·m·d_blk·2 — fine for m ≤ 1024 at the defaults).
DEFAULT_N_BLK = 8


def _spmm_batched_kernel(src_ref, dst_ref, w_ref, h_ref, out_ref, *,
                         m: int, n_blk: int):
    eb = pl.program_id(2)                  # edge-block = innermost (reduction)
    e_blk = src_ref.shape[2]
    node_ids = jax.lax.broadcasted_iota(jnp.int32, (e_blk, m), 1)
    for i in range(n_blk):                 # static unroll over the seg block
        src = src_ref[i, 0, :]             # (e_blk,)
        dst = dst_ref[i, 0, :]
        w = w_ref[i, 0, :]                 # (e_blk,) float, 0 on padding
        h = h_ref[i]                       # (m, d_blk)
        gather = (src[:, None] == node_ids).astype(h.dtype)     # (e_blk, m)
        scatter = (dst[:, None] == node_ids).astype(h.dtype)    # (e_blk, m)
        msgs = jnp.dot(gather, h, preferred_element_type=jnp.float32)
        msgs = msgs * w[:, None].astype(jnp.float32)
        contrib = jnp.dot(scatter.T, msgs.astype(h.dtype),
                          preferred_element_type=jnp.float32)   # (m, d_blk)

        @pl.when(eb == 0)
        def _init(i=i, contrib=contrib):
            out_ref[i] = contrib.astype(out_ref.dtype)

        @pl.when(eb != 0)
        def _acc(i=i, contrib=contrib):
            out_ref[i] = out_ref[i] + contrib.astype(out_ref.dtype)


def _spmm_batched_raw(h, src, dst, w, e_blk: int, d_blk: int, n_blk,
                      interpret: bool):
    N, m, d = h.shape
    e = src.shape[1]
    e_blk = min(e_blk, e)
    d_blk = min(d_blk, d)
    if n_blk is None:
        if interpret:
            # interpret mode pays per-grid-step overhead, not VMEM: use big
            # segment blocks (capped — the kernel body unrolls n_blk times,
            # so unbounded blocks explode trace/compile time)
            n_blk = min(N, 32)
        else:
            # keep the n_blk·(h + out) working set within a VMEM budget
            budget = 2 * 1024 * 1024
            n_blk = max(1, min(DEFAULT_N_BLK, budget // (m * d_blk * 4 * 2)))
    n_blk = min(n_blk, N)
    # pad edge dim to a multiple of e_blk (w = 0 ⇒ no contribution)
    pad_e = (-e) % e_blk
    if pad_e:
        src = jnp.pad(src, ((0, 0), (0, pad_e)))
        dst = jnp.pad(dst, ((0, 0), (0, pad_e)))
        w = jnp.pad(w, ((0, 0), (0, pad_e)))
    pad_d = (-d) % d_blk
    if pad_d:
        h = jnp.pad(h, ((0, 0), (0, 0), (0, pad_d)))
    # pad segment dim to a multiple of n_blk (all-zero w ⇒ zero rows)
    pad_n = (-N) % n_blk
    if pad_n:
        h = jnp.pad(h, ((0, pad_n), (0, 0), (0, 0)))
        src = jnp.pad(src, ((0, pad_n), (0, 0)))
        dst = jnp.pad(dst, ((0, pad_n), (0, 0)))
        w = jnp.pad(w, ((0, pad_n), (0, 0)))
    grid = ((N + pad_n) // n_blk, (d + pad_d) // d_blk, (e + pad_e) // e_blk)
    # the edge arrays get a unit middle axis so their block's last two dims,
    # (1, e_blk), meet the TPU tiling rule (divisible by (8, 128) or equal
    # to the array's) for every n_blk the VMEM budget picks
    src, dst, w = (a[:, None, :] for a in (src, dst, w))
    edge_spec = pl.BlockSpec((n_blk, 1, e_blk), lambda n, db, eb: (n, 0, eb))
    out = pl.pallas_call(
        functools.partial(_spmm_batched_kernel, m=m, n_blk=n_blk),
        grid=grid,
        in_specs=[
            edge_spec, edge_spec, edge_spec,
            pl.BlockSpec((n_blk, m, d_blk), lambda n, db, eb: (n, 0, db)),
        ],
        out_specs=pl.BlockSpec((n_blk, m, d_blk), lambda n, db, eb: (n, 0, db)),
        out_shape=jax.ShapeDtypeStruct((N + pad_n, m, d + pad_d), jnp.float32),
        interpret=interpret,
    )(src, dst, w, h)
    return out[:N, :, :d].astype(h.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _spmm_batched(h, src, dst, w, e_blk, d_blk, n_blk, interpret):
    return _spmm_batched_raw(h, src, dst, w, e_blk, d_blk, n_blk, interpret)


def _spmm_fwd(h, src, dst, w, e_blk, d_blk, n_blk, interpret):
    out = _spmm_batched_raw(h, src, dst, w, e_blk, d_blk, n_blk, interpret)
    return out, (h, src, dst, w)


def _spmm_bwd(e_blk, d_blk, n_blk, interpret, res, g):
    h, src, dst, w = res
    g = g.astype(h.dtype)
    # transpose of the weighted scatter-add: swap src/dst roles
    dh = _spmm_batched_raw(g, dst, src, w, e_blk, d_blk, n_blk, interpret)
    dh = dh.astype(h.dtype)
    g_dst = jnp.take_along_axis(g, dst[..., None].astype(jnp.int32), axis=1)
    h_src = jnp.take_along_axis(h, src[..., None].astype(jnp.int32), axis=1)
    dw = jnp.sum(g_dst.astype(jnp.float32) * h_src.astype(jnp.float32),
                 axis=-1).astype(w.dtype)
    return dh, None, None, dw


_spmm_batched.defvjp(_spmm_fwd, _spmm_bwd)


def segment_spmm_batched(h, src, dst, w, *, e_blk: int = DEFAULT_E_BLK,
                         d_blk: int = DEFAULT_D_BLK,
                         n_blk=None, interpret: bool = False):
    """Batched weighted neighbor scatter-add over N padded segments.

    out[n, v] = Σ_{e: dst[n,e]=v} w[n,e] · h[n, src[n,e]].

    h: (N, m, d); src/dst: (N, e) int32; w: (N, e) float, 0 on padding.
    One ``pallas_call`` for the whole batch; differentiable wrt h and w.
    n_blk=None picks automatically: the whole batch per grid step in
    interpret mode, a VMEM-budgeted block (≤ DEFAULT_N_BLK) when compiled.
    """
    return _spmm_batched(h, src, dst, w, e_blk, d_blk, n_blk, interpret)


def segment_spmm(h, src, dst, w, *, e_blk: int = DEFAULT_E_BLK,
                 d_blk: int = DEFAULT_D_BLK, interpret: bool = False):
    """out[v] = Σ_{e: dst_e=v} w_e · h[src_e].   h: (m, d); src/dst/w: (e,).

    Single-segment convenience wrapper over the batched kernel (N = 1).
    """
    return _spmm_batched(h[None], src[None], dst[None], w[None],
                         e_blk, d_blk, 1, interpret)[0]
