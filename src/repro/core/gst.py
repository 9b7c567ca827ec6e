"""GST train/eval/finetune step builders — the paper's Algorithm 1 & 2.

Generic over the backbone: ``encode_fn(backbone_params, seg_inputs_flat)``
maps a flat batch of segments (leading dim N) to embeddings (N, d_h) plus an
auxiliary loss (e.g. MoE load-balance).  The same builders therefore drive
the GNN track (padded-CSR segments) and all 10 assigned transformer
architectures (token-chunk segments) — DESIGN.md §3.

Variants (paper §5.1 "Methods"):
    full     — all segments require grad (Full Graph Training analogue)
    gst      — sampled segments with grad; rest recomputed under stop_grad
    gst_one  — only sampled segments, no aggregation of the rest
    gst_e    — historical embedding table for the rest
    gst_ef   — +E with head finetuning at the end (schedule, same step)
    gst_ed   — +E with Stale Embedding Dropout (Eq. 1)
    gst_efd  — the complete method
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import embedding_table as tbl
from repro.core import segment as seg
from repro.kernels import ops as kops
from repro.models.common import dense_init


# ---------------------------------------------------------------------------
# variants
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class GSTVariant:
    name: str
    use_table: bool          # E: stale embeddings come from the table
    recompute_stale: bool    # GST: stop-grad forward for non-sampled segments
    use_sed: bool            # D: Eq. 1 dropout/up-weighting
    sampled_only: bool       # GST-One: drop all non-sampled segments
    finetune_head: bool      # F: head finetuning phase at end of training


VARIANTS: Dict[str, GSTVariant] = {
    "full":    GSTVariant("full", False, False, False, False, False),
    "gst":     GSTVariant("gst", False, True, False, False, False),
    "gst_one": GSTVariant("gst_one", False, False, False, True, False),
    "gst_e":   GSTVariant("gst_e", True, False, False, False, False),
    "gst_ef":  GSTVariant("gst_ef", True, False, False, False, True),
    "gst_ed":  GSTVariant("gst_ed", True, False, True, False, False),
    "gst_efd": GSTVariant("gst_efd", True, False, True, False, True),
}


# ---------------------------------------------------------------------------
# heads and losses
# ---------------------------------------------------------------------------


def head_init(key, d_h: int, num_out: int, mode: str, dtype=jnp.float32):
    """mode 'mlp': 2-layer MLP graph head F'.  mode 'segment_sum': linear
    per-segment scalar head (part of F; F' = Σ, paper §5.3)."""
    k1, k2 = jax.random.split(key)
    if mode == "mlp":
        return {
            "w1": dense_init(k1, d_h, d_h, dtype),
            "b1": jnp.zeros((d_h,), dtype),
            "w2": dense_init(k2, d_h, num_out, dtype),
            "b2": jnp.zeros((num_out,), dtype),
        }
    return {"w": dense_init(k1, d_h, 1, dtype), "b": jnp.zeros((1,), dtype)}


def head_apply(p, h, mode: str):
    if mode == "mlp":
        z = jax.nn.relu(h @ p["w1"] + p["b1"])
        return z @ p["w2"] + p["b2"]
    return (h @ p["w"] + p["b"])[..., 0]


def ce_loss(logits, labels):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    acc = (jnp.argmax(logits, -1) == labels).astype(jnp.float32)
    return jnp.mean(nll), jnp.mean(acc)


def pairwise_hinge_loss(preds, labels):
    """PairwiseHinge within batch (paper Appendix B) + OPA metric."""
    dy = preds[:, None] - preds[None, :]
    gt = (labels[:, None] > labels[None, :]).astype(jnp.float32)
    loss = jnp.sum(gt * jnp.maximum(0.0, 1.0 - dy)) / jnp.maximum(jnp.sum(gt), 1.0)
    opa = jnp.sum(gt * (dy > 0)) / jnp.maximum(jnp.sum(gt), 1.0)
    return loss, opa


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _gathers_itself(seg_inputs) -> bool:
    """Whether ``seg_inputs`` gathers its own segments (``take`` and
    ``whole``), as the feeder's handle on a dataset kept on the device
    does (graphs/batching.py::ResidentSegments)."""
    return hasattr(seg_inputs, "take") and hasattr(seg_inputs, "whole")


def gather_segments(seg_inputs, idx):
    """Pytree (B, J, ...) gathered at idx (B, S) -> (B, S, ...); an input
    that gathers its own segments is asked for these alone."""
    if _gathers_itself(seg_inputs):
        return seg_inputs.take(idx)

    def g(x):
        expand = idx.reshape(idx.shape + (1,) * (x.ndim - 2))
        return jnp.take_along_axis(x, expand.astype(jnp.int32), axis=1)
    return jax.tree_util.tree_map(g, seg_inputs)


def _flatten_bs(tree):
    return jax.tree_util.tree_map(lambda x: x.reshape((-1,) + x.shape[2:]), tree)


def _all_segments(seg_inputs):
    """Every segment of the batch, flattened to (B*J, ...)."""
    if _gathers_itself(seg_inputs):
        seg_inputs = seg_inputs.whole()
    return _flatten_bs(seg_inputs)


class GSTBatch(NamedTuple):
    """One batch of segmented inputs.

    seg_inputs: pytree, leaves (B, J_max, ...) — per-segment model inputs;
                or an input that gathers its own segments, ``take(idx)``
                (B, S, ...) and ``whole()`` (B, J_max, ...), as the
                feeder's handle on a dataset kept on the device does.
    seg_valid:  (B, J_max) 1/0.
    graph_ids:  (B,) int32 row in the historical table.
    labels:     (B,) int32 (ce) or float32 (ranking).
    batch_pos:  optional (B,) int32 — each row's position in the GLOBAL
                batch.  When set, segment sampling / SED draws use one key
                per row (seg.per_row_keys) so a data-parallel shard of the
                batch sees the same stream as the whole batch on one device.
    """
    seg_inputs: Any
    seg_valid: jnp.ndarray
    graph_ids: jnp.ndarray
    labels: jnp.ndarray
    batch_pos: Optional[jnp.ndarray] = None


class TrainState(NamedTuple):
    backbone: Any
    head: Any
    opt_state: Any
    table: tbl.EmbeddingTable
    step: jnp.ndarray


def _fused_sed_pool(h, seg_valid, fresh_mask, drop_mask, stale_valid, *,
                    keep_prob: float, num_sampled: int, agg: str,
                    ages=None, decay: float = 0.0):
    """Eq. 1 η-weighting + ⊕ pooling in ONE fused kernel pass (sed_pool).

    Uninitialized stale slots are folded into the drop mask (η = 0), which is
    exactly what the reference path's ``eta * where(fresh, 1, stale_valid)``
    correction does.  ``ages``/``decay`` thread the optional staleness decay
    into the kernel's stale branch (ref.sed_eta); λ=0 keeps the historical
    4-operand dispatch bit-exact.
    """
    drop_arg = 1.0 - (1.0 - drop_mask) * stale_valid.astype(jnp.float32)
    return kops.sed_aggregate(
        h, seg_valid.astype(jnp.float32), fresh_mask.astype(jnp.float32),
        drop_arg, ages, keep_prob=keep_prob, num_sampled=num_sampled, agg=agg,
        decay=decay, use_pallas=True)


def _fused_plain_pool(h, seg_valid, *, agg: str):
    """η = 1 pooling through the same fused kernel (eval / finetune path):
    with keep_prob = 1 every Eq.-1 weight collapses to the validity mask."""
    valid = seg_valid.astype(jnp.float32)
    return kops.sed_aggregate(h, valid, valid, jnp.zeros_like(valid),
                              keep_prob=1.0, num_sampled=1, agg=agg,
                              use_pallas=True)


def _scalar_head_preds(scal, seg_valid, eta, agg: str, pool=None):
    """Pool (B, J) per-segment scalar predictions into (B,) graph preds.

    pool: optional fused (B, J, 1) -> (B, 1) kernel pooling (already carrying
    its η weighting); None = the reference η-weighted sum.  Shared by the
    train / eval / finetune steps so the two paths can't drift per-step.
    """
    if pool is not None:
        return pool(scal[..., None])[..., 0]
    denom = (jnp.maximum(jnp.sum(seg_valid, -1), 1.0)
             if agg == "mean" else 1.0)
    return jnp.sum(scal * eta, axis=-1) / denom


# ---------------------------------------------------------------------------
# step builders
# ---------------------------------------------------------------------------


def make_train_step(
    encode_fn: Callable,
    optimizer,
    variant: GSTVariant,
    *,
    num_sampled: int = 1,
    keep_prob: float = 0.5,
    head_mode: str = "mlp",
    loss_kind: str = "ce",
    agg: str = "mean",
    aux_weight: float = 1e-2,
    use_pallas: bool = False,
    table_lookup: Optional[Callable] = None,
    table_update: Optional[Callable] = None,
    table_lookup_age: Optional[Callable] = None,
    sed_decay: float = 0.0,
    axis_name: Optional[str] = None,
):
    """Returns ``step(state, batch, rng) -> (state, metrics)`` implementing
    Algorithm 1 (gst*) / Algorithm 2 lines 1-10 (e-variants).

    use_pallas: for the SED variants (gst_ed / gst_efd) the Eq.-1 η-weighting
    and the ⊕ pooling run as ONE fused sed_pool kernel pass over the
    (B, J, d) tensor instead of the multi-HBM-pass jnp composition.  The jnp
    path stays the oracle (parity asserted in tests/test_fused_path.py).

    table_lookup / table_update: alternative historical-table accessors with
    the signatures of ``tbl.lookup`` / ``tbl.update_sampled``.  dist/train.py
    injects the ring-exchange ops of dist/table.py here so the SAME step
    body runs per shard with a row-sharded table.  These are the store
    layer's device-access points: ``state.table`` is whatever device tier
    the driver's EmbeddingStore (store/) provides, and ``batch.graph_ids``
    are that store's device-row ids — a TieredStore renames rows host-side
    (store.prepare) so nothing inside the jitted step knows the table is
    capped.

    sed_decay / table_lookup_age: λ of the staleness decay exp(-λ·age)
    folded into the stale branch of Eq. 1 (--sed-age-weighting).  λ=0 (the
    default) traces the exact historical step — no age lookup, no extra
    operand, bit-exact.  ``table_lookup_age(table, graph_ids) -> (B, J)``
    reads the per-segment last-refresh step (dist/train.py injects the
    exchange's ``lookup_ages``); the default reads ``table.age`` directly.

    axis_name: when set the step body is assumed to run inside shard_map /
    pmap over that axis — gradients, loss and metrics are pmean'd across it
    before the (replicated) optimizer update.
    """
    S = num_sampled
    loss_pair = ce_loss if loss_kind == "ce" else pairwise_hinge_loss
    fused_sed = use_pallas and variant.use_sed and not variant.sampled_only
    t_lookup = table_lookup or tbl.lookup
    t_update = table_update or tbl.update_sampled
    use_age = variant.use_sed and variant.use_table and sed_decay > 0.0
    t_age = table_lookup_age or (lambda table, ids: table.age[ids])

    def step(state: TrainState, batch: GSTBatch, rng):
        B, J = batch.seg_valid.shape
        r_sample, r_sed = jax.random.split(jax.random.fold_in(rng, state.step))
        if batch.batch_pos is None:
            idx = seg.sample_segments(r_sample, batch.seg_valid, S)   # (B, S)
        else:
            idx = seg.sample_segments_rowwise(
                seg.per_row_keys(r_sample, batch.batch_pos),
                batch.seg_valid, S)
        fresh_mask = seg.sampled_mask(idx, J) * batch.seg_valid       # (B, J)
        sampled_inputs = _flatten_bs(gather_segments(batch.seg_inputs, idx))

        # ---- stale embeddings (no grad) ---------------------------------
        age_steps = None
        if variant.use_table:
            h_stale, initialized = t_lookup(state.table, batch.graph_ids)
            stale_valid = batch.seg_valid * initialized.astype(batch.seg_valid.dtype)
            if use_age:
                age_steps = jnp.maximum(
                    state.step - t_age(state.table, batch.graph_ids),
                    0).astype(jnp.float32)
        elif variant.recompute_stale:
            h_all, _ = encode_fn(state.backbone, _all_segments(batch.seg_inputs))
            h_stale = jax.lax.stop_gradient(h_all.reshape(B, J, -1))
            stale_valid = batch.seg_valid
        else:  # full / gst_one: no stale path
            h_stale = None
            stale_valid = jnp.zeros_like(batch.seg_valid)

        # ---- SED / η weights (Eq. 1) ------------------------------------
        drop_mask = None
        if variant.use_sed:
            if batch.batch_pos is None:
                eta, drop_mask = seg.sed_weights(r_sed, batch.seg_valid,
                                                 fresh_mask, keep_prob, S)
            else:
                eta, drop_mask = seg.sed_weights_rowwise(
                    seg.per_row_keys(r_sed, batch.batch_pos),
                    batch.seg_valid, fresh_mask, keep_prob, S)
            eta = eta * jnp.where(
                fresh_mask > 0, 1.0,
                stale_valid.astype(jnp.float32))  # uninitialized stale -> 0
            if age_steps is not None:
                # staleness decay on the stale branch only — fresh segments
                # have age 0 by definition (ref.sed_eta's aged formula)
                eta = eta * jnp.where(fresh_mask > 0, 1.0,
                                      jnp.exp(-sed_decay * age_steps))
        elif variant.sampled_only:
            eta = fresh_mask
        elif variant.name == "full":
            eta = batch.seg_valid.astype(jnp.float32)
        else:
            eta = (fresh_mask + (1.0 - fresh_mask) * stale_valid).astype(jnp.float32)

        def loss_fn(trainable):
            backbone, head = trainable
            if variant.name == "full":
                h_flat, aux = encode_fn(backbone, _all_segments(batch.seg_inputs))
                h_comb = h_flat.reshape(B, J, -1)
            else:
                h_s_flat, aux = encode_fn(backbone, sampled_inputs)
                h_s = h_s_flat.reshape(B, S, -1)
                if h_stale is None:
                    base = jnp.zeros((B, J, h_s.shape[-1]), h_s.dtype)
                else:
                    base = h_stale.astype(h_s.dtype)
                # scatter fresh embeddings over the stale base
                b_idx = jnp.arange(B)[:, None]
                h_comb = base.at[b_idx, idx].set(h_s)

            if head_mode == "segment_sum":
                # per-segment scalar predictions; F' = Σ (paper §5.3)
                scal = head_apply(head, h_comb, "segment_sum")        # (B, J)
                pool = (lambda x: _fused_sed_pool(
                    x, batch.seg_valid, fresh_mask, drop_mask, stale_valid,
                    keep_prob=keep_prob, num_sampled=S, agg=agg,
                    ages=age_steps, decay=sed_decay)
                ) if fused_sed else None
                preds = _scalar_head_preds(scal, batch.seg_valid, eta, agg,
                                           pool)
                loss, metric = loss_pair(preds, batch.labels)
            else:
                if variant.sampled_only:
                    # GST-One: mean over the sampled segments only
                    h_graph = jnp.sum(
                        h_comb * fresh_mask[..., None].astype(h_comb.dtype), 1) / S
                elif fused_sed:
                    h_graph = _fused_sed_pool(
                        h_comb, batch.seg_valid, fresh_mask, drop_mask,
                        stale_valid, keep_prob=keep_prob, num_sampled=S,
                        agg=agg, ages=age_steps, decay=sed_decay)
                else:
                    h_graph = seg.aggregate(h_comb, eta, batch.seg_valid, agg)
                out = head_apply(head, h_graph, "mlp")
                if loss_kind == "ce":
                    loss, metric = loss_pair(out, batch.labels)
                else:
                    loss, metric = loss_pair(out[..., 0] if out.ndim > 1 else out,
                                             batch.labels)
            return loss + aux_weight * aux, (metric, h_comb)

        (loss, (metric, h_comb)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)((state.backbone, state.head))
        if axis_name is not None:
            # data-parallel: per-shard means -> global means (params and
            # opt_state stay replicated because every shard applies the
            # identical pmean'd update)
            grads = jax.lax.pmean(grads, axis_name)
            loss = jax.lax.pmean(loss, axis_name)
            metric = jax.lax.pmean(metric, axis_name)
        (new_backbone, new_head), new_opt, opt_metrics = optimizer.update(
            (state.backbone, state.head), grads, state.opt_state)

        new_table = state.table
        if variant.use_table:
            h_s_new = jax.lax.stop_gradient(
                jnp.take_along_axis(h_comb, idx[..., None], axis=1))  # (B,S,d)
            new_table = t_update(
                state.table, batch.graph_ids, idx, h_s_new, state.step)

        new_state = TrainState(new_backbone, new_head, new_opt, new_table,
                               state.step + 1)
        metrics = {"loss": loss, "metric": metric, **opt_metrics}
        return new_state, metrics

    return step


def make_eval_step(encode_fn: Callable, *, head_mode: str = "mlp",
                   loss_kind: str = "ce", agg: str = "mean",
                   use_pallas: bool = False,
                   axis_name: Optional[str] = None):
    """Test-time: every segment fresh (paper's P(⊕ h_j, y) distribution)."""
    loss_pair = ce_loss if loss_kind == "ce" else pairwise_hinge_loss

    def step(state: TrainState, batch: GSTBatch):
        B, J = batch.seg_valid.shape
        h_flat, _ = encode_fn(state.backbone, _all_segments(batch.seg_inputs))
        h_all = h_flat.reshape(B, J, -1)
        eta = batch.seg_valid.astype(jnp.float32)
        if head_mode == "segment_sum":
            scal = head_apply(state.head, h_all, "segment_sum")
            pool = (lambda x: _fused_plain_pool(x, batch.seg_valid, agg=agg)
                    ) if use_pallas else None
            preds = _scalar_head_preds(scal, batch.seg_valid, eta, agg, pool)
            loss, metric = loss_pair(preds, batch.labels)
        else:
            if use_pallas:
                h_graph = _fused_plain_pool(h_all, batch.seg_valid, agg=agg)
            else:
                h_graph = seg.aggregate(h_all, eta, batch.seg_valid, agg)
            out = head_apply(state.head, h_graph, "mlp")
            if loss_kind == "ce":
                loss, metric = loss_pair(out, batch.labels)
            else:
                loss, metric = loss_pair(out[..., 0] if out.ndim > 1 else out,
                                         batch.labels)
        if axis_name is not None:
            loss = jax.lax.pmean(loss, axis_name)
            metric = jax.lax.pmean(metric, axis_name)
        return {"loss": loss, "metric": metric}

    return step


def make_refresh_step(encode_fn: Callable,
                      table_update_all: Optional[Callable] = None):
    """Algorithm 2 line 12: refresh T with the final backbone.

    table_update_all: alternative writer with the ``tbl.update_all``
    signature (dist/train.py injects the ring-exchange writer)."""
    t_update_all = table_update_all or tbl.update_all

    def step(state: TrainState, batch: GSTBatch):
        B, J = batch.seg_valid.shape
        h_flat, _ = encode_fn(state.backbone, _all_segments(batch.seg_inputs))
        h_all = h_flat.reshape(B, J, -1)
        table = t_update_all(state.table, batch.graph_ids, h_all,
                             batch.seg_valid, state.step)
        return state._replace(table=table)

    return step


def make_finetune_step(optimizer, *, head_mode: str = "mlp",
                       loss_kind: str = "ce", agg: str = "mean",
                       use_pallas: bool = False,
                       table_lookup: Optional[Callable] = None,
                       axis_name: Optional[str] = None):
    """Algorithm 2 lines 13-18: train F' only, inputs from the (fresh) table.

    Supports both heads: the MLP graph head F' (pool then predict) and the
    per-segment scalar head of the TpuGraphs track (predict then Σ / mean),
    so gst_ef / gst_efd no longer silently skip the finetuning phase on the
    segment_sum track.
    """
    loss_pair = ce_loss if loss_kind == "ce" else pairwise_hinge_loss
    t_lookup = table_lookup or tbl.lookup

    def step(state: TrainState, batch: GSTBatch):
        h_all, _ = t_lookup(state.table, batch.graph_ids)
        h_all = h_all.astype(jnp.float32)
        eta = batch.seg_valid.astype(jnp.float32)
        if head_mode != "segment_sum":
            if use_pallas:
                h_graph = _fused_plain_pool(h_all, batch.seg_valid, agg=agg)
            else:
                h_graph = seg.aggregate(h_all, eta, batch.seg_valid, agg)

        def loss_fn(head):
            if head_mode == "segment_sum":
                scal = head_apply(head, h_all, "segment_sum")      # (B, J)
                pool = (lambda x: _fused_plain_pool(x, batch.seg_valid,
                                                    agg=agg)
                        ) if use_pallas else None
                preds = _scalar_head_preds(scal, batch.seg_valid, eta, agg,
                                           pool)
                return loss_pair(preds, batch.labels)
            out = head_apply(head, h_graph, "mlp")
            if loss_kind == "ce":
                return loss_pair(out, batch.labels)
            return loss_pair(out[..., 0] if out.ndim > 1 else out, batch.labels)

        (loss, metric), grads = jax.value_and_grad(loss_fn, has_aux=True)(state.head)
        if axis_name is not None:
            grads = jax.lax.pmean(grads, axis_name)
            loss = jax.lax.pmean(loss, axis_name)
            metric = jax.lax.pmean(metric, axis_name)
        new_head, new_opt, _ = optimizer.update(state.head, grads, state.opt_state)
        return state._replace(head=new_head, opt_state=new_opt,
                              step=state.step + 1), {"loss": loss, "metric": metric}

    return step
