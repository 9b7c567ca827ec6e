"""Pluggable table-exchange strategies for the row-sharded historical table.

The distributed GST lookups/write-backs (dist/table.py geometry: device k
owns rows [k·R, (k+1)·R)) used to be hard-wired to a ring of
``jax.lax.ppermute`` hops.  The ring's per-device traffic is
``D · B_local · row_bytes`` per exchange — every payload buffer visits
every shard — which stops winning as the shard count grows (ROADMAP's
ring-vs-all-to-all crossover).  This module makes the exchange a STRATEGY
behind one ``Exchange`` API, everything still running INSIDE ``shard_map``
on global row ids against the local (R, J, d) table shard:

  ``ring``      the original D-hop ppermute loop: the (ids, payload)
                buffers ride the ring, every shard answers/applies the
                rows it owns as the buffer passes through.  D hops for
                lookups (answers must come home), D-1 for writes.

  ``alltoall``  one-shot dissemination of the FULL local buffer: queries
                all_gather to every shard, each shard answers everything
                it owns, and one ``jax.lax.all_to_all`` brings the dense
                (D, B_local) answer block home (the requester selects its
                owner's answer — pure row selection, no reductions).
                Saves the ring's per-hop latency (2 collectives instead
                of D) and one payload hop, but still moves the dense
                answer block: ~(D-1)·B_local·row_bytes.

  ``bucketed``  owner-direct: queries are sorted by owner shard
                (device-side stable sort; the CAPACITY of the per-owner
                buckets is planned host-side — see ``plan_capacity``) and
                each row travels exactly one hop to its owner and one hop
                back, as two ``all_to_all`` s of (D, cap) buckets.  With a
                near-uniform owner distribution cap ≈ B_local/D and the
                traffic drops to ~2·B_local·row_bytes per device,
                independent of the shard count — the high-shard-count
                winner.

Every strategy ships an ANALYTIC per-device bytes-per-exchange model
(``lookup_bytes`` / ``update_sampled_bytes`` / ``update_all_bytes`` /
``train_step_bytes``) whose conventions match ``measured_exchange_bytes``,
which counts the actual collective traffic in a jaxpr — the parity of the
two is asserted per strategy in tests/test_exchange_props.py, and
``select_exchange`` ("--exchange=auto") picks the min-bytes strategy at
the current shard count.  The choice is by bytes alone: no chip has yet
timed the strategies against each other.

Bit-exactness contract (tests/test_exchange_props.py): every strategy is
pure row selection / single-owner scatter — no cross-shard reductions —
so lookups and write-backs are BIT-exact vs the dense single-device table
ops, and all 7 GST variants train to oracle parity through any of them.

Ragged batches: a global batch whose size doesn't divide the shard count
must be padded to one that does BEFORE sharding (``pad_ragged``).  Pad
rows carry the sentinel id ``num_shards · rows`` which every strategy's
write path drops and every strategy's lookup answers with zeros.

Compressed traffic (``--payload-dtype``): the embedding payloads crossing
the collectives — lookup answers and write-back rows — optionally travel
as bf16 or int8-with-per-row-scale wire rows (``PayloadCodec`` over
kernels/quant.py) and dequantize at the endpoint.  Lookups round
deterministically to nearest; write-backs round STOCHASTICALLY (keyed on
(step, shard)) so repeated round-trips stay unbiased.  ids / seg_idx /
seg_valid / initialized stay exact, f32 is the identity codec (the
bit-exactness contract above keeps its teeth), and the bytes models —
still asserted against ``measured_exchange_bytes`` — shrink accordingly,
moving the ``select_exchange`` crossover points per (shard count, dtype).

Prefetched lookups (``--prefetch-lookups``): the lookup for batch k+1 can
be dispatched as its OWN jitted collective while step k's device work is
in flight (``prefetch_lookup`` — same collectives as ``lookup``, so it
moves the same bytes, just earlier).  The buffer it returns is stale by
exactly the <= B_local*S rows step k itself writes back, so every
strategy grows ``update_sampled_patch``: the fused write-back that ALSO
patches the prefetched (B_local, J, d) buffer with the rows it is about
to write.  For ``ring`` and ``alltoall`` the write payload already
visits every shard, so the patch rides the existing hops and adds ZERO
wire bytes (``patch_bytes`` == 0, asserted vs the jaxpr).  ``bucketed``
writes travel owner-direct, so its patch is a genuinely tiny extra hop:
each wb row whose id reappears in the next batch travels once to the
shard that prefetched it (routing planned host-side — ``consumer_shards``
/ ``plan_patch_capacity`` — like the write buckets), costing
``(D-1) * patch_cap`` wb rows per device.  At f32 the patched buffer is
BIT-exact vs an inline lookup of the post-write table; under bf16/int8
the patch delivers the table's stored (write-rounded) value without the
read-side re-rounding — inside the existing bounded-error contract.
"""
from __future__ import annotations

from typing import Iterable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import embedding_table as tbl
from repro.kernels.ops import (dequantize_payload, iter_jaxpr_eqns,
                               quantize_payload)
from repro.kernels.quant import PAYLOAD_DTYPES

EXCHANGES = ("ring", "alltoall", "bucketed")

# collective primitives counted by measured_exchange_bytes, with the
# per-device send cost of each as a fraction of the operand:
#   ppermute   — the whole buffer leaves the device every hop:        1
#   all_to_all — (D-1) of the D leading-axis chunks leave:      (D-1)/D
#   all_gather — ring dissemination forwards D-1 chunks:           (D-1)
_COLLECTIVES = ("ppermute", "all_to_all", "all_gather")


# ---------------------------------------------------------------------------
# payload wire format
# ---------------------------------------------------------------------------


class PayloadCodec:
    """Wire format for the embedding payloads that cross the collectives.

    ``f32`` is the identity codec — encode/decode pass the array through
    untouched, so the default exchange stays bit-exact with an unchanged
    jaxpr.  ``bf16``/``int8`` pack rows via kernels/quant.py: encoded
    parts are ``(values,)`` for bf16 and ``(values int8, scale f32)`` for
    int8, with one scale per LEADING row (a bucket slot / batch row), 0
    for all-zero rows so ragged sentinel rows decode to exact zeros
    through every strategy.

    Read path (lookup answers): deterministic round-to-nearest — a repeated
    lookup of an unchanged row answers identically.  Write path: stochastic
    rounding with bits folded from ``(seed, step, shard)`` so repeated
    write round-trips stay unbiased and shards never share a bit stream.
    ``use_pallas`` routes the pack/unpack through the Pallas kernels
    (interpret mode off-TPU); the default jnp path computes the same bits
    exactly and lets XLA fuse the elementwise math into the step.
    """

    def __init__(self, dtype: str = "f32", *, axis_name: Optional[str] = None,
                 use_pallas: bool = False, seed: int = 0x6E57):
        if dtype not in PAYLOAD_DTYPES:
            raise ValueError(f"unknown payload dtype {dtype!r} — expected "
                             f"one of {PAYLOAD_DTYPES}")
        self.dtype = dtype
        self.axis_name = axis_name
        self.use_pallas = use_pallas
        self.seed = seed

    @property
    def itemsize(self) -> int:
        return {"f32": 4, "bf16": 2, "int8": 1}[self.dtype]

    @property
    def scale_bytes(self) -> int:
        """Per-leading-row side-channel bytes riding next to the values."""
        return 4 if self.dtype == "int8" else 0

    def row_bytes(self, n_elem: int) -> int:
        """Wire bytes of one leading row holding n_elem f32 elements."""
        return n_elem * self.itemsize + self.scale_bytes

    def encode_zeros(self, shape) -> Tuple[jnp.ndarray, ...]:
        """Encoded parts of an all-zero (B, ...) buffer — the ring
        lookup's riding answer buffer starts as this."""
        if self.dtype == "f32":
            return (jnp.zeros(shape, jnp.float32),)
        if self.dtype == "bf16":
            return (jnp.zeros(shape, jnp.bfloat16),)
        return (jnp.zeros(shape, jnp.int8),
                jnp.zeros(shape[:1], jnp.float32))

    def encode_read(self, x) -> Tuple[jnp.ndarray, ...]:
        """Lookup-answer packing: deterministic round-to-nearest."""
        if self.dtype == "f32":
            return (x,)
        return quantize_payload(x, dtype=self.dtype,
                                use_pallas=self.use_pallas)

    def encode_write(self, x, step) -> Tuple[jnp.ndarray, ...]:
        """Write-back packing: stochastic rounding, bits folded from
        (seed, step, shard) — deterministic given the step, distinct per
        shard, unbiased over repeated round-trips."""
        if self.dtype == "f32":
            return (x,)
        key = jax.random.fold_in(jax.random.PRNGKey(self.seed), step)
        if self.axis_name is not None:
            key = jax.random.fold_in(key, jax.lax.axis_index(self.axis_name))
        bits = jax.random.bits(key, x.shape, jnp.uint32)
        return quantize_payload(x, bits, dtype=self.dtype,
                                use_pallas=self.use_pallas)

    def decode(self, parts) -> jnp.ndarray:
        parts = tuple(parts)
        if self.dtype == "f32":
            return parts[0]
        return dequantize_payload(parts, dtype=self.dtype,
                                  use_pallas=self.use_pallas)


# ---------------------------------------------------------------------------
# strategy base
# ---------------------------------------------------------------------------


class Exchange:
    """One exchange strategy bound to a (axis_name, num_shards, rows) mesh
    geometry.  ``rows`` is the per-shard row count OF THE TABLE THE STEP
    SEES (``DistContext.table_rows`` — device-tier rows under a tiered
    store); owner arithmetic is ``id // rows`` throughout.

    ``cap`` (bucketed only): per-(device, owner) bucket capacity.  None
    falls back to the trace-time B_local — always safe, never smaller
    than needed — while a host-planned cap (``plan_capacity``) is what
    makes the strategy win; a batch exceeding the planned cap would be
    silently truncated, so drivers must validate with
    ``required_capacity`` before stepping.

    ``payload_dtype``: wire format for the embedding payloads
    (``PayloadCodec``).  Forced to the f32 identity at num_shards == 1,
    where nothing crosses a wire — single-shard runs stay bit-exact no
    matter the setting.

    ``patch_cap`` (bucketed only): per-(device, consumer) bucket capacity
    of the prefetch patch hop (``update_sampled_patch``).  Same contract
    as ``cap``: None falls back to the trace-time B_local, a host-planned
    value (``plan_patch_capacity``) makes it tiny, and exceeding it means
    silent truncation — validate with ``required_patch_capacity``.
    """

    name = "?"

    def __init__(self, *, axis_name: str, num_shards: int, rows: int,
                 cap: Optional[int] = None, payload_dtype: str = "f32",
                 patch_cap: Optional[int] = None):
        self.axis_name = axis_name
        self.num_shards = num_shards
        self.rows = rows
        self.cap = cap
        self.patch_cap = patch_cap
        self.payload_dtype = "f32" if num_shards <= 1 else payload_dtype
        self.codec = PayloadCodec(self.payload_dtype, axis_name=axis_name)

    @property
    def sentinel(self) -> int:
        """Row id used for ragged padding: out of every shard's range, so
        writes drop it and lookups answer zeros (``pad_ragged``)."""
        return self.num_shards * self.rows

    # -- table ops (inside shard_map) --------------------------------------

    def lookup(self, table: tbl.EmbeddingTable, graph_ids
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        raise NotImplementedError

    def lookup_ages(self, table: tbl.EmbeddingTable, graph_ids
                    ) -> jnp.ndarray:
        """Distributed read of the per-segment last-refresh-step plane
        (``table.age``, (R, J) int32) for global ``graph_ids`` (B,) —
        the age-weighted SED (``--sed-age-weighting``) input.  Pure row
        selection like ``lookup``'s init plane, always exact int32 on
        the wire (no payload codec), answering 0 for rows this exchange
        doesn't own (sentinel pads included — their η is masked anyway).
        Only traced when the decay is on, so the default train step's
        jaxpr is untouched."""
        raise NotImplementedError

    def update_sampled(self, table: tbl.EmbeddingTable, graph_ids, seg_idx,
                       h_new, step) -> tbl.EmbeddingTable:
        raise NotImplementedError

    def update_all(self, table: tbl.EmbeddingTable, graph_ids, h_all,
                   seg_valid, step) -> tbl.EmbeddingTable:
        raise NotImplementedError

    # -- prefetch lane (lookahead lookup + fused write-back patch) ---------

    def prefetch_lookup(self, table: tbl.EmbeddingTable, graph_ids
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """The lookup for the NEXT batch, dispatched as its own jitted
        collective while the current step is in flight.  Identical
        collectives (and bytes) to ``lookup`` — prefetch moves the same
        traffic EARLIER, it adds none.  The result is stale by exactly
        the rows the in-flight step writes back; ``update_sampled_patch``
        repairs those."""
        return self.lookup(table, graph_ids)

    def update_sampled_patch(self, table: tbl.EmbeddingTable, graph_ids,
                             seg_idx, h_new, step, pref, next_ids,
                             next_dest=None):
        """Fused ``update_sampled`` + prefetched-buffer patch.

        Applies the sampled write-back to the table exactly like
        ``update_sampled`` AND patches ``pref`` — the next batch's
        prefetched ``(emb (B, J, d), initialized (B, J))`` pair, looked
        up from the PRE-write table — with the rows this write is about
        to make stale.  ``next_ids`` is this device's (B,) slice of the
        next batch's global ids (sentinel-padded when ragged; sentinel
        slots are never patched).  ``next_dest`` is only consumed by the
        bucketed strategy: the host-planned (B_local,) consumer shard of
        each write row (``consumer_shards``; ``num_shards`` = no
        consumer).

        Returns ``(new_table, (patched_emb, patched_init))``.  At f32 the
        patched pair is bit-exact vs ``lookup(new_table, next_ids)``; at
        bf16/int8 it holds the table's stored (write-rounded) values —
        i.e. it SKIPS the read-side re-rounding an inline lookup would
        add, staying inside the bounded-error contract.
        """
        raise NotImplementedError

    def _local_update_patch(self, table, graph_ids, seg_idx, h_new, step,
                            pref, next_ids):
        """num_shards == 1 fused path: local scatter + local patch."""
        local_row = self._local_write_rows(graph_ids)
        table = tbl.update_sampled(table, local_row, seg_idx, h_new, step,
                                   mode="drop")
        emb, init = self._apply_patch(pref[0], pref[1], next_ids,
                                      graph_ids, seg_idx, h_new)
        return table, (emb, init)

    def _apply_patch(self, pref_emb, pref_init, next_ids, g_ids, g_sidx,
                     g_h):
        """Scatter decoded write rows ``(g_ids (G,), g_sidx (G, S),
        g_h (G, S, d))`` onto the prefetched ``(B, J, d)`` buffer wherever
        their id appears in ``next_ids`` (B,).  Purely local — no
        collectives.  Sentinel ids on either side never match (write-side
        sentinels are masked, a sentinel in ``next_ids`` exceeds every
        real id), so ragged padding no-ops.  Ids are unique within a
        batch, so each next-batch row has at most one matching write row
        and ``argmax`` over the match matrix is exact."""
        B, J = pref_init.shape[:2]
        match = ((g_ids[:, None] == next_ids[None, :])
                 & (g_ids[:, None] < self.sentinel))        # (G, B)
        has = match.any(axis=0)
        g_of = jnp.argmax(match, axis=0)                    # (B,)
        idx_j = jnp.where(has[:, None], g_sidx[g_of], J)    # J => dropped
        b_idx = jnp.arange(B)[:, None]
        emb = pref_emb.at[b_idx, idx_j].set(
            g_h[g_of].astype(pref_emb.dtype), mode="drop")
        init = pref_init.at[b_idx, idx_j].set(
            jnp.ones((), pref_init.dtype), mode="drop")
        return emb, init

    # -- analytic per-device bytes (match measured_exchange_bytes) ---------

    def lookup_bytes(self, b_local: int, j_max: int, d_h: int) -> int:
        raise NotImplementedError

    def update_sampled_bytes(self, b_local: int, s: int, d_h: int) -> int:
        raise NotImplementedError

    def update_all_bytes(self, b_local: int, j_max: int, d_h: int) -> int:
        raise NotImplementedError

    def train_step_bytes(self, b_local: int, j_max: int, s: int, d_h: int,
                         *, use_table: bool) -> int:
        """Per-device exchange traffic of one dist train step (lookup +
        sampled write-back when the variant uses the table), at this
        exchange's payload dtype."""
        if not use_table:
            return 0
        return (self.lookup_bytes(b_local, j_max, d_h)
                + self.update_sampled_bytes(b_local, s, d_h))

    def prefetch_lookup_bytes(self, b_local: int, j_max: int,
                              d_h: int) -> int:
        """Same collectives as ``lookup`` — prefetch moves bytes earlier,
        it adds none."""
        return self.lookup_bytes(b_local, j_max, d_h)

    def patch_bytes(self, b_local: int, s: int, d_h: int) -> int:
        """EXTRA wire bytes ``update_sampled_patch`` moves beyond
        ``update_sampled``.  0 for ring/alltoall: their write payload
        already visits every shard, so the patch rides the existing hops.
        Only bucketed (owner-direct writes never reach the consumers)
        pays a real — tiny, patch_cap-sized — extra hop."""
        return 0

    def update_sampled_patch_bytes(self, b_local: int, s: int,
                                   d_h: int) -> int:
        return (self.update_sampled_bytes(b_local, s, d_h)
                + self.patch_bytes(b_local, s, d_h))

    def prefetch_train_step_bytes(self, b_local: int, j_max: int, s: int,
                                  d_h: int, *, use_table: bool) -> int:
        """Per-device exchange traffic of one PREFETCHED dist train step:
        the next batch's prefetch lookup (same bytes as inline, just
        earlier) + the fused write-back-and-patch.  Net extra over
        ``train_step_bytes`` is exactly ``patch_bytes`` — 0 except
        bucketed."""
        if not use_table:
            return 0
        return (self.prefetch_lookup_bytes(b_local, j_max, d_h)
                + self.update_sampled_patch_bytes(b_local, s, d_h))

    # -- shared local fallbacks (num_shards == 1: no collectives) ----------

    def _local_lookup(self, table, graph_ids):
        mine = (graph_ids // self.rows) == 0
        local = jnp.clip(graph_ids, 0, self.rows - 1)
        e, i = tbl.lookup(table, local)
        return (jnp.where(mine[:, None, None], e, 0),
                jnp.where(mine[:, None], i, False))

    def _local_lookup_ages(self, table, graph_ids):
        mine = (graph_ids // self.rows) == 0
        local = jnp.clip(graph_ids, 0, self.rows - 1)
        return jnp.where(mine[:, None], table.age[local], 0)

    def _local_write_rows(self, graph_ids):
        mine = (graph_ids // self.rows) == 0
        return jnp.where(mine, graph_ids, self.rows)  # rows => dropped


# ---------------------------------------------------------------------------
# ring (the PR 3 exchange, now a strategy)
# ---------------------------------------------------------------------------


def _ring_perm(num_shards: int):
    return [(i, (i + 1) % num_shards) for i in range(num_shards)]


def _hop(axis_name, num_shards, *bufs):
    perm = _ring_perm(num_shards)
    return tuple(jax.lax.ppermute(b, axis_name, perm) for b in bufs)


class RingExchange(Exchange):
    """D-hop ppermute ring: rows a device owns are answered by a plain
    local gather on the first ring stop (zero communication for a
    perfectly-aligned batch); remote rows ride the ring — the (ids,
    payload) buffers hop with shift +1 and every shard answers/applies
    the rows it owns as the buffer passes through.  D hops for lookups
    (the answered buffer must come home), D-1 for writes (applied in
    place, nothing returns)."""

    name = "ring"

    def lookup(self, table, graph_ids):
        """Distributed ``tbl.lookup``: global graph_ids (B_l,) against the
        local (R, J, d) shard.  Pure row selection — no reductions — so at
        f32 the result is BIT-EXACT vs the dense single-device lookup; at
        bf16/int8 the answer buffer rides the ring in encoded form (each
        owner packs its answers in place) and decodes once at home."""
        me = jax.lax.axis_index(self.axis_name)
        rows, num_shards = self.rows, self.num_shards
        B = graph_ids.shape[0]
        parts = self.codec.encode_zeros((B,) + table.emb.shape[1:])
        init = jnp.zeros((B,) + table.initialized.shape[1:],
                         table.initialized.dtype)
        ids = graph_ids
        for _ in range(num_shards):
            owner = ids // rows
            mine = owner == me
            local_row = jnp.clip(ids - me * rows, 0, rows - 1)
            e, i = tbl.lookup(table, local_row)
            e_parts = self.codec.encode_read(e)
            parts = tuple(
                jnp.where(mine.reshape((B,) + (1,) * (p.ndim - 1)), ep, p)
                for p, ep in zip(parts, e_parts))
            init = jnp.where(mine[:, None], i, init)
            if num_shards > 1:
                ids, init, *parts = _hop(self.axis_name, num_shards,
                                         ids, init, *parts)
        return self.codec.decode(parts), init

    def lookup_ages(self, table, graph_ids):
        """Age plane over the same D ring hops as ``lookup``'s init plane:
        the (ids, ages) pair rides the ring, every owner answers its rows
        in place, exact int32 end to end."""
        me = jax.lax.axis_index(self.axis_name)
        rows, num_shards = self.rows, self.num_shards
        B = graph_ids.shape[0]
        ages = jnp.zeros((B,) + table.age.shape[1:], table.age.dtype)
        ids = graph_ids
        for _ in range(num_shards):
            mine = (ids // rows) == me
            local_row = jnp.clip(ids - me * rows, 0, rows - 1)
            ages = jnp.where(mine[:, None], table.age[local_row], ages)
            if num_shards > 1:
                ids, ages = _hop(self.axis_name, num_shards, ids, ages)
        return ages

    def update_sampled(self, table, graph_ids, seg_idx, h_new, step):
        """Distributed ``tbl.update_sampled``: the (ids, seg_idx, payload)
        write buffer rides the ring; each shard applies the writes it owns
        in place (donated scatter, mode="drop" for everything else).  The
        payload is packed ONCE at the source (stochastic rounding) and
        each shard decodes the passing buffer before its scatter."""
        ids, sidx = graph_ids, seg_idx
        parts = self.codec.encode_write(h_new, step)
        me = jax.lax.axis_index(self.axis_name)
        rows, num_shards = self.rows, self.num_shards
        for t in range(num_shards):
            mine = (ids // rows) == me
            local_row = jnp.where(mine, ids - me * rows, rows)  # => dropped
            table = tbl.update_sampled(table, local_row, sidx,
                                       self.codec.decode(parts), step,
                                       mode="drop")
            if t < num_shards - 1:  # write buffers need no homecoming hop
                ids, sidx, *parts = _hop(self.axis_name, num_shards,
                                         ids, sidx, *parts)
        return table

    def update_sampled_patch(self, table, graph_ids, seg_idx, h_new, step,
                             pref, next_ids, next_dest=None):
        """Fused write-back + patch on the SAME D-1 ring hops: the write
        buffer already visits every shard, so each shard patches its
        prefetched buffer with the passing rows as it applies the ones it
        owns — zero added wire bytes (asserted vs the jaxpr)."""
        emb, init = pref
        ids, sidx = graph_ids, seg_idx
        parts = self.codec.encode_write(h_new, step)
        me = jax.lax.axis_index(self.axis_name)
        rows, num_shards = self.rows, self.num_shards
        for t in range(num_shards):
            mine = (ids // rows) == me
            local_row = jnp.where(mine, ids - me * rows, rows)  # => dropped
            h_dec = self.codec.decode(parts)
            table = tbl.update_sampled(table, local_row, sidx, h_dec, step,
                                       mode="drop")
            emb, init = self._apply_patch(emb, init, next_ids,
                                          ids, sidx, h_dec)
            if t < num_shards - 1:
                ids, sidx, *parts = _hop(self.axis_name, num_shards,
                                         ids, sidx, *parts)
        return table, (emb, init)

    def update_all(self, table, graph_ids, h_all, seg_valid, step):
        """Distributed ``tbl.update_all`` (refresh phase) over the ring."""
        ids, sv = graph_ids, seg_valid
        parts = self.codec.encode_write(h_all, step)
        me = jax.lax.axis_index(self.axis_name)
        rows, num_shards = self.rows, self.num_shards
        for t in range(num_shards):
            mine = (ids // rows) == me
            local_row = jnp.where(mine, ids - me * rows, rows)
            table = tbl.update_all(table, local_row,
                                   self.codec.decode(parts), sv, step,
                                   mode="drop")
            if t < num_shards - 1:  # write buffers need no homecoming hop
                ids, sv, *parts = _hop(self.axis_name, num_shards,
                                       ids, sv, *parts)
        return table

    def lookup_bytes(self, b_local, j_max, d_h):
        # D hops of the (ids i32, init bool, payload) buffer
        if self.num_shards <= 1:
            return 0
        per_hop = b_local * (
            4 + j_max * 1 + self.codec.row_bytes(j_max * d_h))
        return self.num_shards * per_hop

    def update_sampled_bytes(self, b_local, s, d_h):
        if self.num_shards <= 1:
            return 0
        per_hop = b_local * (4 + s * 4 + self.codec.row_bytes(s * d_h))
        return (self.num_shards - 1) * per_hop

    def update_all_bytes(self, b_local, j_max, d_h):
        if self.num_shards <= 1:
            return 0
        per_hop = b_local * (
            4 + j_max * 4 + self.codec.row_bytes(j_max * d_h))
        return (self.num_shards - 1) * per_hop


# ---------------------------------------------------------------------------
# alltoall (full-buffer dissemination, one payload round-trip)
# ---------------------------------------------------------------------------


def _a2a(x, axis_name):
    """Transpose-exchange: x (D, cap, ...) where x[j] is destined to device
    j; the result's row j is what device j sent here."""
    return jax.lax.all_to_all(x, axis_name, 0, 0, tiled=True)


class AllToAllExchange(Exchange):
    """Full-buffer dissemination: queries ``all_gather`` to every shard
    (ids are cheap), each shard answers the dense (D, B_local) block for
    the rows it owns, and ONE ``all_to_all`` brings the answers home —
    the requester selects its owner's answer by direct indexing (no
    masked sums, so -0.0 and NaN payloads stay bit-identical).  Writes
    are the dual: the full (ids, payload) buffers all_gather to every
    shard and each shard applies the rows it owns with mode="drop".

    vs ring: 2 collectives instead of D hops and one payload leg fewer
    on lookups ((D-1) vs D), but the dense answer block still scales
    with D·B_local."""

    name = "alltoall"

    def lookup(self, table, graph_ids):
        rows, D, ax = self.rows, self.num_shards, self.axis_name
        B = graph_ids.shape[0]
        if D == 1:
            return self._local_lookup(table, graph_ids)
        me = jax.lax.axis_index(ax)
        all_ids = jax.lax.all_gather(graph_ids, ax)          # (D, B)
        local = jnp.clip(all_ids - me * rows, 0, rows - 1).reshape(-1)
        owned = (all_ids // rows).reshape(-1) == me
        e, i = tbl.lookup(table, local)
        # zero non-owned answers so ragged/padded positions come home as
        # zeros no matter which shard they were clipped into (an all-zero
        # row packs to scale 0 under int8, so it decodes to exact zeros)
        e = jnp.where(owned[:, None, None], e, 0)
        i = jnp.where(owned[:, None], i, False)
        parts = self.codec.encode_read(e)                    # leading D·B
        parts_back = tuple(_a2a(p.reshape((D, B) + p.shape[1:]), ax)
                           for p in parts)
        i_back = _a2a(i.reshape((D, B) + table.initialized.shape[1:]), ax)
        owner = jnp.clip(graph_ids // rows, 0, D - 1)
        r = jnp.arange(B)
        return (self.codec.decode(tuple(p[owner, r] for p in parts_back)),
                i_back[owner, r])

    def lookup_ages(self, table, graph_ids):
        """Age plane over the same all_gather + all_to_all pair as
        ``lookup``'s init plane — owner answers, one a2a home, direct
        [owner, r] selection."""
        rows, D, ax = self.rows, self.num_shards, self.axis_name
        B = graph_ids.shape[0]
        if D == 1:
            return self._local_lookup_ages(table, graph_ids)
        me = jax.lax.axis_index(ax)
        all_ids = jax.lax.all_gather(graph_ids, ax)          # (D, B)
        local = jnp.clip(all_ids - me * rows, 0, rows - 1).reshape(-1)
        owned = (all_ids // rows).reshape(-1) == me
        a = jnp.where(owned[:, None], table.age[local], 0)
        a_back = _a2a(a.reshape((D, B) + table.age.shape[1:]), ax)
        owner = jnp.clip(graph_ids // rows, 0, D - 1)
        return a_back[owner, jnp.arange(B)]

    def _gathered_writes(self, graph_ids, *payloads):
        """all_gather the global write buffers; returns the RAW gathered
        ids too (the fused patch reuses them — no second gather)."""
        ax = self.axis_name
        ids = jax.lax.all_gather(graph_ids, ax).reshape(-1)
        flat = [jax.lax.all_gather(p, ax).reshape((-1,) + p.shape[1:])
                for p in payloads]
        me = jax.lax.axis_index(ax)
        mine = (ids // self.rows) == me
        local_row = jnp.where(mine, ids - me * self.rows, self.rows)
        return (ids, local_row, *flat)

    def update_sampled(self, table, graph_ids, seg_idx, h_new, step):
        if self.num_shards == 1:
            local_row = self._local_write_rows(graph_ids)
            return tbl.update_sampled(table, local_row, seg_idx, h_new,
                                      step, mode="drop")
        parts = self.codec.encode_write(h_new, step)
        _, local_row, sidx, *eparts = self._gathered_writes(
            graph_ids, seg_idx, *parts)
        return tbl.update_sampled(table, local_row, sidx,
                                  self.codec.decode(eparts), step,
                                  mode="drop")

    def update_sampled_patch(self, table, graph_ids, seg_idx, h_new, step,
                             pref, next_ids, next_dest=None):
        """Fused write-back + patch on the SAME all_gathers: every shard
        already receives the full global write buffer, so the patch is a
        local scatter over it — zero added wire bytes (asserted vs the
        jaxpr)."""
        emb, init = pref
        if self.num_shards == 1:
            return self._local_update_patch(table, graph_ids, seg_idx,
                                            h_new, step, pref, next_ids)
        parts = self.codec.encode_write(h_new, step)
        ids, local_row, sidx, *eparts = self._gathered_writes(
            graph_ids, seg_idx, *parts)
        h_dec = self.codec.decode(eparts)
        table = tbl.update_sampled(table, local_row, sidx, h_dec, step,
                                   mode="drop")
        emb, init = self._apply_patch(emb, init, next_ids, ids, sidx,
                                      h_dec)
        return table, (emb, init)

    def update_all(self, table, graph_ids, h_all, seg_valid, step):
        if self.num_shards == 1:
            local_row = self._local_write_rows(graph_ids)
            return tbl.update_all(table, local_row, h_all, seg_valid, step,
                                  mode="drop")
        parts = self.codec.encode_write(h_all, step)
        _, local_row, sv, *eparts = self._gathered_writes(
            graph_ids, seg_valid, *parts)
        return tbl.update_all(table, local_row, self.codec.decode(eparts),
                              sv, step, mode="drop")

    def lookup_bytes(self, b_local, j_max, d_h):
        if self.num_shards <= 1:
            return 0
        # ids all_gather + (payload, init bool) answers all_to_all
        return (self.num_shards - 1) * b_local * (
            4 + self.codec.row_bytes(j_max * d_h) + j_max * 1)

    def update_sampled_bytes(self, b_local, s, d_h):
        if self.num_shards <= 1:
            return 0
        # (ids, seg_idx, payload) all_gathered to every shard
        return (self.num_shards - 1) * b_local * (
            4 + s * 4 + self.codec.row_bytes(s * d_h))

    def update_all_bytes(self, b_local, j_max, d_h):
        if self.num_shards <= 1:
            return 0
        # (ids, payload, seg_valid f32) all_gathered to every shard
        return (self.num_shards - 1) * b_local * (
            4 + self.codec.row_bytes(j_max * d_h) + j_max * 4)


# ---------------------------------------------------------------------------
# bucketed (owner-direct: one hop there, one hop back)
# ---------------------------------------------------------------------------


class BucketedExchange(Exchange):
    """Owner-direct exchange: local queries are stable-sorted by owner
    shard and scattered into (D, cap) per-owner buckets; ONE all_to_all
    delivers each bucket straight to its owner, which answers/applies it,
    and (for lookups) one all_to_all brings exactly the requested rows
    back.  Each row travels one hop to its owner and one hop home —
    traffic scales with the BUCKET capacity, not the shard count.

    ``cap`` is a static shape: None falls back to B_local (safe for any
    owner distribution, but then the buckets are as big as the alltoall
    block).  The win comes from host-side planning — ``plan_capacity``
    over the epoch's id schedule gives the tightest safe cap (≈ B_local/D
    for near-uniform batches).  A batch needing more than ``cap`` rows of
    one owner from one device would be silently truncated by the
    mode="drop" bucket scatter, so drivers MUST validate planned caps
    with ``required_capacity`` (launch/train_dist.py and the parity
    harness do)."""

    name = "bucketed"

    def _plan_by(self, key):
        """(order, sorted_key, rank-within-key) for a (B,) routing key.
        Keys may exceed num_shards - 1 (the patch's "no consumer" mark);
        the bucket scatter's mode="drop" discards those rows."""
        order = jnp.argsort(key, stable=True)
        sk = key[order]
        pos = jnp.arange(key.shape[0]) - jnp.searchsorted(sk, sk,
                                                          side="left")
        return order, sk, pos

    def _plan(self, graph_ids):
        """(order, sorted_owner, rank-within-owner) for the local batch."""
        owner = jnp.clip(graph_ids // self.rows, 0, self.num_shards - 1)
        return self._plan_by(owner)

    def _bucket(self, cap, so, pos, x_sorted, fill):
        b = jnp.full((self.num_shards, cap) + x_sorted.shape[1:], fill,
                     x_sorted.dtype)
        return b.at[so, pos].set(x_sorted, mode="drop")

    def lookup(self, table, graph_ids):
        rows, D, ax = self.rows, self.num_shards, self.axis_name
        B = graph_ids.shape[0]
        if D == 1:
            return self._local_lookup(table, graph_ids)
        cap = self.cap or B
        order, so, pos = self._plan(graph_ids)
        buckets = self._bucket(cap, so, pos, graph_ids[order],
                               jnp.int32(self.sentinel))
        q = _a2a(buckets, ax)                      # (D, cap) queries I own
        me = jax.lax.axis_index(ax)
        local = jnp.clip(q - me * rows, 0, rows - 1).reshape(-1)
        owned = (q // rows).reshape(-1) == me      # False for sentinel slots
        e, i = tbl.lookup(table, local)
        e = jnp.where(owned[:, None, None], e, 0)  # scale 0 under int8
        i = jnp.where(owned[:, None], i, False)
        parts = self.codec.encode_read(e)          # leading D·cap
        parts_back = tuple(_a2a(p.reshape((D, cap) + p.shape[1:]), ax)
                           for p in parts)
        i_back = _a2a(i.reshape((D, cap) + table.initialized.shape[1:]), ax)
        inv = jnp.argsort(order, stable=True)
        return (self.codec.decode(tuple(p[so, pos][inv]
                                        for p in parts_back)),
                i_back[so, pos][inv])

    def lookup_ages(self, table, graph_ids):
        """Age plane owner-direct: the same (D, cap) id buckets as
        ``lookup``, one all_to_all there, one back, inverse-permuted
        home."""
        rows, D, ax = self.rows, self.num_shards, self.axis_name
        B = graph_ids.shape[0]
        if D == 1:
            return self._local_lookup_ages(table, graph_ids)
        cap = self.cap or B
        order, so, pos = self._plan(graph_ids)
        buckets = self._bucket(cap, so, pos, graph_ids[order],
                               jnp.int32(self.sentinel))
        q = _a2a(buckets, ax)
        me = jax.lax.axis_index(ax)
        local = jnp.clip(q - me * rows, 0, rows - 1).reshape(-1)
        owned = (q // rows).reshape(-1) == me  # False for sentinel slots
        a = jnp.where(owned[:, None], table.age[local], 0)
        a_back = _a2a(a.reshape((D, cap) + table.age.shape[1:]), ax)
        inv = jnp.argsort(order, stable=True)
        return a_back[so, pos][inv]

    def _bucketed_writes(self, graph_ids, *payloads):
        cap = self.cap or graph_ids.shape[0]
        order, so, pos = self._plan(graph_ids)
        idb = self._bucket(cap, so, pos, graph_ids[order],
                           jnp.int32(self.sentinel))
        bufs = [self._bucket(cap, so, pos, p[order], p.dtype.type(0))
                for p in payloads]
        q = _a2a(idb, self.axis_name).reshape(-1)
        flat = [_a2a(b, self.axis_name).reshape((-1,) + b.shape[2:])
                for b in bufs]
        me = jax.lax.axis_index(self.axis_name)
        mine = (q // self.rows) == me              # sentinel never matches
        local_row = jnp.where(mine, q - me * self.rows, self.rows)
        return (local_row, *flat)

    def update_sampled(self, table, graph_ids, seg_idx, h_new, step):
        if self.num_shards == 1:
            local_row = self._local_write_rows(graph_ids)
            return tbl.update_sampled(table, local_row, seg_idx, h_new,
                                      step, mode="drop")
        parts = self.codec.encode_write(h_new, step)
        local_row, sidx, *eparts = self._bucketed_writes(
            graph_ids, seg_idx, *parts)
        return tbl.update_sampled(table, local_row, sidx,
                                  self.codec.decode(eparts), step,
                                  mode="drop")

    def update_sampled_patch(self, table, graph_ids, seg_idx, h_new, step,
                             pref, next_ids, next_dest=None):
        """Fused write-back + patch.  Owner-direct writes never reach the
        shards that prefetched the rows, so — alone among the strategies
        — bucketed pays a real (tiny) patch hop: each write row whose id
        reappears in the next batch is bucketed by its CONSUMER shard
        (``next_dest``, planned host-side like the write buckets — zero
        wire cost for the routing itself) and one all_to_all of
        ``patch_cap``-sized buckets delivers it for the local scatter.
        Rows with no consumer (next_dest == num_shards) are dropped by
        the bucket scatter and never travel."""
        emb, init = pref
        if self.num_shards == 1:
            return self._local_update_patch(table, graph_ids, seg_idx,
                                            h_new, step, pref, next_ids)
        if next_dest is None:
            raise ValueError(
                "bucketed update_sampled_patch needs next_dest — the "
                "host-planned consumer shard of each write row "
                "(consumer_shards)")
        ax = self.axis_name
        parts = self.codec.encode_write(h_new, step)
        local_row, sidx, *eparts = self._bucketed_writes(
            graph_ids, seg_idx, *parts)
        table = tbl.update_sampled(table, local_row, sidx,
                                   self.codec.decode(eparts), step,
                                   mode="drop")
        cap = self.patch_cap or graph_ids.shape[0]
        order, sd, pos = self._plan_by(next_dest)
        idb = self._bucket(cap, sd, pos, graph_ids[order],
                           jnp.int32(self.sentinel))
        sxb = self._bucket(cap, sd, pos, seg_idx[order], jnp.int32(0))
        pbufs = [self._bucket(cap, sd, pos, p[order], p.dtype.type(0))
                 for p in parts]
        q_ids = _a2a(idb, ax).reshape(-1)
        q_sidx = _a2a(sxb, ax).reshape((-1,) + seg_idx.shape[1:])
        q_parts = [_a2a(b, ax).reshape((-1,) + b.shape[2:])
                   for b in pbufs]
        emb, init = self._apply_patch(emb, init, next_ids, q_ids, q_sidx,
                                      self.codec.decode(q_parts))
        return table, (emb, init)

    def update_all(self, table, graph_ids, h_all, seg_valid, step):
        if self.num_shards == 1:
            local_row = self._local_write_rows(graph_ids)
            return tbl.update_all(table, local_row, h_all, seg_valid, step,
                                  mode="drop")
        parts = self.codec.encode_write(h_all, step)
        local_row, sv, *eparts = self._bucketed_writes(
            graph_ids, seg_valid, *parts)
        return tbl.update_all(table, local_row, self.codec.decode(eparts),
                              sv, step, mode="drop")

    def _cap(self, b_local: int) -> int:
        return self.cap if self.cap is not None else b_local

    def lookup_bytes(self, b_local, j_max, d_h):
        if self.num_shards <= 1:
            return 0
        c = self._cap(b_local)
        # id buckets one hop there + (payload, init bool) one hop back
        return (self.num_shards - 1) * c * (
            4 + self.codec.row_bytes(j_max * d_h) + j_max * 1)

    def update_sampled_bytes(self, b_local, s, d_h):
        if self.num_shards <= 1:
            return 0
        c = self._cap(b_local)
        return (self.num_shards - 1) * c * (
            4 + s * 4 + self.codec.row_bytes(s * d_h))

    def update_all_bytes(self, b_local, j_max, d_h):
        if self.num_shards <= 1:
            return 0
        c = self._cap(b_local)
        return (self.num_shards - 1) * c * (
            4 + self.codec.row_bytes(j_max * d_h) + j_max * 4)

    def patch_bytes(self, b_local, s, d_h):
        # one consumer-direct all_to_all of (ids, seg_idx, payload)
        # patch_cap-sized buckets — the only strategy with a nonzero
        # prefetch surcharge
        if self.num_shards <= 1:
            return 0
        c = self.patch_cap if self.patch_cap is not None else b_local
        return (self.num_shards - 1) * c * (
            4 + s * 4 + self.codec.row_bytes(s * d_h))


# ---------------------------------------------------------------------------
# construction / auto selection
# ---------------------------------------------------------------------------

_STRATEGIES = {cls.name: cls
               for cls in (RingExchange, AllToAllExchange, BucketedExchange)}


def make_exchange(name: str, *, axis_name: str, num_shards: int, rows: int,
                  cap: Optional[int] = None,
                  payload_dtype: str = "f32",
                  patch_cap: Optional[int] = None) -> Exchange:
    """Strategy by name.  "auto" is a DRIVER-side policy — resolve it with
    ``select_exchange`` (it needs the batch geometry) before building."""
    if name == "auto":
        raise ValueError(
            '"auto" must be resolved before building steps: call '
            "select_exchange(num_shards, b_local, j_max, s, d_h) with the "
            "batch geometry and pass the returned strategy name")
    if name not in _STRATEGIES:
        raise ValueError(f"unknown exchange strategy {name!r} — expected "
                         f"one of {EXCHANGES} or 'auto'")
    return _STRATEGIES[name](axis_name=axis_name, num_shards=num_shards,
                             rows=rows, cap=cap, payload_dtype=payload_dtype,
                             patch_cap=patch_cap)


def select_exchange(num_shards: int, b_local: int, j_max: int, s: int,
                    d_h: int, *, cap: Optional[int] = None,
                    payload_dtype: str = "f32") -> str:
    """The "--exchange=auto" policy: the strategy with the fewest analytic
    per-device train-step bytes at this (shard count, payload dtype) —
    first of EXCHANGES wins ties, so 1 shard — where every model is 0 —
    stays on the ring.  Precision-aware: compression shrinks the payload
    term but not the fixed id/seg_idx/init overhead, so the ring/alltoall/
    bucketed break-even points shift with the dtype.

    ``cap``: the bucketed strategy's planned bucket capacity; defaults to
    the uniform-owner estimate ceil(b_local / num_shards), which is what a
    host-planned cap converges to for shuffled batches."""
    if num_shards <= 1:
        return "ring"
    cap_est = cap if cap is not None else -(-b_local // num_shards)
    best_name, best_bytes = None, None
    for name in EXCHANGES:
        ex = make_exchange(name, axis_name="_model", num_shards=num_shards,
                           rows=1, cap=cap_est, payload_dtype=payload_dtype)
        b = ex.train_step_bytes(b_local, j_max, s, d_h, use_table=True)
        if best_bytes is None or b < best_bytes:
            best_name, best_bytes = name, b
    return best_name


# ---------------------------------------------------------------------------
# host-side planning: ragged batches + bucket capacity
# ---------------------------------------------------------------------------


def pad_ragged(num_shards: int, rows: int, ids, *payloads):
    """Pad a GLOBAL exchange batch to a shard-divisible size.

    The shard_map batch specs split the leading axis evenly, so a batch
    whose global size doesn't divide the shard count (a ragged last
    shard) used to be the CALLER's problem.  This is the guard: ids are
    padded with the strategies' sentinel (``num_shards · rows`` — out of
    every shard's owner range, so writes drop the pad rows and lookups
    answer zeros there) and payloads with zeros.

    Returns ``(padded_ids, *padded_payloads, n_real)``; slice exchange
    results back to ``[:n_real]``.
    """
    ids = np.asarray(ids)
    B = ids.shape[0]
    Bp = -(-B // num_shards) * num_shards
    if Bp == B:
        return (ids, *[np.asarray(p) for p in payloads], B)
    out = [np.concatenate(
        [ids, np.full(Bp - B, num_shards * rows, ids.dtype)])]
    for p in payloads:
        p = np.asarray(p)
        out.append(np.concatenate(
            [p, np.zeros((Bp - B,) + p.shape[1:], p.dtype)]))
    return (*out, B)


def required_capacity(global_ids, *, num_shards: int, rows: int) -> int:
    """Smallest per-(device, owner) bucket capacity that fits ONE global
    batch under the contiguous batch split (device k gets batch rows
    [k·B_local, (k+1)·B_local)).  Out-of-range/sentinel ids count against
    the last shard's bucket, matching the clipped owner arithmetic."""
    ids = np.asarray(global_ids).ravel()
    if ids.size % num_shards:
        ids = pad_ragged(num_shards, rows, ids)[0]
    per_dev = ids.reshape(num_shards, -1)
    owner = np.clip(per_dev // rows, 0, num_shards - 1)
    cap = 1
    for dev in range(num_shards):
        counts = np.bincount(owner[dev], minlength=num_shards)
        cap = max(cap, int(counts.max()))
    return cap


def plan_capacity(id_batches: Iterable, *, num_shards: int,
                  rows: int) -> int:
    """Bucket capacity covering EVERY batch of an id schedule — the
    host-side planning step that makes ``bucketed`` beat the ring (the
    cap, not the shard count, sizes its buckets)."""
    cap = 1
    for ids in id_batches:
        cap = max(cap, required_capacity(ids, num_shards=num_shards,
                                         rows=rows))
    return cap


def consumer_shards(cur_ids, next_ids, *, num_shards: int,
                    rows: int) -> np.ndarray:
    """For each row of the CURRENT global batch, the shard whose slice of
    the NEXT global batch contains the same id — the bucketed patch's
    host-planned routing (``next_dest``), computed under the contiguous
    batch split.  ``num_shards`` marks rows with no next-batch consumer
    (they never travel); sentinel pad rows on either side never match.
    Ragged batches are sentinel-padded first, like the device path."""
    sent = num_shards * rows
    cur = np.asarray(cur_ids).ravel()
    if cur.size % num_shards:
        cur = pad_ragged(num_shards, rows, cur)[0]
    nxt = np.asarray(next_ids).ravel()
    if nxt.size % num_shards:
        nxt = pad_ragged(num_shards, rows, nxt)[0]
    b_next = nxt.size // num_shards
    shard_of = {int(r): i // b_next for i, r in enumerate(nxt)
                if int(r) != sent}
    return np.asarray([shard_of.get(int(r), num_shards) for r in cur],
                      np.int32)


def required_patch_capacity(cur_ids, next_ids, *, num_shards: int,
                            rows: int) -> int:
    """Smallest per-(device, consumer) patch bucket capacity for ONE
    (batch k, batch k+1) pair — how many of one device's write rows
    reappear in one consumer shard's next-batch slice."""
    dest = consumer_shards(cur_ids, next_ids, num_shards=num_shards,
                           rows=rows)
    cap = 1
    for per_dev in dest.reshape(num_shards, -1):
        real = per_dev[per_dev < num_shards]
        if real.size:
            cap = max(cap, int(np.bincount(real).max()))
    return cap


def plan_patch_capacity(id_batches: Iterable, *, num_shards: int,
                        rows: int) -> int:
    """Patch bucket capacity covering every CONSECUTIVE pair of an id
    schedule — the prefetch lane patches step k's writes onto batch
    k+1's buffer, so only adjacent batches matter.  Near-disjoint
    shuffled schedules plan to ~1; an all-overlap schedule degenerates
    to ``required_capacity``-sized buckets."""
    cap = 1
    batches = [np.asarray(b) for b in id_batches]
    for a, b in zip(batches, batches[1:]):
        cap = max(cap, required_patch_capacity(
            a, b, num_shards=num_shards, rows=rows))
    return cap


# ---------------------------------------------------------------------------
# measured collective traffic (validates the analytic models)
# ---------------------------------------------------------------------------


def measured_exchange_bytes(fn, num_shards: int, *args, **kwargs) -> int:
    """Per-device bytes moved through the collective eqns of ``fn``'s
    jaxpr (recursing through shard_map/pjit).  Counting conventions match
    the analytic models: a ppermute sends its whole operand every hop, an
    all_to_all keeps 1/D of its operand home, an all_gather forwards D-1
    chunks of its input.  tests/test_exchange_props.py asserts equality
    with every strategy's ``*_bytes`` model."""
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    total = 0
    for eqn in iter_jaxpr_eqns(closed.jaxpr):
        if eqn.primitive.name not in _COLLECTIVES:
            continue
        for v in eqn.invars:
            aval = getattr(v, "aval", None)
            if aval is None or not hasattr(aval, "shape"):
                continue
            nbytes = int(np.prod(aval.shape, dtype=np.int64)) * \
                np.dtype(aval.dtype).itemsize
            if eqn.primitive.name == "ppermute":
                total += nbytes
            elif eqn.primitive.name == "all_to_all":
                total += nbytes * (num_shards - 1) // num_shards
            else:  # all_gather
                total += nbytes * (num_shards - 1)
    return total

