"""One embedding-store API over the historical table's three former lives.

The historical segment-embedding table T (paper §3.2) used to exist three
times — replicated (core/embedding_table.py consumers), row-sharded
(dist/table.py) and as the serving cache's slot pool (serve/cache.py).
``EmbeddingStore`` unifies them behind a single residency contract:

  * the jitted step code keeps operating on a plain device-resident
    ``EmbeddingTable`` through the existing ``tbl.lookup`` /
    ``tbl.update_sampled`` / ``tbl.update_all`` accessors (or the
    dist/table.py ring versions) — nothing inside jit knows about tiers;
  * the store owns WHICH rows that device table holds.  Before a step, the
    driver hands it the batch's global row ids; the store returns the
    device rows ("slots") to address instead, migrating rows between the
    device tier and a host-RAM tier as needed (TieredStore) or passing ids
    straight through (DeviceStore, where row == slot).

Because the indirection is pure host-side row renaming — the slot holds
bit-for-bit the row's (emb, age, initialized) triple — a capped-capacity
TieredStore trains bitwise identically to the device-resident oracle
(tests/test_store.py asserts this for all 7 GST variants).

The two-phase ``begin``/``commit`` split exists for the async pipeline:
``begin`` does all host work (residency bookkeeping, host-tier gather,
staging device_put) and is safe on the feeder thread while a step runs;
``commit`` applies the staged migration to the live table and must run in
``begin`` order on the consumer thread.  ``prepare`` fuses both for
synchronous drivers.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import embedding_table as tbl
from repro.kernels.ops import pad_leading, pad_rows_pow2
from repro.obs.metrics import get_registry
from repro.obs.trace import span


# -- block row partition (canonical home; dist/table.py re-exports) ---------


def rows_per_shard(n_rows: int, num_shards: int) -> int:
    """R such that D·R >= n (block row partition, last shard may pad)."""
    return -(-n_rows // max(num_shards, 1))


def padded_rows(n_rows: int, num_shards: int) -> int:
    return rows_per_shard(n_rows, num_shards) * max(num_shards, 1)


def device_rows_per_shard(n_rows: int, num_shards: int,
                          device_rows: int) -> int:
    """Device-tier rows per shard for a TOTAL cap of ``device_rows``:
    the cap split evenly over shards, clamped to [1, rows_per_shard]."""
    num_shards = max(num_shards, 1)
    per = -(-min(device_rows, padded_rows(n_rows, num_shards)) // num_shards)
    return max(1, min(rows_per_shard(n_rows, num_shards), per))


@dataclass
class StoreCounters:
    """Residency-traffic counters (satellite: surfaced by the CLIs and the
    store benchmark)."""
    lookups: int = 0         # batch rows requested
    hits: int = 0            # already device-resident
    misses: int = 0          # faulted host -> device
    evictions: int = 0       # spilled device -> host
    bytes_h2d: int = 0
    bytes_d2h: int = 0
    writeback_wait_ms: float = 0.0   # begin() blocked on pending write-backs
    # delta-gated write-back admission (TieredStore ``wb_threshold``):
    # evicted rows whose embedding moved less than the threshold skip the
    # host-tier emb write; bytes_d2h is settled down by the skipped emb
    # bytes when the writer thread lands the eviction
    wb_skipped_rows: int = 0
    wb_skipped_bytes: int = 0

    def as_dict(self) -> dict:
        total = max(self.lookups, 1)
        return {
            "lookups": self.lookups,
            "hits": self.hits,
            "misses": self.misses,
            "hit_rate": self.hits / total,
            "evictions": self.evictions,
            "bytes_h2d": self.bytes_h2d,
            "bytes_d2h": self.bytes_d2h,
            "migration_bytes": self.bytes_h2d + self.bytes_d2h,
            "wb_skipped_rows": self.wb_skipped_rows,
            "wb_skipped_bytes": self.wb_skipped_bytes,
            "writeback_wait_ms": round(self.writeback_wait_ms, 3),
        }


# registry mirror of StoreCounters: (field, published metric name, unit).
# ``misses`` surfaces as ``store.faults`` — the residency fault count.
_COUNTER_METRICS = (
    ("lookups", "store.lookups", "rows"),
    ("hits", "store.hits", "rows"),
    ("misses", "store.faults", "rows"),
    ("evictions", "store.evictions", "rows"),
    ("bytes_h2d", "store.bytes_h2d", "bytes"),
    ("bytes_d2h", "store.bytes_d2h", "bytes"),
    ("writeback_wait_ms", "store.writeback_wait_ms", "ms"),
    ("wb_skipped_rows", "store.wb_skipped_rows", "rows"),
    ("wb_skipped_bytes", "store.wb_skipped_bytes", "bytes"),
)


class PreparedMigration(NamedTuple):
    """Output of ``begin``: the batch's device rows plus the staged data
    movement ``commit`` will apply.  Device staging buffers live here so
    the host->device copy overlaps with the running step."""
    slots: np.ndarray                      # (B,) device rows for the batch
    ticket: int
    n_up: int = 0
    n_ev: int = 0
    up_slots: Optional[jnp.ndarray] = None     # pow2-padded scatter rows
    up_emb: Optional[jnp.ndarray] = None
    up_age: Optional[jnp.ndarray] = None
    up_init: Optional[jnp.ndarray] = None
    ev_slots: Optional[jnp.ndarray] = None     # pow2-padded gather rows
    ev_rows: Optional[np.ndarray] = None       # (n_ev,) global rows going home


class EmbeddingStore:
    """Base geometry + the no-op residency contract (see module docstring).

    Subclasses override the begin/commit pair; everything is sized by
    ``n_rows`` logical rows split block-wise over ``num_shards`` (shard s
    owns rows [s*R, (s+1)*R), the dist/table.py partition), with
    ``device_rows_per_shard`` of them device-resident at a time.
    """

    def __init__(self, n_rows: int, j_max: int, d_h: int, *,
                 num_shards: int = 1, dtype=jnp.float32, sharding=None):
        self.n_rows = n_rows
        self.j_max = j_max
        self.d_h = d_h
        self.num_shards = max(num_shards, 1)
        self.dtype = dtype
        self.sharding = sharding
        self.rows_per_shard = rows_per_shard(n_rows, self.num_shards)
        self.padded_rows = padded_rows(n_rows, self.num_shards)
        self.counters = StoreCounters()
        self._evict_jit = jax.jit(tbl.evict_rows)

    # ``store.counters`` stays the mutation surface (callers reset it by
    # assigning a fresh StoreCounters); the registry carries a cumulative
    # mirror published by diffing, so resets of the view never rewind the
    # process-wide counters.
    @property
    def counters(self) -> StoreCounters:
        return self._counters

    @counters.setter
    def counters(self, c: StoreCounters) -> None:
        if not hasattr(self, "_publish_mu"):   # first call is from __init__
            self._publish_mu = threading.Lock()
        with self._publish_mu:
            self._counters = c
            self._published = {f: getattr(c, f)
                               for f, _, _ in _COUNTER_METRICS}

    def publish_counters(self) -> None:
        """Mirror counter movement since the last publish into the metrics
        registry (host-side; no-op when metrics are disabled).  Callable
        from any thread — begin runs on the feeder, commit on the
        consumer, delta-gate settlement on the writer."""
        reg = get_registry()
        if not reg.enabled:
            return
        with self._publish_mu:
            for field, name, unit in _COUNTER_METRICS:
                cur = getattr(self._counters, field)
                moved = cur - self._published[field]
                if moved:
                    reg.inc(name, moved, unit=unit)
                    self._published[field] = cur

    # bytes of one (emb, age, init) row triple — the migration-unit size
    @property
    def row_bytes(self) -> int:
        item = jnp.dtype(self.dtype).itemsize
        return self.j_max * (self.d_h * item + 4 + 1)

    @property
    def device_rows_per_shard(self) -> int:
        return self.rows_per_shard

    @property
    def device_rows(self) -> int:
        return self.device_rows_per_shard * self.num_shards

    def _place(self, table: tbl.EmbeddingTable) -> tbl.EmbeddingTable:
        if self.sharding is None:
            return jax.tree_util.tree_map(jnp.asarray, table)
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(x, self.sharding), table)

    # -- residency ---------------------------------------------------------

    def begin(self, row_ids, *, fetch: bool = True,
              step: Optional[int] = None,
              pin: bool = False) -> PreparedMigration:
        raise NotImplementedError

    def commit(self, table: tbl.EmbeddingTable,
               prep: PreparedMigration) -> tbl.EmbeddingTable:
        raise NotImplementedError

    def prepare(self, table: tbl.EmbeddingTable, row_ids, *,
                fetch: bool = True, step: Optional[int] = None,
                ) -> Tuple[tbl.EmbeddingTable, np.ndarray]:
        """begin + commit in one call (synchronous drivers), as one
        ``store.prepare`` span.  ``step``: refresh hint for stale-first
        eviction (see TieredStore.begin)."""
        with span("store.prepare"):
            prep = self.begin(row_ids, fetch=fetch, step=step)
            return self.commit(table, prep), prep.slots

    def release(self, prep: PreparedMigration) -> None:
        """Drop the residency pins ``begin(pin=True)`` took for this
        batch.  Only meaningful under lookahead pinning (the
        --prefetch-lookups lane, where batch k+1's commit lands while
        batch k's rows must stay resident); a no-op everywhere else."""

    def resident_slot(self, row: int) -> Optional[int]:
        """Device row currently holding ``row`` (no LRU side effects), or
        None when the row lives in the host tier."""
        raise NotImplementedError

    # -- lifecycle ---------------------------------------------------------

    def init_device_table(self) -> tbl.EmbeddingTable:
        """The fresh device tier that goes into TrainState."""
        return self._place(tbl.init_table(
            self.device_rows, self.j_max, self.d_h, self.dtype))

    def snapshot(self, table: tbl.EmbeddingTable) -> tbl.EmbeddingTable:
        """Full dense host copy (n_rows, J, d) — both tiers merged; the
        checkpointable view of the store."""
        raise NotImplementedError

    def restore(self, snap: tbl.EmbeddingTable) -> tbl.EmbeddingTable:
        """Reset residency from a dense snapshot; returns the new device
        table to place into TrainState."""
        raise NotImplementedError

    def invalidate_rows(self, table: tbl.EmbeddingTable,
                        rows) -> tbl.EmbeddingTable:
        """Clear ``initialized`` for the given global rows in whichever tier
        holds them (the serving keying layer's eviction)."""
        raise NotImplementedError

    def ages_init(self, table: tbl.EmbeddingTable):
        """(ages (n_rows, J), initialized (n_rows, J)) numpy — the staleness
        bookkeeping merged across tiers (serving stats)."""
        raise NotImplementedError

    def refresh_ages(self, table: tbl.EmbeddingTable) -> None:
        """Re-report device-plane ages to the eviction bookkeeping (the
        TieredStore stale-first readback); a no-op for backends whose
        eviction never consults ages."""

    def flush_writebacks(self) -> None:
        """Wait until every pending device->host write-back has landed."""

    def close(self) -> None:
        pass

    def stats(self) -> dict:
        self.publish_counters()
        d = self.counters.as_dict()
        d.update({
            "backend": type(self).__name__,
            "n_rows": self.n_rows,
            "device_rows": min(self.device_rows, self.padded_rows),
            "occupancy": self.occupancy(),
        })
        return d

    def occupancy(self) -> int:
        return 0


class DeviceStore(EmbeddingStore):
    """The device-resident oracle backend: the whole (padded) table lives in
    device memory and global row ids ARE the device rows — ``begin`` /
    ``commit`` are pure bookkeeping no-ops, preserving the donated in-place
    scatter semantics of the original core/embedding_table.py path."""

    def begin(self, row_ids, *, fetch: bool = True,
              step: Optional[int] = None,
              pin: bool = False) -> PreparedMigration:
        slots = np.asarray(row_ids, np.int32)
        # count UNIQUE rows like TieredStore.begin, so the counters the
        # CLIs/bench print are comparable across backends (callers pass
        # pow2-padded row arrays whose padding repeats the last row)
        uniq = len(set(slots.tolist()))
        self.counters.lookups += uniq
        self.counters.hits += uniq
        self.publish_counters()
        return PreparedMigration(slots=slots, ticket=0)

    def commit(self, table, prep):
        return table

    def resident_slot(self, row: int) -> Optional[int]:
        return int(row)

    def snapshot(self, table: tbl.EmbeddingTable) -> tbl.EmbeddingTable:
        host = jax.tree_util.tree_map(
            lambda x: np.asarray(jax.device_get(x)), table)
        return tbl.EmbeddingTable(*(x[:self.n_rows] for x in host))

    def restore(self, snap: tbl.EmbeddingTable) -> tbl.EmbeddingTable:
        padded = tbl.EmbeddingTable(
            *(pad_leading(np.asarray(x), self.padded_rows) for x in snap))
        return self._place(padded)

    def invalidate_rows(self, table, rows) -> tbl.EmbeddingTable:
        if len(rows) == 0:
            return table
        (rows_p,) = pad_rows_pow2(list(rows))
        return self._evict_jit(table, jnp.asarray(rows_p))

    def ages_init(self, table):
        age = np.asarray(jax.device_get(table.age))[:self.n_rows]
        init = np.asarray(jax.device_get(table.initialized))[:self.n_rows]
        return age, init

    def occupancy(self) -> int:
        return min(self.n_rows, self.device_rows)
