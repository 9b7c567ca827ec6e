"""Row-sharded historical embedding table geometry (dist subsystem).

The dense table T (n_graphs, J_max, d_h) of core/embedding_table.py is
partitioned BLOCK-wise on the graph-row axis across the data mesh axis:
device k owns rows [k·R, (k+1)·R) with R = ceil(n / D) (the row count is
padded to D·R; padding rows are never referenced — graph_ids < n).

FreshGNN / Bai et al. (PAPERS.md) motivate the design: the historical
embedding store is the scaling bottleneck, so it must be partitioned with
the compute instead of replicated.  HOW the shards exchange lookups and
write-backs is a pluggable strategy — dist/exchange.py owns
the ring / alltoall / bucketed implementations and their bytes models;
this module keeps the row geometry (pad/unpad, rows_per_shard).
"""
from __future__ import annotations

from repro.core import embedding_table as tbl
from repro.kernels.ops import pad_leading

# canonical row-partition definitions live with the embedding store
# (store/base.py), which owns row geometry now
from repro.store.base import padded_rows, rows_per_shard  # noqa: F401


def pad_table(table: tbl.EmbeddingTable, num_shards: int) -> tbl.EmbeddingTable:
    """Pad the row axis to a multiple of the shard count (no-op if aligned)."""
    n_pad = padded_rows(table.emb.shape[0], num_shards)
    return tbl.EmbeddingTable(*(pad_leading(x, n_pad) for x in table))


def unpad_table(table: tbl.EmbeddingTable, n_rows: int) -> tbl.EmbeddingTable:
    return tbl.EmbeddingTable(table.emb[:n_rows], table.age[:n_rows],
                              table.initialized[:n_rows])

