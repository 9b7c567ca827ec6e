"""Operations and bytes that the algorithm needs, from shapes alone.

These count the algorithm's own work, not what an implementation spends:
the one-hot SpMM's products with zeros, padded nodes and padded edges are
not counted.  Floats are 4 bytes (the program keeps float32 activations).
"""
from __future__ import annotations

from typing import Dict

F32 = 4


def segment_spmm(n_seg: int, nodes: float, edges: float, d: int) -> Dict:
    """One weighted neighbour sum over ``n_seg`` segments of ``nodes`` valid
    nodes and ``edges`` valid edges at width ``d``: each edge gathers its
    source row, scales it and adds it into its destination row."""
    return {"flops": n_seg * 2.0 * edges * d,
            "bytes": n_seg * F32 * (2.0 * nodes * d + 3.0 * edges)}


def sed_pool(batch: int, segments: float, d: int) -> Dict:
    """Eq.-1 weighting and pooling of ``segments`` embeddings of width ``d``
    per graph: read the embeddings and three masks, write one row."""
    return {"flops": batch * 2.0 * segments * d,
            "bytes": batch * F32 * (segments * d + 3.0 * segments + d)}


def train_flops_per_graph(cfg: Dict, sampled: int, nodes: float,
                          edges: float, segments: float) -> float:
    """Useful forward and backward FLOPs of one graph's GST step: the
    GraphGym layers on ``sampled`` segments of ``nodes`` valid nodes and
    ``edges`` valid edges, the Eq.-1 pooling and the head over the graph's
    ``segments`` valid segments.  Backward counts each matmul's weight
    gradient and its input gradient, except the first layer's input (data),
    and the transpose of each neighbour sum.  The head is the
    configuration's: the summed per-segment scalar of the ranking track
    (``segment_sum``), or a two-layer MLP of width ``hidden`` and
    ``n_out`` outputs on the pooled embedding (``mlp``), whose loss's few
    operations per class are not counted."""
    d, f = cfg["hidden"], cfg["n_feat"]
    first = 2.0 * nodes * f * d
    mm = (2.0 * nodes * d * d * (cfg["n_pre"] - 1)
          + 4.0 * nodes * d * d * cfg["n_mp"]
          + 2.0 * nodes * d * d * cfg["n_post"])
    agg = 2.0 * edges * d * cfg["n_mp"]
    pool = nodes * d
    encoder = first * 2 + mm * 3 + agg * 2 + pool * 2
    if cfg["head"] == "mlp":
        head = 3 * (2.0 * d * d + 2.0 * d * cfg["n_out"])
        pooling = 2.0 * segments * d + 2.0 * sampled * d
    else:
        head = 2 * (2.0 * segments * d) + 2.0 * sampled * d
        pooling = 2.0 * segments + 2.0 * sampled
    return sampled * encoder + head + pooling
