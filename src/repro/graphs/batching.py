"""Static-shape segment batching (the XLA adaptation of the paper's pipeline).

Each segment is padded to (m_max nodes, e_max edges) with validity masks;
each graph is padded to J_max segments with a segment mask.  Edges are local
to a segment (indices into the segment's node list); cross-segment edges are
dropped — the paper's Table 6 ablation shows locality-preserving partitions
make this information loss negligible.

The dataset lives in host numpy.  Its per-segment arrays are also copied
once to the default device, each example's elements together, when they
take at most half of the device's free memory less what the step needs
beside them (``SegmentedDataset.resident``); ``batch_iterator`` then
gathers each batch there, and otherwise on the host.
"""
from __future__ import annotations

import threading
import weakref
from dataclasses import dataclass, field
from functools import partial
from typing import Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.graphs.data import SyntheticGraph
from repro.graphs.partition import partition_graph
from repro.obs.metrics import get_registry
from repro.obs.trace import span

SEG_FIELDS = ("x", "edges", "edge_valid", "node_valid")
_gauge_lock = threading.Lock()


def _device_free_bytes() -> Optional[int]:
    """Free bytes of the default device (its limit less the bytes in use),
    or None where the backend reports no memory stats (the CPU)."""
    stats = jax.local_devices()[0].memory_stats()
    if not stats:
        return None
    return stats["bytes_limit"] - stats["bytes_in_use"]


def _add_to_gauge(gauge, v: float) -> None:
    with _gauge_lock:
        gauge.set(gauge.value + v)


def _in_rows(a: np.ndarray) -> np.ndarray:
    """``a`` with each example's elements in rows of 128, where they fill
    whole rows.  The TPU's default layout of ``x`` (n, J, m, F) may tile
    the example axis (at J 190 it is ``major_to_minor`` (1, 3, 0, 2)), so
    that a gather reads 512-byte pieces 4 KiB apart; that of (n, K/128,
    128) keeps an example's elements together."""
    k = a[0].size
    return a.reshape(len(a), k // 128, 128) if k % 128 == 0 else a


@partial(jax.jit, static_argnums=2)
def _take_rows(arrays: Dict[str, jax.Array], ids,
               shapes: Tuple[Tuple[str, Tuple[int, ...]], ...]
               ) -> Dict[str, jax.Array]:
    """One batch's rows of every resident array, in one dispatch: one
    dynamic slice per id, stacked, in each field's example shape
    (``shapes``).  On a TPU v5e (n 64, J 190, m 256, F 140, B 16) this
    took 5.9 ms for ``x`` in rows of 128 and 7.9 ms from (n, J, m, F),
    where ``jnp.take`` first copied the whole array (14.2 ms) and a loop
    of dynamic updates took 22.9 ms."""
    b = ids.shape[0]
    return {k: jnp.stack([lax.dynamic_index_in_dim(arrays[k], ids[i],
                                                   keepdims=False)
                          for i in range(b)]).reshape((b,) + shape)
            for k, shape in shapes}


@dataclass
class SegmentedDataset:
    """Host numpy arrays, leading dims (n_graphs, J_max, ...), and the
    lazily placed device copy of the ``SEG_FIELDS`` (``resident``)."""
    x: np.ndarray          # (n, J, m_max, F)
    edges: np.ndarray      # (n, J, e_max, 2) int32 — local node indices
    edge_valid: np.ndarray  # (n, J, e_max) float32
    node_valid: np.ndarray  # (n, J, m_max) float32
    seg_valid: np.ndarray  # (n, J) float32
    labels: np.ndarray     # (n,) int32 or float32
    j_max: int
    m_max: int
    e_max: int
    # None until ``resident`` decides; then the device copy, or {} where it
    # does not fit
    _device: Optional[Dict[str, jax.Array]] = field(
        default=None, init=False, repr=False, compare=False)

    @property
    def n(self):
        return self.x.shape[0]

    def seg_inputs(self, ids: np.ndarray) -> Dict[str, np.ndarray]:
        return {
            "x": self.x[ids],
            "edges": self.edges[ids],
            "edge_valid": self.edge_valid[ids],
            "node_valid": self.node_valid[ids],
        }

    @property
    def seg_nbytes(self) -> int:
        """Bytes of the ``SEG_FIELDS``: what the resident copy takes."""
        return sum(getattr(self, k).nbytes for k in SEG_FIELDS)

    def resident(self, reserve_bytes: int = 0
                 ) -> Optional[Dict[str, jax.Array]]:
        """The device copy of the ``SEG_FIELDS``, or None.  Decided once
        per dataset, at the first call: the copy is placed, under one
        ``feeder.place`` span, when its bytes are at most half of the
        default device's free memory less ``reserve_bytes`` (what the
        caller's step needs beside it), or when the backend reports
        none."""
        if self._device is None:
            free = _device_free_bytes()
            self._device = {}
            if free is None or 2 * self.seg_nbytes <= free - reserve_bytes:
                mib = self.seg_nbytes / 2**20
                with span("feeder.place", mib=mib):
                    self._device = jax.block_until_ready(jax.device_put(
                        {k: _in_rows(getattr(self, k)) for k in SEG_FIELDS}))
                reg = get_registry()
                if reg.enabled:   # the copy's MiB, until it is freed
                    gauge = reg.gauge("feeder.resident_mib", unit="MiB")
                    _add_to_gauge(gauge, mib)
                    weakref.finalize(self, _add_to_gauge, gauge, -mib)
        return self._device or None

    def keep_on_host(self) -> None:
        """Decides for the host gather, where ``resident`` has not yet
        decided."""
        if self._device is None:
            self._device = {}

    def device_seg_inputs(self, ids: np.ndarray) -> Dict[str, jax.Array]:
        """``seg_inputs(ids)`` gathered from the resident copy on the
        device: the same values, bit for bit.  The device gather would
        clamp an id out of range, so such an id raises here."""
        ids = np.asarray(ids)
        if ids.size and not 0 <= ids.min() <= ids.max() < self.n:
            raise IndexError(f"graph ids outside [0, {self.n})")
        return _take_rows(self.resident(), ids, tuple(
            (k, getattr(self, k).shape[1:]) for k in SEG_FIELDS))


def pad_segment(graph: SyntheticGraph, node_ids: np.ndarray, m_max: int,
                e_max: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Extract one segment as padded arrays (x, edges_local, edge_valid, node_valid)."""
    node_ids = node_ids[:m_max]
    g2l = {int(g): l for l, g in enumerate(node_ids)}
    sel = np.isin(graph.edges[:, 0], node_ids) & np.isin(graph.edges[:, 1], node_ids)
    e = graph.edges[sel]
    if len(e) > e_max:
        e = e[np.random.default_rng(0).permutation(len(e))[:e_max]]
    e_local = np.asarray([[g2l[int(a)], g2l[int(b)]] for a, b in e], np.int32)
    x = np.zeros((m_max, graph.x.shape[1]), np.float32)
    x[: len(node_ids)] = graph.x[node_ids]
    edges = np.zeros((e_max, 2), np.int32)
    edge_valid = np.zeros((e_max,), np.float32)
    if len(e_local):
        edges[: len(e_local)] = e_local
        edge_valid[: len(e_local)] = 1.0
    node_valid = np.zeros((m_max,), np.float32)
    node_valid[: len(node_ids)] = 1.0
    return x, edges, edge_valid, node_valid


def segment_dataset(
    graphs: List[SyntheticGraph],
    max_seg_nodes: int = 64,
    method: str = "bfs",
    j_max: Optional[int] = None,
    e_max: Optional[int] = None,
    seed: int = 0,
) -> SegmentedDataset:
    """Preprocessing phase: partition every graph and pad (paper §3.1)."""
    all_segs = []
    for gi, g in enumerate(graphs):
        segs = partition_graph(len(g.x), g.edges, max_seg_nodes, method, seed + gi)
        all_segs.append(segs)
    J = j_max or max(len(s) for s in all_segs)
    m_max = max_seg_nodes
    if e_max is None:
        e_max = 0
        for g, segs in zip(graphs, all_segs):
            for s in segs:
                sel = np.isin(g.edges[:, 0], s) & np.isin(g.edges[:, 1], s)
                e_max = max(e_max, int(sel.sum()))
        e_max = max(e_max, 1)
    n, F = len(graphs), graphs[0].x.shape[1]
    X = np.zeros((n, J, m_max, F), np.float32)
    E = np.zeros((n, J, e_max, 2), np.int32)
    EV = np.zeros((n, J, e_max), np.float32)
    NV = np.zeros((n, J, m_max), np.float32)
    SV = np.zeros((n, J), np.float32)
    labels = np.asarray([g.label for g in graphs])
    labels = labels.astype(np.int32 if np.issubdtype(labels.dtype, np.integer) else np.float32)
    for gi, (g, segs) in enumerate(zip(graphs, all_segs)):
        for j, s in enumerate(segs[:J]):
            x, e, ev, nv = pad_segment(g, s, m_max, e_max)
            X[gi, j], E[gi, j], EV[gi, j], NV[gi, j] = x, e, ev, nv
            SV[gi, j] = 1.0
    return SegmentedDataset(X, E, EV, NV, SV, labels, J, m_max, e_max)


def batch_id_schedule(n: int, batch_size: int, *, rng: np.random.Generator,
                      shuffle: bool = True) -> List[np.ndarray]:
    """One epoch's id batches (drop-last) — THE batching policy, shared by
    ``batch_iterator`` and the dist feeders (dist/pipeline.py::epoch_ids)
    so the two paths cannot diverge."""
    order = rng.permutation(n) if shuffle else np.arange(n)
    return [order[i : i + batch_size]
            for i in range(0, n - batch_size + 1, batch_size)]


def batch_iterator(ds: SegmentedDataset, batch_size: int, *, rng: np.random.Generator,
                   shuffle: bool = True) -> Iterator[Tuple[Dict, np.ndarray, np.ndarray, np.ndarray]]:
    """Yields (seg_inputs, seg_valid, graph_ids, labels) batches (drop-last).
    ``seg_inputs`` is gathered on the device from the dataset's resident
    copy where it has one (decided at the first batch, when the dataset
    has not decided yet), else on the host; the rest is host numpy.  Each
    batch's gather is one ``feeder.assemble`` span, closed before the yield
    so that it never covers the consumer's work, and counts as a
    ``feeder.device_gathers`` or ``feeder.host_gathers``."""
    # beside the copy, the device holds one gathered batch
    device = ds.resident(batch_size * ds.seg_nbytes // ds.n) is not None
    gather = ds.device_seg_inputs if device else ds.seg_inputs
    counter = "feeder.device_gathers" if device else "feeder.host_gathers"
    for ids in batch_id_schedule(ds.n, batch_size, rng=rng, shuffle=shuffle):
        with span("feeder.assemble", batch=len(ids), device=device):
            tup = (gather(ids), ds.seg_valid[ids],
                   ids.astype(np.int32), ds.labels[ids])
        reg = get_registry()
        if reg.enabled:
            reg.inc(counter)
        yield tup


def whole_graph_dataset(graphs: List[SyntheticGraph]) -> SegmentedDataset:
    """Full Graph Training baseline: each graph is ONE segment padded to the
    dataset max — memory scales with the largest graph (the paper's OOM case)."""
    m_max = max(len(g.x) for g in graphs)
    e_max = max(len(g.edges) for g in graphs)
    n, F = len(graphs), graphs[0].x.shape[1]
    X = np.zeros((n, 1, m_max, F), np.float32)
    E = np.zeros((n, 1, e_max, 2), np.int32)
    EV = np.zeros((n, 1, e_max), np.float32)
    NV = np.zeros((n, 1, m_max), np.float32)
    SV = np.ones((n, 1), np.float32)
    labels = np.asarray([g.label for g in graphs])
    labels = labels.astype(np.int32 if np.issubdtype(labels.dtype, np.integer) else np.float32)
    for gi, g in enumerate(graphs):
        X[gi, 0, : len(g.x)] = g.x
        E[gi, 0, : len(g.edges)] = g.edges
        EV[gi, 0, : len(g.edges)] = 1.0
        NV[gi, 0, : len(g.x)] = 1.0
    return SegmentedDataset(X, E, EV, NV, SV, labels, 1, m_max, e_max)
