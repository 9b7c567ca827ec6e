"""Static-shape segment batching (the XLA adaptation of the paper's pipeline).

Each segment is padded to (m_max nodes, e_max edges) with validity masks;
each graph is padded to J_max segments with a segment mask.  Edges are local
to a segment (indices into the segment's node list); cross-segment edges are
dropped — the paper's Table 6 ablation shows locality-preserving partitions
make this information loss negligible.

The dataset lives in host numpy.  Its per-segment arrays are also copied
once to the default device, each example's elements together, when they
take at most half of the device's free memory less what the step needs
beside them (``SegmentedDataset.resident``).  ``batch_iterator`` then
hands the step a ``ResidentSegments`` handle: the resident arrays and the
batch's ids, from which the step gathers only the segments it encodes
(``take``), or every one (``whole``).  Otherwise it gathers each batch on
the host.
"""
from __future__ import annotations

import math
import threading
import weakref
from collections.abc import Mapping
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
def _gather_whole(arrays: Dict[str, jax.Array], ids,
                  shapes: Tuple[Tuple[str, Tuple[int, ...]], ...]
                  ) -> Dict[str, jax.Array]:
    """Every segment of the examples ``ids`` of the resident arrays named
    in ``shapes``: one dynamic slice per id, stacked, in each field's
    example shape.  On a TPU v5e (n 64, J 190, m 256, F 140, B 16) this
    took 5.9 ms for ``x`` in rows of 128 and 7.9 ms from (n, J, m, F),
    where ``jnp.take`` first copied the whole array (14.2 ms) and a loop
    of dynamic updates took 22.9 ms; it was the feeder's gather of every
    batch until the step gathered only its sampled segments."""
    b = ids.shape[0]
    return {k: jnp.stack([lax.dynamic_index_in_dim(arrays[k], ids[i],
                                                   keepdims=False)
                          for i in range(b)]).reshape((b,) + shape)
            for k, shape in shapes}


def _segment(a: jax.Array, i, j, shape: Tuple[int, ...]) -> jax.Array:
    """Segment ``j`` of example ``i`` of the resident array ``a``, whose
    examples have ``shape`` (J, ...): one dynamic slice of the example's
    rows of 128 (``_in_rows``), or of the example as it is."""
    seg = tuple(shape[1:])
    size = math.prod(seg)
    if a.shape[1:] == tuple(shape):
        return lax.dynamic_slice(a, (i, j) + (0,) * len(seg),
                                 (1, 1) + seg).reshape(seg)
    if size % 128 == 0:                 # the segment's own whole rows
        r = size // 128
        return lax.dynamic_slice(a, (i, j * r, 0), (1, r, 128)).reshape(seg)
    # the rows that hold the segment, moved back at the example's end so
    # that the slice is never clamped, then the segment out of them
    rows = a.shape[1]
    w = min(rows, -(-size // 128) + 1)
    start = jnp.minimum(j * size // 128, rows - w)
    piece = lax.dynamic_slice(a, (i, start, 0), (1, w, 128)).reshape(-1)
    return lax.dynamic_slice(piece, (j * size - 128 * start,),
                             (size,)).reshape(seg)


@partial(jax.tree_util.register_dataclass, data_fields=("arrays", "ids"),
         meta_fields=("shapes",))
@dataclass(frozen=True, eq=False)
class ResidentSegments(Mapping):
    """A batch's ``seg_inputs`` left on the device: the resident arrays
    as ``SegmentedDataset.resident`` placed them, the batch's dataset ids
    ((B,) int32) and each field's example shape (static).  A pytree whose
    leaves are the arrays and the ids, so that a jitted step receives the
    resident copy and gathers from it: ``take(idx)`` the B*S segments it
    encodes, ``whole()`` every segment.  Read as a mapping (``items()``,
    ``[k]``) it answers with ``whole()``, gathered where it is called.
    Its leaves may be abstract (shapes, tracers)."""
    arrays: Dict[str, jax.Array]
    ids: jax.Array
    shapes: Tuple[Tuple[str, Tuple[int, ...]], ...]

    def take(self, idx) -> Dict[str, jax.Array]:
        """The segments ``idx`` (B, S) of each example, (B, S, ...) a
        field: one dynamic slice a segment.  An index reads as
        ``jnp.take_along_axis`` reads it on the gathered batch: a negative
        one counts from the end, and one out of range gives its fill."""
        b, s = idx.shape
        J = self.shapes[0][1][0]
        idx = jnp.where(idx < 0, idx + J, idx)
        inside = (idx >= 0) & (idx <= J - 1)
        out = {}
        for k, shape in self.shapes:
            segs = jnp.stack([_segment(self.arrays[k], self.ids[i],
                                       idx[i, j], shape)
                              for i in range(b) for j in range(s)]
                             ).reshape((b, s) + shape[1:])
            fill = (jnp.nan if jnp.issubdtype(segs.dtype, jnp.inexact)
                    else jnp.iinfo(segs.dtype).min)
            out[k] = jnp.where(
                inside.reshape(inside.shape + (1,) * (len(shape) - 1)),
                segs, fill)
        return out

    def whole(self) -> Dict[str, jax.Array]:
        """Every segment, (B, J, ...) a field: ``seg_inputs(ids)``."""
        return _gather_whole(self.arrays, self.ids, self.shapes)

    def __getitem__(self, k: str) -> jax.Array:
        shape = dict(self.shapes)[k]
        return _gather_whole(self.arrays, self.ids, ((k, shape),))[k]

    def __iter__(self):
        return (k for k, _ in self.shapes)

    def __len__(self) -> int:
        return len(self.shapes)

    def __contains__(self, k) -> bool:
        return k in dict(self.shapes)

    def items(self):
        return self.whole().items()

    def values(self):
        return self.whole().values()


def _take_rows(arrays: Dict[str, jax.Array], ids,
               shapes: Tuple[Tuple[str, Tuple[int, ...]], ...]
               ) -> ResidentSegments:
    """Where dataset ids meet the resident rows: the handle through which
    a step gathers the examples ``ids`` of ``arrays`` (each field of
    example shape ``shapes``), with the ids put on the device."""
    return ResidentSegments(arrays, jnp.asarray(ids, jnp.int32), shapes)


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

    def device_seg_inputs(self, ids: np.ndarray) -> ResidentSegments:
        """The examples ``ids`` of the resident copy, as a handle that the
        step gathers from: its ``whole()`` is ``seg_inputs(ids)`` and its
        ``take(idx)`` the segments ``idx`` of it, bit for bit, on the
        device.  The device gather would clamp an id out of range, so such
        an id raises here."""
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
    ``seg_inputs`` is a ``ResidentSegments`` handle on the dataset's
    resident copy where it has one (decided at the first batch, when the
    dataset has not decided yet), from which the step gathers; else the
    host gather, a dict of numpy arrays.  The rest is host numpy.  Each
    batch's assembly is one ``feeder.assemble`` span, closed before the
    yield so that it never covers the consumer's work, and counts as a
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
