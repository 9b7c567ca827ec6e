"""Async double-buffered host→device segment pipeline (dist subsystem).

Training steps should never wait on the host.  The synchronous loop does
``gather batch -> device_put -> step`` serially, so segment assembly (a
numpy gather over the SegmentedDataset) and the host→device copy sit on
the critical path every iteration.  ``AsyncSegmentFeeder`` moves both off
it: a background thread assembles the NEXT batch and ``jax.device_put``s
it onto the mesh (sharded on the batch dim) while the CURRENT step runs,
keeping up to ``depth`` device-resident batches in flight (depth=2 =
classic double buffering).

Both feeders expose the same iterator protocol (the async one is
single-shot — build one per epoch; the id schedule is the reusable part)
and count their host-blocked milliseconds — the time ``next()`` spends
before a device batch is available — on the same trace, so that the two
can be compared where they run.

``put_fn`` owns what a delivered item IS: launch/train_dist.py's put
calls ``store.begin`` (tiered-table residency bookkeeping + staging,
safe on this producer thread) and returns ``(prep, device_batch)`` —
the consumer commits each staged migration in delivery order.  The
matching device→host lane is the AsyncHostWriter re-exported below.

Padding policy is SHARED with serving: ``shared_bucket`` picks the
(m_max, e_max) shape from the serve bucket ladder (serve/buckets.py) and
``segment_dataset_shared`` pads the training dataset to it via the same
``graphs/batching.py::pad_segment``.  One shared caveat inherited from
the ladder: training uses ONE static shape (the rung fitting
max_seg_nodes) while serving routes each segment to the smallest rung it
fits, so padded bytes — and serving-cache fingerprints — coincide exactly
for the segments serving routes to that same rung; smaller segments land
in smaller rungs with their own addresses.
"""
from __future__ import annotations

import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Iterator, List, Optional, Tuple

import numpy as np

from repro.core import gst as G
from repro.graphs import batching as Bt
from repro.obs.memory import get_probe, tree_nbytes
from repro.obs.metrics import get_registry
from repro.obs.trace import span
from repro.serve.buckets import BucketSpec, choose_bucket, default_ladder


# ---------------------------------------------------------------------------
# shared train/serve padding policy
# ---------------------------------------------------------------------------


def shared_bucket(max_seg_nodes: int, batch: int = 8,
                  ladder: Optional[Tuple[BucketSpec, ...]] = None) -> BucketSpec:
    """The serve-ladder bucket a training run pads to: smallest rung fitting
    ``max_seg_nodes`` (its e_max = 8x nodes covers the synthetic densities;
    oversized edge lists truncate exactly as in serving)."""
    ladder = ladder or default_ladder(max_seg_nodes, batch=batch)
    return ladder[choose_bucket(ladder, max_seg_nodes, 0)]


def segment_dataset_shared(graphs, max_seg_nodes: int = 64, *,
                           method: str = "bfs", seed: int = 0,
                           j_max: Optional[int] = None,
                           ) -> Tuple[Bt.SegmentedDataset, BucketSpec]:
    """``Bt.segment_dataset`` padded to the serve bucket ladder's shapes."""
    spec = shared_bucket(max_seg_nodes)
    ds = Bt.segment_dataset(graphs, spec.m_max, method=method, seed=seed,
                            j_max=j_max, e_max=spec.e_max)
    return ds, spec


# ---------------------------------------------------------------------------
# async device→host write-back lane
# ---------------------------------------------------------------------------

# The opposite lane of this pipeline: the tiered embedding store submits its
# eviction write-backs to an AsyncHostWriter so the device_get + host-array
# copy overlaps with the running step.  The class itself lives under store/
# (import-graph leaf); re-exported here because it IS the pipeline's
# device→host half.
from repro.store.writeback import AsyncHostWriter  # noqa: E402,F401


# ---------------------------------------------------------------------------
# feeders
# ---------------------------------------------------------------------------


@dataclass
class FeederStats:
    batches: int = 0
    host_blocked_ms: float = 0.0     # time next() waited on host work
    put_ms: float = 0.0              # device_put time (async: off-thread)
    blocked_per_batch: List[float] = field(default_factory=list)

    @property
    def host_blocked_ms_per_batch(self) -> float:
        return self.host_blocked_ms / max(self.batches, 1)

    def record_batch(self, blocked_ms: float) -> None:
        """One delivered batch: local stats + the registry mirror (the
        local lists/floats stay for the CLI's report and tests)."""
        self.batches += 1
        self.host_blocked_ms += blocked_ms
        self.blocked_per_batch.append(blocked_ms)
        reg = get_registry()
        if reg.enabled:
            reg.inc("feeder.batches")
            reg.inc("feeder.host_blocked_ms", blocked_ms, unit="ms")


def _assemble(ds: Bt.SegmentedDataset, ids: np.ndarray) -> G.GSTBatch:
    """Host-side batch assembly (the numpy gather) as a GSTBatch of numpy
    arrays, batch_pos = global table rows' positions within this batch."""
    return G.GSTBatch(ds.seg_inputs(ids), ds.seg_valid[ids],
                      ids.astype(np.int32), ds.labels[ids],
                      np.arange(len(ids), dtype=np.int32))


def epoch_ids(ds: Bt.SegmentedDataset, batch_size: int, *,
              rng: np.random.Generator, shuffle: bool = True) -> List[np.ndarray]:
    """The id schedule of one epoch, precomputed so sync and async feeders
    can replay the IDENTICAL trace — same policy as ``batch_iterator``
    (one shared implementation: graphs/batching.py::batch_id_schedule)."""
    return Bt.batch_id_schedule(ds.n, batch_size, rng=rng, shuffle=shuffle)


class SyncSegmentFeeder:
    """Baseline feeder: assemble + device_put inline on the consumer thread
    (all host work is blocked time by construction)."""

    def __init__(self, ds: Bt.SegmentedDataset, id_schedule: List[np.ndarray],
                 put_fn: Callable[[G.GSTBatch], Any]):
        self._ds = ds
        self._sched = id_schedule
        self._put = put_fn
        self.stats = FeederStats()

    def __iter__(self) -> Iterator[G.GSTBatch]:
        for ids in self._sched:
            t0 = time.perf_counter()
            with span("feeder.assemble", batch=len(ids)):
                host = _assemble(self._ds, ids)
            p = get_probe()
            if p.enabled:
                p.observe_host("feeder.staging", tree_nbytes(host))
            t1 = time.perf_counter()
            with span("feeder.put"):
                dev = self._put(host)
            t2 = time.perf_counter()
            blocked = (t2 - t0) * 1e3
            self.stats.put_ms += (t2 - t1) * 1e3
            self.stats.record_batch(blocked)
            yield dev


class AsyncSegmentFeeder:
    """Double-buffered feeder: a daemon thread assembles and device_puts
    batch k+1..k+depth while the consumer runs step k; ``next()`` only
    blocks when the producer hasn't caught up.

    Abandoning the iterator mid-epoch (a step raising, a break) closes the
    feeder: the producer is signalled to stop and the queued device batches
    are dropped instead of staying referenced by a forever-blocked thread."""

    _DONE = object()

    def __init__(self, ds: Bt.SegmentedDataset, id_schedule: List[np.ndarray],
                 put_fn: Callable[[G.GSTBatch], Any], *, depth: int = 2):
        if depth < 1:
            raise ValueError("depth must be >= 1")
        self._ds = ds
        self._sched = id_schedule
        self._put = put_fn
        self._q: "queue.Queue" = queue.Queue(maxsize=depth)
        self._exc: Optional[BaseException] = None
        self._stop = threading.Event()
        self._consumed = False
        self.stats = FeederStats()
        self._thread = threading.Thread(target=self._produce, daemon=True)
        self._thread.start()

    def _put_q(self, item) -> bool:
        """Stop-aware blocking put; False when the feeder was closed."""
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def _produce(self):
        try:
            for ids in self._sched:
                if self._stop.is_set():
                    return
                t1 = time.perf_counter()
                with span("feeder.assemble", batch=len(ids)):
                    host = _assemble(self._ds, ids)
                p = get_probe()
                if p.enabled:
                    p.observe_host("feeder.staging", tree_nbytes(host))
                with span("feeder.put"):
                    dev = self._put(host)
                self.stats.put_ms += (time.perf_counter() - t1) * 1e3
                if not self._put_q(dev):
                    return
        except BaseException as e:  # surfaced on the consumer side
            self._exc = e
        finally:
            self._put_q(self._DONE)

    def close(self) -> None:
        """Stop the producer and release the in-flight device batches."""
        self._stop.set()

        def drain():
            try:
                while True:
                    self._q.get_nowait()
            except queue.Empty:
                pass

        drain()  # wake a put-blocked producer immediately
        self._thread.join(timeout=5.0)
        drain()  # a put racing past the first drain may have landed

    def __iter__(self) -> Iterator[G.GSTBatch]:
        if self._consumed:  # the producer ran once; a re-iteration would
            raise RuntimeError(  # block forever on the empty queue
                "AsyncSegmentFeeder is single-shot — construct a new feeder "
                "per epoch (the id schedule is the reusable part)")
        self._consumed = True
        try:
            while True:
                t0 = time.perf_counter()
                with span("feeder.wait"):
                    item = self._q.get()
                blocked = (time.perf_counter() - t0) * 1e3
                if item is self._DONE:
                    self._thread.join()
                    if self._exc is not None:
                        raise self._exc
                    return
                self.stats.record_batch(blocked)
                yield item
        finally:  # abandoned mid-epoch (break / step raised) -> shut down
            self.close()


def make_feeder(kind: str, ds: Bt.SegmentedDataset,
                id_schedule: List[np.ndarray],
                put_fn: Callable[[G.GSTBatch], Any], *,
                depth: int = 2):
    if kind == "async":
        return AsyncSegmentFeeder(ds, id_schedule, put_fn, depth=depth)
    if kind == "sync":
        return SyncSegmentFeeder(ds, id_schedule, put_fn)
    raise ValueError(f"unknown feeder kind {kind!r}")


# ---------------------------------------------------------------------------
# prefetch lane (lookahead exchange dispatch, ISSUE 9)
# ---------------------------------------------------------------------------


class PrefetchLane:
    """One-item lookahead over a feeder that dispatches the NEXT batch's
    exchange lookup before the CURRENT step launches.

    Wraps any feeder (sync or async) and calls ``dispatch_fn(item)``
    exactly once per delivered item, at pull time — i.e. for batch k+1
    this runs right BEFORE the driver launches step k, so the prefetch
    collective it issues (dist/train.py::make_prefetch_lookup) is
    enqueued ahead of the table-donating step and its hops overlap step
    k's compute.  The driver's dispatch closure is also where
    ``store.commit`` for the next migration belongs (the prefetch must
    read the post-commit table).

    Yields ``(item, handle, nxt_item, nxt_handle)``: the current item,
    the value ``dispatch_fn`` returned for it (the driver only consumes
    this on the FIRST batch — afterwards it carries the step's patched
    buffer instead), and the looked-ahead next pair (``None``/``None``
    on the last batch, where the step patches a dummy).

    Error propagation: a ``dispatch_fn`` failure (or an abandoned
    iteration) closes the wrapped feeder before the exception surfaces,
    so its producer thread never blocks on a dead consumer.  Counters:
    ``feeder.prefetch_batches`` / ``feeder.prefetch_dispatch_ms`` mirror
    to the metrics registry beside the wrapped feeder's own stats."""

    def __init__(self, feeder, dispatch_fn: Callable[[Any], Any]):
        self._feeder = feeder
        self._dispatch = dispatch_fn
        self.prefetch_batches = 0
        self.dispatch_ms = 0.0

    @property
    def stats(self) -> FeederStats:
        return self._feeder.stats

    def _dispatch_timed(self, item):
        t0 = time.perf_counter()
        with span("feeder.prefetch_dispatch"):
            handle = self._dispatch(item)
        dt = (time.perf_counter() - t0) * 1e3
        self.prefetch_batches += 1
        self.dispatch_ms += dt
        reg = get_registry()
        if reg.enabled:
            reg.inc("feeder.prefetch_batches")
            reg.inc("feeder.prefetch_dispatch_ms", dt, unit="ms")
        return handle

    def close(self) -> None:
        close = getattr(self._feeder, "close", None)
        if close is not None:
            close()

    def __iter__(self):
        it = iter(self._feeder)
        try:
            try:
                cur = next(it)
            except StopIteration:
                return
            cur_h = self._dispatch_timed(cur)
            while True:
                try:
                    nxt = next(it)
                except StopIteration:
                    nxt, nxt_h = None, None
                else:
                    nxt_h = self._dispatch_timed(nxt)
                yield cur, cur_h, nxt, nxt_h
                if nxt is None:
                    return
                cur, cur_h = nxt, nxt_h
        finally:
            self.close()
