"""Reduce a JAX profiler trace (``.xplane.pb``) to device busy and idle
time, kernel time, exposed collective time and the host's spans in the
device's idle gaps.

Device planes are named ``/device:TPU:<n>``.  Their ``XLA Ops`` line holds
one event per executed HLO operation, named by the operation's HLO text;
its instruction name (``%name = ...``) is kept.  A Pallas kernel is a
``tpu_custom_call``, whose instruction name carries the name of the jitted
wrapper that launched it (``batched_neighbor_sum`` for the SpMM,
``sed_aggregate`` for the SED pooling), not the kernel function's.  The
``Async XLA Ops`` line holds the asynchronous parts of operations, such as
collectives in flight; it counts for exposed collectives, not for busy
time.  The ``XLA Modules`` line holds one event per program run: the
benchmark runs its one-op program ``bench_mark`` at each end of the
traced window, and the window lies between the two runs.  The host's
spans are the driver's own, by its clock, from the first ``bench_mark``
run on; they are placed at that run's start on the device.  Times are in
seconds.
"""
from __future__ import annotations

import gzip
import re
from collections import defaultdict
from dataclasses import dataclass
from typing import Dict, List, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, ASYNC_LINE, MODULES_LINE = "XLA Ops", "Async XLA Ops", "XLA Modules"
MARK = "jit_bench_mark"
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
COLLECTIVE = re.compile(r"^(all-reduce|all-gather|all-to-all|"
                        r"collective-permute|reduce-scatter|"
                        r"collective-broadcast|send|recv)")


Event = Tuple[float, float, str]      # (start, end, name)


@dataclass
class Trace:
    ops: Dict[int, List[Event]]          # device -> its XLA Ops
    kernels: Dict[int, List[Event]]      # device -> its Pallas kernels
    async_ops: Dict[int, List[Event]]    # device -> its Async XLA Ops
    spans: List[Event]                   # the host's spans
    window: Interval


def op_name(hlo: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def load(path: str, spans=(), host_window_s: float = 0.0) -> Trace:
    """Read a trace (``.xplane.pb``, or gzipped ``.xplane.pb.gz``) with the
    host's ``spans`` ``(start, end, name)``; device ops and spans are
    clipped to the window.  Without ``bench_mark`` runs (no device plane)
    the window is ``host_window_s`` long and spans keep their times."""
    from jax.profiler import ProfileData
    if str(path).endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(str(path))
    ops: Dict[int, List[Event]] = {}
    kernels: Dict[int, List[Event]] = {}
    async_ops: Dict[int, List[Event]] = {}
    marks: Dict[int, List[Event]] = {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        d = int(m.group(1))
        ops[d], kernels[d], async_ops[d], marks[d] = [], [], [], []
        for line in plane.lines:
            if line.name not in (OPS_LINE, ASYNC_LINE, MODULES_LINE):
                continue
            for e in line.events:
                s = e.start_ns * 1e-9
                ev = (s, s + e.duration_ns * 1e-9, op_name(e.name))
                if line.name == MODULES_LINE:
                    if e.name.startswith(MARK):
                        marks[d].append(ev)
                elif line.name == ASYNC_LINE:
                    async_ops[d].append(ev)
                else:
                    ops[d].append(ev)
                    if KERNEL_MARK in e.name:
                        kernels[d].append(ev)
    marks = sorted(marks[min(marks)]) if marks else []
    if len(marks) >= 2:
        window, shift = (marks[0][0], marks[-1][1]), marks[0][0]
    else:
        window, shift = (0.0, host_window_s), 0.0
    spans = [(s + shift, e + shift, n) for s, e, n in spans]
    clip = lambda evs: [(max(s, window[0]), min(e, window[1]), n)
                        for s, e, n in evs if e > window[0] and s < window[1]]
    per_device = lambda x: {d: sorted(clip(evs)) for d, evs in x.items()
                            if d in ops and ops[d]}
    return Trace(per_device(ops), per_device(kernels), per_device(async_ops),
                 sorted(clip(spans)), window)


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals: List[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Parts of the union ``a`` not covered by the union ``b``."""
    out, b, j = [], union(b), 0
    for s, e in union(a):
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def window_s(tr: Trace) -> float:
    return tr.window[1] - tr.window[0]


def busy_s(tr: Trace) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    if not tr.ops:
        return 0.0
    return sum(total(union([(s, e) for s, e, _ in evs]))
               for evs in tr.ops.values()) / len(tr.ops)


def kernel_s(tr: Trace, wrapper: str) -> Tuple[float, int]:
    """Seconds and calls per device of the Pallas kernels launched by the
    jitted ``wrapper``."""
    if not tr.kernels:
        return 0.0, 0
    t = n = 0
    for evs in tr.kernels.values():
        for s, e, name in evs:
            if wrapper in name:
                t += e - s
                n += 1
    return t / len(tr.kernels), n // len(tr.kernels)


def exposed_collective_s(tr: Trace) -> float:
    """Collective time during which no other operation ran on that device,
    averaged over the devices."""
    if not tr.ops:
        return 0.0
    out = 0.0
    for d, evs in tr.ops.items():
        coll = [(s, e) for s, e, n in evs + tr.async_ops.get(d, [])
                if COLLECTIVE.match(n)]
        comp = [(s, e) for s, e, n in evs if not COLLECTIVE.match(n)]
        out += total(subtract(coll, comp))
    return out / len(tr.ops)


def has_collectives(tr: Trace) -> bool:
    return any(COLLECTIVE.match(n) for x in (tr.ops, tr.async_ops)
               for evs in x.values() for _, _, n in evs)


def top_ops(tr: Trace, k: int = 10) -> List[List]:
    """The device operations that took most time, per device."""
    acc: Dict[str, float] = defaultdict(float)
    for evs in tr.ops.values():
        for s, e, name in evs:
            acc[name] += e - s
    n = max(len(tr.ops), 1)
    return [[name, t / n] for name, t in
            sorted(acc.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(tr: Trace, k: int = 10) -> List[List]:
    """Device idle time (averaged over devices) attributed to the host span
    that covered it; idle time under no span is ``unattributed``.  Where
    spans nest, the innermost (latest-starting) one takes the time."""
    acc: Dict[str, float] = defaultdict(float)
    spans = tr.spans
    n = max(len(tr.ops), 1)
    for evs in tr.ops.values():
        gaps = subtract([tr.window], [(s, e) for s, e, _ in evs])
        for gs, ge in gaps:
            cov = []
            for ss, se, name in spans:
                if se <= gs or ss >= ge:
                    continue
                cov.append((max(ss, gs), min(se, ge), name))
            # innermost first: a later start wins the overlap
            claimed: List[Interval] = []
            for s, e, name in sorted(cov, key=lambda c: -c[0]):
                part = subtract([(s, e)], claimed)
                acc[name] += total(part)
                claimed = union(claimed + part)
            acc["unattributed"] += (ge - gs) - total(claimed)
    return [[name, t / n] for name, t in
            sorted(acc.items(), key=lambda kv: -kv[1])[:k] if t > 0]
