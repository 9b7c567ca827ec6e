"""Reductions of the program's own spans (``repro.obs``) in a traced window.

The program names the layers of its single-chip training step:
``feeder.assemble`` (the host gather of the batch's padded segments),
``feeder.put`` (its host-to-device puts), ``store.prepare`` (row routing),
``train.step`` (the step's dispatch) and its child ``train.wait`` (the wait
for the loss).  Spans whose names start with ``bench.`` are the driver's
own.  Times are in seconds; a span that is absent reads ``None``.
"""
from __future__ import annotations

from typing import Optional

import trace_reduce as TR

DRIVER_PREFIX = "bench."


def span_s(tr: TR.Trace, name: str) -> Optional[float]:
    """Seconds of the window in which a span named ``name`` was open."""
    iv = [(s, e) for s, e, n in tr.spans if n == name]
    return TR.total(TR.union(iv)) if iv else None


def idle_outside_s(tr: TR.Trace) -> Optional[float]:
    """Device idle seconds (averaged over the devices) under no program
    span; the driver's spans do not count as cover."""
    cover = [(s, e) for s, e, n in tr.spans
             if not n.startswith(DRIVER_PREFIX)]
    if not cover or not tr.ops:
        return None
    out = 0.0
    for evs in tr.ops.values():
        idle = TR.subtract([tr.window], [(s, e) for s, e, _ in evs])
        out += TR.total(TR.subtract(idle, cover))
    return out / len(tr.ops)


def per_step_ms(run, seconds: Optional[float]) -> Optional[float]:
    """``seconds`` over the traced window's steps, in milliseconds."""
    if seconds is None or not run.steps:
        return None
    return seconds / run.steps * 1e3
