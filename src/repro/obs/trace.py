"""Span-based tracing -> Chrome-trace JSON (chrome://tracing / Perfetto).

One tracer covers every thread of a run: the consumer's train step and
store commits, the feeder thread's batch assembly + device_put, the
AsyncHostWriter's eviction write-backs, and the serve request path
(window -> bucket encode -> cache insert -> gather -> head).  Spans are
recorded as *complete* ("X") events — one record per finished span,
exported with ``ts``/``dur`` in microseconds — which both viewers load
directly.

Each span records its parent: the span that enclosed it on the same
thread when it began (a per-thread stack, kept only by a live
:class:`Tracer`).  Times are read from ``time.perf_counter``, the clock a
host program shares with anything else in the process that reads it, so
``Tracer.spans()`` can be laid against another record of that clock (a
device profile anchored to it, say).  The export names the clock and the
epoch its ``ts`` counts from.  Once a tracer is installed, every backend
compile JAX reports also becomes a ``jit.compile`` span.

Like the metrics registry, tracing is host-side only (spans wrap jit
*dispatch*, never run inside traced code) and the disabled path is free:
the module-global tracer defaults to :class:`NullTracer`, whose
``span()`` returns one shared reusable no-op context manager.

``jax_annotations=True`` additionally enters
``jax.profiler.TraceAnnotation(name)`` for every span, so the same span
names line up inside a captured device profile when one is taken.
"""
from __future__ import annotations

import json
import os
import threading
import time
from typing import Dict, List, Optional, Tuple

CLOCK = "time.perf_counter"
# the duration JAX reports when a backend compile (or its cache lookup) ends
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# (start, end, name, parent): seconds on CLOCK; parent is None at top level
SpanRecord = Tuple[float, float, str, Optional[str]]


class _NullSpan:
    """Reusable no-op context manager (the disabled-tracing path)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    __slots__ = ("_tracer", "name", "args", "parent", "_t0", "_jax_ctx")

    def __init__(self, tracer: "Tracer", name: str, args: Optional[Dict]):
        self._tracer = tracer
        self.name = name
        self.args = args
        self.parent = None
        self._t0 = 0
        self._jax_ctx = None

    def __enter__(self):
        if self._tracer._jax_annotations:
            ctx = _jax_annotation(self.name)
            if ctx is not None:
                self._jax_ctx = ctx
                ctx.__enter__()
        stack = self._tracer._stack()
        self.parent = stack[-1] if stack else None
        stack.append(self.name)
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        self._tracer._stack().pop()
        if self._jax_ctx is not None:
            self._jax_ctx.__exit__(*exc)
        self._tracer._record(self.name, self._t0, t1, self.args, self.parent)
        return False


def _jax_annotation(name: str):
    """jax.profiler.TraceAnnotation passthrough, or None when jax (or the
    profiler) is unavailable — tracing must not import-require jax."""
    try:
        from jax.profiler import TraceAnnotation
        return TraceAnnotation(name)
    except Exception:
        return None


class Tracer:
    """Collects spans from any thread; ``export()`` writes Chrome JSON."""

    enabled = True

    def __init__(self, *, jax_annotations: bool = False):
        self._lock = threading.Lock()
        # finished spans: (t0_ns, t1_ns, name, parent, tid, args)
        self._spans: List[Tuple] = []
        self._events: List[Dict] = []          # counter events
        self._thread_names: Dict[int, str] = {}
        self._local = threading.local()        # per-thread open-span stack
        self._jax_annotations = jax_annotations
        self._epoch_ns = time.perf_counter_ns()
        self._pid = os.getpid()

    # -- recording ---------------------------------------------------------

    def span(self, name: str, **args) -> _Span:
        """``with tracer.span("train.step", epoch=3): ...`` — records one
        complete event when the block exits (exception included, so a
        failing step still shows its span)."""
        return _Span(self, name, args or None)

    def counter(self, name: str, **values) -> None:
        """Chrome "C" counter event: each kwarg is one numeric series under
        ``name``, rendered by the viewers as a timeline counter track —
        live bytes (the obs.memory probe), queue depths, occupancy.  Only
        numeric values are recorded; at least one is required."""
        series = {k: float(v) for k, v in values.items()
                  if isinstance(v, (int, float)) and not isinstance(v, bool)}
        if not series:
            raise ValueError(f"counter {name!r} needs at least one numeric "
                             f"series (got {sorted(values)})")
        ts = (time.perf_counter_ns() - self._epoch_ns) // 1000
        ev = {"name": name, "ph": "C", "ts": ts, "pid": self._pid,
              "tid": self._tid(), "args": series}
        with self._lock:
            self._events.append(ev)

    def ended(self, name: str, duration_s: float, **args) -> None:
        """Record a span that ends now and lasted ``duration_s`` (work that
        reports its length only once it is done, such as a compile).  Its
        parent is the span open on this thread; it starts no earlier than
        the tracer itself."""
        t1 = time.perf_counter_ns()
        t0 = max(t1 - int(duration_s * 1e9), self._epoch_ns)
        stack = self._stack()
        self._record(name, t0, t1, args or None, stack[-1] if stack else None)

    def _stack(self) -> List[str]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _record(self, name: str, t0_ns: int, t1_ns: int,
                args: Optional[Dict], parent: Optional[str]) -> None:
        rec = (t0_ns, t1_ns, name, parent, self._tid(), args)
        with self._lock:
            self._spans.append(rec)

    def _tid(self) -> int:
        t = threading.current_thread()
        tid = t.ident or 0
        if tid not in self._thread_names:
            with self._lock:
                self._thread_names.setdefault(tid, t.name)
        return tid

    # -- views / export ----------------------------------------------------

    def spans(self) -> List[SpanRecord]:
        """Every finished span as ``(start_s, end_s, name, parent)``, in
        seconds on ``time.perf_counter`` (``CLOCK``), in the order they
        ended."""
        with self._lock:
            recs = list(self._spans)
        return [(t0 / 1e9, t1 / 1e9, name, parent)
                for t0, t1, name, parent, _, _ in recs]

    def _chrome_span(self, rec: Tuple) -> Dict:
        t0, t1, name, _, tid, args = rec
        ev = {"name": name, "ph": "X", "ts": (t0 - self._epoch_ns) // 1000,
              "dur": max((t1 - t0) // 1000, 1), "pid": self._pid, "tid": tid}
        if args:
            ev["args"] = args
        return ev

    def events(self) -> List[Dict]:
        """Chrome events: the finished spans, then the counters."""
        with self._lock:
            recs, counters = list(self._spans), list(self._events)
        return [self._chrome_span(r) for r in recs] + counters

    def __len__(self) -> int:
        with self._lock:
            return len(self._spans) + len(self._events)

    # export order at equal ts: spans before counters, and longer spans
    # (parents) before shorter ones — spans are recorded at EXIT while
    # counters are recorded live, so raw order from multiple threads
    # interleaves them nondeterministically
    _PH_ORDER = {"X": 0, "C": 1}

    def export(self, path: str) -> str:
        """Write ``{"traceEvents": [...]}`` Chrome/Perfetto JSON: the
        recorded spans plus one thread-name metadata event per thread
        seen, sorted on a total deterministic key (ts, phase, -dur, tid)
        so the stream is ts-monotonic — and stable across reruns — even
        when counter and span events interleave from multiple threads.
        ``otherData`` names the clock and the epoch (seconds on it) that
        ``ts`` counts microseconds from."""
        events = sorted(
            self.events(),
            key=lambda e: (e["ts"], self._PH_ORDER.get(e["ph"], 2),
                           -e.get("dur", 0), e.get("tid", 0)))
        with self._lock:
            names = dict(self._thread_names)
        meta = [{"name": "thread_name", "ph": "M", "pid": self._pid,
                 "tid": tid, "args": {"name": tname}}
                for tid, tname in sorted(names.items())]
        payload = {"traceEvents": meta + events, "displayTimeUnit": "ms",
                   "otherData": {"clock": CLOCK,
                                 "epoch_s": self._epoch_ns / 1e9}}
        with open(path, "w") as f:
            json.dump(payload, f)
            f.write("\n")
        return path


class NullTracer:
    """The disabled path: span() hands back one shared no-op context."""

    enabled = False

    def span(self, name: str, **args) -> _NullSpan:
        return _NULL_SPAN

    def counter(self, name: str, **values) -> None:
        pass

    def ended(self, name: str, duration_s: float, **args) -> None:
        pass

    def spans(self) -> List[SpanRecord]:
        return []

    def events(self) -> List[Dict]:
        return []

    def __len__(self) -> int:
        return 0

    def export(self, path: str) -> str:
        raise RuntimeError("NullTracer has nothing to export — enable "
                           "tracing (--trace-out) first")


_NULL_TRACER = NullTracer()
_tracer = _NULL_TRACER


def get_tracer():
    return _tracer


def set_tracer(tracer) -> object:
    """Install ``tracer`` process-wide; returns the previous tracer.  The
    first live tracer also hooks JAX's compile reports (``jit.compile``)."""
    global _tracer
    if tracer.enabled:
        _listen_for_compiles()
    prev = _tracer
    _tracer = tracer
    return prev


def _on_compile(event: str, duration_s: float, **kw) -> None:
    if event == COMPILE_EVENT:       # kw: what JAX passes (``fun_name``)
        _tracer.ended("jit.compile", duration_s, **kw)


_compile_listener_on = False


def _listen_for_compiles() -> None:
    """Register ``_on_compile`` with ``jax.monitoring``, once per process
    (JAX keeps its listeners for the process's life)."""
    global _compile_listener_on
    if _compile_listener_on:
        return
    try:
        from jax import monitoring
    except ImportError:        # tracing must not import-require jax
        return
    monitoring.register_event_duration_secs_listener(_on_compile)
    _compile_listener_on = True


def null_tracer() -> NullTracer:
    return _NULL_TRACER


def span(name: str, **args):
    """``with span("serve.encode", bucket=2): ...`` against the current
    process-wide tracer — the one-liner instrumented code uses."""
    return _tracer.span(name, **args)


def counter(name: str, **values) -> None:
    """``counter("mem.device_bytes", train_step=4.2e5)`` against the
    current process-wide tracer (no-op on the NullTracer)."""
    _tracer.counter(name, **values)


def validate_chrome_trace(payload: Dict) -> List[str]:
    """Structural checks a Chrome-trace consumer relies on; returns a list
    of problems (empty = valid).  Used by tests and the CI obs gate."""
    problems: List[str] = []
    events = payload.get("traceEvents")
    if not isinstance(events, list):
        return ["traceEvents missing or not a list"]
    begins: Dict = {}
    last_ts = None
    for i, ev in enumerate(events):
        ph = ev.get("ph")
        if ph == "M":
            continue
        if not isinstance(ev.get("ts"), int) or ev["ts"] < 0:
            problems.append(f"event {i}: bad ts {ev.get('ts')!r}")
            continue
        if last_ts is not None and ev["ts"] < last_ts:
            problems.append(f"event {i}: ts not monotonic ({ev['ts']} < {last_ts})")
        last_ts = ev["ts"]
        if ph == "X":
            if not isinstance(ev.get("dur"), int) or ev["dur"] < 0:
                problems.append(f"event {i}: X event with bad dur")
        elif ph == "C":
            args = ev.get("args")
            if not isinstance(args, dict) or not args:
                problems.append(f"event {i}: C event without args series")
            elif not all(isinstance(v, (int, float))
                         and not isinstance(v, bool)
                         for v in args.values()):
                problems.append(f"event {i}: C event with non-numeric "
                                "series values")
        elif ph == "B":
            begins.setdefault((ev.get("pid"), ev.get("tid")), []).append(ev)
        elif ph == "E":
            stack = begins.get((ev.get("pid"), ev.get("tid")), [])
            if not stack:
                problems.append(f"event {i}: E without matching B")
            else:
                stack.pop()
        elif ph not in ("i", "I"):
            problems.append(f"event {i}: unsupported phase {ph!r}")
        if ph != "M" and ("pid" not in ev or "tid" not in ev):
            problems.append(f"event {i}: missing pid/tid")
    for key, stack in begins.items():
        if stack:
            problems.append(f"{len(stack)} unmatched B events on {key}")
    return problems
