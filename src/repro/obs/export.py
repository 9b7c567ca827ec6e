"""Telemetry export: periodic JSONL event log, final summaries, CLI glue.

The three CLIs (launch/train.py, launch/train_dist.py,
launch/serve_graphs.py) share one flag set (``add_obs_args``) and one
lifecycle object (:class:`Obs`):

    add_obs_args(ap)
    args = ap.parse_args()
    obs = Obs.from_args(args)          # installs registry + tracer globals
    ...
    obs.tick(step=..., epoch=...)      # JSONL line: per-interval deltas
    ...
    summary = obs.close(run_meta)      # summary JSONL line + trace export

JSONL stream format (one JSON object per line):

    {"type": "meta", "wall_time": ..., "argv": ..., **run_meta}
    {"type": "tick", "step": N, "wall_s": ..., "delta": {name: change},
     "gauges": {...}, **extra}         # delta() since the previous tick
    {"type": "event", "event": "...", **payload}
    {"type": "summary", "wall_s": ..., "metrics": {name: value|summary},
     **extra}                          # cumulative, report-grade

The final summary dict is also RETURNED to the caller, and the CI obs
gate (``python -m repro.obs.gate``) asserts its count contracts against
the same stream.
"""
from __future__ import annotations

import json
import sys
import time
from typing import Dict, Optional

from repro.obs.memory import MemoryProbe, null_probe, set_probe
from repro.obs.metrics import (MetricsRegistry, NullRegistry, get_registry,
                               null_registry, set_registry)
from repro.obs.trace import NullTracer, Tracer, null_tracer, set_tracer


def add_obs_args(ap) -> None:
    """The shared observability flag set (no-cost defaults: everything
    off)."""
    g = ap.add_argument_group("observability (repro.obs)")
    g.add_argument("--metrics", action="store_true",
                   help="enable the process-wide metrics registry "
                        "(store/exchange/feeder/serve counters, staleness "
                        "histograms); off = null registry, zero overhead")
    g.add_argument("--metrics-out", default=None, metavar="PATH",
                   help="write the JSONL telemetry stream (per-interval "
                        "ticks + final summary) here; implies --metrics")
    g.add_argument("--metrics-interval", type=int, default=1,
                   help="emit a JSONL tick every N intervals (epochs for "
                        "the trainers, windows for the serve replay)")
    g.add_argument("--trace-out", default=None, metavar="PATH",
                   help="record spans (train step, feeder, write-back "
                        "lane, serve request path) and write a Chrome-"
                        "trace JSON here (chrome://tracing / Perfetto)")
    g.add_argument("--jax-trace-annotations", action="store_true",
                   help="also enter jax.profiler.TraceAnnotation for each "
                        "span so span names line up inside a captured "
                        "device profile")
    g.add_argument("--mem-probe", action="store_true",
                   help="capture compiled.memory_analysis() / "
                        "cost_analysis() at every probed jit entry point "
                        "(train/refresh/finetune steps, serve bucket "
                        "encodes, store migrations), keyed by (site, "
                        "shape signature), publishing mem.device.* / "
                        "mem.host.* gauges; costs one extra AOT compile "
                        "per compiled shape while on.  Implies --metrics")


class JsonlExporter:
    """Append-only JSONL event stream over one registry."""

    def __init__(self, path: str, registry: MetricsRegistry):
        self.path = path
        self.registry = registry
        self._f = open(path, "w")
        self._t0 = time.perf_counter()
        self._n_ticks = 0

    def _emit(self, obj: Dict) -> None:
        self._f.write(json.dumps(obj) + "\n")
        self._f.flush()

    def meta(self, **run_meta) -> None:
        self._emit({"type": "meta", "wall_time": time.time(),
                    "argv": sys.argv, **run_meta})

    def tick(self, step: Optional[int] = None, **extra) -> Dict:
        """One per-interval line: the registry's delta() since the last
        tick (per-interval rates, the counter-reset fix) plus any extras
        (epoch number, loss, staleness summary...)."""
        self._n_ticks += 1
        rec = {"type": "tick",
               "wall_s": round(time.perf_counter() - self._t0, 6)}
        if step is not None:
            rec["step"] = int(step)
        rec["delta"] = _jsonable(self.registry.delta())
        rec.update(_jsonable(extra))
        self._emit(rec)
        return rec

    def event(self, event: str, **payload) -> None:
        self._emit({"type": "event", "event": event,
                    "wall_s": round(time.perf_counter() - self._t0, 6),
                    **_jsonable(payload)})

    def summary(self, **extra) -> Dict:
        rec = {"type": "summary",
               "wall_s": round(time.perf_counter() - self._t0, 6),
               "n_ticks": self._n_ticks,
               "metrics": _jsonable(self.registry.summary())}
        rec.update(_jsonable(extra))
        self._emit(rec)
        return rec

    def close(self) -> None:
        if not self._f.closed:
            self._f.close()


def _jsonable(obj):
    """Round-trip-safe coercion (numpy scalars/arrays -> python)."""
    import numpy as np
    if isinstance(obj, dict):
        return {str(k): _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    if isinstance(obj, np.generic):
        return obj.item()
    if isinstance(obj, np.ndarray):
        return obj.tolist()
    if isinstance(obj, float) and (obj != obj or obj in (float("inf"),
                                                         float("-inf"))):
        return None
    return obj


class Obs:
    """One run's telemetry bundle: registry + tracer + JSONL exporter,
    installed process-wide on construction so every subsystem (store,
    exchange, feeders, serve engine) publishes without plumbing."""

    def __init__(self, *, metrics: bool = False,
                 metrics_out: Optional[str] = None,
                 trace_out: Optional[str] = None,
                 metrics_interval: int = 1,
                 jax_annotations: bool = False,
                 mem_probe: bool = False,
                 install: bool = True):
        # --mem-probe implies a live registry: the probe's gauges need
        # somewhere to land even without --metrics
        self.enabled = bool(metrics or metrics_out or mem_probe)
        self.trace_out = trace_out
        self.interval = max(int(metrics_interval), 1)
        self.registry = MetricsRegistry() if self.enabled else null_registry()
        self.tracer = (Tracer(jax_annotations=jax_annotations)
                       if trace_out else null_tracer())
        self.probe = MemoryProbe() if mem_probe else null_probe()
        self.exporter = (JsonlExporter(metrics_out, self.registry)
                         if metrics_out else None)
        self._prev_registry = None
        self._prev_tracer = None
        self._prev_probe = None
        self._installed = False
        self._closed = False
        if install:
            self.install()

    @classmethod
    def from_args(cls, args, **run_meta) -> "Obs":
        obs = cls(metrics=getattr(args, "metrics", False),
                  metrics_out=getattr(args, "metrics_out", None),
                  trace_out=getattr(args, "trace_out", None),
                  metrics_interval=getattr(args, "metrics_interval", 1),
                  jax_annotations=getattr(args, "jax_trace_annotations",
                                          False),
                  mem_probe=getattr(args, "mem_probe", False))
        if obs.exporter is not None:
            obs.exporter.meta(**run_meta)
        return obs

    # -- lifecycle ---------------------------------------------------------

    def install(self) -> "Obs":
        if not self._installed:
            self._prev_registry = set_registry(self.registry)
            self._prev_tracer = set_tracer(self.tracer)
            self._prev_probe = set_probe(self.probe)
            self._installed = True
        return self

    def uninstall(self) -> None:
        if self._installed:
            set_registry(self._prev_registry or null_registry())
            set_tracer(self._prev_tracer or null_tracer())
            set_probe(self._prev_probe or null_probe())
            self._installed = False

    def __enter__(self) -> "Obs":
        return self.install()

    def __exit__(self, *exc):
        self.close()
        return False

    # -- recording ---------------------------------------------------------

    def should_tick(self, interval_index: int) -> bool:
        return self.exporter is not None and \
            interval_index % self.interval == 0

    def tick(self, step: Optional[int] = None, **extra) -> Optional[Dict]:
        if self.exporter is None:
            return None
        return self.exporter.tick(step=step, **extra)

    def event(self, event: str, **payload) -> None:
        if self.exporter is not None:
            self.exporter.event(event, **payload)

    def summary(self, **extra) -> Dict:
        """Cumulative report-grade dict (registry summary + extras); does
        NOT close anything."""
        return {"metrics": _jsonable(self.registry.summary()),
                **_jsonable(extra)}

    def close(self, **summary_extra) -> Optional[Dict]:
        """Final summary JSONL line, trace export, uninstall.  Returns the
        summary record (None when telemetry was fully disabled)."""
        if self._closed:
            return None
        self._closed = True
        rec = None
        if self.exporter is not None:
            if self.probe.enabled:
                # per-(site, signature) compiled memory records, ahead of
                # the summary so gate/bench readers still see the summary
                # as the final record
                self.exporter.event("memory", **self.probe.snapshot())
            rec = self.exporter.summary(**summary_extra)
            self.exporter.close()
        elif self.enabled:
            rec = {"type": "summary",
                   "metrics": _jsonable(self.registry.summary()),
                   **_jsonable(summary_extra)}
        if self.trace_out and len(self.tracer):
            self.tracer.export(self.trace_out)
        self.uninstall()
        return rec
