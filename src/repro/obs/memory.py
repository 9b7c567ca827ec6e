"""Memory observability: measure the constant-memory claim, don't argue it.

GST's headline promise is that segment training predicts large-graph
properties with a constant device-memory footprint.  Until now the repo
only argued this analytically (``kernels/ops.py::max_intermediate_bytes``
buffer accounting); this module measures it from the compiled artifacts
and feeds the PR 7 telemetry spine so CI can gate on it.

:class:`MemoryProbe` captures ``compiled.memory_analysis()`` /
``cost_analysis()`` from every jit entry point it is hooked into —
train/refresh/finetune steps (core + dist), every serve bucket compile,
the streaming encoder, the tiered-store migrate jits — keyed by
``(site, shape signature)``, so one record exists per compiled shape.
Capture is AOT-on-the-side: the probe runs ``jitted.lower(*args)
.compile()`` purely to read the stats, then the ORIGINAL jitted callable
executes the step — the traced jaxpr is bit-identical with the probe
installed or not (tests/test_obs_memory.py), and the extra compile
happens once per (site, signature) only while probing.

Per capture the probe publishes into the metrics registry:

    mem.device.peak_bytes.<site>   argument + output + temp − alias
    mem.device.temp_bytes.<site>   XLA temp (intermediate) buffers
    mem.host.rss_bytes             process RSS at capture time

and emits a Chrome-trace "C" counter event (``obs/trace.py``) so live
bytes render as a timeline counter track.  Host-side byte tracking
(tiered-store host tier, feeder staging buffers) goes through
:meth:`MemoryProbe.observe_host` → ``mem.host.<site>_bytes`` gauges.

Off the TPU, where ``memory_analysis`` may be unavailable, the shared
extraction helper (``roofline/analysis.py``) returns ``None`` and the
probe degrades to accounting-only: the record carries the jaxpr-walk
``max_intermediate_bytes`` lower bound instead of compiled stats.  On the
TPU a failed compile or a missing ``memory_analysis`` raises instead.

Like the registry and tracer, the probe is a process-wide global
defaulting to :class:`NullProbe`; instrumented call sites use
:func:`probe_jit`, whose disabled path is one global read + branch per
call (batch-grained, never inside traced code).
"""
from __future__ import annotations

import threading
from typing import Any, Callable, Dict, List, Optional, Tuple

from repro.obs.metrics import get_registry
from repro.obs.trace import get_tracer


# ---------------------------------------------------------------------------
# shape signatures + host-side byte helpers (jax-free until actually used)
# ---------------------------------------------------------------------------


def shape_signature(tree) -> str:
    """Canonical dtype[shape] signature of a pytree of arrays — the probe's
    dedup key: two calls with the same signature hit the same compiled
    executable, so they share one capture."""
    import jax

    parts = []
    for leaf in jax.tree_util.tree_leaves(tree):
        dtype = getattr(leaf, "dtype", None)
        shape = getattr(leaf, "shape", None)
        if dtype is None or shape is None:
            parts.append(type(leaf).__name__)
        else:
            parts.append(f"{dtype}[{','.join(str(s) for s in shape)}]")
    return ";".join(parts)


def tree_nbytes(tree) -> int:
    """Total bytes of every array leaf (host staging buffers, numpy tiers)."""
    import jax

    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        nb = getattr(leaf, "nbytes", None)
        if nb is not None:
            total += int(nb)
    return total


def process_rss_bytes() -> int:
    """Resident-set size of this process, in bytes (0 when unreadable)."""
    try:
        with open("/proc/self/statm") as f:
            pages = int(f.read().split()[1])
        import os
        return pages * os.sysconf("SC_PAGE_SIZE")
    except Exception:
        try:
            import resource
            # ru_maxrss is KiB on Linux (bytes on macOS — close enough for
            # a monitoring gauge; the gates never read RSS)
            return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
        except Exception:
            return 0


# ---------------------------------------------------------------------------
# the probe
# ---------------------------------------------------------------------------


class MemoryProbe:
    """Captures compiled memory/cost stats per (site, shape signature)."""

    enabled = True

    def __init__(self, *, accounting_fallback: bool = True):
        self._lock = threading.Lock()
        self._records: Dict[Tuple[str, str], Dict[str, Any]] = {}
        self._host_bytes: Dict[str, int] = {}
        self.accounting_fallback = accounting_fallback

    # -- device-side capture ----------------------------------------------

    def observe_call(self, site: str, jitted: Callable, args, kwargs) -> None:
        """Record one call of a probed jit entry point: on the first call
        per (site, signature) run the AOT lower→compile on the side and
        extract stats; afterwards just count calls."""
        sig = shape_signature((args, kwargs))
        key = (site, sig)
        with self._lock:
            rec = self._records.get(key)
            if rec is not None:
                rec["calls"] += 1
                return
            # reserve before the (slow, lock-free) measurement so a racing
            # second caller with the same signature doesn't compile twice
            rec = {"site": site, "signature": sig, "calls": 1,
                   "memory": None, "cost": None, "mode": "pending"}
            self._records[key] = rec
        measured = self._measure(jitted, args, kwargs)
        with self._lock:
            rec.update(measured)
        self._publish(site, rec)

    def _measure(self, jitted, args, kwargs) -> Dict[str, Any]:
        import jax

        from repro.roofline.analysis import (compiled_cost_stats,
                                             compiled_memory_stats,
                                             device_peak_bytes)
        # on the chip a failed compile or missing memory stats is a fault
        # to surface, never a record to degrade into
        on_tpu = jax.default_backend() == "tpu"
        try:
            compiled = jitted.lower(*args, **kwargs).compile()
        except Exception as e:
            if on_tpu:
                raise
            return {"mode": "error", "error": str(e)}
        mem = compiled_memory_stats(compiled)
        cost = compiled_cost_stats(compiled)
        out: Dict[str, Any] = {"cost": cost}
        if mem is not None:
            out.update(mode="compiled", memory=mem,
                       peak_bytes=device_peak_bytes(mem),
                       temp_bytes=mem.get("temp_size_in_bytes", 0))
            return out
        if on_tpu:
            raise RuntimeError("memory_analysis() reported nothing for a "
                               "program compiled on the TPU")
        # accounting-only degrade: the jaxpr-walk largest-intermediate
        # bound stands in for the unavailable compiled temp stats
        out["mode"] = "accounting"
        if self.accounting_fallback:
            try:
                from repro.kernels.ops import max_intermediate_bytes
                bound = int(max_intermediate_bytes(jitted, *args, **kwargs))
                out.update(temp_bytes=bound, peak_bytes=bound,
                           accounting_bound_bytes=bound)
            except Exception as e:
                out.update(mode="error", error=str(e))
        return out

    def _publish(self, site: str, rec: Dict[str, Any]) -> None:
        peak = rec.get("peak_bytes")
        if peak is None:
            return
        temp = rec.get("temp_bytes", 0)
        reg = get_registry()
        reg.set(f"mem.device.peak_bytes.{site}", float(peak), unit="bytes")
        reg.set(f"mem.device.temp_bytes.{site}", float(temp), unit="bytes")
        rss = process_rss_bytes()
        if rss:
            reg.set("mem.host.rss_bytes", float(rss), unit="bytes")
        get_tracer().counter("mem.device.temp_bytes", **{site: temp})

    # -- host-side gauges --------------------------------------------------

    def observe_host(self, site: str, nbytes: int) -> None:
        """Host-memory gauge for ``site`` (tiered-store host tier, feeder
        staging buffers): ``mem.host.<site>_bytes`` + a trace counter."""
        nbytes = int(nbytes)
        with self._lock:
            self._host_bytes[site] = nbytes
        reg = get_registry()
        reg.set(f"mem.host.{site}_bytes", float(nbytes), unit="bytes")
        rss = process_rss_bytes()
        if rss:
            reg.set("mem.host.rss_bytes", float(rss), unit="bytes")
        get_tracer().counter("mem.host_bytes", **{site: nbytes})

    # -- views -------------------------------------------------------------

    def records(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [dict(r) for r in self._records.values()]

    def host_bytes(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._host_bytes)

    def sites(self) -> List[str]:
        with self._lock:
            return sorted({site for site, _ in self._records})

    def site_records(self, prefix: str) -> List[Dict[str, Any]]:
        with self._lock:
            return [dict(r) for (site, _), r in self._records.items()
                    if site.startswith(prefix)]

    def ladder_total_bytes(self, prefix: str = "serve.encode.") -> int:
        """Sum of per-bucket (peak) bytes across every compiled bucket of
        the serve ladder — the number the bucket-ladder device-budget gate
        compares against; 0 until a bucket compiles."""
        with self._lock:
            return sum(int(r.get("peak_bytes", 0))
                       for (site, _), r in self._records.items()
                       if site.startswith(prefix))

    def snapshot(self) -> Dict[str, Any]:
        """Report-grade dict: per-(site, signature) records, host gauges,
        the serve-ladder total, and current RSS — what Obs.close() writes
        into the JSONL stream as a ``memory`` event."""
        return {
            "records": self.records(),
            "host_bytes": self.host_bytes(),
            "serve_ladder_peak_bytes": self.ladder_total_bytes(),
            "rss_bytes": process_rss_bytes(),
        }


class NullProbe:
    """The disabled path: observe calls are empty, views are empty."""

    enabled = False

    def observe_call(self, site, jitted, args, kwargs) -> None:
        pass

    def observe_host(self, site: str, nbytes: int) -> None:
        pass

    def records(self) -> List[Dict[str, Any]]:
        return []

    def host_bytes(self) -> Dict[str, int]:
        return {}

    def sites(self) -> List[str]:
        return []

    def site_records(self, prefix: str) -> List[Dict[str, Any]]:
        return []

    def ladder_total_bytes(self, prefix: str = "serve.encode.") -> int:
        return 0

    def snapshot(self) -> Dict[str, Any]:
        return {"records": [], "host_bytes": {},
                "serve_ladder_peak_bytes": 0, "rss_bytes": 0}


_NULL_PROBE = NullProbe()
_probe = _NULL_PROBE


def get_probe():
    """The process-wide memory probe (a NullProbe until --mem-probe)."""
    return _probe


def set_probe(probe) -> object:
    """Install ``probe`` process-wide; returns the previous probe."""
    global _probe
    prev = _probe
    _probe = probe
    return prev


def null_probe() -> NullProbe:
    return _NULL_PROBE


class _ProbedJit:
    """Call-through wrapper around one jitted entry point: late-binds the
    process-wide probe at call time (so hooks built before the probe is
    installed still report) and NEVER wraps the traced computation — it
    measures on the side, then delegates to the original callable."""

    __slots__ = ("site", "_jitted")

    def __init__(self, site: str, jitted: Callable):
        self.site = site
        self._jitted = jitted

    def __call__(self, *args, **kwargs):
        p = _probe
        if p.enabled:
            p.observe_call(self.site, self._jitted, args, kwargs)
        return self._jitted(*args, **kwargs)

    def __getattr__(self, name):  # .lower / .trace passthrough
        return getattr(self._jitted, name)


def probe_jit(site: str, jitted: Callable) -> Callable:
    """Hook one jitted callable into the memory probe under ``site``.

    The returned wrapper is signature-transparent and adds one global
    read + branch per call when probing is disabled.  Sites: train.step,
    train.refresh, dist.train_step, serve.encode.<bucket>, serve.stream,
    store.migrate, ...
    """
    return _ProbedJit(site, jitted)
