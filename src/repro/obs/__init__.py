"""repro.obs — the telemetry spine: metrics registry, span tracing,
staleness observability, JSONL/trace export.

Import surface kept flat so instrumented code needs only::

    from repro.obs import get_registry, span

and CLIs only::

    from repro.obs import Obs, add_obs_args
"""
from repro.obs.metrics import (AGE_BUCKETS_STEPS, BYTES_BUCKETS, Counter,
                               Gauge, Histogram, LATENCY_BUCKETS_MS,
                               MetricsRegistry, NullRegistry, dict_delta,
                               enable_metrics, exponential_buckets,
                               get_registry, null_registry, set_registry,
                               summarize)
from repro.obs.trace import (NullTracer, Tracer, counter, get_tracer,
                             null_tracer, set_tracer, span,
                             validate_chrome_trace)
from repro.obs.memory import (MemoryProbe, NullProbe, get_probe, null_probe,
                              probe_jit, process_rss_bytes, set_probe,
                              shape_signature, tree_nbytes)
from repro.obs.staleness import (StalenessProbe, record_exchange_bytes,
                                 record_prefetch_exchange, sed_age_bound,
                                 sed_drop_stats, wb_skip_rate)
from repro.obs.export import JsonlExporter, Obs, add_obs_args

__all__ = [
    "AGE_BUCKETS_STEPS", "BYTES_BUCKETS", "LATENCY_BUCKETS_MS",
    "Counter", "Gauge", "Histogram",
    "MetricsRegistry", "NullRegistry",
    "dict_delta", "enable_metrics", "exponential_buckets",
    "get_registry", "null_registry", "set_registry", "summarize",
    "NullTracer", "Tracer", "counter", "get_tracer",
    "null_tracer", "set_tracer", "span", "validate_chrome_trace",
    "MemoryProbe", "NullProbe", "get_probe", "null_probe", "probe_jit",
    "process_rss_bytes", "set_probe", "shape_signature", "tree_nbytes",
    "StalenessProbe", "record_exchange_bytes", "record_prefetch_exchange",
    "sed_age_bound", "sed_drop_stats", "wb_skip_rate",
    "JsonlExporter", "Obs", "add_obs_args",
]
