"""repro.obs — observability for the serving stack (stdlib, plus JAX's
profiler annotations for spans).

Five pieces (docs/observability.md):

  * :mod:`repro.obs.registry` — labeled counters / gauges / histograms
    with Prometheus text exposition (``/metrics``), plus OpenMetrics
    exposition with trace-id exemplars.
  * :mod:`repro.obs.tracing` — ``dllm.*`` host spans on the profiler's
    clock, and the Chrome-trace / Perfetto collector (``--trace-out
    trace.json``).
  * :mod:`repro.obs.drift` — live measured-vs-modeled per-stage drift
    against ``sim/analytical`` predictions.
  * :mod:`repro.obs.events` — crash-safe structured event log: one JSONL
    record per request lifecycle edge (``python -m repro.obs.logquery``
    is the reader).
  * :mod:`repro.obs.slo` — SLO tiers: per-class deadlines and violation
    accounting keyed by each request's ``slo_class``.

:class:`~repro.obs.serving.ServingObs` bundles them behind the hooks the
engine / router / frontend call.
"""
from repro.obs.drift import (DriftMonitor, HOST_DRIFT_BAND,
                             modeled_tick_stages)
from repro.obs.events import (EVENT_TYPES, EventLog, SCHEMA_VERSION,
                              read_events, validate_events)
from repro.obs.registry import (CONTENT_TYPE, Counter, Gauge, Histogram,
                                LATENCY_BUCKETS, OPENMETRICS_CONTENT_TYPE,
                                Registry, exp_buckets, parse_exposition,
                                validate_histogram)
from repro.obs.serving import (ServingObs, delivery_histogram,
                               frontend_metrics)
from repro.obs.slo import (DEFAULT_CLASS, SLOClass, VIOLATION_KINDS,
                           default_classes, resolve_classes)
from repro.obs.tracing import Spans, TraceCollector, now_us, validate_trace

__all__ = [
    "CONTENT_TYPE", "Counter", "DEFAULT_CLASS", "DriftMonitor",
    "EVENT_TYPES", "EventLog", "Gauge", "Histogram", "HOST_DRIFT_BAND",
    "LATENCY_BUCKETS", "OPENMETRICS_CONTENT_TYPE", "Registry",
    "SCHEMA_VERSION", "SLOClass", "ServingObs", "Spans", "TraceCollector",
    "VIOLATION_KINDS", "default_classes", "delivery_histogram",
    "exp_buckets", "frontend_metrics", "modeled_tick_stages", "now_us",
    "parse_exposition", "read_events", "resolve_classes",
    "validate_events", "validate_histogram", "validate_trace",
]
