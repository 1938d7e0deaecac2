"""Observability subsystem: metrics registry, request tracer, exporters.

Three pieces, all stdlib-only and clock-agnostic:

* :mod:`repro.obs.registry` — a thread-safe :class:`MetricsRegistry` of
  counters, gauges, and fixed-bucket histograms with label support; the
  engine's ``EngineMetrics`` is a live view over it, and it exports as
  Prometheus text exposition or a deterministic JSON dump.
* :mod:`repro.obs.trace` — a ring-buffered request-lifecycle
  :class:`Tracer` stamping every span event from an injected clock (the
  engine's single time base), exportable as Perfetto/Chrome
  ``trace_event`` JSON.
* :mod:`repro.obs.exporters` — an optional background HTTP thread
  serving ``/metrics`` (Prometheus scrape endpoint) plus file-dump
  helpers for both exposition formats.

See ``docs/observability.md`` for metric names, the event schema, and
the recording-overhead bound.
"""
from repro.obs.exporters import (dump_metrics, dump_trace,
                                 start_metrics_server)
from repro.obs.registry import (EXPERT_LOAD_BUCKETS, ITL_BUCKETS,
                                PHASE_BUCKETS, SPEC_REQUEST_BUCKETS,
                                SPEC_WINDOW_BUCKETS, TTFT_BUCKETS, Counter,
                                Gauge, Histogram, MetricsRegistry)
from repro.obs.trace import TraceEvent, Tracer, perfetto_json

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "TTFT_BUCKETS", "ITL_BUCKETS", "PHASE_BUCKETS",
    "SPEC_WINDOW_BUCKETS", "SPEC_REQUEST_BUCKETS", "EXPERT_LOAD_BUCKETS",
    "TraceEvent", "Tracer", "perfetto_json",
    "start_metrics_server", "dump_metrics", "dump_trace",
]
