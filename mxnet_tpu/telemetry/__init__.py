"""mxnet_tpu.telemetry — unified observability for a live process.

Four pieces (docs/observability.md):

  registry   central metrics registry (Counter/Gauge/Histogram with
             labels) + *views*: the five existing stat silos
             (execCacheStats, servingStats, hostSyncStats,
             inputPipelineStats, graphPassStats) register their
             snapshot functions here, so every consumer reads the
             SAME live counters the profiler dump embeds.
  trace      always-on structured tracing: `span()` over a fixed-size
             ring buffer with correlation ids threaded through
             serving (submit -> enqueue -> batch_flush -> execute ->
             reply; the request Future carries `.trace_id`) and
             through fit (per-step data-wait / dispatch /
             metric-drain spans).
  http       opt-in stdlib exporter thread (MXNET_TELEMETRY_PORT):
             /metrics (Prometheus text), /statusz (one JSON snapshot
             of everything), /healthz.
  flight     crash flight recorder (MXNET_TELEMETRY_FLIGHT_DIR):
             last-N spans + full registry snapshot dumped atomically
             on unhandled exceptions and FaultInjector trips.

Stdlib-only by design: nothing here imports jax, so a scrape, a span
record, or a crash dump can never add a host<->device sync (mxlint's
MX001 polices the hot paths statically).
"""
from __future__ import annotations

from . import registry
from . import trace
from . import http
from . import flight
from .registry import (
    REGISTRY,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    counter,
    gauge,
    has_view,
    histogram,
    prometheus_text,
    register_view,
    view_items,
    view_snapshot,
)
from .trace import (
    Span,
    new_trace_id,
    recent_spans,
    record_span,
    span,
    span_summary,
    spans_for_trace,
    trace_stats,
)
from .http import (
    Exporter,
    exporter_port,
    maybe_start_exporter,
    start_exporter,
    statusz,
    stop_exporter,
)
from .flight import dump_flight_record, flight_record, maybe_dump

# crash hooks chain the previous handlers and no-op until
# MXNET_TELEMETRY_FLIGHT_DIR is set — free to install eagerly
flight.install()
