"""Unified metrics/span/watchdog spine for training and serving.

The reference DL4J observed training through three disconnected mechanisms —
PerformanceListener samples/sec, Spark per-phase stats, StatsListener memory
sections (SURVEY.md §5.1). This package is the single instrumentation path
that replaces all of them, TPU-honest by construction (no per-step host
syncs; see docs/observability.md):

- :mod:`registry` — process-wide counters/gauges/histograms with Prometheus
  text exposition (``GET /metrics`` on the UI server) and JSON snapshots.
- :mod:`spans` — host spans exporting Chrome/Perfetto trace JSON, wrapped in
  ``jax.profiler.TraceAnnotation`` so they align with XLA slices.
- :mod:`device` — the per-step jnp metrics vector computed inside the jitted
  step (loss, grad norm, non-finite flag).
- :mod:`session` — :class:`Telemetry`, the K-step-fetch glue the fit paths
  call.
- :mod:`watchdog` — structured anomaly events (nan-loss,
  exploding-grad-norm, stalled-step-time) with pluggable sinks.
- :mod:`memory` — HBM accounting: XLA ``memory_analysis`` of cached
  executables, per-layer attribution via ``jax.eval_shape``
  (:func:`memory_report`), the :func:`preflight` will-it-fit check, and
  the single live ``device_memory_stats`` source.
- :mod:`flight_recorder` — bounded event ring + post-mortem JSON dump
  bundles, auto-triggered by watchdog anomalies.
- :mod:`tracing` — distributed request tracing: head-sampled
  :class:`TraceContext` propagated across processes via the
  ``x-dl4jtpu-trace`` header, per-hop Chrome-trace spans in a bounded
  ring, latency-histogram exemplars.
- :mod:`slo` — declared objectives (latency budget, availability) with
  multi-window burn-rate alerting over serving observations; breaches
  emit ``slo-burn`` watchdog anomalies and flight bundles.
- :mod:`history` — bounded multi-resolution time-series store
  (raw→1m→5m rollups, counter→rate, histogram-quantile series), the
  Deadline-paced :class:`HistorySampler`, and the fleet recording
  rules + EWMA/Holt ``dl4jtpu_forecast_*`` signals behind
  ``GET /api/history`` — the autoscaler's sensor suite.
"""

from .flight_recorder import (
    FlightRecorder,
    get_flight_recorder,
    install_crash_hook,
)
from .history import (
    FleetRecordingRules,
    Forecast,
    HistorySampler,
    HistoryStore,
    ensure_default_sampler,
    get_default_sampler,
    get_history_store,
    history_enabled,
    parse_prometheus_text,
    set_default_sampler,
    set_history_store,
)
from .memory import (
    MemoryPreflightError,
    device_memory_stats,
    executable_memory,
    memory_report,
    preflight,
    sample_device_memory,
)
from .registry import (
    DEFAULT_BUCKETS,
    MetricFamily,
    MetricsRegistry,
    get_registry,
)
from .session import Telemetry
from .slo import SLOMonitor, get_slo_monitor, set_slo_monitor
from .spans import Span, SpanRecorder, get_recorder, identified, span
from .tracing import (
    TRACE_HEADER,
    TRACE_SAMPLE_ENV,
    TraceContext,
    TraceRing,
    current_trace,
    get_trace_ring,
    record_trace_event,
    sample_rate,
    set_default_baggage,
    should_sample,
    trace_span,
    use_trace,
)
from .watchdog import (
    EXPLODING_GRAD_NORM,
    INPUT_SHIFT,
    LOSS_DRIFT,
    NAN_LOSS,
    SLO_BURN,
    STALLED_STEP_TIME,
    AnomalyEvent,
    Watchdog,
    logging_sink,
)

__all__ = [
    "DEFAULT_BUCKETS",
    "MetricFamily",
    "MetricsRegistry",
    "get_registry",
    "Telemetry",
    "Span",
    "SpanRecorder",
    "get_recorder",
    "identified",
    "span",
    "AnomalyEvent",
    "Watchdog",
    "logging_sink",
    "NAN_LOSS",
    "EXPLODING_GRAD_NORM",
    "STALLED_STEP_TIME",
    "LOSS_DRIFT",
    "INPUT_SHIFT",
    "SLO_BURN",
    "TRACE_HEADER",
    "TRACE_SAMPLE_ENV",
    "TraceContext",
    "TraceRing",
    "current_trace",
    "get_trace_ring",
    "record_trace_event",
    "sample_rate",
    "set_default_baggage",
    "should_sample",
    "trace_span",
    "use_trace",
    "SLOMonitor",
    "get_slo_monitor",
    "set_slo_monitor",
    "FleetRecordingRules",
    "Forecast",
    "HistorySampler",
    "HistoryStore",
    "ensure_default_sampler",
    "get_default_sampler",
    "get_history_store",
    "history_enabled",
    "parse_prometheus_text",
    "set_default_sampler",
    "set_history_store",
    "FlightRecorder",
    "get_flight_recorder",
    "install_crash_hook",
    "MemoryPreflightError",
    "device_memory_stats",
    "executable_memory",
    "memory_report",
    "preflight",
    "sample_device_memory",
]
