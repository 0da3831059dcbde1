"""Unified observability plane: metrics registry, trajectory lifecycle
tracing, Perfetto trace export, fleet sampling, structured logging.

Opt-in end to end: ``RuntimeConfig.observability=True`` (or setting
``trace_path``) attaches a :class:`MetricsRegistry` + a
:class:`TrajectoryTracer` to the lifecycle bus; disabled (the default)
every instrumentation site goes through ``NOOP_REGISTRY`` / ``None``
guards and the seed paths stay byte-identical. Program spans
(``repro.obs.spans.span``) always go to the profiler's trace, and to the
tracer when one is attached.

See ``docs/architecture.md`` "Observability" for the span model, the
exporter track layout, and how to open a trace in Perfetto.
"""
from repro.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    NOOP_REGISTRY,
)
from repro.obs.stats import Ring, percentile, percentiles
from repro.obs.tracer import Activity, Segment, TrajSpan, TrajectoryTracer
from repro.obs.spans import span
from repro.obs.export import (
    export_chrome_trace,
    load_trace,
    validate_chrome_trace,
)
from repro.obs.sampler import FleetSampler
from repro.obs.logs import get_logger, setup_logging

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "NOOP_REGISTRY",
    "Ring",
    "percentile",
    "percentiles",
    "Activity",
    "Segment",
    "TrajSpan",
    "TrajectoryTracer",
    "span",
    "export_chrome_trace",
    "load_trace",
    "validate_chrome_trace",
    "FleetSampler",
    "get_logger",
    "setup_logging",
]
