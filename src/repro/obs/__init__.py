"""Unified telemetry substrate: metrics registry, span tracing (marked in
the ``jax.profiler`` trace too), compile counters, exporters (DESIGN.md
§11).

The one import every instrumented subsystem makes::

    from repro import obs

    with obs.span("train.epoch", epoch=epoch) as sp:
        params, losses = segment(...)
        sp.block_on((params, losses))   # only where the code blocks anyway

Hard rules, enforced by the §10 auditor's ``obs-in-jit`` lint rule:
instrumentation lives host-side *between* jitted calls, never inside a
traced region — obs calls inside ``jax.jit``/``lax.scan``/... bodies run
at trace time (recording nothing meaningful) or force host syncs, and are
a hard lint failure either way.

``obs.disabled()`` turns the whole telemetry layer into a no-op (zero
obs-owned allocations per call — checked by ``debug_allocs`` accounting in
tests); the overhead benchmark (``benchmarks/obs_bench.py``) gates the
instrumented-vs-disabled delta at <2% on the fused-epoch and serving rows.
"""
from __future__ import annotations

from repro.obs._state import (
    debug_allocs,
    disabled,
    is_enabled,
    set_enabled,
)
from repro.obs.detect import (
    Alert,
    AnomalyMonitor,
    DetectorThresholds,
    health_block,
)
from repro.obs.export import (
    format_summary,
    prometheus_text,
    read_events,
    summarize_events,
    validate_events,
)
from repro.obs.metrics import (
    DEFAULT_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    RollingWindow,
    default_registry,
)
from repro.obs.profiling import record_compile_counts
from repro.obs.probes import (
    record_snapshot,
    set_snapshot_transform,
)
from repro.obs.timeline import (
    TIMELINE_SCHEMA_VERSION,
    TimelineWriter,
    read_timeline,
    render_diff,
    render_report,
    timeline_to,
    validate_timeline,
)
from repro.obs.trace import (
    SCHEMA_VERSION,
    Span,
    Tracer,
    configure,
    current_span_name,
    current_tracer,
    event_span,
    point,
    shutdown,
    span,
    trace_to,
)

__all__ = [
    # switch / accounting
    "disabled", "is_enabled", "set_enabled", "debug_allocs",
    # metrics
    "Counter", "Gauge", "Histogram", "RollingWindow", "MetricsRegistry",
    "default_registry", "DEFAULT_BUCKETS",
    # tracing
    "SCHEMA_VERSION", "Span", "Tracer", "span", "point", "event_span",
    "configure", "shutdown", "trace_to", "current_tracer",
    "current_span_name",
    # profiling
    "record_compile_counts",
    # export
    "prometheus_text", "read_events", "validate_events",
    "summarize_events", "format_summary",
    # training-dynamics probes / timeline / anomaly detection (§12)
    "record_snapshot", "set_snapshot_transform",
    "TIMELINE_SCHEMA_VERSION", "TimelineWriter", "timeline_to",
    "read_timeline", "validate_timeline", "render_report", "render_diff",
    "AnomalyMonitor", "DetectorThresholds", "Alert", "health_block",
]
