"""Back-compat shim: the observability layer grew into the
:mod:`peritext_tpu.obs` package (spans/tracing, histograms, flight
recorder, exporters — see its docstring).  Every historical name re-exports
from there, unchanged in identity (``GLOBAL_COUNTERS`` here IS
``peritext_tpu.obs.GLOBAL_COUNTERS``), so existing imports keep working.
New code should import from :mod:`peritext_tpu.obs` directly.
"""

from __future__ import annotations

from .obs import (  # noqa: F401
    ConvergenceMonitor,
    Counters,
    EventLog,
    FlightRecorder,
    GLOBAL_COUNTERS,
    GLOBAL_HISTOGRAMS,
    GLOBAL_TRACER,
    Histogram,
    HistogramRegistry,
    LATENCY_BUCKETS_S,
    MergeStats,
    MetricsServer,
    RecompileSentinel,
    SIZE_BUCKETS,
    Span,
    TraceContext,
    Tracer,
    health_snapshot,
    merge_traces,
    prometheus_text,
)
from .obs.metrics import _HEALTH_PREFIXES  # noqa: F401
from .obs.sentinel import _COMPILE_MSG_RE  # noqa: F401

__all__ = [
    "ConvergenceMonitor",
    "Counters",
    "EventLog",
    "FlightRecorder",
    "GLOBAL_COUNTERS",
    "GLOBAL_HISTOGRAMS",
    "GLOBAL_TRACER",
    "Histogram",
    "HistogramRegistry",
    "LATENCY_BUCKETS_S",
    "MergeStats",
    "MetricsServer",
    "RecompileSentinel",
    "SIZE_BUCKETS",
    "Span",
    "TraceContext",
    "Tracer",
    "health_snapshot",
    "merge_traces",
    "prometheus_text",
]
