"""peritext_tpu.obs — the fleet telemetry subsystem.

What grew out of ``peritext_tpu/observability.py`` (which remains as a
re-export shim so no historical import breaks): the instrumentation layer
every streaming-perf PR is judged by.  Four cooperating pieces:

* :mod:`.spans` — structured pipeline spans (:class:`Tracer`): nested,
  monotonic-id spans over the merge pipeline (ingest → encode →
  device-apply → resolve → decode → patch-scatter), serialized as
  Perfetto-compatible Chrome trace-event JSON and correlated ACROSS HOSTS
  by a compact trace-context field carried in the wire codec (frame v5)
  and the anti-entropy frontier.
* :mod:`.histograms` — fixed-bucket latency/size histograms with
  p50/p95/p99 readout; the rolling round-latency window behind the
  supervisor's deadline autotuning.
* :mod:`.recorder` — the flight recorder: a bounded ring of recent
  spans+events per session, dumped as JSONL on quarantine, rollback, or
  transport give-up so chaos-soak failures become post-mortems.
* :mod:`.convergence` — per-peer replication-lag watermarks (ops-behind
  clock-delta sums, staleness) and divergence probes (same frontier +
  different commutative store digest = a first-class incident) fed by
  every anti-entropy frontier exchange; the behind-states the
  ``parallel/gossip.py`` healing scheduler consumes.
* :mod:`.devprof` — the DEVICE-facing layer the host-side telemetry above
  cannot provide: per-jit-site / per-shape-bucket XLA cost and memory
  introspection (``cost_analysis``/``memory_analysis`` of the compiled
  merge executables), bucket-occupancy accounting (real vs padded ops per
  padded-shape bucket) and round-boundary device-memory watermarks.  Off
  by default; ``GLOBAL_DEVPROF.enable()`` arms every hook in the stack.
* :mod:`.ledger` — the append-only JSONL perf history (bench ladder rows +
  devprof snapshots keyed by git sha / device / config) behind
  ``python -m peritext_tpu.obs perf`` and the CI perf-gate job.
* :mod:`.latency` — the time-to-visibility latency plane: per-drain-batch
  stage-watermark records (admit → window → stage → dispatch → commit →
  visibility) fed by the serve tier, per-stage histograms + SLO burn-rate
  gauges (``peritext_latency_*``, ``/latency.json``), and the
  ``python -m peritext_tpu.obs why`` attribution engine that names the
  dominant moved stage when the perf gate fails.  Off by default;
  ``GLOBAL_LATENCY.enable()`` arms the serve-tier hooks.
* :mod:`.incidents` — the fleet incident plane: a deterministic,
  round-counted :class:`IncidentMonitor` that folds every plane above into
  typed incidents (host-death, divergence, quarantine-storm, shed-storm,
  slo-burn, recompile-storm, migration-failure, perf-regression) with a
  two-watermark open→ack→resolve lifecycle, (host, doc, trace)-window
  causal correlation ordered by the ``latency.attribute`` tie-break, and a
  frontier-sentinel summary so two frontends agree on the incident view;
  plus :func:`merge_flight_dumps`, the cross-host black-box timeline
  (``python -m peritext_tpu.obs incidents`` / ``status`` / ``flight``).
* :mod:`.timeseries` — the fleet history plane: a deterministic,
  round-counted :class:`TimeSeriesPlane` that periodically samples every
  plane above into min/max/last frames retained across downsampling
  tiers (recent full-rate, older merged N:1 so spikes survive), persists
  append-only JSONL segments that replay byte-identically, scores a
  rolling-median + MAD anomaly per gauge key (findings feed the incident
  monitor as its ninth signal source), and records the fused serving
  tier's per-window occupancy rows — the ``propose(history=...)``
  feedback loop (``peritext_history_*``, ``/timeseries.json``,
  ``python -m peritext_tpu.obs history`` / ``top``).  Off by default;
  ``GLOBAL_HISTORY.enable()`` arms the serve-tier hooks.
* :mod:`.exporters` — Prometheus text exposition and JSON snapshot
  endpoints (:class:`MetricsServer`, mounted by ``ReplicaServer``:
  ``/metrics`` with ``peritext_convergence_*`` gauges, ``/health.json``,
  ``/convergence.json``, ``/trace.json``), plus the
  ``python -m peritext_tpu.obs`` CLI (:mod:`.__main__`) that renders a
  trace dump into a per-stage/per-host summary table and
  ``/convergence.json`` scrapes into the fleet lag view (``fleet``).

Design rule (DESIGN.md "Telemetry"): timestamps are telemetry, not merge
inputs.  Merge-scope modules (``core/``, ``ops/``, ``parallel/``) never
read the wall clock directly — they open spans and observe histograms, and
the clock reads happen HERE, outside graftlint's PTL006 merge scope, so the
determinism contract stays machine-checkable.
"""

from .convergence import ConvergenceMonitor, DivergenceIncident, PeerLag
from .devprof import (
    DeviceProfiler,
    GLOBAL_DEVPROF,
    note_jit_dispatch,
    occupancy_key,
)
from .events import EventLog
from .histograms import (
    GLOBAL_HISTOGRAMS,
    Histogram,
    HistogramRegistry,
    LATENCY_BUCKETS_S,
    SIZE_BUCKETS,
)
from .incidents import (
    Incident,
    IncidentMonitor,
    TAXONOMY,
    merge_flight_dumps,
)
from .latency import (
    GLOBAL_LATENCY,
    LatencyPlane,
    STAGES,
    attribute,
    check_sum_consistency,
)
from .metrics import Counters, GLOBAL_COUNTERS, health_snapshot
from .recorder import FlightRecorder
from .sentinel import RecompileSentinel
from .spans import (
    GLOBAL_TRACER,
    Span,
    TraceContext,
    Tracer,
    ambient_parent,
    current_span,
    merge_traces,
)
from .stats import MergeStats
from .timeseries import (
    GLOBAL_HISTORY,
    TimeSeriesPlane,
    anomaly_kind,
    replay_segments,
)
from .exporters import MetricsServer, prometheus_text

__all__ = [
    "ConvergenceMonitor",
    "Counters",
    "DeviceProfiler",
    "DivergenceIncident",
    "EventLog",
    "FlightRecorder",
    "GLOBAL_COUNTERS",
    "GLOBAL_DEVPROF",
    "GLOBAL_HISTOGRAMS",
    "GLOBAL_HISTORY",
    "GLOBAL_LATENCY",
    "GLOBAL_TRACER",
    "Histogram",
    "HistogramRegistry",
    "Incident",
    "IncidentMonitor",
    "LATENCY_BUCKETS_S",
    "LatencyPlane",
    "MergeStats",
    "MetricsServer",
    "PeerLag",
    "RecompileSentinel",
    "SIZE_BUCKETS",
    "STAGES",
    "Span",
    "TAXONOMY",
    "TimeSeriesPlane",
    "TraceContext",
    "Tracer",
    "ambient_parent",
    "anomaly_kind",
    "attribute",
    "check_sum_consistency",
    "current_span",
    "health_snapshot",
    "merge_flight_dumps",
    "merge_traces",
    "note_jit_dispatch",
    "occupancy_key",
    "prometheus_text",
    "replay_segments",
]
