"""Unified telemetry: metrics registry, instrumentation, straggler
detection, and the per-worker /metrics exporter.

The observability layer the training stack was missing (the serving
plane had its own Prometheus-text metrics; training had Chrome traces
and ad-hoc module-level ints).  Layering, bottom up:

* :mod:`~horovod_tpu.telemetry.metrics` — Counter / Gauge / Summary
  primitives + the process-wide :func:`default_registry` (promoted out
  of ``serve/metrics.py``, which re-exports for back-compat);
* :mod:`~horovod_tpu.telemetry.instrument` — per-collective hook points
  threaded through the eager and jit data planes; zero-overhead identity
  objects when ``HVDT_TELEMETRY`` is off;
* :mod:`~horovod_tpu.telemetry.step_stats` — :class:`StepTimer`
  (step time, examples/s, MFU) and :class:`GoodputLedger` (time lost to
  recompiles / restores / recovered faults);
* :mod:`~horovod_tpu.telemetry.compile_ledger` — set-up seen from
  inside: the package's one ``jax.monitoring`` consumer (what was traced,
  lowered, compiled or loaded from the persistent cache, per program and
  per ``hvdt.kernel.*`` site, and the start-up phases JAX does not name);
  always on, it runs only when JAX compiles;
* :mod:`~horovod_tpu.telemetry.straggler` — cross-rank step-duration
  skew detection publishing a ``straggler_rank`` gauge;
* :mod:`~horovod_tpu.telemetry.exporter` — per-worker ``/metrics`` +
  ``/healthz`` + ``/flightrecorder`` HTTP endpoint (started by
  ``hvd.init()`` when enabled) and driver-side snapshot aggregation
  over the rendezvous KV;
* :mod:`~horovod_tpu.telemetry.trace` — distributed span tracing:
  bounded per-rank Chrome-trace buffers with deterministic per-step
  trace ids, merged driver-side into one rank-as-pid trace
  (``hvdtrun --trace-dir``);
* :mod:`~horovod_tpu.telemetry.flight_recorder` — always-cheap ring of
  recent collective events (seq/op/dtype/bytes/wire, in-flight vs done)
  + the cross-rank desync analyzer that names the first divergent
  collective on stall-abort;
* :mod:`~horovod_tpu.telemetry.history` — bounded per-metric time
  series (``HVDT_HISTORY``), served as ``/timeseries`` and embedded in
  the KV snapshot for step-aligned driver roll-ups;
* :mod:`~horovod_tpu.telemetry.anomaly` — windowed detectors over the
  series + the JSONL anomaly event log (``HVDT_EVENT_LOG``) and the
  driver-side pod-correlated cluster rules;
* :mod:`~horovod_tpu.telemetry.aggregate` — step-id-joined cross-rank
  roll-ups (per-pod median/p99, cluster wire bytes, goodput series);
* :mod:`~horovod_tpu.telemetry.top` — the ``hvdtrun top`` live
  terminal view over ``/timeseries``.

Predicted-vs-observed attribution lives in :mod:`~horovod_tpu.
telemetry.step_stats`: ``hvd.init()`` prices the expected schedule
fingerprint (``HVDT_EXPECTED_SCHEDULE``) with the analytical cost model
and the StepTimer stream keeps ``hvdt_perf_deviation_ratio`` live.

Knobs: ``HVDT_TELEMETRY``, ``HVDT_METRICS_PORT``,
``HVDT_STRAGGLER_WINDOW``, ``HVDT_STRAGGLER_THRESHOLD``,
``HVDT_TELEMETRY_PUBLISH_S``, ``HVDT_HISTORY``/``HVDT_HISTORY_*``,
``HVDT_EVENT_LOG``, ``HVDT_PERF_DEVIATION_RATIO`` (common/config.py);
launcher flags ``hvdtrun --telemetry`` / ``--metrics-port``.  See
docs/observability.md for semantics and docs/metrics.md for the
generated metric catalog.
"""

from .metrics import (  # noqa: F401
    Counter,
    Gauge,
    MetricsRegistry,
    Summary,
    default_registry,
    reset_default_registry,
)
from .instrument import (  # noqa: F401
    CollectiveRecorder,
    enabled,
    get_recorder,
    wrap_step,
)
from .step_stats import (  # noqa: F401
    DeviationTracker,
    GoodputLedger,
    PerfExpectation,
    StepTimer,
    bind_resilience_gauges,
    expected_vs_observed_doc,
    get_deviation_tracker,
    maybe_publish_expected_cost,
    peak_flops_for,
    publish_expected_schedule_cost,
)
from .straggler import StragglerMonitor  # noqa: F401
from .history import (  # noqa: F401
    MetricHistory,
    Series,
    get_history,
)
from .anomaly import (  # noqa: F401
    AnomalyMonitor,
    ClusterAnomalyMonitor,
    EventLog,
    get_event_log,
    read_event_log,
)
from .aggregate import rollup  # noqa: F401
from .exporter import (  # noqa: F401
    MetricsExporter,
    bind_process_gauges,
    collect_driver_snapshots,
    get_exporter,
    maybe_start_exporter,
    snapshot_dict,
    start_exporter,
    stop_exporter,
)
from .trace import (  # noqa: F401
    Tracer,
    get_tracer,
    merge_dumps,
    step_trace_id,
)
from .flight_recorder import (  # noqa: F401
    FlightRecorder,
    analyze_desync,
    emit_desync_report,
    get_flight_recorder,
)

__all__ = [
    "Counter", "Gauge", "Summary", "MetricsRegistry",
    "default_registry", "reset_default_registry",
    "CollectiveRecorder", "enabled", "get_recorder", "wrap_step",
    "StepTimer", "GoodputLedger", "bind_resilience_gauges",
    "peak_flops_for", "StragglerMonitor",
    "PerfExpectation", "DeviationTracker", "get_deviation_tracker",
    "publish_expected_schedule_cost", "maybe_publish_expected_cost",
    "expected_vs_observed_doc",
    "MetricHistory", "Series", "get_history",
    "AnomalyMonitor", "ClusterAnomalyMonitor", "EventLog",
    "get_event_log", "read_event_log", "rollup",
    "MetricsExporter", "start_exporter", "stop_exporter", "get_exporter",
    "maybe_start_exporter", "snapshot_dict", "collect_driver_snapshots",
    "bind_process_gauges",
    "Tracer", "get_tracer", "merge_dumps", "step_trace_id",
    "FlightRecorder", "analyze_desync", "emit_desync_report",
    "get_flight_recorder",
]
