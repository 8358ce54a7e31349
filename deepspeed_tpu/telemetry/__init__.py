"""Unified telemetry: metrics registry, span tracer, event log, health
monitor, monitor bridge.

See docs/OBSERVABILITY.md for the metric catalog, span naming
convention, event schema, and overhead guarantees. Env knobs:
``DS_TPU_TELEMETRY=0`` disables registry, tracer and event log at
startup; ``set_enabled()`` flips them at runtime.
"""

from .registry import (DEFAULT_BUCKETS, Counter, Gauge, Histogram,
                       MetricsRegistry, get_registry)
from .tracing import SpanTracer, current_span, dump_trace, get_tracer, self_times, span
from .bridge import MonitorBridge
from .events import (EventLog, get_event_log, latency_summary,
                     lifecycle_signature, request_metrics,
                     request_timelines, validate_timeline)
from .health import (Alert, CallbackAlertSink, Detector,
                     GradNormSpikeDetector, HBMPressureDetector,
                     HealthMonitor, JsonlAlertSink, LoggerAlertSink,
                     NonFiniteLossDetector, QueueStallDetector,
                     SLOBurnRateDetector, StepStallDetector, StragglerDetector,
                     get_health_monitor)
from .costs import CostCard, PerfAccountant, get_perf_accountant, resolve_peaks
from .agg import (detect_stragglers, histogram_quantile, merge_snapshot_files,
                  merge_snapshots, rank_stamp, write_rank_snapshot)
from .flight import (FlightRecorder, get_flight_recorder,
                     maybe_attach_flight_recorder, resolved_knobs)
from .journal import (Journal, Session, get_journal, journal_override,
                      read_journal, set_journal)
from .ops_plane import OpsServer, get_ops_server, maybe_start_ops_server
from .profiler import (DeviceProfiler, build_waterfall, get_device_profiler,
                       maybe_arm_profiler, parse_trace_events,
                       request_capture, summarize_trace_dir)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "DEFAULT_BUCKETS",
    "get_registry", "SpanTracer", "get_tracer", "span", "dump_trace",
    "current_span", "self_times",
    "MonitorBridge", "set_enabled",
    "EventLog", "get_event_log", "request_timelines", "request_metrics",
    "latency_summary", "lifecycle_signature", "validate_timeline",
    "Alert", "Detector", "HealthMonitor", "get_health_monitor",
    "NonFiniteLossDetector", "GradNormSpikeDetector", "QueueStallDetector",
    "SLOBurnRateDetector", "HBMPressureDetector", "StragglerDetector", "StepStallDetector",
    "LoggerAlertSink", "JsonlAlertSink", "CallbackAlertSink",
    "CostCard", "PerfAccountant", "get_perf_accountant", "resolve_peaks",
    "rank_stamp", "write_rank_snapshot", "merge_snapshots",
    "merge_snapshot_files", "histogram_quantile", "detect_stragglers",
    "FlightRecorder", "get_flight_recorder", "maybe_attach_flight_recorder",
    "resolved_knobs", "OpsServer", "get_ops_server", "maybe_start_ops_server",
    "Journal", "Session", "get_journal", "set_journal", "journal_override",
    "read_journal",
    "DeviceProfiler", "get_device_profiler", "maybe_arm_profiler",
    "request_capture", "parse_trace_events", "build_waterfall",
    "summarize_trace_dir",
]


def set_enabled(flag: bool) -> None:
    """Enable/disable metric recording, span tracing and event emission
    process-wide."""
    get_registry().enabled = bool(flag)
    get_tracer().enabled = bool(flag)
    get_event_log().enabled = bool(flag)
