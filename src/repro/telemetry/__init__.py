"""Unified observability layer for serving, pipeline, and fleet.

Three pillars (see ``docs/observability.md``):

- **Spans** (:mod:`repro.telemetry.spans`): per-request lifecycle timelines
  on the scheduler-tick clock — admission waits, per-stage queue/execute
  slices, park/resume/migrate/scale instants — collected per engine and
  exported as Chrome trace-event JSON
  (:mod:`repro.telemetry.chrome_trace`, viewable in Perfetto).  Program
  spans (``serve/step``, ``serve/pod``, ``serve/stage/<name>``) are also
  stamped on the wall clock and annotated into a profiler trace, and
  :mod:`repro.telemetry.compiles` counts JAX compiles per stage under them.
- **Metrics** (:mod:`repro.telemetry.metrics`): typed ``Counter`` /
  ``Gauge`` / ``Histogram`` registry; ``Histogram`` is the streaming
  fixed-bucket percentile estimator behind the engine's latency stats, and
  :func:`percentiles` the single exact summary helper the ledger-style
  paths share.
- **Schema** (:mod:`repro.telemetry.schema`): the versioned, test-validated
  shape of ``engine.stats`` / ``stats["fleet"]`` / registry snapshots.
"""

from repro.telemetry.chrome_trace import (
    TRACE_SCHEMA_VERSION,
    chrome_trace_events,
    write_chrome_trace,
    write_trace,
)
from repro.telemetry.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    json_ready,
    percentiles,
)
from repro.telemetry.schema import (
    PCTL_KEYS,
    SNAPSHOT_SCHEMA_VERSION,
    STATS_SCHEMA_VERSION,
    validate_engine_stats,
    validate_fleet_summary,
    validate_snapshot,
)
from repro.telemetry.spans import SpanCollector, SpanEvent, open_spans

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "percentiles",
    "json_ready",
    "SpanCollector",
    "SpanEvent",
    "open_spans",
    "chrome_trace_events",
    "write_chrome_trace",
    "write_trace",
    "TRACE_SCHEMA_VERSION",
    "STATS_SCHEMA_VERSION",
    "SNAPSHOT_SCHEMA_VERSION",
    "PCTL_KEYS",
    "validate_engine_stats",
    "validate_fleet_summary",
    "validate_snapshot",
]
