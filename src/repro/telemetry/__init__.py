"""repro.telemetry — the observability plane over the fused epoch loop.

TurboKV's switches are *monitoring stations* (paper §5.1); until now the
reproduction only surfaced aggregate per-epoch rows.  This subsystem
answers the two questions aggregates cannot: *why was this query in the
p999* and *which pipeline stage burns the time*:

    trace.py       — device-resident sampled span records, carried
                     through the fused period scan (no RNG consumed:
                     tracing on/off is bit-identical either way)
    attribution.py — exact latency decomposition into
                     {queue, inflation, bounce, retry_backoff, service}
    export.py      — Chrome-trace / JSONL span-tree exports
    profiler.py    — pipeline stage timers and counters, and the map
                     from the period program's device ops to its stages
    flight.py      — ring-buffer flight recorder with postmortem dumps
    recorder.py    — the per-run host accumulator the driver feeds
    metrics.py     — the fleet metrics plane: a (window, n_series) ring
                     carried/donated through the fused scan
    slo.py         — declarative SLOs + multi-window burn-rate alerts
    incident.py    — one-command postmortem artifacts
    dashboard.py   — terminal sparkline view over a persisted ring

Enable with ``ClusterConfig(telemetry=TelemetryConfig(...))``; the
driver then exposes ``EpochDriver.telemetry``.
"""

from repro.telemetry.attribution import (
    BUCKETS,
    decompose,
    reconstruct,
    tail_attribution,
)
from repro.telemetry.export import (
    chrome_trace,
    link_retries,
    span_tree,
    write_jsonl,
)
from repro.telemetry.flight import FlightRecorder
from repro.telemetry import incident
from repro.telemetry.metrics import (
    MetricsConfig,
    MetricsState,
    build_layout,
    series_view,
    to_openmetrics,
)
from repro.telemetry.slo import SLO, AlertEngine
from repro.telemetry.profiler import (
    OpScopes,
    StageTimers,
    hlo_op_scopes,
    scope_seconds,
)
from repro.telemetry.recorder import TelemetryRecorder
from repro.telemetry.trace import (
    SF,
    SI,
    SPAN_F_FIELDS,
    SPAN_I_FIELDS,
    TelemetryConfig,
    collect_spans,
    rate_threshold,
    sample_mask,
)

__all__ = [
    "TelemetryConfig", "TelemetryRecorder",
    "SPAN_I_FIELDS", "SPAN_F_FIELDS", "SI", "SF",
    "collect_spans", "sample_mask", "rate_threshold",
    "BUCKETS", "decompose", "reconstruct", "tail_attribution",
    "chrome_trace", "link_retries", "span_tree", "write_jsonl",
    "StageTimers", "OpScopes", "hlo_op_scopes", "scope_seconds",
    "FlightRecorder",
    "MetricsConfig", "MetricsState", "build_layout", "series_view",
    "to_openmetrics", "SLO", "AlertEngine", "incident",
]
