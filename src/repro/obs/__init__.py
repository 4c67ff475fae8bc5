"""Observability: deterministic query tracing, scoped metrics, analysis.

- `trace`         per-query span trees (Tracer/NullTracer): modeled on
                  the VirtualClock for tiered engines, on the host clock
                  and the profiler's timeline for flat ones
- `metrics`       scoped counter/gauge/histogram registry + unified
                  snapshot
- `audit`         conservation checker: span bytes/joules == ledger lines
- `export`        Chrome-trace-event JSON (Perfetto) + plain-text
                  waterfall
- `critical_path` per-query critical-path extraction + bottleneck
                  attribution, reconciled against the audit
- `timeseries`    fixed-cadence ring-buffer series on the VirtualClock
- `slo`           multi-window multi-burn-rate SLO alerting (per-tenant
                  error budgets, deterministic virtual timestamps)
- `diff`          trace-diff regression explanation (per-category,
                  per-shape wall-time attribution between two runs)
"""
from repro.obs.audit import AuditReport, ConservationError, audit, check
from repro.obs.critical_path import (CriticalPath, Segment, attribute,
                                     critical_path, verify)
from repro.obs.diff import (DiffReport, DiffRow, diff_digests, diff_traces,
                            digest, trace_category_seconds)
from repro.obs.export import (chrome_trace, chrome_trace_json, waterfall,
                              waterfall_query)
from repro.obs.metrics import (MetricsRegistry, default_registry, scoped,
                               unified_snapshot)
from repro.obs.slo import Alert, BurnRateRule, SLOMonitor, default_rules
from repro.obs.timeseries import RingSeries
from repro.obs.trace import (NULL_TRACE, NullTracer, QueryTrace, Span,
                             Tracer)

__all__ = [
    "AuditReport", "ConservationError", "audit", "check",
    "CriticalPath", "Segment", "attribute", "critical_path", "verify",
    "DiffReport", "DiffRow", "diff_digests", "diff_traces", "digest",
    "trace_category_seconds",
    "chrome_trace", "chrome_trace_json", "waterfall", "waterfall_query",
    "MetricsRegistry", "default_registry", "scoped", "unified_snapshot",
    "Alert", "BurnRateRule", "SLOMonitor", "default_rules",
    "RingSeries",
    "NULL_TRACE", "NullTracer", "QueryTrace", "Span", "Tracer",
]
