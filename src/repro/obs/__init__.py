"""repro.obs — tracing, metrics, and "where did the time go" analysis.

Three layers:

- :mod:`repro.obs.trace` — hierarchical spans with trace/span/parent
  ids, a near-zero-cost no-op path when disabled, and a process-safe
  JSONL shard sink so fleet workers contribute to one merged trace.
- :mod:`repro.obs.metrics` — an in-process counter/histogram registry
  (used by ``repro.serve`` for per-stage latency percentiles).
- :mod:`repro.obs.report` — loads merged traces, checks span-tree
  well-formedness, renders critical-path/attribution reports, and
  exports Chrome trace-event JSON (Perfetto-viewable).
"""

from .metrics import Counter, Histogram, MetricsRegistry
from .trace import (
    SCHEMA_VERSION,
    disable,
    enable,
    enabled,
    event,
    shipping_context,
    span,
    tally,
    timed_span,
    trace_dir,
)

__all__ = [
    "SCHEMA_VERSION",
    "Counter",
    "Histogram",
    "MetricsRegistry",
    "disable",
    "enable",
    "enabled",
    "event",
    "shipping_context",
    "span",
    "tally",
    "timed_span",
    "trace_dir",
]
