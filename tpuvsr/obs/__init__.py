"""tpuvsr.obs — shared observability layer for every checking engine.

The pieces:

* **run journal** (``journal.py``) — append-only JSONL event stream
  (``run_start`` / ``level_done`` / ``checkpoint`` / ``spill`` /
  ``grow`` / ``violation`` / ``run_end``) with a stable, validated
  schema; survives ``-recover`` by appending to the same file;
* **metrics collector** (``metrics.py``) — per-level counters and
  exclusive phase timers, dumped as ``tpuvsr-metrics/1`` JSON
  (``-metrics FILE.json``), merged into the ``-json`` one-line
  summary, and rendered as a final stats table on stderr;
* **spans** (``spans.py``, ``profiler.py``) — ``RunObserver.span`` is
  the one way to mark a host phase: an exclusive phase timer and,
  under ``TPUVSR_PROFILE=DIR``, a ``TraceAnnotation`` of the same
  fixed name inside the run's profiler session;
* **build counters** (``builds.py``) — JAX's own trace / lower /
  backend-compile events, forwarded to the observer running on the
  calling thread.

``RunObserver`` (``observer.py``) bundles them; engines accept
``obs=None`` and collect privately, so ``CheckResult.metrics`` exists
on every run.  Schemas are documented in ``SCHEMA.md``.
"""

from __future__ import annotations

from . import spans
from .journal import (EVENT_REQUIRED, JOURNAL_SCHEMA, Journal,
                      new_run_id, new_span_id, new_trace_id,
                      read_journal, root_span, trace_env, trace_scope,
                      validate_journal_line)
from .metrics import (LEVEL_ROW_KEYS, METRICS_SCHEMA, Metrics,
                      validate_metrics)
from .observer import RunObserver, closes_observer
from .profiler import profile_dir, profile_trace
from .telemetry import (TELEMETRY_SCHEMA, TelemetryAggregator,
                        prometheus_text)

__all__ = [
    "RunObserver", "closes_observer", "Metrics", "Journal",
    "JOURNAL_SCHEMA", "METRICS_SCHEMA", "EVENT_REQUIRED",
    "LEVEL_ROW_KEYS", "new_run_id", "read_journal",
    "validate_journal_line", "validate_metrics",
    "profile_dir", "profile_trace", "spans",
    "new_trace_id", "new_span_id", "root_span", "trace_env",
    "trace_scope",
    "TELEMETRY_SCHEMA", "TelemetryAggregator", "prometheus_text",
]
