"""Metrics collector: phase timers + counters + per-level rows.

One ``Metrics`` instance rides a single engine run (inside a
``RunObserver``).  Three kinds of measurement:

* **phases** — wall-clock seconds per named phase, recorded with the
  ``timer(name)`` context manager.  Timers nest, and the accounting is
  EXCLUSIVE: time spent inside an inner timer is subtracted from the
  enclosing phase, so the phase values are disjoint and sum to the
  instrumented wall-clock.  Engines wrap their whole fixpoint loop in
  ``timer("check")`` and carve out ``compile`` / ``dispatch`` /
  ``host_sync`` inside it — which is what makes the per-phase
  breakdown comparable engine-to-engine and lets the reported phases
  sum to (within noise of) ``CheckResult.elapsed``.
* **counters** — monotonically accumulated ints (``dispatches``,
  ``grows`` / ``grow_<what>``, ``spills``, ``spill_rows``,
  ``spill_bytes``, ``checkpoints``).
* **gauges** — last-write-wins numbers (``fpset_capacity``,
  ``fpset_occupancy``, ``dedup_hit_rate``…).

Per-level rows (``level(...)``) capture the BFS trajectory: frontier
size, cumulative distinct/generated, and elapsed at each level
boundary — the data a ``-metrics FILE.json`` dump is built from.

The serialized form (``to_dict``) is the ``tpuvsr-metrics/1`` schema
documented in ``tpuvsr/obs/SCHEMA.md`` and validated by
``validate_metrics``.
"""

from __future__ import annotations

import time
from contextlib import contextmanager

METRICS_SCHEMA = "tpuvsr-metrics/1"

# phase names every engine uses where applicable; other names are
# allowed (liveness uses graph_build/scc) but these are the canonical
# cross-engine vocabulary.  "inflight" is the pipelined engines'
# blocked wait on the oldest in-flight dispatch (ISSUE 4) — zero on
# synchronous (-pipeline 1) runs.
WELL_KNOWN_PHASES = ("check", "compile", "dispatch", "host_sync",
                     "inflight", "checkpoint", "init")

# keys a metrics document must carry to be schema-valid
REQUIRED_METRICS_KEYS = ("schema", "run_id", "engine", "elapsed_s",
                         "phases", "counters", "gauges", "levels")

LEVEL_ROW_KEYS = ("depth", "frontier", "distinct", "generated",
                  "elapsed_s")


class Metrics:
    def __init__(self):
        self.phases = {}        # name -> exclusive seconds
        self.counters = {}      # name -> int
        self.gauges = {}        # name -> number
        self.levels = []        # per-level trajectory rows
        self._stack = []        # [phase, child_seconds, t0] frames

    # -- phase timers --------------------------------------------------
    def begin(self, phase):
        """Open a phase frame (see ``timer``).  ``end`` closes the
        innermost open frame; RunObserver.finish drains any frames an
        early return left open, so unpaired ``begin`` is safe for
        run-scoped phases like the outer "check"."""
        self._stack.append([phase, 0.0, time.perf_counter()])

    def end(self):
        if not self._stack:     # drain() already closed this frame
            return
        phase, child, t0 = self._stack.pop()
        dt = time.perf_counter() - t0
        self.phases[phase] = self.phases.get(phase, 0.0) + dt - child
        if self._stack:
            self._stack[-1][1] += dt

    def drain(self):
        while self._stack:
            self.end()

    @contextmanager
    def timer(self, phase):
        """Time a code section under ``phase``.  Nests: the enclosing
        phase is charged only for time NOT covered by inner timers."""
        self.begin(phase)
        try:
            yield
        finally:
            self.end()

    # -- counters / gauges ---------------------------------------------
    def count(self, name, n=1):
        self.counters[name] = self.counters.get(name, 0) + int(n)

    def gauge(self, name, value):
        self.gauges[name] = value

    # -- per-level trajectory ------------------------------------------
    def level(self, depth, *, frontier, distinct, generated, elapsed_s,
              **extra):
        row = {"depth": int(depth), "frontier": int(frontier),
               "distinct": int(distinct), "generated": int(generated),
               "elapsed_s": round(float(elapsed_s), 6)}
        row.update(extra)
        self.levels.append(row)
        return row

    # -- serialization -------------------------------------------------
    def to_dict(self, **header):
        """The ``tpuvsr-metrics/1`` document; `header` supplies the
        run-identity and result-summary fields."""
        out = {"schema": METRICS_SCHEMA}
        out.update(header)
        out["phases"] = {k: round(v, 6) for k, v in self.phases.items()}
        out["counters"] = dict(self.counters)
        out["gauges"] = {
            k: (round(v, 6) if isinstance(v, float) else v)
            for k, v in self.gauges.items()}
        out["levels"] = list(self.levels)
        return out


#: optional result-summary keys a metrics doc may carry, and the type
#: check each must pass WHEN PRESENT (``validate_metrics(strict=True)``,
#: ISSUE 17 satellite — the default mode keeps ignoring them, so old
#: callers and old documents are untouched).  None is always legal
#: (an aborted run reports what it has).
OPTIONAL_RESULT_KEYS = {
    "ok": lambda v: isinstance(v, bool),
    "distinct": lambda v: isinstance(v, int) and not isinstance(
        v, bool) and v >= 0,
    "generated": lambda v: isinstance(v, int) and not isinstance(
        v, bool) and v >= 0,
    "diameter": lambda v: isinstance(v, int) and not isinstance(
        v, bool) and v >= 0,
    "walks": lambda v: isinstance(v, int) and not isinstance(
        v, bool) and v >= 0,
    "steps": lambda v: isinstance(v, int) and not isinstance(
        v, bool) and v >= 0,
    "traces": lambda v: isinstance(v, int) and not isinstance(
        v, bool) and v >= 0,
    "divergences": lambda v: isinstance(v, int) and not isinstance(
        v, bool) and v >= 0,
    "violated": lambda v: isinstance(v, str),
    "error": lambda v: isinstance(v, str),
}


def validate_metrics(doc, strict=False):
    """Raise ValueError unless `doc` is a schema-valid
    ``tpuvsr-metrics/1`` document.  Returns the doc.

    ``strict=True`` additionally type-checks the OPTIONAL
    result-summary keys when present (``OPTIONAL_RESULT_KEYS``) —
    the default mode ignores them entirely, as it always has."""
    if not isinstance(doc, dict):
        raise ValueError(f"metrics document is {type(doc).__name__}, "
                         f"not an object")
    if doc.get("schema") != METRICS_SCHEMA:
        raise ValueError(f"schema is {doc.get('schema')!r}, "
                         f"want {METRICS_SCHEMA!r}")
    missing = [k for k in REQUIRED_METRICS_KEYS if k not in doc]
    if missing:
        raise ValueError(f"metrics document missing keys: {missing}")
    for section in ("phases", "counters", "gauges"):
        if not isinstance(doc[section], dict):
            raise ValueError(f"{section} must be an object")
    for name, v in doc["phases"].items():
        if not isinstance(v, (int, float)) or v < 0:
            raise ValueError(f"phase {name} has non-duration value {v!r}")
    for name, v in doc["counters"].items():
        if not isinstance(v, int):
            raise ValueError(f"counter {name} has non-int value {v!r}")
    if not isinstance(doc["levels"], list):
        raise ValueError("levels must be an array")
    for i, row in enumerate(doc["levels"]):
        missing = [k for k in LEVEL_ROW_KEYS if k not in row]
        if missing:
            raise ValueError(f"level row {i} missing keys: {missing}")
    if strict:
        if not isinstance(doc["elapsed_s"], (int, float)) \
                or isinstance(doc["elapsed_s"], bool) \
                or doc["elapsed_s"] < 0:
            raise ValueError(f"elapsed_s must be a non-negative "
                             f"number, got {doc['elapsed_s']!r}")
        for key, check in OPTIONAL_RESULT_KEYS.items():
            if key not in doc or doc[key] is None:
                continue
            if not check(doc[key]):
                raise ValueError(
                    f"optional result key {key} has ill-typed value "
                    f"{doc[key]!r}")
    return doc
