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

* **the unfed clock** — seconds in which the device had nothing
  queued (``unfed_start`` / ``unfed_stop``, driven by
  ``engine/pipeline.DispatchPipeline``), charged to the phase that is
  current while it runs: ``begin`` and ``end`` settle the running
  clock onto the phase they leave, so ``unfed`` is exclusive by phase
  like ``phases`` and each entry is at most its phase's seconds.

* **parts of a phase** — seconds of a named part of the phase that is
  current (``part_begin`` / ``part_end``, driven by
  ``RunObserver.part``).  A part is no frame: the phase's exclusive
  seconds are what they are without it, the part's own exclude what
  inner timers took out of the phase while it ran, and so the parts of
  a phase sum to at most the phase.

Per-level rows (``level(...)``) capture the BFS trajectory: frontier
size, cumulative distinct/generated, and elapsed at each level
boundary — the data a ``-metrics FILE.json`` dump is built from — and
what the level cost: its own wall seconds, the phase and unfed seconds
and the dispatches accrued since the previous row.

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
                     "inflight", "checkpoint", "init", "boundary",
                     "finish")

# keys a metrics document must carry to be schema-valid
REQUIRED_METRICS_KEYS = ("schema", "run_id", "engine", "elapsed_s",
                         "phases", "counters", "gauges", "levels")

LEVEL_ROW_KEYS = ("depth", "frontier", "distinct", "generated",
                  "elapsed_s")


class Metrics:
    def __init__(self, clock=time.perf_counter):
        self.phases = {}        # name -> exclusive seconds
        self.unfed = {}         # name -> exclusive seconds, device unfed
        self.parts = {}         # phase -> {part: seconds inside it}
        self.counters = {}      # name -> int
        self.gauges = {}        # name -> number
        self.levels = []        # per-level trajectory rows
        self._clock = clock
        self._stack = []        # [phase, child_seconds, t0] frames
        self._unfed_since = None    # clock reading; None: device is fed
        self._row_base = None   # (t, phases, unfed, dispatches) at the last row

    # -- phase timers --------------------------------------------------
    def begin(self, phase):
        """Open a phase frame (see ``timer``).  ``end`` closes the
        innermost open frame; RunObserver.finish drains any frames an
        early return left open, so unpaired ``begin`` is safe for
        run-scoped phases like the outer "check"."""
        t = self._clock()
        self._settle_unfed(t)
        self._stack.append([phase, 0.0, t])

    def end(self):
        if not self._stack:     # drain() already closed this frame
            return
        t = self._clock()
        self._settle_unfed(t)
        phase, child, t0 = self._stack.pop()
        dt = t - t0
        self.phases[phase] = self.phases.get(phase, 0.0) + dt - child
        if self._stack:
            self._stack[-1][1] += dt

    def drain(self):
        while self._stack:
            self.end()
        self._unfed_since = None    # no frame, no run: nothing to feed

    # -- the unfed clock -----------------------------------------------
    def unfed_start(self):
        """The device has nothing queued from now on (no-op while the
        clock already runs)."""
        if self._unfed_since is None:
            self._unfed_since = self._clock()

    def unfed_stop(self):
        """Work was enqueued: charge the running clock to the current
        phase and stop it (no-op while it is stopped)."""
        if self._unfed_since is not None:
            self._settle_unfed(self._clock())
            self._unfed_since = None

    def _settle_unfed(self, t):
        """Charge the running clock, up to `t`, to the current phase."""
        if self._unfed_since is None:
            return
        # outside every frame nothing is timed, unfed seconds neither
        if self._stack and t > self._unfed_since:
            phase = self._stack[-1][0]
            self.unfed[phase] = (self.unfed.get(phase, 0.0)
                                 + t - self._unfed_since)
        self._unfed_since = t

    def _phases_at(self, t):
        """Exclusive seconds by phase up to `t`, open frames included."""
        out = dict(self.phases)
        inner_t0 = t
        for phase, child, t0 in reversed(self._stack):
            out[phase] = out.get(phase, 0.0) + inner_t0 - t0 - child
            inner_t0 = t0
        return out

    # -- parts of the current phase -----------------------------------
    def part_begin(self, phase):
        """A part of `phase` starts; `phase` has to be the innermost
        open frame (a RuntimeError otherwise: a part lies inside its
        phase).  Returns what ``part_end`` wants back."""
        if not self._stack or self._stack[-1][0] != phase:
            raise RuntimeError(
                f"a part of phase {phase!r} opened under "
                f"{self._stack[-1][0] if self._stack else None!r}")
        return self._clock(), self._stack[-1][1]

    def part_end(self, phase, part, token):
        """Charge `part` what the phase's frame accrued since
        ``part_begin``: its seconds less those of the inner timers that
        ran meanwhile.  A frame that ``drain`` closed first (an
        abnormal exit) charges nothing."""
        if not self._stack or self._stack[-1][0] != phase:
            return
        t0, child0 = token
        secs = max(0.0, self._clock() - t0
                   - (self._stack[-1][1] - child0))
        parts = self.parts.setdefault(phase, {})
        parts[part] = parts.get(part, 0.0) + secs

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
        """One row a level.  Beside the trajectory it says what the
        level cost, as accrued since the previous row (the first row:
        since the first frame opened): ``wall_s``, exclusive ``phases``
        seconds (open frames included), ``unfed_s`` and
        ``dispatches``."""
        t = self._clock()
        self._settle_unfed(t)
        phases = self._phases_at(t)
        unfed = sum(self.unfed.values())
        dispatches = self.counters.get("dispatches", 0)
        t_b, phases_b, unfed_b, dispatches_b = self._row_base or (
            self._stack[0][2] if self._stack else t, {}, 0.0, 0)
        self._row_base = (t, phases, unfed, dispatches)
        row = {"depth": int(depth), "frontier": int(frontier),
               "distinct": int(distinct), "generated": int(generated),
               "elapsed_s": round(float(elapsed_s), 6),
               "wall_s": round(t - t_b, 6),
               "phases": {k: round(v - phases_b.get(k, 0.0), 6)
                          for k, v in phases.items()
                          if v > phases_b.get(k, 0.0)},
               "unfed_s": round(unfed - unfed_b, 6),
               "dispatches": dispatches - dispatches_b}
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
        if self.unfed:      # only a run that drove a dispatch window
            out["phases_unfed"] = {k: round(v, 6)
                                   for k, v in self.unfed.items()}
            out["gauges"]["unfed_s"] = round(sum(self.unfed.values()), 6)
        if self.parts:      # only a run that opened a part
            out["phase_parts"] = {
                phase: {k: round(v, 6) for k, v in parts.items()}
                for phase, parts in self.parts.items()}
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
    # `phases_unfed` is optional: documents from before the unfed clock,
    # and runs that drove no dispatch window, carry none
    if not isinstance(doc.get("phases_unfed", {}), dict):
        raise ValueError("phases_unfed must be an object")
    for section in ("phases", "phases_unfed"):
        for name, v in doc.get(section, {}).items():
            if not isinstance(v, (int, float)) or v < 0:
                raise ValueError(f"{section} {name} has non-duration "
                                 f"value {v!r}")
    # `phase_parts` is optional like `phases_unfed`: older documents,
    # and runs that opened no part, carry none
    if not isinstance(doc.get("phase_parts", {}), dict):
        raise ValueError("phase_parts must be an object")
    for phase, parts in doc.get("phase_parts", {}).items():
        if not isinstance(parts, dict):
            raise ValueError(f"phase_parts {phase} must be an object")
        for name, v in parts.items():
            if not isinstance(v, (int, float)) or v < 0:
                raise ValueError(f"phase_parts {phase}.{name} has "
                                 f"non-duration value {v!r}")
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
