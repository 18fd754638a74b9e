"""Run journal: append-only JSONL event stream for a checking run.

Every line is one JSON object with at least ``event`` (the type),
``ts`` (unix seconds), and ``run_id``.  The event vocabulary and the
per-event required keys are fixed (``EVENT_REQUIRED``) so downstream
tooling can parse any journal the framework ever wrote; engines may
add EXTRA keys but never omit required ones — ``validate_journal_line``
enforces exactly that and is what the golden-file tests run.

The journal is opened in APPEND mode and each event is flushed as it
is written, so:

* a run killed mid-flight leaves a valid prefix (the whole point:
  multi-hour TLC-style runs whose only artifact today is a scrollback
  of progress lines);
* a ``-recover`` resume pointed at the same path CONTINUES the same
  file — one journal spans the checkpoint/resume chain, with the
  resumed segment announcing itself via ``run_start{resumed: true}``
  and all ``elapsed_s`` fields cumulative across the chain (engines
  rewind their t0 by the checkpoint's recorded elapsed).
"""

from __future__ import annotations

import contextlib
import json
import os
import time
import uuid

JOURNAL_SCHEMA = "tpuvsr-journal/1"

# event type -> required keys (beyond the common event/ts/run_id)
EVENT_REQUIRED = {
    "run_start": ("schema", "engine", "module", "backend", "resumed"),
    "level_done": ("depth", "frontier", "distinct", "generated",
                   "elapsed_s"),
    "checkpoint": ("path", "depth", "distinct", "elapsed_s"),
    "spill": ("depth", "rows", "bytes", "elapsed_s"),
    # the paged engine's page-ins (ISSUE 31): one frontier page, host
    # RAM -> device; `spill` is the way out
    "page_in": ("depth", "rows", "bytes", "elapsed_s"),
    # streamed edge emission (ISSUE 15): a committed block of behavior-
    # graph (src, action, dst) triples drained off the device append
    # buffer into the host CSR builder — the edge-stream spill analog
    "edge_flush": ("depth", "rows", "bytes", "elapsed_s"),
    "grow": ("what", "to", "elapsed_s"),
    "violation": ("kind", "name", "elapsed_s"),
    "run_end": ("ok", "elapsed_s"),
    # build counters (ISSUE 25): one program whose trace + lower +
    # backend stages took half a second or more (obs/builds.py);
    # `cache` is hit | miss | none (not kept by the persistent cache)
    "build": ("fun_name", "trace_s", "lower_s", "backend_s", "cache",
              "elapsed_s"),
    # resilience events (ISSUE 3): injected/real faults, supervised
    # retry/degrade steps, and preemption rescue snapshots
    "fault": ("what", "site", "elapsed_s"),
    "retry": ("attempt", "backoff_s", "elapsed_s"),
    "degrade": ("what", "from", "to", "elapsed_s"),
    "rescue_checkpoint": ("path", "depth", "distinct", "signal",
                          "elapsed_s"),
    # elastic sharded resume (ISSUE 5): an N-shard snapshot was
    # re-hash-partitioned onto an M-device mesh at load time
    "reshard": ("from_shards", "to_shards", "distinct", "elapsed_s"),
    # verification dispatch service (ISSUE 6): job lifecycle events,
    # appended by the service worker to each job's OWN journal (the
    # engine/supervisor events of every attempt interleave in the same
    # file, so one journal tells a job's whole story)
    "job_submitted": ("job_id", "spec", "engine"),
    "job_admitted": ("job_id", "elapsed_s"),
    "job_started": ("job_id", "attempt", "devices"),
    "job_requeued": ("job_id", "reason", "elapsed_s"),
    "job_done": ("job_id", "state", "elapsed_s"),
    # serving tier (ISSUE 14): `sched_decision` records WHY the
    # fair-share policy popped this job (tenant deficit + aged
    # priority — the answer to "why did my job wait?");
    # `worker_heartbeat` is the periodic liveness note of the worker
    # holding the job (the claim-file mtime is the machine-read
    # heartbeat; this row is the human-readable trail)
    "sched_decision": ("job_id", "tenant", "policy"),
    "worker_heartbeat": ("job_id", "worker"),
    # serving tier (ISSUE 15 satellite): the pool parent respawned a
    # dead worker process (bounded restarts with backoff; `rc` is the
    # dead child's exit status, `attempt` the restart count)
    "worker_respawn": ("worker", "attempt", "rc"),
    # walker-fleet simulation (ISSUE 7): the chunk boundary is the
    # sim analog of level_done (walks/steps cumulative); `split` is an
    # importance-splitting resample; `hunt_violation` a UNIQUE
    # deduped violation found by the continuous hunt; `hunt_elastic`
    # a walker-count reshape at a round boundary
    "sim_chunk": ("depth", "walks", "steps", "elapsed_s"),
    "split": ("killed", "novelty_best", "elapsed_s"),
    "hunt_violation": ("name", "walk", "depth", "elapsed_s"),
    "hunt_elastic": ("from", "to", "elapsed_s"),
    # batched trace validation (ISSUE 8): the chunk boundary is the
    # validator's level_done analog (traces/divergences cumulative);
    # `divergence` is one trace's first spec-inconsistent event
    "validate_chunk": ("depth", "traces", "divergences", "elapsed_s"),
    "divergence": ("trace", "step", "elapsed_s"),
    # fleet telemetry plane (ISSUE 17): the SLO watchdog inside the
    # telemetry aggregator observed a headline gauge regress against
    # its rolling baseline (or a tenant's p99 queue wait exceed its
    # target) — `what` names the gauge, `value` the observed number,
    # `target` the threshold it crossed
    "slo_breach": ("what", "value", "target"),
    # serving-tier guard (ISSUE 18): every edge rejection and breaker
    # transition is a first-class event in `<spool>/guard.jsonl`
    # (run_id "guard") so the telemetry fold counts abuse
    # restart-convergently.  `auth_denied` covers both 401 (missing /
    # unknown token) and 403 (valid token acting cross-tenant) —
    # `reason` says which; `rate_limited` is a 429 with the
    # refill-derived Retry-After it returned; `backpressure` a 503
    # past the queue high-water mark; `breaker_open`/`breaker_close`
    # the per-(tenant, spec-digest) circuit-breaker transitions.
    "auth_denied": ("reason",),
    "rate_limited": ("tenant", "retry_after_s"),
    "backpressure": ("depth", "high_water"),
    "breaker_open": ("tenant", "digest", "failures"),
    "breaker_close": ("tenant", "digest"),
    # spool data plane (ISSUE 20): driver-level events in
    # `<spool>/spool.jsonl` (run_id "spool").  `fence` is a zombie
    # worker's terminal append rejected by claim-epoch fencing
    # (`holder` is the live claim's epoch, None when the claim is
    # gone); `replica_lost`/`replica_rejoin` the quorum driver's
    # membership changes (`records` counts anti-entropy-healed
    # frames); `host_lease` the first lease a driver instance writes
    # for a host (the machine-read leases are records in the `hosts`
    # stream; this row is the journal trail).
    "fence": ("job_id", "epoch"),
    "replica_lost": ("replica",),
    "replica_rejoin": ("replica", "records"),
    "host_lease": ("host",),
}
COMMON_REQUIRED = ("event", "ts", "run_id")

# Optional COMMON keys (ISSUE 17): any event may additionally carry
# `trace_id` (one id for a whole job's story, minted at job_submitted),
# `span_id` (this process segment), and `parent_span` (the segment that
# spawned it).  They are deliberately NOT in EVENT_REQUIRED — journals
# written before the telemetry plane stay valid — but every Journal
# stamps them automatically when trace context is set (directly or via
# the TPUVSR_TRACE_ID / TPUVSR_SPAN_ID / TPUVSR_PARENT_SPAN env vars a
# worker exports around each engine run), so one correlation id
# survives the service -> worker -> engine process hops.
TRACE_KEYS = ("trace_id", "span_id", "parent_span")
TRACE_ENV_KEYS = ("TPUVSR_TRACE_ID", "TPUVSR_SPAN_ID", "TPUVSR_PARENT_SPAN")


def new_run_id():
    return uuid.uuid4().hex[:12]


def new_trace_id():
    return uuid.uuid4().hex[:16]


def new_span_id():
    return uuid.uuid4().hex[:8]


def root_span(trace_id):
    """The deterministic service-level root span of a trace: every
    process that touches the job (submitter, recoverer, worker) derives
    the same root without coordination, so their events all land in one
    span and the attempt spans parent onto it."""
    return f"r{str(trace_id)[:8]}"


@contextlib.contextmanager
def trace_scope(trace_id=None, span_id=None, parent_span=None):
    """Export the trace env triple for the duration of a block (and
    restore whatever was there afterwards) — how a worker hands its
    attempt span down to the engine's RunObserver journal and to any
    child process it launches.  Journals created inside the scope with
    no explicit trace context inherit it, minting their own segment
    span under ``parent_span``."""
    saved = {k: os.environ.get(k) for k in TRACE_ENV_KEYS}
    for k in TRACE_ENV_KEYS:
        os.environ.pop(k, None)
    os.environ.update(trace_env(trace_id, span_id, parent_span))
    try:
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def trace_env(trace_id=None, span_id=None, parent_span=None):
    """The env-var triple a parent exports so a child process's
    journals inherit its trace context (None values are omitted)."""
    env = {}
    if trace_id:
        env["TPUVSR_TRACE_ID"] = str(trace_id)
    if span_id:
        env["TPUVSR_SPAN_ID"] = str(span_id)
    if parent_span:
        env["TPUVSR_PARENT_SPAN"] = str(parent_span)
    return env


class Journal:
    """Append-only JSONL writer.  ``path=None`` makes every method a
    no-op so engines can call unconditionally."""

    def __init__(self, path=None, run_id=None, trace_id=None,
                 span_id=None, parent_span=None):
        self.path = path
        self.run_id = run_id or new_run_id()
        # trace context (ISSUE 17): explicit args win, then the env
        # triple a parent process exported, else no trace keys at all.
        # Passing an explicit EMPTY string means "no trace context" and
        # suppresses the env fallback (a multi-threaded worker's
        # journal writes must not inherit a sibling job's exported
        # scope)
        def _ctx(explicit, envkey):
            if explicit is not None:
                return explicit or None
            return os.environ.get(envkey)
        self.trace_id = _ctx(trace_id, "TPUVSR_TRACE_ID")
        self.span_id = _ctx(span_id, "TPUVSR_SPAN_ID")
        self.parent_span = _ctx(parent_span, "TPUVSR_PARENT_SPAN")
        if self.span_id is None and self.trace_id is not None:
            # a traced journal with no named span is its OWN segment
            # (an engine run inside a worker's trace_scope): mint a
            # fresh span under parent_span, so each attempt/retry
            # segment is distinguishable in the span tree
            self.span_id = new_span_id()
        # opt-in crash consistency: fsync after every event so even a
        # SIGKILL mid-write never leaves a torn LAST line for a tailing
        # aggregator (the flush-per-event default already guarantees a
        # valid prefix on clean-ish deaths; fsync closes the page-cache
        # window at a per-event latency cost)
        self._fsync = os.environ.get("TPUVSR_JOURNAL_FSYNC") == "1"
        self._fh = None
        if path:
            d = os.path.dirname(os.path.abspath(path))
            os.makedirs(d, exist_ok=True)
            self._fh = open(path, "a")

    @property
    def enabled(self):
        return self._fh is not None

    def reopen(self):
        """Re-open a closed journal in append mode (observer reuse
        across a checkpoint/recover pair).  No-op when pathless or
        already open."""
        if self.path and self._fh is None:
            self._fh = open(self.path, "a")

    def write(self, event, **fields):
        if self._fh is None:
            return None
        rec = {"event": event, "ts": round(time.time(), 3),
               "run_id": self.run_id}
        if self.trace_id:
            rec["trace_id"] = self.trace_id
        if self.span_id:
            rec["span_id"] = self.span_id
        if self.parent_span:
            rec["parent_span"] = self.parent_span
        rec.update(fields)
        self._fh.write(json.dumps(rec, sort_keys=True,
                                  default=str) + "\n")
        self._fh.flush()
        if self._fsync:
            os.fsync(self._fh.fileno())
        return rec

    def close(self):
        if self._fh is not None:
            self._fh.close()
            self._fh = None


def validate_journal_line(obj):
    """Raise ValueError unless `obj` is a schema-valid journal event.
    Returns the event type."""
    if not isinstance(obj, dict):
        raise ValueError(f"journal line is {type(obj).__name__}, "
                         f"not an object")
    missing = [k for k in COMMON_REQUIRED if k not in obj]
    if missing:
        raise ValueError(f"journal line missing common keys: {missing}")
    ev = obj["event"]
    if ev not in EVENT_REQUIRED:
        raise ValueError(f"unknown journal event type {ev!r}")
    missing = [k for k in EVENT_REQUIRED[ev] if k not in obj]
    if missing:
        raise ValueError(f"{ev} event missing keys: {missing}")
    if ev == "run_start" and obj["schema"] != JOURNAL_SCHEMA:
        raise ValueError(f"run_start schema {obj['schema']!r}, "
                         f"want {JOURNAL_SCHEMA!r}")
    return ev


def read_journal(path):
    """Parse + validate a journal file into a list of event dicts."""
    out = []
    with open(path) as f:
        for i, line in enumerate(f):
            line = line.strip()
            if not line:
                continue
            try:
                obj = json.loads(line)
            except ValueError as e:
                raise ValueError(f"{path}:{i + 1}: not JSON: {e}")
            validate_journal_line(obj)
            out.append(obj)
    return out
