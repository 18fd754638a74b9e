"""RunObserver: the one observability object an engine run carries.

Bundles the three obs pieces — run journal (JSONL event stream),
metrics collector (phase timers + counters + per-level rows), and the
JAX profiler hooks — behind a single interface every engine threads
through its fixpoint loop:

    obs = RunObserver.ensure(obs, "device", spec, log=log)
    obs.start(t0, backend=jax.default_backend(), resumed=False)
    # start() opens the run's root span (the "check" phase frame: what
    # no span below names) and, under TPUVSR_PROFILE, the profiler
    # session; finish() closes both
    while ...:
        with obs.span(spans.DISPATCH, depth=d):     # DispatchPipeline
            out = self._level(...)
        with obs.span(spans.HOST_SYNC):
            sc = jax.device_get(...)
        obs.boundary(depth=d)       # open until the next launch
        obs.level_done(depth, frontier=.., distinct=.., generated=..)
        obs.progress(depth=.., distinct=.., generated=..)
    return self._finish(res, obs, fp_count)   # -> obs.finish(res, ...)

Engines that are handed ``obs=None`` get a private collector: metrics
are always gathered (they're cheap dict/clock ops and become
``CheckResult.metrics``), while the journal file, the ``-metrics``
dump, and the stderr stats table only exist when the caller asked for
them (CLI ``-journal`` / ``-metrics`` flags).

``primary`` exists for the multi-host sharded path: every process
collects, only host 0 writes files / renders the table (per-shard
numbers are reduced host-side before they reach the collector).
"""

from __future__ import annotations

import functools
import json
import os
import time

from . import builds, spans
from .journal import JOURNAL_SCHEMA, Journal
from .metrics import Metrics
from .profiler import annotation_factory, profile_trace


def closes_observer(fn):
    """Decorator for engine ``run`` methods: on ANY escaping exception,
    finalize the engine's active observer (``self._obs_active``, set
    right after ``RunObserver.ensure``) — drains timers, stops the
    TPUVSR_PROFILE jax-profiler session so the failing run's trace is
    still written, closes the journal — then re-raises."""
    @functools.wraps(fn)
    def wrapper(self, *args, **kwargs):
        try:
            return fn(self, *args, **kwargs)
        except BaseException:
            obs = getattr(self, "_obs_active", None)
            if obs is not None:
                self._obs_active = None
                obs.close()
            raise
    return wrapper


class _Span:
    """One host phase: an exclusive phase frame of the metrics
    collector and, when the run is profiled, a TraceAnnotation of the
    same name around it."""

    __slots__ = ("_metrics", "_phase", "_annotation")

    def __init__(self, metrics, phase, annotation):
        self._metrics = metrics
        self._phase = phase
        self._annotation = annotation

    def __enter__(self):
        if self._annotation is not None:
            self._annotation.__enter__()
        self._metrics.begin(self._phase)
        return self

    def __exit__(self, *exc):
        self._metrics.end()
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        return False


class _Part:
    """One part of a host phase: seconds inside the phase's frame
    (``Metrics.part_begin`` / ``part_end``) and, when the run is
    profiled, a TraceAnnotation of the part's name nested in the
    phase's."""

    __slots__ = ("_metrics", "_phase", "_part", "_annotation", "_token")

    def __init__(self, metrics, phase, part, annotation):
        self._metrics = metrics
        self._phase = phase
        self._part = part
        self._annotation = annotation

    def __enter__(self):
        self._token = self._metrics.part_begin(self._phase)
        if self._annotation is not None:
            self._annotation.__enter__()
        return self

    def __exit__(self, *exc):
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        self._metrics.part_end(self._phase, self._part, self._token)
        return False


class RunObserver:
    def __init__(self, journal_path=None, metrics_path=None, log=None,
                 progress_every=10.0, run_id=None, primary=True,
                 table=None, annotation=annotation_factory):
        self.journal = Journal(journal_path if primary else None,
                               run_id=run_id)
        self.run_id = self.journal.run_id
        self.metrics = Metrics()
        self.metrics_path = metrics_path
        self.primary = primary
        self.progress_every = progress_every
        self.engine = None
        self.module = None
        self.backend = None
        # dispatch-window depth (ISSUE 4): engines with a pipelined
        # dispatch loop set this before start(); every run_start
        # carries it (1 = synchronous) so journals stay key-set
        # uniform across engines
        self.pipeline = 1
        # packed-frontier encoding in effect (ISSUE 9): engines set it
        # before start(); journaled on run_start like pipeline so a
        # journal identifies the run's state representation
        self.pack = False
        # level-kernel commit mode (ISSUE 10): "fused" | "per-action"
        # on the BFS engines, None on engines without a level kernel —
        # journaled on run_start with key-set parity across engines
        self.commit = None
        # symmetry canonicalization in effect (ISSUE 11): True when
        # the run fingerprints orbit-least images (engine/canon.py),
        # False when reduction is off, None on engines without the
        # seam — journaled on run_start with key-set parity
        self.symmetry = None
        # bounds pre-pass facts in effect (ISSUE 13): the compact
        # {tightened, dead_actions, state_bound} object on the BFS
        # engines consuming the speclint bounds pass, None when off or
        # on engines without the seam — journaled on run_start with
        # key-set parity
        self.bounds = None
        # streamed edge emission in effect (ISSUE 15): True when the
        # run's level kernel appends (src, action, dst) triples to the
        # behavior-graph stream, False when the seam exists but is
        # off, None on engines without it — journaled on run_start
        # with key-set parity
        self.edges = None
        # ample-set partial-order reduction in effect (ISSUE 16): the
        # compact {digest, actions, eligible_actions, sharded_proviso,
        # independence} object when the run's fused commit applies the
        # ample filter, None when off or on engines without the seam —
        # journaled on run_start with key-set parity
        self.por = None
        self._log = log
        # stats table on stderr: on when explicitly requested, else only
        # for runs that asked for observability artifacts
        self._table = table
        self._t0 = None
        self._last_progress = None
        self._finished = False
        self._profile_cm = None
        # the profiler side of a span: `annotation()` is asked ONCE, at
        # start(), for the TraceAnnotation class or None (profiling
        # off); tests inject a recording factory
        self._annotation_factory = annotation
        self._annotation = None
        self._root = None
        self._boundary = None       # the open boundary span, if any
        # what the run built (obs/builds.py), across every segment this
        # observer rides; attached to the calling thread from start()
        # to finish() on the device engines
        self.builds = builds.BuildMeter(report=self._build_event)
        self._builds_attached = False
        self._builds_previous = None
        # `checkpoint` parts as of the last `checkpoint` event: the
        # event carries what its own snapshot added
        self._snapshot_parts = {}

    # ------------------------------------------------------------------
    @classmethod
    def ensure(cls, obs, engine, spec=None, log=None,
               progress_every=None):
        """Engine entry point: adopt the caller's observer or create a
        private one; stamp run identity either way."""
        if obs is None:
            obs = cls(log=log,
                      progress_every=(10.0 if progress_every is None
                                      else progress_every))
        else:
            if obs._log is None:
                obs._log = log
            if progress_every is not None:
                obs.progress_every = progress_every
        obs.engine = engine
        if spec is not None and obs.module is None:
            obs.module = spec.module.name
        return obs

    @property
    def detailed(self):
        """True when the run asked for observability artifacts (journal
        or metrics dump) — the gate for stats that cost a device pull."""
        return self.journal.enabled or self.metrics_path is not None

    def log(self, msg):
        if self._log:
            self._log(msg)

    # -- lifecycle -----------------------------------------------------
    def start(self, t0, backend=None, resumed=False, **extra):
        """Begin the run clock.  `t0` is the engine's epoch — already
        rewound by the checkpoint's elapsed on a resume, so every
        ``elapsed_s`` this observer reports is cumulative across a
        checkpoint/recover chain.

        Also opens the run-wide instrumentation: the catch-all "check"
        phase frame (inner compile/dispatch/host_sync timers carve
        their time out of it, so the reported phases are disjoint and
        sum to the run's wall-clock) and, under ``TPUVSR_PROFILE=DIR``,
        the ``jax.profiler.trace`` session around the fixpoint loop.
        Both are closed by ``finish``.  Starting a FINISHED observer
        re-arms it (journal reopened in append mode, run_end guard
        reset) so one observer can ride a checkpoint run and its
        resume — the documented one-continuous-journal pattern —
        without the second segment silently journaling nothing;
        metrics keep accumulating across the segments, matching the
        cumulative elapsed convention."""
        if self._finished:
            self._finished = False
            if self.primary:
                self.journal.reopen()
        self._t0 = t0
        self._last_progress = time.time()
        self.backend = backend or self.backend or "host"
        # the device this process really has, as JAX reports it (null
        # on the host engines, which never touch JAX)
        device = dict.fromkeys(("platform", "device_kind",
                                "device_count"))
        if self.backend != "host":
            from ..models.registry import device_doc
            device = device_doc()
        self.journal.write("run_start", schema=JOURNAL_SCHEMA,
                           engine=self.engine, module=self.module,
                           backend=self.backend, resumed=bool(resumed),
                           **device,
                           pipeline=int(self.pipeline or 1),
                           pack=bool(self.pack),
                           commit=self.commit,
                           symmetry=self.symmetry,
                           bounds=self.bounds,
                           edges=self.edges,
                           por=self.por, **extra)
        self._profile_cm = profile_trace(log=self._log)
        self._profile_cm.__enter__()
        self._annotation = self._annotation_factory()
        self.builds.annotation = self._annotation
        if self.backend != "host":
            self._builds_previous = builds.attach(self.builds)
            self._builds_attached = True
        attrs = {"run_id": self.run_id}
        if self.journal.trace_id:
            attrs["trace_id"] = self.journal.trace_id
        self._root = self.span(spans.CHECK, **attrs)
        self._root.__enter__()

    def _stop_instruments(self):
        """Close the root span with every frame an early return left
        open, give the thread back its previous build meter, stop the
        profiler session."""
        self.metrics.drain()
        for held in (self._boundary, self._root):   # inner first
            if held is not None and held._annotation is not None:
                held._annotation.__exit__(None, None, None)
        self._boundary = self._root = None
        if self._builds_attached:
            builds.detach(self._builds_previous)
            self._builds_attached = False
            self._builds_previous = None
        if self._profile_cm is not None:
            self._profile_cm.__exit__(None, None, None)
            self._profile_cm = None

    def close(self):
        """Finalize instrumentation on an abnormal exit: drain open
        timer frames, stop the profiler session (so the trace of the
        FAILING run — the one worth inspecting — still gets written),
        close the journal file.  Idempotent; a normal ``finish`` covers
        all of it.  Engines with a delegating run funnel call this on
        exception; elsewhere an in-band engine error behaves like a
        kill (valid journal prefix, no run_end — the documented crash
        contract)."""
        self._stop_instruments()
        self.journal.close()

    def set_epoch(self, t0):
        """Re-anchor the run clock after ``start`` — used when a resume
        rewinds t0 by the checkpoint's recorded elapsed so reported
        ``elapsed_s`` stays cumulative across the recover chain."""
        self._t0 = t0

    def elapsed(self):
        return time.time() - self._t0 if self._t0 is not None else 0.0

    # -- the one span primitive ----------------------------------------
    def span(self, name, **attrs):
        """Mark a host phase.  `name` is one of ``spans.ENGINE_SPANS``
        (a KeyError otherwise: the vocabulary is fixed); numbers go in
        `attrs`.  The phase is timed exclusively under its key of the
        metrics document and, when the run is profiled, lies in the
        profile as a TraceAnnotation of the same name."""
        phase = spans.ENGINE_SPANS[name]
        annotation = self._annotation
        return _Span(self.metrics, phase,
                     None if annotation is None
                     else annotation(name, **attrs))

    def part(self, name, **attrs):
        """Mark a part of the host phase that is open.  `name` is one
        of ``spans.ENGINE_PARTS`` outside ``spans.READ_BACK_PARTS`` (a
        KeyError otherwise, as ``span``), and its phase has to be the
        innermost open one (a RuntimeError otherwise).  The phase is
        timed as it is without the part; the part's seconds go to the
        metrics document's ``phase_parts`` and, when the run is
        profiled, a TraceAnnotation of its name lies inside the
        phase's."""
        if name in spans.READ_BACK_PARTS:
            raise KeyError(name)
        phase, part = spans.ENGINE_PARTS[name]
        annotation = self._annotation
        return _Part(self.metrics, phase, part,
                     None if annotation is None
                     else annotation(name, **attrs))

    def boundary(self, **attrs):
        """Open the ``tpuvsr.engine.boundary`` span: the host is between
        the last collect of one unit of device work (a level; a chunk of
        the paged engine) and the first launch of the next.  It stays
        open across statements, other spans nest inside it, and
        ``end_boundary`` closes it: ``DispatchPipeline.launch`` does, and
        an engine's ``_finish``.  A no-op while one is open."""
        if self._boundary is None:
            self._boundary = self.span(spans.BOUNDARY, **attrs)
            self._boundary.__enter__()

    def end_boundary(self):
        span, self._boundary = self._boundary, None
        if span is not None:
            span.__exit__(None, None, None)

    def _build_event(self, rec):
        # a program read back through the seam of `builds.py` says what
        # its read, its decompression and its load cost
        read_back = {k: (round(v, 6) if isinstance(v, float) else v)
                     for k, v in rec.items() if k in builds.READ_BACK_KEYS}
        self.journal.write(
            "build", fun_name=str(rec["fun_name"]),
            trace_s=round(rec["trace_s"], 6),
            lower_s=round(rec["lower_s"], 6),
            backend_s=round(rec["backend_s"], 6), cache=rec["cache"],
            export=rec["export"], **read_back,
            elapsed_s=round(self.elapsed(), 3))

    # -- metrics delegates ---------------------------------------------
    def count(self, name, n=1):
        self.metrics.count(name, n)

    def gauge(self, name, value):
        self.metrics.gauge(name, value)

    # -- events --------------------------------------------------------
    def level_done(self, depth, *, frontier, distinct, generated,
                   **extra):
        el = self.elapsed()
        row = self.metrics.level(depth, frontier=frontier,
                                 distinct=distinct, generated=generated,
                                 elapsed_s=el, **extra)
        # the journal outlives a killed run: it carries what the level
        # cost too
        self.journal.write("level_done", depth=int(depth),
                           frontier=int(frontier), distinct=int(distinct),
                           generated=int(generated),
                           elapsed_s=round(el, 3), wall_s=row["wall_s"],
                           phases=row["phases"], unfed_s=row["unfed_s"],
                           dispatches=row["dispatches"], **extra)

    def checkpoint(self, path, depth, distinct, nbytes, fmt):
        """A level-boundary snapshot was written: `nbytes` staged
        (payloads + manifest, ``save_checkpoint``'s return; 0 on a
        rank that wrote nothing) in snapshot format `fmt`.  `parts`
        on the event: the seconds this snapshot added to each part of
        the ``checkpoint`` phase."""
        self.count("checkpoints")
        self.count("checkpoint_bytes", nbytes)
        now = dict(self.metrics.parts.get("checkpoint", {}))
        parts = {k: round(v - self._snapshot_parts.get(k, 0.0), 6)
                 for k, v in now.items()}
        self._snapshot_parts = now
        self.journal.write("checkpoint", path=str(path), depth=int(depth),
                           distinct=int(distinct), bytes=int(nbytes),
                           format=int(fmt), parts=parts,
                           elapsed_s=round(self.elapsed(), 3))

    def spill(self, depth, rows, nbytes, **extra):
        """A frontier page moved down a tier: device -> host RAM (the
        paged drain; no ``tier`` key), or host RAM -> disk
        (``tier: "disk"`` — the ISSUE 11 spill tier's level files)."""
        self.count("spills")
        self.count("spill_rows", rows)
        self.count("spill_bytes", nbytes)
        if extra.get("tier") == "disk":
            self.count("spill_disk_bytes", nbytes)
        self.journal.write("spill", depth=int(depth), rows=int(rows),
                           bytes=int(nbytes),
                           elapsed_s=round(self.elapsed(), 3), **extra)

    def page_in(self, depth, rows, nbytes):
        """A frontier page moved up a tier: host RAM -> device (the
        paged engine's chunk).  `nbytes` counts the rows the page
        holds, as ``spill`` does; the transfer is a whole page."""
        self.count("page_ins")
        self.count("page_in_rows", rows)
        self.count("page_in_bytes", nbytes)
        self.journal.write("page_in", depth=int(depth), rows=int(rows),
                           bytes=int(nbytes),
                           elapsed_s=round(self.elapsed(), 3))

    def grow(self, what, to):
        """A growth pause (message table / FPSet / buffers / exchange
        bucket): counters + journal; the engine logs its own wording."""
        self.count("grows")
        self.count(f"grow_{what}")
        self.journal.write("grow", what=what, to=int(to),
                           elapsed_s=round(self.elapsed(), 3))

    def edge_flush(self, depth, rows, nbytes):
        """A committed block of behavior-graph edge triples drained
        off the device append buffer into the host CSR builder
        (ISSUE 15) — the edge-stream analog of ``spill``."""
        self.count("edge_flushes")
        self.count("edge_rows", rows)
        self.count("edge_bytes", nbytes)
        self.journal.write("edge_flush", depth=int(depth),
                           rows=int(rows), bytes=int(nbytes),
                           elapsed_s=round(self.elapsed(), 3))

    # -- resilience events (ISSUE 3) -----------------------------------
    def fault(self, what, site, **extra):
        """An injected (or detected) fault, journaled BEFORE it acts so
        the journal always records why a run died or degraded."""
        self.count("faults")
        self.count(f"fault_{what.replace('-', '_')}")
        self.journal.write("fault", what=what, site=site,
                           elapsed_s=round(self.elapsed(), 3), **extra)

    def retry(self, attempt, backoff_s, **extra):
        self.count("retries")
        self.journal.write("retry", attempt=int(attempt),
                           backoff_s=round(float(backoff_s), 3),
                           elapsed_s=round(self.elapsed(), 3), **extra)

    def degrade(self, what, from_, to):
        self.count("degrades")
        self.journal.write("degrade", what=what,
                           elapsed_s=round(self.elapsed(), 3),
                           **{"from": from_, "to": to})

    def reshard(self, from_shards, to_shards, distinct):
        """An elastic sharded resume: the snapshot's N FPSet shards and
        frontier were re-hash-partitioned onto this M-device mesh at
        load time (ISSUE 5)."""
        self.count("reshards")
        self.gauge("resharded_from", int(from_shards))
        self.journal.write("reshard", from_shards=int(from_shards),
                           to_shards=int(to_shards),
                           distinct=int(distinct),
                           elapsed_s=round(self.elapsed(), 3))

    # -- walker-fleet simulation events (ISSUE 7) ----------------------
    def sim_chunk(self, depth, *, walks, steps, **extra):
        """A committed fleet chunk boundary — the sim analog of
        ``level_done`` (where service ticks, rescues and splits
        land).  `depth` is the committed walk step within the round;
        `walks`/`steps` are cumulative across the run."""
        self.count("sim_chunks")
        self.journal.write("sim_chunk", depth=int(depth),
                           walks=int(walks), steps=int(steps),
                           elapsed_s=round(self.elapsed(), 3), **extra)

    def split(self, *, killed, novelty_best, **extra):
        """An importance-splitting resample at a chunk boundary:
        `killed` low-novelty walkers were respawned as clones of the
        best ones (0 = the population was score-flat)."""
        self.count("splits")
        if killed:
            self.count("split_killed", int(killed))
        self.journal.write("split", killed=int(killed),
                           novelty_best=float(novelty_best),
                           elapsed_s=round(self.elapsed(), 3), **extra)

    def hunt_violation(self, name, walk, depth, **extra):
        """A UNIQUE (fleet-deduped) violation collected by the
        continuous hunt, replayed to a TRACE-format counterexample."""
        self.count("hunt_violations")
        self.journal.write("hunt_violation", name=str(name),
                           walk=int(walk), depth=int(depth),
                           elapsed_s=round(self.elapsed(), 3), **extra)

    def hunt_elastic(self, from_, to):
        """A walker-count reshape at a round boundary (elastic
        shrink/grow under the scheduler, or an elastic resume)."""
        self.count("hunt_elastics")
        self.journal.write("hunt_elastic",
                           elapsed_s=round(self.elapsed(), 3),
                           **{"from": int(from_), "to": int(to)})

    # -- batched trace validation events (ISSUE 8) ---------------------
    def validate_chunk(self, depth, *, traces, divergences, **extra):
        """A committed validation chunk boundary — the validator's
        ``level_done``/``sim_chunk`` analog (where service ticks and
        rescues land).  `depth` is the committed event step within the
        round; `traces`/`divergences` are cumulative across the run."""
        self.count("validate_chunks")
        self.journal.write("validate_chunk", depth=int(depth),
                           traces=int(traces),
                           divergences=int(divergences),
                           elapsed_s=round(self.elapsed(), 3), **extra)

    def divergence(self, trace, step, **extra):
        """One trace's first divergence: the recorded event at `step`
        matches no spec transition from any candidate state."""
        self.count("divergences")
        self.journal.write("divergence", trace=str(trace),
                           step=int(step),
                           elapsed_s=round(self.elapsed(), 3), **extra)

    def rescue(self, path, depth, distinct, signal_name):
        """A preemption rescue snapshot written at a level boundary
        (the run exits with the resumable code right after)."""
        self.count("rescue_checkpoints")
        self.journal.write("rescue_checkpoint", path=str(path),
                           depth=int(depth), distinct=int(distinct),
                           signal=str(signal_name),
                           elapsed_s=round(self.elapsed(), 3))

    # -- the one progress formatter (drift-proof across engines) -------
    def progress(self, depth=None, distinct=None, generated=None,
                 frontier=None, walks=None, steps=None, traces=None,
                 extra=None, force=False):
        """Throttled, uniformly formatted progress line.  BFS engines
        pass depth/distinct/generated(/frontier); simulation engines
        pass walks/steps; the trace validator passes traces.  Returns
        True when a line was emitted."""
        if self._log is None:
            return False
        now = time.time()
        if not force and self._last_progress is not None and \
                now - self._last_progress < self.progress_every:
            return False
        self._last_progress = now
        el = max(now - self._t0, 1e-9) if self._t0 is not None else None
        parts = []
        if traces is not None:
            parts.append(f"{traces} traces")
            if el:
                parts.append(f"{traces / el:.0f} traces/s")
        elif walks is not None:
            parts.append(f"{walks} walks")
            if steps is not None:
                parts.append(f"{steps} steps")
                if el:
                    parts.append(f"{steps / el:.0f} steps/s")
        else:
            if depth is not None:
                parts.append(f"depth {depth}")
            if distinct is not None:
                parts.append(f"{distinct} distinct")
            if generated is not None:
                parts.append(f"{generated} generated")
            if el and distinct is not None:
                parts.append(f"{distinct / el:.0f} distinct/s")
            if el and generated is not None:
                parts.append(f"{generated / el:.0f} gen/s")
            if frontier is not None:
                parts.append(f"frontier {frontier}")
        if extra:
            parts.append(str(extra))
        first, rest = parts[0], ", ".join(parts[1:])
        self._log(f"{first}: {rest}" if (depth is not None and rest)
                  else ", ".join(parts))
        return True

    # -- finish --------------------------------------------------------
    def finish(self, res, levels=None):
        """Uniform result finalization for every engine: stamps
        ``elapsed`` / ``states_per_sec`` / ``levels`` / ``metrics`` on
        the result object, journals violation + run_end, dumps the
        ``-metrics`` file, renders the stderr stats table."""
        attached = self._builds_attached
        self._stop_instruments()      # close "check" + any open frames
        if attached:
            self.builds.stamp(self.metrics)
        elapsed = self.elapsed() if self._t0 is not None \
            else getattr(res, "elapsed", 0.0) or 0.0
        res.elapsed = elapsed
        el = max(elapsed, 1e-9)
        summary = {"ok": bool(res.ok), "elapsed_s": round(elapsed, 6)}
        violated = getattr(res, "violated_invariant",
                           getattr(res, "property_name", None))
        error = getattr(res, "error", None)
        if hasattr(res, "states_generated"):            # CheckResult
            if levels is not None:
                res.levels = [int(x) for x in levels]
            res.states_per_sec = res.states_generated / el
            self.gauge("states_per_sec", res.states_per_sec)
            self.gauge("distinct_per_s", res.distinct_states / el)
            if res.states_generated:
                self.gauge("dedup_hit_rate",
                           1.0 - res.distinct_states
                           / res.states_generated)
            summary.update(distinct=int(res.distinct_states),
                           generated=int(res.states_generated),
                           diameter=int(res.diameter))
        elif hasattr(res, "walks"):                     # SimResult
            self.gauge("steps_per_s", res.steps / el)
            self.gauge("walks_per_s", res.walks / el)
            summary.update(walks=int(res.walks), steps=int(res.steps),
                           deadlocks=int(res.deadlocks))
            if getattr(res, "violations", None) is not None:
                summary["unique_violations"] = len(res.violations)
        elif hasattr(res, "traces_checked"):            # ValidateResult
            self.gauge("traces_per_s", res.traces_checked / el)
            summary.update(traces=int(res.traces_checked),
                           accepted=int(res.accepted),
                           divergences=len(res.divergences or []))
        elif hasattr(res, "property_name"):             # LivenessResult
            summary.update(distinct=int(res.distinct_states))
        summary["violated"] = violated
        summary["error"] = error
        if not res.ok and not self._finished:
            divs = getattr(res, "divergences", None)
            kind = ("divergence" if divs else
                    "invariant" if violated else
                    "deadlock" if (error == "deadlock"
                                   or getattr(res, "deadlocks", 0))
                    else "error")
            name = (f"trace {divs[0].get('trace')}" if divs
                    else violated or error or kind)
            self.journal.write("violation", kind=kind, name=name,
                               elapsed_s=round(elapsed, 3))
        if not self._finished:
            self.journal.write("run_end", **summary)
        self._finished = True
        doc = self.metrics.to_dict(
            run_id=self.run_id, engine=self.engine, module=self.module,
            backend=self.backend, **summary)
        res.metrics = doc
        if self.metrics_path and self.primary:
            d = os.path.dirname(os.path.abspath(self.metrics_path))
            os.makedirs(d, exist_ok=True)
            with open(self.metrics_path, "w") as f:
                json.dump(doc, f, indent=1, sort_keys=True)
                f.write("\n")
            self.log(f"metrics written to {self.metrics_path}")
        if self._log and self.primary and (
                self._table or (self._table is None and self.detailed)):
            self._render_table(doc)
        self.journal.close()
        return res

    def _render_table(self, doc):
        ph = doc["phases"]
        if ph:
            tot = sum(ph.values()) or 1e-9
            unfed = doc.get("phases_unfed")

            def cell(k, v):
                out = f"{k} {v:.2f}s ({100 * v / tot:.0f}%"
                if unfed is not None:
                    out += f", unfed {unfed.get(k, 0.0):.2f}s"
                return out + ")"
            self.log("phase seconds: " + ", ".join(
                cell(k, v)
                for k, v in sorted(ph.items(), key=lambda kv: -kv[1])))
        if doc["counters"]:
            self.log("counters: " + ", ".join(
                f"{k}={v}" for k, v in sorted(doc["counters"].items())))
        ga = doc["gauges"]
        keyed = [f"{k}={ga[k]:.3g}" if isinstance(ga[k], (int, float))
                 else f"{k}={ga[k]}" for k in sorted(ga)]
        if keyed:
            self.log("gauges: " + ", ".join(keyed))
