"""The dispatch worker: claims jobs, runs them supervised, maps
outcomes back onto the queue.

One worker process hosts MANY jobs (the ``run_supervised`` library
mode, ISSUE 6 satellite — nothing in a job's ending may own the
process exit).  Per job the worker:

1. **admits** — loads the spec and runs the speclint gate
   (``queued -> admitted``, or ``failed`` with the lint findings as
   the reason: a rejected job never costs device time);
2. **claims** (atomic claim file), allocates devices from the
   scheduler's pool (a sharded job's allocation IS its mesh size),
   journals ``job_started``;
3. **runs** under ``resilience.Supervisor`` via ``run_supervised`` —
   OOM degrades (tile halving / mesh shrink / paged fallback) stay
   per-job, the job's journal and metrics doc collect every attempt,
   and a rescue handoff on the queue makes the run resume from its
   snapshot;
4. at every level boundary (a :class:`JobObserver` tick) it polls for
   cancellation and asks the scheduler to **rebalance** — a
   higher-priority arrival or freed devices preempts the run through
   the ordinary rescue-checkpoint path (``request_preemption``: the
   same flag SIGTERM sets, so the machinery is identical to a real
   preemption) and requeues it with the scheduler's new mesh size;
5. **maps the outcome** to a terminal state through the ONE table in
   ``tpuvsr/exitcodes.py`` — exit 75 / ``Preempted`` means
   ``preempted-requeued`` with the rescue checkpoint attached, never
   a dead job.

Jobs with ``flags.stub`` run the inline counter spec through the REAL
device/paged/sharded engines on the stub kernel
(``tpuvsr/testing.py``) — the tier-1 path every service test and
``scripts/serve_demo.py`` exercises without the reference mount.

``kind="shell"`` jobs (argv + timeout) run arbitrary commands under
the same spool, the same claim discipline and the same exit-code
table — one queue implementation.
"""

from __future__ import annotations

import contextlib
import gc
import os
import signal
import subprocess
import threading
import time

from ..exitcodes import EX_RESUMABLE, job_state
from ..obs import Journal, RunObserver, spans
from ..obs.journal import (new_span_id, root_span, trace_env,
                           trace_scope)
from ..obs.profiler import annotation_factory
from .scheduler import DevicePool, Scheduler, advise_backend

# NOTE: the serving-tier pieces (fair-share policy, multi-runner) live
# in the HIGHER tpuvsr/serve layer and are imported lazily inside the
# Worker — the default policy/lane wiring lives here for one-stop
# construction, but `import tpuvsr.service` must not eagerly drag the
# serving tier in (the dependency arrow stays serve -> service)


def _is_light(job):
    from ..serve.multirunner import is_light
    return is_light(job)


# the ONE trace serializer (engine/trace.py), re-exported under the
# name the service's callers and tests already use
from ..engine.trace import trace_to_jsonable  # noqa: E402,F401


def result_summary(res):
    """CheckResult -> the JSON-able summary stored on the job."""
    out = {"ok": bool(res.ok),
           "distinct": int(res.distinct_states),
           "generated": int(res.states_generated),
           "diameter": int(res.diameter),
           "levels": ([int(x) for x in res.levels]
                      if res.levels else None),
           "violated": res.violated_invariant,
           "error": res.error,
           "elapsed_s": round(float(res.elapsed or 0.0), 3)}
    if res.trace:
        out["trace"] = trace_to_jsonable(res.trace)
    return out


_NO_SPAN = contextlib.nullcontext()


class JobObserver(RunObserver):
    """RunObserver whose ``level_done`` also ticks the worker — the
    hook that makes scheduling LIVE: cancellation and rebalance
    decisions land at level boundaries, exactly where the engines
    poll the preemption flag."""

    def __init__(self, *args, tick=None, **kwargs):
        super().__init__(*args, **kwargs)
        self._tick = tick

    def level_done(self, depth, **kw):
        super().level_done(depth, **kw)
        if self._tick is not None:
            self._tick(int(depth))

    def sim_chunk(self, depth, **kw):
        # fleet chunk boundaries are the sim analog of level
        # boundaries (ISSUE 7): the same tick drives cancel and
        # elastic rebalance for kind="sim" jobs
        super().sim_chunk(depth, **kw)
        if self._tick is not None:
            self._tick(int(depth))

    def validate_chunk(self, depth, **kw):
        # validation chunk boundaries complete the set (ISSUE 8):
        # kind="validate" jobs cancel/rebalance where the batch
        # validator polls the preemption flag
        super().validate_chunk(depth, **kw)
        if self._tick is not None:
            self._tick(int(depth))


class Worker:
    """Serial drain loop over one :class:`JobQueue` (see module doc).

    `on_level(worker, job, depth)` is the test/demo hook invoked at
    every level boundary of a running job BEFORE the scheduler looks —
    the deterministic stand-in for "a job arrives mid-run"."""

    def __init__(self, queue, *, devices=None, scheduler=None,
                 log=None, on_level=None, owner=None, poll=0.25,
                 bench_dir=None, tpu_devices=0, shell_retry_gate=None,
                 policy="auto", light_threads=2,
                 hb_journal_every=30.0, guard=None):
        self.queue = queue
        # the serving-tier admission guard (ISSUE 18): when present,
        # its per-(tenant, spec-digest) circuit breaker is consulted
        # BEFORE any device allocation and fed every terminal outcome
        self.guard = guard
        if devices is None:
            import jax
            devices = len(jax.devices())
        # fair-share pop order (ISSUE 14): "auto" builds the default
        # deficit-round-robin + aging policy; None reverts to the
        # original priority-then-seq order
        if policy == "auto":
            from ..serve.fairshare import FairSharePolicy
            policy = FairSharePolicy()
        self.policy = policy
        self.pool = (scheduler.pool if scheduler
                     else DevicePool(devices))
        self.scheduler = scheduler or Scheduler(self.pool,
                                                policy=self.policy)
        # the light-job side lane (ISSUE 14): shell / interp-validate /
        # lint-only jobs run on threads with a zero-device allocation
        # while this worker's mesh job keeps running; 0 disables
        if light_threads:
            from ..serve.multirunner import MultiRunner
            self.multirunner = MultiRunner(self, threads=light_threads)
        else:
            self.multirunner = None
        self.hb_journal_every = hb_journal_every
        self._last_hb = 0.0
        # every claim this worker currently holds, heartbeated by a
        # background thread — the level-boundary tick alone cannot
        # cover a multi-minute first compile or a light job waiting in
        # the multi-runner's backlog, and a silent claim looks DEAD to
        # a cross-host recover_stale after heartbeat_timeout
        self._held = set()
        self._hb_thread = None
        self._hb_stop = threading.Event()
        self.on_level = on_level
        self.owner = owner or f"worker-{os.getpid()}"
        self.poll = poll
        self.bench_dir = bench_dir
        self.tpu_devices = tpu_devices
        # shell jobs only: gate(job, rc) -> True means the failure
        # never really ran (e.g. a lost machine) — refund the attempt
        # and requeue instead of burning one
        self.shell_retry_gate = shell_retry_gate
        self._log = log
        self._specs = {}             # job_id -> loaded spec (admission)
        self._spans = {}             # job_id -> this attempt's span id
        # TraceAnnotation while TPUVSR_PROFILE is set, asked once per
        # job (run_one) and per admission sweep, never per span
        self._annotation = None
        self._current = None
        self._preempt_sent = False
        self._cancelled = False
        self._requeue_devices = None
        self._requeue_reason = None
        self._shutdown = False       # external SIGTERM/SIGINT landed
        self.processed = []          # [(job_id, state), ...] this drain

    def log(self, msg):
        if self._log:
            self._log(f"service: {msg}")

    # -- claim heartbeats ----------------------------------------------
    def _hb_loop(self, interval):
        while not self._hb_stop.wait(interval):
            for jid in list(self._held):
                self.queue.heartbeat(jid)

    def _hold(self, job_id):
        """Track a held claim and make sure the heartbeat thread is
        alive — from here until ``_release_hold`` the claim mtime
        stays fresh no matter what the job is doing (compiling,
        queued behind the light lane, mid-subprocess)."""
        self._held.add(job_id)
        if self._hb_thread is None or not self._hb_thread.is_alive():
            timeout = getattr(self.queue, "heartbeat_timeout", 300.0)
            self._hb_stop.clear()
            self._hb_thread = threading.Thread(
                target=self._hb_loop,
                args=(max(1.0, min(15.0, timeout / 10.0)),),
                name="tpuvsr-heartbeat", daemon=True)
            self._hb_thread.start()

    def _release_hold(self, job_id):
        self._held.discard(job_id)

    def _span(self, name, **attrs):
        """One of ``spans.SERVICE_SPANS`` in the profile when one is
        being taken, else a shared no-op.  No phase: the job journal
        times a job."""
        annotation = self._annotation
        return _NO_SPAN if annotation is None else annotation(name,
                                                              **attrs)

    def _trace_ctx(self, job):
        """This job's trace context for a service-side journal write:
        the attempt span while one is open (parented on the service
        root), the deterministic root span otherwise.  Jobs from a
        pre-telemetry spool (no trace_id) get no trace keys at all."""
        tid = getattr(job, "trace_id", None)
        if not tid:
            # explicit empty strings so a concurrently exported
            # trace_scope (another job on this process) can never
            # leak its env context into THIS job's events
            return {"trace_id": "", "span_id": "", "parent_span": ""}
        span = self._spans.get(job.job_id)
        if span:
            return {"trace_id": tid, "span_id": span,
                    "parent_span": root_span(tid)}
        return {"trace_id": tid, "span_id": root_span(tid)}

    def _journal(self, job, event, **fields):
        """Append one job_* event to the JOB'S OWN journal (the same
        file the engine/supervisor attempts write to)."""
        j = Journal(self.queue.journal_path(job.job_id),
                    run_id=f"svc-{self.owner}", **self._trace_ctx(job))
        try:
            j.write(event, job_id=job.job_id,
                    elapsed_s=round(time.time() - job.submitted_ts, 3),
                    **fields)
        finally:
            j.close()

    def _placement(self, job):
        """(backend, why) for a device job's ``job_started`` event:
        the platform this process really has, as JAX reports it, and
        the scheduler's advisory (which never places anything —
        ROADMAP D1) as the reason string."""
        from ..models.registry import device_doc
        _advised, why = advise_backend(job, tpu_devices=self.tpu_devices,
                                       bench_dir=self.bench_dir)
        return device_doc()["platform"], why

    # -- admission (the speclint gate) ---------------------------------
    def _load_spec(self, job):
        with self._span(spans.LOAD_SPEC, job_id=job.job_id):
            return self._load_spec_inner(job)

    def _load_spec_inner(self, job):
        if job.flags.get("stub"):
            from ..testing import bad_counter_spec, counter_spec
            if job.flags.get("stub_bad"):
                return bad_counter_spec()
            return counter_spec(
                inv_bound=job.flags.get("inv_bound"),
                inv_x_bound=job.flags.get("inv_x_bound"))
        from ..engine.spec import load_spec
        cfg = job.cfg or os.path.splitext(job.spec)[0] + ".cfg"
        return load_spec(job.spec, cfg)

    def admit_pending(self):
        """queued -> admitted (or failed): load each new job's spec
        and run the full speclint report — rejection happens HERE,
        before any device time is spent.  A QueueError from any
        transition is a lost race against a concurrent worker (same as
        a lost claim): skip, never crash."""
        from .queue import FencedError, QueueError
        self._annotation = annotation_factory()
        for job in [j for j in self.queue.jobs()
                    if j.state == "queued"]:
            try:
                self._admit_one(job)
            except (QueueError, FencedError):
                continue

    def _admit_one(self, job):
        from ..analysis import lint_enabled, run_lint
        if job.kind == "shell":
            self.queue.transition(job.job_id, "admitted")
            self._journal(job, "job_admitted")
            return
        try:
            spec = self._load_spec(job)
        except Exception as e:  # noqa: BLE001 — a job, not the worker
            self.queue.finish(job.job_id, "failed",
                              reason=f"spec-load: "
                                     f"{type(e).__name__}: {e}")
            self._journal(job, "job_done", state="failed",
                          reason="spec-load")
            return
        if not job.flags.get("stub"):
            # the worker's engines (device/paged/sharded) all need
            # a compiled kernel; saying so at admission beats a
            # KeyError out of the model registry mid-claim
            from ..models.registry import has_device_model
            if not has_device_model(spec):
                self.queue.finish(
                    job.job_id, "failed",
                    reason=f"no device kernel for module "
                           f"{spec.module.name!r} "
                           f"(models/registry)")
                self._journal(job, "job_done", state="failed",
                              reason="no-device-kernel")
                return
        if lint_enabled(spec):
            report = run_lint(spec)
            if report.exit_code:
                findings = [f"{f.passname}: {f.message}"
                            for f in report.errors]
                self.queue.finish(job.job_id, "failed",
                                  reason="speclint",
                                  result={"speclint": findings})
                self._journal(job, "job_done", state="failed",
                              reason="speclint")
                self.log(f"job {job.job_id} rejected by speclint "
                         f"({len(findings)} error(s))")
                return
            if self._reject_oversized(job, spec):
                return
        self._specs[job.job_id] = spec
        self.queue.transition(job.job_id, "admitted")
        self._journal(job, "job_admitted")

    def _reject_oversized(self, job, spec):
        """Bounds-pass admission gate (ISSUE 13): a check job whose
        static state-space upper bound provably exceeds the requested
        tier's capacity (``scheduler.tier_states_for``) is rejected —
        with the minimum tier that WOULD fit as the re-advisory —
        before any device time.  Returns True when the job was
        finished (rejected)."""
        if job.kind != "check":
            return False
        try:
            from ..analysis.passes.bounds import analyze
            facts = analyze(spec)
        except Exception:  # noqa: BLE001 — advisory gate, never fatal
            return False
        if facts.state_bound is None:
            return False
        from .scheduler import TIER_STATES_PER_DEVICE, tier_states_for
        cap = tier_states_for(job)
        if facts.state_bound <= cap:
            return False
        advised = -(-facts.state_bound // TIER_STATES_PER_DEVICE)
        self.queue.finish(
            job.job_id, "failed", reason="bounds-admission",
            result={"state_bound": int(facts.state_bound),
                    "tier_states": int(cap),
                    "advised_devices": int(advised)})
        self._journal(job, "job_done", state="failed",
                      reason="bounds-admission")
        self.log(f"job {job.job_id} rejected at admission: static "
                 f"state bound {facts.state_bound} exceeds the "
                 f"requested tier's {cap} states (re-advise: "
                 f">= {advised} device(s) or a paged/spill tier)")
        return True

    # -- the level-boundary tick ---------------------------------------
    def _tick(self, job, depth):
        # heartbeat FIRST, even when a preemption is already pending:
        # the claim heartbeat record is what keeps a cross-host
        # recover_stale from declaring this worker dead (ISSUE 14)
        self.queue.heartbeat(job.job_id)
        try:
            # on replicated drivers, ship the latest snapshot into the
            # driver blob store so a rescue survives THIS host's disk
            # (no-op on fs, and until the snapshot depth advances)
            self.queue.replicate_snapshot(job.job_id)
        except Exception:  # noqa: BLE001 — replication is best-effort
            pass
        if self.hb_journal_every and \
                time.time() - self._last_hb >= self.hb_journal_every:
            self._last_hb = time.time()
            self._journal(job, "worker_heartbeat", worker=self.owner,
                          depth=int(depth))
        if self._preempt_sent:
            return
        from ..resilience.supervisor import request_preemption
        # fold spool lines appended by OTHER processes since the last
        # look — live admission/rebalance must see a `submit` from a
        # second terminal, not just jobs entered through this object
        self.queue.refresh()
        if self.queue.cancel_requested(job.job_id):
            self._cancelled = True
            self._preempt_sent = True
            request_preemption("CANCEL")
            self.log(f"job {job.job_id}: cancel requested; rescuing "
                     f"at the level boundary")
            return
        if self.on_level is not None:
            self.on_level(self, job, depth)
        self.admit_pending()
        dec = self.scheduler.rebalance(job, self.queue.jobs())
        if dec is not None:
            self._requeue_devices = dec.devices
            self._requeue_reason = f"{dec.action}: {dec.reason}"
            self._preempt_sent = True
            request_preemption("SCHED")
            self.log(f"job {job.job_id}: {self._requeue_reason}; "
                     f"preempting at the level boundary "
                     f"(next mesh {dec.devices})")

    # -- one job -------------------------------------------------------
    def run_one(self, job):
        self._current = job
        self._preempt_sent = False
        self._cancelled = False
        self._requeue_devices = None
        self._requeue_reason = None
        # one span per ATTEMPT, parented on the service root span:
        # job_started/job_done/job_requeued of this attempt share it,
        # and the engine-run segments parent onto it via trace_scope
        if getattr(job, "trace_id", None):
            self._spans[job.job_id] = new_span_id()
        self._annotation = annotation_factory()
        try:
            # the job's spans share the journal's identifiers
            with self._span(spans.JOB, job_id=job.job_id,
                            trace_id=getattr(job, "trace_id", None)
                            or ""):
                if self._breaker_blocks(job):
                    return None
                if job.kind == "shell":
                    return self._run_shell(job)
                if job.kind == "sim":
                    return self._run_sim(job)
                if job.kind == "validate":
                    if _is_light(job):
                        return self._run_validate_interp(job)
                    return self._run_validate(job)
                if _is_light(job):
                    return self._run_lint_only(job)
                return self._run_check(job)
        finally:
            self._release_hold(job.job_id)
            self.pool.release(job.job_id)
            self._current = None
            self._specs.pop(job.job_id, None)
            self._spans.pop(job.job_id, None)
            # the finished job's engine is garbage in reference cycles,
            # and holds its device buffers and the level program's
            # executable: free them here, not whenever the cyclic
            # collector next runs a full pass.  While they live, the
            # next job's executable loads a second slower, so what a
            # job cost depended on how many objects its predecessor
            # had allocated (PERF.md §6, PR 43)
            gc.collect()

    def run_one_light(self, job):
        """Run one LIGHT job (shell / interp validate / lint-only) —
        the multi-runner's thread entry.  Touches none of the per-job
        preemption fields ``run_one`` owns, so it is safe beside a
        concurrently running mesh job; any unexpected error fails the
        JOB, never the thread pool."""
        from .queue import FencedError, QueueError
        if getattr(job, "trace_id", None):
            self._spans[job.job_id] = new_span_id()
        try:
            if self._breaker_blocks(job):
                return
            if job.kind == "shell":
                self._run_shell(job)
            elif job.kind == "validate":
                self._run_validate_interp(job)
            elif job.kind == "check" and job.flags.get("lint_only"):
                self._run_lint_only(job)
            else:
                self._finish(job, "failed",
                             reason="not-a-light-job (multi-runner "
                                    "dispatch bug)")
        except (QueueError, FencedError):
            pass                  # lost race against a sibling worker
        except Exception as e:  # noqa: BLE001 — a job, not the worker
            try:
                self._finish(job, "failed",
                             reason=f"light-runner: "
                                    f"{type(e).__name__}: {e}")
            except (QueueError, FencedError):
                pass
        finally:
            self._release_hold(job.job_id)
            self.pool.release(job.job_id)
            self._specs.pop(job.job_id, None)
            self._spans.pop(job.job_id, None)

    # -- light jobs (the multi-runner lane, ISSUE 14) ------------------
    def _run_validate_interp(self, job):
        """``kind="validate"`` + ``flags.interp``: the interpreter
        reference validator (``tpuvsr/validate/host.py``) — pure
        Python, zero devices, safe on the multi-runner threads.  The
        full nondeterminism handling is identical to the batch
        engine's (the batch engine cross-checks against THIS path), so
        verdicts match the device run bit-for-bit."""
        from ..validate import host_validate_batch, load_traces
        from ..validate.batch import validate_result_summary
        spec = self._specs.get(job.job_id) or self._load_spec(job)
        self._journal(job, "job_started", attempt=job.attempts,
                      devices=0, backend="cpu",
                      placement="light: interpreter validator "
                                "(multi-runner)")
        try:
            traces_path = job.flags.get("traces")
            if not traces_path:
                raise ValueError("validate jobs need flags.traces "
                                 "(the TRACE.jsonl path)")
            traces = load_traces(traces_path, spec)
            res = host_validate_batch(spec, traces, log=self._log)
        except Exception as e:  # noqa: BLE001 — a job, not the worker
            self._finish(job, "failed",
                         reason=f"job-setup: {type(e).__name__}: {e}")
            return
        state = ("failed" if res.error
                 else "violated" if res.divergences else "done")
        self._finish(job, state, result=validate_result_summary(res),
                     reason=res.error)

    def _run_lint_only(self, job):
        """``kind="check"`` + ``flags.lint_only``: a speclint report
        job — the analyzer already gated admission, so by the time
        this runs the spec is clean; the "run" publishes the full
        report as the job result.  Zero devices, zero jax."""
        from ..analysis import run_lint
        spec = self._specs.get(job.job_id) or self._load_spec(job)
        self._journal(job, "job_started", attempt=job.attempts,
                      devices=0, backend="cpu",
                      placement="light: speclint report "
                                "(multi-runner)")
        try:
            report = run_lint(spec)
        except Exception as e:  # noqa: BLE001 — a job, not the worker
            self._finish(job, "failed",
                         reason=f"job-setup: {type(e).__name__}: {e}")
            return
        findings = [f"{f.passname}: {f.message}"
                    for f in (report.errors + report.warnings)]
        state = "failed" if report.exit_code else "done"
        self._finish(job, state,
                     result={"speclint": findings,
                             "errors": len(report.errors),
                             "warnings": len(report.warnings)},
                     reason="speclint" if report.exit_code else None)

    def _breaker_blocks(self, job):
        """Fail a job fast — reason ``"breaker-open"`` — when its
        (tenant, spec-digest) circuit breaker is open (ISSUE 18): a
        crash-looping spec must stop consuming device time after K
        failures.  The check runs BEFORE any scheduler allocation;
        the half-open probe after cooldown is the one run allowed
        through to test recovery."""
        if self.guard is None:
            return False
        from ..serve.guard import spec_digest
        digest = spec_digest(job.spec, job.cfg)
        if self.guard.breaker_allow(job.tenant, digest,
                                    ts=time.time()):
            return False
        self._finish(job, "failed", reason="breaker-open")
        return True

    def _finish(self, job, state, **kw):
        from .queue import FencedError
        try:
            self.queue.finish(job.job_id, state, **kw)
        except FencedError as e:
            # our claim was recovered (and possibly re-issued) while
            # we were presumed dead — the successor owns this job now.
            # Drop OUR outcome: committing it too would double-count
            # the job (the exactly-once story the fence exists for)
            self.log(f"job {job.job_id}: fenced, dropping {state} "
                     f"({e})")
            self.processed.append((job.job_id, "fenced"))
            return
        self._journal(job, "job_done", state=state,
                      reason=kw.get("reason"))
        self.processed.append((job.job_id, state))
        # feed the circuit breaker every REAL terminal outcome:
        # `failed` is a breaker failure, `done`/`violated` successes
        # (a counterexample is the engine working, not crashing);
        # breaker-open fast-fails must not re-count as failures or an
        # open breaker would feed itself
        if self.guard is not None and kw.get("reason") != "breaker-open" \
                and state in ("done", "violated", "failed"):
            from ..serve.guard import spec_digest
            self.guard.breaker_record(
                job.tenant, spec_digest(job.spec, job.cfg),
                state != "failed", ts=time.time())
        self.log(f"job {job.job_id}: {state}"
                 + (f" ({kw.get('reason')})" if kw.get("reason")
                    else ""))

    def _run_check(self, job):
        from ..resilience import faults
        from ..resilience.supervisor import run_supervised
        spec = self._specs.get(job.job_id) or self._load_spec(job)
        kind = job.engine if job.engine in ("device", "paged",
                                            "sharded") else "device"
        alloc = self.scheduler.alloc_for(job)
        self.pool.alloc(job.job_id, alloc)
        backend, why = self._placement(job)
        self._journal(job, "job_started", attempt=job.attempts,
                      devices=alloc, backend=backend,
                      placement=why)
        flags = job.flags
        injected = None
        try:
            # everything from here to the outcome is THIS JOB's
            # problem: malformed flags (bad supervisor kwargs, a bad
            # -inject grammar) fail the job, never the worker
            factory = None
            if flags.get("stub"):
                from ..testing import stub_service_factory
                engine_kw = {}
                if flags.get("pipeline"):
                    engine_kw["pipeline"] = int(flags["pipeline"])
                factory = stub_service_factory(
                    spec, inv_bound=flags.get("inv_bound"),
                    inv_x_bound=flags.get("inv_x_bound"), **engine_kw)
            sup_kw = dict(flags.get("supervisor") or {})
            sup_kw.setdefault("backoff_base", 0.0)

            def observer_factory(**kw):
                return JobObserver(
                    tick=lambda depth: self._tick(job, depth), **kw)

            injected = flags.get("inject")
            if injected:
                faults.install(injected)
            # the engine's own journal (RunObserver) runs inside the
            # attempt span's trace scope, so every run_start /
            # level_done / fault / run_end of this attempt carries the
            # job's trace_id with a fresh per-segment span (ISSUE 17)
            with trace_scope(job.trace_id,
                             parent_span=self._spans.get(job.job_id)), \
                    self._span(spans.RUN, job_id=job.job_id):
                out = run_supervised(
                    spec, engine=kind, span=self._span,
                    checkpoint_path=self.queue.checkpoint_path(
                        job.job_id),
                    journal_path=self.queue.journal_path(job.job_id),
                    metrics_path=self.queue.metrics_path(job.job_id),
                    log=self._log, engine_factory=factory,
                    observer_factory=observer_factory,
                    mesh_devices=(alloc if kind == "sharded" else None),
                    engine_kwargs=(
                        {"pipeline": int(flags["pipeline"])}
                        if flags.get("pipeline") and not factory
                        else None),
                    **sup_kw,
                    run_kwargs={
                        "max_states": flags.get("maxstates"),
                        "max_depth": flags.get("maxdepth"),
                        "max_seconds": flags.get("maxseconds"),
                        "check_deadlock": bool(flags.get("deadlock")),
                        "resume_from": (job.rescue or {}).get("path"),
                    })
        except Exception as e:  # noqa: BLE001 — a job, not the worker
            self._finish(job, "failed",
                         reason=f"job-setup: {type(e).__name__}: {e}")
            return
        finally:
            if injected:
                faults.clear()

        self._settle(job, out, result_summary)

    def _settle(self, job, out, summarize):
        """Map a run :class:`Outcome` onto the queue — shared by the
        check and sim paths."""
        with self._span(spans.SETTLE, job_id=job.job_id):
            self._settle_inner(job, out, summarize)

    def _settle_inner(self, job, out, summarize):
        if out.state == "preempted-requeued":
            if self._cancelled:
                self._finish(job, "cancelled", reason="cancelled",
                             result={"rescue": out.rescue})
                return
            reason = self._requeue_reason or \
                f"preempted ({(out.rescue or {}).get('signal')})"
            from .queue import FencedError
            try:
                self.queue.requeue(
                    job.job_id, reason=reason, rescue=out.rescue,
                    devices=self._requeue_devices)
            except FencedError as e:
                # recovered out from under us mid-run: the successor
                # already requeued (or re-ran) this job — drop ours
                self.log(f"job {job.job_id}: fenced, dropping "
                         f"requeue ({e})")
                self.processed.append((job.job_id, "fenced"))
                return
            self._journal(job, "job_requeued", reason=reason,
                          rescue=out.rescue,
                          devices=self._requeue_devices or job.devices)
            self.processed.append((job.job_id, "preempted-requeued"))
            self.log(f"job {job.job_id}: requeued ({reason})")
            # a REAL operator signal (not our scheduler/cancel tick,
            # not the job's own injected kill drill) means the whole
            # worker was asked to stop: requeue-and-exit, or the drain
            # loop would instantly re-claim the job and `serve` could
            # never be stopped gracefully
            sig = (out.rescue or {}).get("signal")
            simulated = "kill" in str(job.flags.get("inject") or "")
            if sig in ("SIGTERM", "SIGINT") and not self._preempt_sent \
                    and not simulated:
                self._shutdown = True
                self.log(f"{sig} received: job requeued; stopping the "
                         f"drain loop (rerun `serve` to resume)")
            return
        result = (summarize(out.result)
                  if out.result is not None else None)
        if result is not None:
            result["supervisor"] = out.summary
        self._finish(job, out.state, result=result, reason=out.error)

    # -- sim jobs (the fleet defect hunt, ISSUE 7) ---------------------
    def _run_sim(self, job):
        """``kind="sim"``: a walker-fleet defect hunt (tpuvsr/sim) run
        through ``run_hunt_job`` — the hunt twin of the supervised
        check path.  Fleet chunk boundaries tick the scheduler exactly
        like BFS level boundaries, so cancel and elastic shrink/grow
        ride the ordinary preempt-requeue machinery; the rescue is the
        walker-frontier snapshot and a resumed hunt replays
        bit-identically."""
        from ..resilience import faults
        from ..sim.hunt import run_hunt_job, sim_result_summary
        spec = self._specs.get(job.job_id) or self._load_spec(job)
        alloc = self.scheduler.alloc_for(job)
        self.pool.alloc(job.job_id, alloc)
        backend, why = self._placement(job)
        self._journal(job, "job_started", attempt=job.attempts,
                      devices=alloc, backend=backend, placement=why)
        flags = job.flags
        injected = None
        try:
            factory = None
            if flags.get("stub"):
                from ..testing import stub_model_factory
                factory = stub_model_factory(
                    inv_bound=flags.get("inv_bound"),
                    inv_x_bound=flags.get("inv_x_bound"))
            split = flags.get("split")
            if isinstance(split, dict):
                from ..sim.splitting import NoveltySplitter
                split = NoveltySplitter(**split)
            else:
                split = True if split else None

            def observer_factory(**kw):
                return JobObserver(
                    tick=lambda depth: self._tick(job, depth), **kw)

            injected = flags.get("inject")
            if injected:
                faults.install(injected)
            # zero/negative values must fail the job, not silently
            # become the defaults (the CLI rejects -walkers 0 with
            # exit 2; the service matches by failing at setup)
            walkers = flags.get("walkers")
            walkers = 512 if walkers is None else int(walkers)
            if flags.get("walkers_per_device"):
                # walker-count elasticity: the fleet size follows the
                # device allocation (applied at round boundaries; a
                # mid-round resume finishes the round at the rescue's
                # count first — the determinism contract)
                walkers = max(1, int(flags["walkers_per_device"])
                              * alloc)
            depth = flags.get("depth")
            depth = 100 if depth is None else int(depth)
            num = flags.get("num")
            if num is None and not flags.get("maxseconds") \
                    and not flags.get("max_violations") \
                    and not flags.get("hunt"):
                # bounded default so an unparameterized job drains;
                # flags {"hunt": true} opts into the continuous mode
                # (runs until cancelled/preempted)
                num = 10000
            with trace_scope(job.trace_id,
                             parent_span=self._spans.get(job.job_id)):
                out = run_hunt_job(
                    spec,
                    checkpoint_path=self.queue.checkpoint_path(
                        job.job_id),
                    journal_path=self.queue.journal_path(job.job_id),
                    metrics_path=self.queue.metrics_path(job.job_id),
                    log=self._log, observer_factory=observer_factory,
                    model_factory=factory, walkers=walkers,
                    n_devices=alloc, depth=depth,
                    seed=int(flags.get("seed") or 0), num=num,
                    max_seconds=flags.get("maxseconds"),
                    max_violations=flags.get("max_violations"),
                    split=split,
                    chunk_steps=int(flags.get("chunk_steps") or 16),
                    pipeline=int(flags.get("pipeline") or 2),
                    resume_from=(job.rescue or {}).get("path"))
        except Exception as e:  # noqa: BLE001 — a job, not the worker
            self._finish(job, "failed",
                         reason=f"job-setup: {type(e).__name__}: {e}")
            return
        finally:
            if injected:
                faults.clear()
        self._settle(job, out, sim_result_summary)

    # -- validate jobs (batched trace validation, ISSUE 8) -------------
    def _run_validate(self, job):
        """``kind="validate"``: a recorded-trace batch checked against
        the spec through ``run_validate_job`` — the validation twin of
        the sim path.  ``flags.traces`` names the TRACE.jsonl file;
        speclint admission already ran at ``queued -> admitted`` (the
        shared gate), so no device time is spent on a rejected spec.
        Validate-chunk boundaries tick the scheduler exactly like BFS
        level boundaries, so cancel and elastic trace-batch placement
        ride the ordinary preempt-requeue machinery; the rescue is the
        CRC'd candidate-frontier snapshot and a resumed batch reports
        bit-identical divergences on whatever mesh the new allocation
        builds."""
        from ..resilience import faults
        from ..validate.batch import (run_validate_job,
                                      validate_result_summary)
        spec = self._specs.get(job.job_id) or self._load_spec(job)
        alloc = self.scheduler.alloc_for(job)
        self.pool.alloc(job.job_id, alloc)
        backend, why = self._placement(job)
        self._journal(job, "job_started", attempt=job.attempts,
                      devices=alloc, backend=backend, placement=why)
        flags = job.flags
        injected = None
        try:
            factory = None
            if flags.get("stub"):
                from ..testing import stub_model_factory
                factory = stub_model_factory(
                    inv_bound=flags.get("inv_bound"),
                    inv_x_bound=flags.get("inv_x_bound"))
            traces_path = flags.get("traces")
            if not traces_path:
                raise ValueError("validate jobs need flags.traces "
                                 "(the TRACE.jsonl path)")
            from ..validate import load_traces
            traces = load_traces(traces_path, spec)

            def observer_factory(**kw):
                return JobObserver(
                    tick=lambda depth: self._tick(job, depth), **kw)

            injected = flags.get("inject")
            if injected:
                faults.install(injected)
            batch = flags.get("batch")
            batch = 1024 if batch is None else int(batch)
            if flags.get("batch_per_device"):
                # elastic trace-batch placement: the round size
                # follows the device allocation (a resume finishes
                # its round at the rescue's batch first — the
                # determinism contract is per-trace, so reports are
                # unchanged either way)
                batch = max(1, int(flags["batch_per_device"]) * alloc)
            with trace_scope(job.trace_id,
                             parent_span=self._spans.get(job.job_id)):
                out = run_validate_job(
                    spec, traces,
                    checkpoint_path=self.queue.checkpoint_path(
                        job.job_id),
                    journal_path=self.queue.journal_path(job.job_id),
                    metrics_path=self.queue.metrics_path(job.job_id),
                    log=self._log, observer_factory=observer_factory,
                    model_factory=factory, batch=batch,
                    n_devices=alloc,
                    cand_cap=int(flags.get("cand_cap") or 4),
                    chunk_steps=int(flags.get("chunk_steps") or 8),
                    pipeline=int(flags.get("pipeline") or 2),
                    max_seconds=flags.get("maxseconds"),
                    resume_from=(job.rescue or {}).get("path"))
        except Exception as e:  # noqa: BLE001 — a job, not the worker
            self._finish(job, "failed",
                         reason=f"job-setup: {type(e).__name__}: {e}")
            return
        finally:
            if injected:
                faults.clear()
        self._settle(job, out, validate_result_summary)

    # -- shell jobs ---------------------------------------------------
    def _run_shell(self, job):
        flags = job.flags
        argv = flags.get("argv") or []
        timeout = float(flags.get("timeout") or 3600)
        env = dict(os.environ)
        env.update(flags.get("env") or {})
        # hand THIS job's trace context to the child (and scrub any
        # scope a sibling job exported on this process): a tpuvsr
        # child journals with the submitting job's trace_id
        for k in ("TPUVSR_TRACE_ID", "TPUVSR_SPAN_ID",
                  "TPUVSR_PARENT_SPAN"):
            env.pop(k, None)
        if getattr(job, "trace_id", None):
            env.update(trace_env(
                job.trace_id,
                parent_span=self._spans.get(job.job_id)))
        cwd = flags.get("cwd")
        # shell jobs are LIGHT (ISSUE 14): they spend their life in a
        # subprocess wait, so they hold a zero-device allocation and
        # never count against the mesh
        self._journal(job, "job_started", attempt=job.attempts,
                      devices=0)
        t0 = time.time()
        cancelled = False
        try:
            p = subprocess.Popen(argv, cwd=cwd, env=env,
                                 stdout=subprocess.PIPE,
                                 stderr=subprocess.STDOUT, text=True,
                                 start_new_session=True)
            # poll in short slices so a `cancel` lands mid-run (shell
            # jobs have no level boundaries — SIGTERM the process
            # group and let it exit; a well-behaved tpuvsr child
            # rescues and exits 75 on its own)
            rc = None
            while True:
                remaining = timeout - (time.time() - t0)
                try:
                    out, _ = p.communicate(
                        timeout=max(0.1, min(2.0, remaining)))
                    rc = p.returncode
                    break
                except subprocess.TimeoutExpired:
                    if remaining <= 0:
                        os.killpg(p.pid, signal.SIGKILL)
                        out, _ = p.communicate()
                        rc = -9
                        break
                    # the poll slice doubles as the heartbeat (shell
                    # jobs have no level boundaries to tick at)
                    self.queue.heartbeat(job.job_id)
                    self.queue.refresh()
                    if not cancelled and \
                            self.queue.cancel_requested(job.job_id):
                        cancelled = True
                        os.killpg(p.pid, signal.SIGTERM)
                        # one more slice to exit, then hard-kill
                        timeout = min(timeout,
                                      (time.time() - t0) + 10.0)
        except Exception as e:  # noqa: BLE001 — a job, not the worker
            rc, out = -1, f"launcher error: {e}"
        tail = "\n".join((out or "").strip().splitlines()[-6:])
        result = {"rc": rc, "tail": tail,
                  "elapsed_s": round(time.time() - t0, 1)}
        if cancelled:
            self._finish(job, "cancelled", reason="cancelled",
                         result=result)
            return
        state = job_state(rc) if rc >= 0 else "failed"
        if rc == EX_RESUMABLE:
            # resumable, but bounded: a child that exits 75 forever
            # without progressing must not hot-loop (the attempt
            # budget)
            if job.attempts < int(flags.get("max_attempts") or 1):
                self.queue.requeue(job.job_id, reason="exit-75",
                                   rescue=None)
                self._journal(job, "job_requeued", reason="exit-75")
                self.processed.append((job.job_id,
                                       "preempted-requeued"))
                return
            self._finish(job, "failed", result=result,
                         reason=f"exit-75 after {job.attempts} "
                                f"attempts (budget exhausted)")
            return
        if state == "failed" and self.shell_retry_gate is not None \
                and self.shell_retry_gate(job, rc):
            # the failure never really ran (e.g. a lost machine):
            # refund the attempt and requeue
            self.queue.requeue(job.job_id, reason="retry-uncounted",
                               uncount=True)
            self._journal(job, "job_requeued", reason="retry-uncounted")
            self.processed.append((job.job_id, "preempted-requeued"))
            return
        if state == "failed" and job.attempts < int(
                flags.get("max_attempts") or 1):
            self.queue.requeue(job.job_id, reason=f"retry rc={rc}")
            self._journal(job, "job_requeued", reason=f"retry rc={rc}")
            self.processed.append((job.job_id, "preempted-requeued"))
            return
        self._finish(job, state, result=result,
                     reason=None if state == "done" else f"rc={rc}")

    # -- the drain loop ------------------------------------------------
    def drain(self, *, max_jobs=None, max_seconds=None,
              idle_exit=True):
        """Process jobs until the queue has nothing claimable (or the
        bounds hit).  Returns the number of job runs executed.

        With the multi-runner enabled, LIGHT jobs (shell /
        interp-validate / lint-only) are handed to the thread-pool
        side lane and the loop immediately claims again, so one worker
        keeps its mesh busy while light jobs drain beside it; the loop
        never exits while a light job is still in flight (its claim
        must settle)."""
        from .queue import CLAIMABLE
        t0 = time.time()
        runs = 0
        try:
            while True:
                if max_jobs is not None and runs >= max_jobs:
                    break
                if max_seconds is not None \
                        and time.time() - t0 >= max_seconds:
                    break
                self.queue.recover_stale(log=self._log)
                self.admit_pending()
                # evict cached specs of jobs this worker will never
                # run (cancelled before claim, drained by another
                # worker) — the cache must not grow with the spool's
                # history
                for jid in list(self._specs):
                    j = self.queue._jobs.get(jid)
                    if j is None or j.state not in (
                            "admitted", "preempted-requeued",
                            "running"):
                        self._specs.pop(jid, None)
                base_order = (self.policy.order if self.policy
                              else (lambda jobs: sorted(
                                  jobs,
                                  key=lambda j: (-j.priority, j.seq))))
                order = base_order
                if self.multirunner is not None and \
                        self.multirunner.inflight() >= \
                        self.multirunner.threads:
                    # light lane saturated: skip light jobs so they
                    # stay claimable for pool siblings instead of
                    # queueing (un-started but claimed) behind OUR
                    # two threads
                    def order(jobs, _base=base_order):
                        return [j for j in _base(jobs)
                                if not _is_light(j)]
                job = self.queue.claim_next(owner=self.owner,
                                            order=order)
                if job is None:
                    # light jobs skipped above are not absent: if the
                    # lane drained between the two inflight() reads,
                    # an idle exit here would strand them admitted
                    if order is not base_order or (
                            self.multirunner is not None
                            and self.multirunner.inflight()):
                        time.sleep(self.poll)
                        continue
                    if idle_exit:
                        break
                    time.sleep(self.poll)
                    continue
                self._hold(job.job_id)
                if self.policy is not None:
                    # charge the fair-share ledger for the REAL claim
                    # and journal why this job won the pop (the
                    # sched_decision audit trail, SCHEMA.md)
                    waiting = [j for j in self.queue.jobs()
                               if j.state in CLAIMABLE]
                    self.policy.charge(job, waiting)
                    self._journal(job, "sched_decision",
                                  worker=self.owner,
                                  **self.policy.explain(job))
                runs += 1
                if self.multirunner is not None and _is_light(job):
                    self.multirunner.submit(job)
                    continue
                self.run_one(job)
                if self._shutdown:
                    break
        finally:
            if self.multirunner is not None:
                self.multirunner.close()
            self._hb_stop.set()
            if self._hb_thread is not None:
                self._hb_thread.join(5)
                self._hb_thread = None
        return runs
