"""Durable job queue for the verification dispatch service.

One spool directory holds the whole queue state, persisted through a
pluggable **spool driver** (``tpuvsr/service/spooldrv.py``, ROADMAP
item 2(b)) so the same queue runs over one POSIX filesystem, an
object-store shape, or a tiny quorum-replicated log:

* the ``jobs`` record stream — an append-only, fsync-per-line spool of
  job records and state transitions.  The queue's in-memory view is a
  pure fold over this log, so a killed worker (or a killed submitter)
  leaves a valid prefix and the next ``JobQueue(spool)`` reconstructs
  exactly the surviving state — the same crash contract as the run
  journal (``tpuvsr/obs/journal.py``).
* **claims** — the driver's conditional-put primitive: exactly one
  claimer wins (``O_CREAT|O_EXCL``-style link dance on ``fs``,
  compare-and-swap records on ``objstore``/``quorum``).  A claim
  carries the attempt **epoch**, and while this queue object holds a
  claim every state append it makes for that job is **fenced** on the
  epoch — a zombie worker whose claim was recovered (and possibly
  re-issued) can never commit a terminal state
  (:class:`~.spooldrv.FencedError`, journaled ``fence``).  Liveness is
  judged pid-first on the claimer's own host and by the driver's
  explicit heartbeat records across hosts (mtime is an ``fs``-only
  legacy fallback); a dead claim is the tombstone of a killed worker,
  and ``recover_stale`` turns those back into claimable jobs — with
  the job's latest snapshot attached as a rescue, so the next attempt
  RESUMES instead of restarting (``checkpoint.snapshot_info``; on
  replicated drivers the snapshot is also driver-held, so it survives
  the claiming host's disk).
* **host leases** — pool parents heartbeat their host identity through
  the driver (``host_heartbeat``), so a survivor host's
  ``recover_stale`` sweeps an ENTIRE dead host's claims at once
  instead of waiting out each claim's own heartbeat window.

Job lifecycle (ISSUE 6; the legal-transition table below is enforced,
an illegal transition is a bug, not a log line):

    queued ──admit──> admitted ──claim──> running ──> done
       │                 │                   │    ├─> violated
       │(lint reject)    │                   │    ├─> failed
       └───> failed      └──> cancelled      │    └─> cancelled
                                             │
                              preempted-requeued <──┘ (exit 75 /
                                    │    rescue checkpoint attached)
                                    └──claim──> running   (again)

Admission (``queued -> admitted``) is where the speclint gate runs —
before any device time is spent (the worker performs it, because only
the worker can load specs; the queue just records the verdict).  The
terminal states are exactly the images of the unified exit-code table
(``tpuvsr/exitcodes.py``).

This module deliberately imports neither jax nor the engines, so the
``submit`` / ``status`` / ``cancel`` CLI verbs stay milliseconds.
"""

from __future__ import annotations

import io
import os
import socket
import tarfile
import threading
import time
import uuid
from dataclasses import dataclass, field

from .spooldrv import (FencedError, SpoolError,  # noqa: F401 — re-export
                       current_host, open_driver)

#: this process's DEFAULT host identity (claims actually record
#: ``spooldrv.current_host()``, which honors the ``TPUVSR_HOST``
#: override fault drills use to fake a multi-host fleet on one box)
HOSTNAME = socket.gethostname()

#: a cross-host claim whose last heartbeat record is older than this is
#: dead (generous: a worker runs a background heartbeat thread touching
#: EVERY claim it holds every few seconds — Worker._hb_loop — on top
#: of the level-boundary ticks, so even a multi-minute compile or a
#: light job queued behind the multi-runner stays visibly alive)
HEARTBEAT_TIMEOUT = 300.0

#: every state a job can be in
STATES = ("queued", "admitted", "running", "done", "violated",
          "failed", "preempted-requeued", "cancelled")
#: states a job never leaves
TERMINAL = frozenset(("done", "violated", "failed", "cancelled"))
#: states a worker may claim from
CLAIMABLE = frozenset(("admitted", "preempted-requeued"))

#: the legal-transition table; queue.transition enforces it
LEGAL = {
    "queued": {"admitted", "failed", "cancelled"},
    "admitted": {"running", "cancelled"},
    "running": {"done", "violated", "failed", "preempted-requeued",
                "cancelled"},
    "preempted-requeued": {"running", "cancelled"},
}


@dataclass
class Job:
    """One verification job: a (spec, cfg, engine, flags) tuple plus
    its lifecycle bookkeeping.  ``flags`` carries everything the worker
    threads through to the engines (maxstates, pipeline, inject,
    supervisor knobs, the tier-1 ``stub`` family); ``devices`` is the
    CURRENT device allocation (the scheduler rewrites it on an elastic
    requeue), ``devices_min``/``devices_max`` bound what elastic
    placement may shrink/grow it to."""

    job_id: str
    spec: str
    cfg: str = None
    engine: str = "auto"
    kind: str = "check"   # "check" (BFS) | "sim" (fleet hunt)
    #                     # | "validate" (trace batch) | "shell"
    #: who submitted — the fair-share scheduling unit (ISSUE 14):
    #: deficit-round-robin pop order and weighted quotas group by this;
    #: None is the anonymous tenant (single-user CLI traffic)
    tenant: str = None
    flags: dict = field(default_factory=dict)
    priority: int = 0
    devices: int = 1
    devices_min: int = None
    devices_max: int = None
    state: str = "queued"
    seq: int = 0
    attempts: int = 0
    rescue: dict = None          # latest rescue-checkpoint handoff
    result: dict = None          # terminal result summary
    reason: str = None           # why failed/requeued/cancelled
    submitted_ts: float = 0.0
    updated_ts: float = 0.0
    #: end-to-end correlation id (ISSUE 17): minted at submit, stamped
    #: on every journal event of the job's whole story across the
    #: service / worker / engine process hops.  None on records written
    #: before the telemetry plane existed (old spools fold fine).
    trace_id: str = None

    @property
    def elastic(self):
        """True when the scheduler may reshape this job's device
        allocation: sharded BFS jobs (mesh reshaped through the PR 5
        reshard-on-load resume), fleet-sim jobs (walker fleet resumed
        on the new mesh; walker count rescales at the next round
        boundary, ISSUE 7), and trace-validation jobs (the batch
        validator re-shards its committed candidate frontier onto
        whatever mesh the resume builds, ISSUE 8)."""
        return ((self.engine == "sharded"
                 or self.kind in ("sim", "validate"))
                and (self.devices_min is not None
                     or self.devices_max is not None))

    def to_dict(self):
        return {k: getattr(self, k) for k in (
            "job_id", "spec", "cfg", "engine", "kind", "tenant",
            "flags", "priority", "devices", "devices_min",
            "devices_max", "state", "seq", "attempts", "rescue",
            "result", "reason", "submitted_ts", "updated_ts",
            "trace_id")}


class QueueError(RuntimeError):
    """An illegal queue operation (unknown job, illegal transition)."""


def _pid_alive(pid):
    try:
        os.kill(int(pid), 0)
    except (OSError, ValueError, TypeError):
        return False
    return True


def _locked(fn):
    """Serialize a JobQueue method on the instance RLock — the HTTP
    front and the multi-runner's light-job threads share one queue
    object with the drain loop (ISSUE 14), and the in-memory fold must
    not interleave.  Cross-PROCESS safety is unchanged: the driver's
    append/claim primitives arbitrate that."""
    def wrapper(self, *args, **kwargs):
        with self._lock:
            return fn(self, *args, **kwargs)
    wrapper.__name__ = fn.__name__
    wrapper.__doc__ = fn.__doc__
    return wrapper


class JobQueue:
    """The durable queue over one spool directory (see module doc).

    All mutators append to the spool BEFORE updating the in-memory
    view, so a crash between the two loses nothing (the next load
    replays the log).  Claims are the only non-log state on the ``fs``
    driver (pure record folds everywhere else), and they are
    self-healing via ``recover_stale``.

    ``driver``/``replicas`` select the spool driver on a NEW spool
    (``spooldrv.open_driver``); an existing spool's persisted choice
    always wins, and no choice at all means ``fs`` — which is how
    every pre-driver spool keeps working with no migration."""

    def __init__(self, spool, *, heartbeat_timeout=HEARTBEAT_TIMEOUT,
                 driver=None, replicas=None, host_lease_timeout=None):
        self.spool = os.path.abspath(spool)
        os.makedirs(self.spool, exist_ok=True)
        self.drv = open_driver(self.spool, driver=driver,
                               replicas=replicas)
        #: the fs-layout jobs log; meaningful on the ``fs``/``objstore``
        #: drivers (tests and legacy tools read it directly), merely
        #: vestigial under ``quorum`` (the stream lives in the replicas)
        self.log_path = os.path.join(self.spool, "jobs.jsonl")
        self.claims_dir = self.drv.claims_dir
        self.journals_dir = os.path.join(self.spool, "journals")
        self.metrics_dir = os.path.join(self.spool, "metrics")
        self.ckpt_dir = os.path.join(self.spool, "ckpt")
        for d in (self.journals_dir, self.metrics_dir, self.ckpt_dir):
            os.makedirs(d, exist_ok=True)
        self.heartbeat_timeout = float(heartbeat_timeout)
        #: a host whose lease record is older than this is dead and
        #: ALL its claims are swept at once (defaults to the per-claim
        #: heartbeat window)
        self.host_lease_timeout = (float(host_lease_timeout)
                                   if host_lease_timeout is not None
                                   else float(heartbeat_timeout))
        self._lock = threading.RLock()
        self._jobs = {}
        self._seq = 0
        self._cursor = None          # driver read cursor over "jobs"
        self._held = {}              # job_id -> claim epoch WE hold
        self._blob_depth = {}        # job_id -> last replicated depth
        self.refresh()

    def lock(self):
        """The instance RLock (a context manager) for callers that
        need several queue calls to be one atomic step against
        sibling threads (the HTTP front's read-modify responses)."""
        return self._lock

    # -- log fold ------------------------------------------------------
    @_locked
    def refresh(self):
        """Fold any ``jobs``-stream records appended since the last
        read — how a long-running worker sees jobs submitted by OTHER
        processes (the CLI ``submit`` verb against a live ``serve``).
        Re-applies this process's own appends too; that is harmless
        because the fold of a log prefix in order is deterministic.  A
        torn final line (a writer killed mid-append) is held back by
        the driver until it is completed."""
        recs, self._cursor = self.drv.read("jobs", self._cursor)
        for rec in recs:
            self._apply(rec)

    def _apply(self, rec):
        op = rec.get("op")
        if op == "submit":
            d = dict(rec["job"])
            job = Job(**d)
            self._jobs[job.job_id] = job
            self._seq = max(self._seq, job.seq)
        elif op == "state":
            job = self._jobs.get(rec["job_id"])
            if job is None:
                return
            job.state = rec["state"]
            job.updated_ts = rec.get("ts", job.updated_ts)
            for k in ("attempts", "devices", "rescue", "result",
                      "reason"):
                if k in rec:
                    setattr(job, k, rec[k])

    # -- paths ---------------------------------------------------------
    def journal_path(self, job_id):
        return os.path.join(self.journals_dir, f"{job_id}.jsonl")

    def metrics_path(self, job_id):
        return os.path.join(self.metrics_dir, f"{job_id}.json")

    def checkpoint_path(self, job_id):
        return os.path.join(self.ckpt_dir, job_id)

    # -- reads (locked too: the drain loop iterates these while the
    # multi-runner's light threads fold new spool lines into _jobs) --
    @_locked
    def jobs(self):
        return sorted(self._jobs.values(), key=lambda j: j.seq)

    @_locked
    def get(self, job_id):
        job = self._jobs.get(job_id)
        if job is None:
            raise QueueError(f"unknown job {job_id!r}")
        return job

    @_locked
    def stats(self):
        """Queue-level gauges: job count per state (the service's
        ``status`` verb surfaces these)."""
        out = {s: 0 for s in STATES}
        for j in self._jobs.values():
            out[j.state] += 1
        out["total"] = len(self._jobs)
        return out

    def spool_status(self):
        """The data plane's own health: driver name plus the quorum
        driver's replica census (``None`` replicas on single-store
        drivers) — what ``status`` and the telemetry plane surface."""
        return {"driver": self.drv.name,
                "replicas": self.drv.replica_status()}

    def backlog(self):
        """Jobs waiting for a worker (queued + admitted +
        preempted-requeued) — the depth the guard's high-water
        backpressure judges (ISSUE 18).  Running jobs don't count:
        they hold devices, not queue headroom."""
        return sum(1 for j in self._jobs.values()
                   if j.state in ("queued",) or j.state in CLAIMABLE)

    def cancel_requested(self, job_id):
        return self.drv.cancel_requested(job_id)

    # -- mutators ------------------------------------------------------
    @_locked
    def submit(self, spec, *, cfg=None, engine="auto", kind="check",
               flags=None, priority=0, devices=1, devices_min=None,
               devices_max=None, tenant=None, job_id=None):
        self.refresh()
        if job_id is None:
            job_id = f"j{self._seq + 1:04d}-{uuid.uuid4().hex[:6]}"
        if job_id in self._jobs:
            raise QueueError(f"job id {job_id!r} already exists")
        self._seq += 1
        flags = dict(flags or {})
        # the ORIGINAL device request survives elastic reshaping (the
        # scheduler rewrites job.devices on shrink/grow requeues; grow
        # decisions compare against what was asked for)
        flags.setdefault("devices_requested", int(devices))
        from ..obs.journal import new_trace_id, root_span
        job = Job(job_id=job_id, spec=str(spec), cfg=cfg, engine=engine,
                  kind=kind, tenant=tenant, flags=flags,
                  priority=int(priority), devices=int(devices),
                  devices_min=devices_min, devices_max=devices_max,
                  seq=self._seq, submitted_ts=round(time.time(), 3),
                  updated_ts=round(time.time(), 3),
                  trace_id=new_trace_id())
        self.drv.append("jobs", {"op": "submit", "job": job.to_dict(),
                                 "ts": job.submitted_ts})
        self._jobs[job.job_id] = job
        # a job's journal opens with its submission — the first line
        # of the story every later attempt appends to (obs.journal is
        # jax-free, so submit stays milliseconds).  The trace is minted
        # HERE: this line carries the correlation id every later event
        # of the job's lifecycle repeats (ISSUE 17)
        from ..obs import Journal
        j = Journal(self.journal_path(job.job_id), run_id="svc-submit",
                    trace_id=job.trace_id,
                    span_id=root_span(job.trace_id))
        try:
            j.write("job_submitted", job_id=job.job_id, spec=job.spec,
                    engine=job.engine, priority=job.priority,
                    devices=job.devices, tenant=job.tenant)
        finally:
            j.close()
        return job

    @_locked
    def transition(self, job_id, state, **fields):
        """Move a job to `state`, recording extra fields (attempts /
        devices / rescue / result / reason).  Raises QueueError on an
        illegal move — the state machine is the API contract.

        While THIS queue object holds the job's claim, the append is
        **fenced** on the claim epoch: if the claim was recovered (and
        possibly re-issued) while we were presumed dead, the driver
        rejects the append with :class:`FencedError` instead of letting
        a zombie commit — the split-brain hole mtime heartbeats only
        papered over."""
        self.refresh()
        job = self.get(job_id)
        if state not in STATES:
            raise QueueError(f"unknown state {state!r}")
        if state not in LEGAL.get(job.state, frozenset()):
            raise QueueError(
                f"illegal transition {job.state!r} -> {state!r} "
                f"for job {job_id}")
        rec = {"op": "state", "job_id": job_id, "state": state,
               "ts": round(time.time(), 3)}
        rec.update(fields)
        epoch = self._held.get(job_id)
        if epoch is not None:
            try:
                self.drv.append_fenced("jobs", rec, job_id=job_id,
                                       epoch=epoch)
            except FencedError:
                # the claim is no longer ours — drop the hold so later
                # calls on this object don't keep fencing against it
                self._held.pop(job_id, None)
                raise
        else:
            self.drv.append("jobs", rec)
        self._apply(rec)
        return job

    # -- claims --------------------------------------------------------
    @_locked
    def claim(self, job_id, owner="worker"):
        """Atomically claim a CLAIMABLE job: the driver's
        conditional-put decides races; the winner transitions the job
        to running (attempt count bumped).  Returns the Job, or None
        on ANY lost race — another holder's claim, or the job left the
        claimable states between our look and our claim (a concurrent
        worker or a ``cancel``).  A lost race is normal multi-worker
        traffic, never an error.  The claim records pid + worker-id
        (`owner`) + host + the attempt **epoch** every later append by
        this holder is fenced on; its explicit heartbeat records are
        what ``recover_stale`` judges cross-host liveness by."""
        self.refresh()
        job = self.get(job_id)
        if job.state not in CLAIMABLE:
            return None
        epoch = job.attempts + 1
        if not self.drv.try_claim(job_id, owner=owner, epoch=epoch):
            return None
        # the claim is ours; re-read the log before announcing — a
        # transition that landed while we were claiming (e.g. a
        # cancel, a concurrent worker at another epoch) wins, and we
        # back out
        self._held[job_id] = epoch
        self.refresh()
        job = self.get(job_id)
        try:
            if job.state not in CLAIMABLE or job.attempts + 1 != epoch:
                raise QueueError("lost the claim race")
            self.transition(job_id, "running", attempts=epoch)
        except (QueueError, FencedError):
            self._held.pop(job_id, None)
            self.drv.release_claim(job_id, epoch=epoch)
            return None
        return job

    @_locked
    def claim_next(self, owner="worker", order=None):
        """Claim the best claimable job.  ``order`` is the pop-order
        policy hook (claimable jobs -> ordered list) — the serving
        tier passes ``FairSharePolicy.order`` (deficit round robin
        over tenants + priority aging, ISSUE 14); without one the
        original greedy order applies (highest priority, then
        submission order)."""
        self.refresh()
        claimable = [j for j in self._jobs.values()
                     if j.state in CLAIMABLE]
        if order is not None:
            ordered = order(claimable)
        else:
            ordered = sorted(claimable,
                             key=lambda j: (-j.priority, j.seq))
        for job in ordered:
            got = self.claim(job.job_id, owner=owner)
            if got is not None:
                return got
        return None

    def heartbeat(self, job_id):
        """Record a liveness heartbeat on the claim — the signal a
        worker sends while it holds a job (every level-boundary tick
        and every shell poll slice).  Returns False when the claim is
        gone (job finished/requeued under us); cheap enough to call
        unconditionally."""
        return self.drv.heartbeat(job_id)

    def release(self, job_id):
        """Drop the claim + cancel marker.  A HOLDER's release is
        conditional on its own epoch (a zombie's release can never
        drop a successor's claim); a non-holder's (recover sweeps)
        is unconditional."""
        self.drv.release_claim(job_id,
                               epoch=self._held.pop(job_id, None))
        self.drv.clear_cancel(job_id)

    # -- endings -------------------------------------------------------
    @_locked
    def finish(self, job_id, state, *, result=None, reason=None):
        if state not in TERMINAL:
            raise QueueError(f"finish wants a terminal state, "
                             f"not {state!r}")
        job = self.transition(job_id, state, result=result,
                              reason=reason)
        self.release(job_id)
        return job

    @_locked
    def requeue(self, job_id, *, reason, rescue=None, devices=None,
                uncount=False):
        """running -> preempted-requeued: the job goes back on the
        queue with its rescue-checkpoint handoff attached (the next
        attempt resumes, not restarts).  ``devices`` lets the scheduler
        reshape an elastic job's next mesh; ``uncount`` refunds the
        attempt (a failure that never really ran, e.g. a lost
        machine)."""
        job = self.get(job_id)
        fields = {"reason": reason}
        if rescue is not None:
            fields["rescue"] = rescue
        if devices is not None:
            fields["devices"] = int(devices)
        if uncount:
            fields["attempts"] = max(0, job.attempts - 1)
        job = self.transition(job_id, "preempted-requeued", **fields)
        self.release(job_id)
        return job

    @_locked
    def cancel(self, job_id):
        """Cancel a job.  Non-running jobs cancel immediately; a
        RUNNING job gets a cancel marker the worker polls at level
        boundaries (it preempts the run, then finishes the job as
        cancelled) — so cancel is honored without killing the worker
        mid-level.  Returns the (possibly still-running) Job."""
        self.refresh()
        job = self.get(job_id)
        if job.state in TERMINAL:
            raise QueueError(f"job {job_id} is already terminal "
                             f"({job.state})")
        if job.state == "running" or \
                self.drv.claim_info(job_id) is not None:
            # a claim holder (running, or mid-claim in another
            # process) owns this job's transitions — leave a marker
            # it polls instead of yanking the state out from under it
            self.drv.set_cancel(job_id)
            return job
        return self.finish(job_id, "cancelled", reason="cancelled")

    # -- snapshot handoff ----------------------------------------------
    def replicate_snapshot(self, job_id):
        """Ship the job's latest checkpoint into the driver's blob
        store, so a rescue survives the claiming HOST's disk (the
        host-death-failover story).  No-op on ``fs`` (the spool IS
        the only store) and until the snapshot's depth advances past
        the last shipped copy.  Returns True when a copy shipped."""
        if self.drv.name == "fs":
            return False
        from ..engine.checkpoint import snapshot_info
        path = self.checkpoint_path(job_id)
        info = snapshot_info(path)
        if info is None or self._blob_depth.get(job_id) == \
                info["depth"]:
            return False
        buf = io.BytesIO()
        with tarfile.open(fileobj=buf, mode="w") as tar:
            for name in sorted(os.listdir(path)):
                p = os.path.join(path, name)
                if os.path.isfile(p):
                    tar.add(p, arcname=name)
        self.drv.put_blob(f"ckpt-{job_id}.tar", buf.getvalue())
        self._blob_depth[job_id] = info["depth"]
        return True

    def _rescue_info(self, job_id):
        """The rescue handoff for a recovered job: the local snapshot
        manifest when one is readable, else (on replicated drivers)
        the driver-held blob restored into the checkpoint path — how
        a SURVIVOR host resumes a job whose snapshot it never wrote."""
        from ..engine.checkpoint import snapshot_info
        path = self.checkpoint_path(job_id)
        info = snapshot_info(path)
        if info is not None or self.drv.name == "fs":
            return info
        data = self.drv.get_blob(f"ckpt-{job_id}.tar")
        if data is None:
            return None
        os.makedirs(path, exist_ok=True)
        try:
            with tarfile.open(fileobj=io.BytesIO(data)) as tar:
                try:
                    tar.extractall(path, filter="data")
                except TypeError:    # pre-3.12 tarfile: no filter=
                    tar.extractall(path)
        except (OSError, tarfile.TarError):
            return None
        return snapshot_info(path)

    # -- crash recovery ------------------------------------------------
    def host_heartbeat(self, host=None):
        """Write one host-lease heartbeat through the driver — called
        from the pool parent's supervision loop, so the whole host's
        liveness is visible to peers independently of any one claim."""
        self.drv.host_heartbeat(host)

    def dead_hosts(self, now=None):
        """Hosts whose lease record has gone stale — every claim from
        one of these is swept by ``recover_stale`` in one pass.  Hosts
        that never wrote a lease (legacy pools, bare Workers) are
        simply absent: their claims fall back to per-claim liveness."""
        now = time.time() if now is None else now
        return {h for h, lease in self.drv.hosts().items()
                if now - lease["ts"] > self.host_lease_timeout}

    def _claim_alive(self, job_id, dead_hosts=()):
        """Liveness of one claim: ``(alive, info)``.

        Same-host claims are judged by their pid (authoritative and
        instant — a dead pid is recovered without waiting out any
        heartbeat window).  A claim from ANOTHER host has no visible
        pid: if that host's LEASE is stale the claim is dead with the
        whole host (the one-sweep failover path); otherwise the
        driver's explicit heartbeat records decide — fresh
        (< ``heartbeat_timeout``) means a live worker elsewhere holds
        the job and it is never stolen."""
        info = self.drv.claim_info(job_id)
        if info is None:
            return False, {}
        host = info.get("host")
        if host is None or host == current_host():
            return _pid_alive(info.get("pid")), info
        if host in dead_hosts:
            return False, info
        age = self.drv.claim_age(job_id)
        if age is None:
            return False, info
        return age < self.heartbeat_timeout, info

    @_locked
    def recover_stale(self, log=None):
        """Requeue running jobs whose claiming worker died (claim
        missing, or judged dead by ``_claim_alive`` — dead pid on this
        host, stale heartbeat or dead host lease from another).  The
        job's latest snapshot — a periodic checkpoint, the rescue the
        dying worker managed to write, or the driver-held replica of
        either — is attached as the rescue handoff, so the next
        attempt resumes bit-identically instead of restarting (the
        PR 4/5 equivalence contract).  Also runs the driver's own
        housekeeping (replica loss detection + anti-entropy heal on
        ``quorum``)."""
        self.drv.maintain(log=log)
        self.refresh()
        dead = self.dead_hosts()
        recovered = []
        for job in list(self._jobs.values()):
            alive, info = self._claim_alive(job.job_id,
                                            dead_hosts=dead)
            if job.state in CLAIMABLE and info and not alive:
                # a worker died in the window between creating the
                # claim and appending the `running` transition: the
                # orphan claim would block every future claim()
                # forever — clear it (the job itself never started)
                self.drv.release_claim(job.job_id)
                if log:
                    log(f"queue: cleared orphan claim of "
                        f"{job.job_id} (worker died before the "
                        f"running transition)")
                continue
            if job.state != "running":
                continue
            if alive:
                continue
            rescue = self._rescue_info(job.job_id)
            try:
                self.requeue(job.job_id, reason="worker-died",
                             rescue=rescue)
            except (QueueError, FencedError):
                # another recovering worker got there first — a lost
                # race, same as a lost claim
                continue
            # the recovery is part of the job's story: journal the
            # requeue (the worker's own requeue path does the same),
            # naming the dead claim's worker/host
            from ..obs import Journal
            from ..obs.journal import root_span
            jr = Journal(self.journal_path(job.job_id),
                         run_id="svc-recover",
                         trace_id=job.trace_id,
                         span_id=(root_span(job.trace_id)
                                  if job.trace_id else None))
            try:
                jr.write("job_requeued", job_id=job.job_id,
                         reason="worker-died", rescue=rescue,
                         elapsed_s=round(
                             time.time() - job.submitted_ts, 3),
                         dead_worker=info.get("owner"),
                         dead_host=info.get("host"))
            finally:
                jr.close()
            recovered.append(job.job_id)
            if log:
                who = info.get("owner") or "worker"
                where = info.get("host") or current_host()
                log(f"queue: job {job.job_id} had a dead claim "
                    f"({who}@{where}); requeued"
                    + (f" with rescue at depth {rescue['depth']}"
                       if rescue else " (no snapshot — restart)"))
        return recovered
