"""Supervised run loop: retry/degrade on OOM, preemption-safe exits.

The survival machinery for multi-day checking runs (ISSUE 3 tentpole):

* **OOM retry/degrade** — ``Supervisor.run`` catches XLA
  ``RESOURCE_EXHAUSTED`` (and the injected ``faults.InjectedOOM``) and
  degrades instead of dying: halve the expansion tile and retry with
  exponential backoff (bounded attempts), resuming from the latest
  level-boundary snapshot; once the tile floor is reached, fall back
  from the HBM-resident device engine to the host-paged frontier
  (``hbm -> paged``).  Every step is journaled (``fault`` / ``retry`` /
  ``degrade`` events) so the journal shows *why* a run slowed.
* **Mesh-aware supervision** (ISSUE 5) — ``engine="sharded"`` runs the
  multi-chip engine through its own ladder: per-shard tile halving ->
  mesh shrink to the largest usable power-of-two device count (device
  loss skips straight to the shrink) -> single-device paged fallback
  (the sharded snapshot is converted in place so the final rung keeps
  the run's progress).  A shrunken-mesh resume re-hash-partitions the
  snapshot's N shards onto the smaller mesh
  (``ShardedBFS`` reshard-on-load, journaled as a ``reshard`` event).
  Restart decisions are rank-agreed — rank 0's classification of the
  failure is broadcast so every process of a multi-host pack takes
  the same branch of the ladder.
* **Preemption** — ``PreemptionGuard`` installs SIGTERM/SIGINT
  handlers that request a checkpoint at the next level boundary; the
  engines write the rescue snapshot, journal a ``rescue_checkpoint``
  event, and raise ``Preempted``, which the CLI maps to the distinct
  resumable exit code ``EXIT_RESUMABLE`` (75, BSD EX_TEMPFAIL).  A
  second signal while a rescue is pending aborts immediately.
* **Resume contract** — exit code 75 means "a resumable snapshot
  exists at the checkpoint dir": rerun with ``-recover DIR`` (or let
  ``scripts/supervise.py`` loop on the exit code) to continue the run
  with cumulative elapsed and one continuous journal.

The guard's pending flag is module state checked by the engines at
level boundaries (``preempt_signal()``); without a guard installed the
flag is never set and the checks are free.
"""

from __future__ import annotations

import contextlib
import os
import signal
import time
from dataclasses import dataclass, field

from ..exitcodes import (EX_OK, EX_RESUMABLE, EX_SOFTWARE, EX_VIOLATION,
                         job_state)
from ..obs import Journal, RunObserver, spans
from .faults import InjectedFault, InjectedOOM

#: exit code of a preempted-but-resumable supervised run (EX_TEMPFAIL:
#: rerun with -recover to continue).  The value lives in the unified
#: exit-code table (tpuvsr/exitcodes.py, ISSUE 6 satellite); this name
#: is kept as the historical alias every caller imports.
EXIT_RESUMABLE = EX_RESUMABLE

#: smallest tile the degrade ladder will retry before falling back to
#: the paged engine
DEFAULT_MIN_TILE = 16


class Preempted(RuntimeError):
    """A run stopped at a level boundary because a PreemptionGuard
    caught SIGTERM/SIGINT; a resumable snapshot was written."""

    def __init__(self, path, depth, distinct, signal_name):
        self.path = path
        self.depth = int(depth)
        self.distinct = int(distinct)
        self.signal = signal_name
        where = (f"resumable snapshot at {path}" if path else
                 "NO snapshot was configured (-checkpoint/"
                 "-checkpointdir) — a restart re-explores from the "
                 "initial states")
        super().__init__(
            f"preempted by {signal_name} at level {depth} "
            f"({distinct} distinct); {where}")


# ---------------------------------------------------------------------
# preemption flag (module state; engines poll at level boundaries)
# ---------------------------------------------------------------------
_PENDING = [None]


def preempt_signal():
    """Name of the pending preemption signal, or None."""
    return _PENDING[0]


def request_preemption(name="SIGTERM"):
    _PENDING[0] = name


def clear_preemption():
    _PENDING[0] = None


class PreemptionGuard:
    """Context manager: SIGTERM/SIGINT -> checkpoint at the next level
    boundary and exit resumable, instead of dying mid-level.  A second
    signal while one is pending escalates to an immediate
    KeyboardInterrupt (impatient-operator escape hatch).  Installing
    handlers outside the main thread is a documented no-op."""

    SIGNALS = (signal.SIGTERM, signal.SIGINT)

    def __init__(self, log=None):
        self._log = log
        self._old = {}

    def _handler(self, signum, frame):
        name = signal.Signals(signum).name
        if preempt_signal() is not None:
            raise KeyboardInterrupt(
                f"second {name} while a rescue checkpoint was pending")
        request_preemption(name)
        if self._log:
            self._log(f"{name} received: checkpointing at the next "
                      f"level boundary, then exiting resumable "
                      f"(exit {EXIT_RESUMABLE})")

    def __enter__(self):
        clear_preemption()
        for sig in self.SIGNALS:
            try:
                self._old[sig] = signal.signal(sig, self._handler)
            except ValueError:      # not the main thread
                break
        return self

    def __exit__(self, exc_type, exc, tb):
        for sig, old in self._old.items():
            signal.signal(sig, old)
        self._old = {}
        clear_preemption()
        return False


# ---------------------------------------------------------------------
# OOM classification
# ---------------------------------------------------------------------
def is_oom(exc):
    """True for allocation-failure exceptions worth a degrade/retry:
    the injected OOM, XLA RESOURCE_EXHAUSTED, or a host MemoryError."""
    if isinstance(exc, (InjectedOOM, MemoryError)):
        return True
    msg = str(exc)
    return "RESOURCE_EXHAUSTED" in msg or "Out of memory" in msg \
        or "out of memory" in msg


def is_device_loss(exc):
    """True for failures that look like a device dropping out of the
    mesh (ICI/DCN link loss, halted chip, dead runtime client) — the
    pod-scale failure the sharded ladder answers with a mesh shrink
    rather than a tile halving (less tile would not bring the device
    back)."""
    msg = str(exc)
    return any(s in msg for s in (
        "DATA_LOSS", "device is in an invalid state",
        "Device or resource busy", "failed to connect",
        "Socket closed", "DEADLINE_EXCEEDED", "device halted",
        "UNAVAILABLE"))


def _pow2_below(n):
    """Largest power of two strictly below n (n >= 2)."""
    p = 1
    while p * 2 < n:
        p *= 2
    return p


class Supervisor:
    """Run a BFS engine to completion through the retry/degrade ladder.

    ``engine_factory(kind, tile_size)`` builds a fresh engine per
    attempt (kind is ``"device"``, ``"paged"`` or ``"sharded"``; a
    factory that also accepts an ``n_devices`` keyword is handed the
    current mesh size); the default factory builds
    DeviceBFS/PagedBFS/ShardedBFS on the supervisor's spec with
    ``engine_kwargs``.  The ladder on OOM:

        device:  tile -> tile/2 -> ... -> min_tile -> paged -> retry
        sharded: tile -> ... -> min_tile -> mesh D -> largest pow2 < D
                 -> ... -> min_devices -> paged (snapshot converted
                 in place so the fallback keeps the run's progress);
                 device-loss failures skip straight to the mesh shrink

    with exponential backoff between attempts and auto-resume from the
    supervisor's checkpoint dir whenever a snapshot exists — a sharded
    resume on a shrunken mesh re-hash-partitions the snapshot
    (``ShardedBFS`` reshard-on-load).  Violations, deadlocks and
    non-retryable errors propagate unchanged; ``Preempted`` propagates
    for the caller to map to EXIT_RESUMABLE.  Every restart decision
    is rank-agreed (rank 0's verdict broadcast) so a multi-host pack
    never splits across ladder branches."""

    def __init__(self, spec, engine="device", *, checkpoint_path=None,
                 checkpoint_every=None, journal_path=None,
                 metrics_path=None, log=None, tile_size=128,
                 min_tile=DEFAULT_MIN_TILE, max_retries=6,
                 backoff_base=0.5, backoff_cap=30.0,
                 engine_kwargs=None, engine_factory=None,
                 mesh_devices=None, min_devices=1,
                 sleep=time.sleep, observer_factory=None,
                 on_event=None, span=None):
        if engine not in ("device", "paged", "sharded"):
            raise ValueError(f"Supervisor supervises the device/paged/"
                             f"sharded engines, not {engine!r}")
        self.spec = spec
        self.kind = engine
        # mesh size for the sharded ladder: starts at `mesh_devices`
        # (default: every visible device) and only ever shrinks —
        # to the largest usable power of two — down to `min_devices`
        if engine == "sharded":
            if mesh_devices is None:
                import jax
                mesh_devices = len(jax.devices())
            self.n_dev = int(mesh_devices)
        else:
            self.n_dev = None
        self.min_devices = max(1, int(min_devices))
        self.checkpoint_path = checkpoint_path
        self.checkpoint_every = checkpoint_every
        self.journal_path = journal_path
        self.metrics_path = metrics_path
        self.tile = int(tile_size)
        self.min_tile = int(min_tile)
        self.max_retries = int(max_retries)
        self.backoff_base = float(backoff_base)
        self.backoff_cap = float(backoff_cap)
        self._engine_kwargs = dict(engine_kwargs or {})
        self._factory = engine_factory
        self._sleep = sleep
        self._log = log
        # per-job hooks (ISSUE 6): `observer_factory` builds the
        # per-attempt RunObserver (the dispatch service substitutes one
        # whose level_done ticks the scheduler); `on_event` mirrors
        # every supervisor journal write as on_event(event, fields) so
        # a host process can track degrades/retries without re-reading
        # the journal file
        self._observer_factory = observer_factory or RunObserver
        self._on_event = on_event
        # `span(name)` is the service worker's profile span (a context
        # manager): engine construction happens here, outside any
        # engine run, and is marked for the worker
        self._span = span or (lambda name: contextlib.nullcontext())
        self.engine = None          # last engine instance (CLI liveness)
        self.attempts = 0           # engine runs started
        self.degrades = []          # [(what, from, to), ...]
        self._skip_resume = False   # set when a snapshot became unusable
        self._journal = Journal(journal_path)
        self._t0 = time.time()

    def log(self, msg):
        if self._log:
            self._log(f"supervisor: {msg}")

    def _jwrite(self, event, **fields):
        self._journal.write(
            event, elapsed_s=round(time.time() - self._t0, 3), **fields)
        if self._on_event is not None:
            self._on_event(event, dict(fields))

    def _agree(self, flag):
        """Rank-agreed boolean: rank 0's verdict, broadcast, so every
        process of a multi-host pack takes the same ladder branch.
        Single-process: the flag itself."""
        import jax
        if jax.process_count() > 1:
            import numpy as np
            from jax.experimental import multihost_utils
            return bool(int(multihost_utils.broadcast_one_to_all(
                np.int32(bool(flag)))))
        return bool(flag)

    def _make_engine(self):
        if self._factory is not None:
            import inspect
            params = inspect.signature(self._factory).parameters
            if "n_devices" in params or any(
                    p.kind is inspect.Parameter.VAR_KEYWORD
                    for p in params.values()):
                return self._factory(self.kind, self.tile,
                                     n_devices=self.n_dev)
            return self._factory(self.kind, self.tile)
        if self.kind == "sharded":
            import numpy as np

            import jax
            from jax.sharding import Mesh

            from ..parallel.sharded_bfs import ShardedBFS
            kw = dict(self._engine_kwargs)
            kw["tile"] = self.tile
            mesh = Mesh(np.array(jax.devices()[:self.n_dev]), ("d",))
            return ShardedBFS(self.spec, mesh, **kw)
        from ..engine.device_bfs import DeviceBFS
        from ..engine.paged_bfs import PagedBFS
        kw = dict(self._engine_kwargs)
        kw["tile_size"] = self.tile
        cls = PagedBFS if self.kind == "paged" else DeviceBFS
        return cls(self.spec, **kw)

    def summary(self):
        return {"attempts": self.attempts, "engine": self.kind,
                "tile": self.tile,
                "mesh_devices": self.n_dev,
                "resharded_from": getattr(self.engine,
                                          "resharded_from", None),
                "degrades": [list(d) for d in self.degrades]}

    # ------------------------------------------------------------------
    def run(self, *, max_states=None, max_depth=None, max_seconds=None,
            check_deadlock=False, resume_from=None, **run_kwargs):
        resume = resume_from
        try:
            with PreemptionGuard(log=self._log):
                while True:
                    self.attempts += 1
                    with self._span(spans.BUILD_ENGINE):
                        self.engine = self._make_engine()
                    obs = self._observer_factory(
                        journal_path=self.journal_path,
                        metrics_path=self.metrics_path,
                        log=self._log)
                    try:
                        return self.engine.run(
                            max_states=max_states, max_depth=max_depth,
                            max_seconds=max_seconds,
                            check_deadlock=check_deadlock,
                            checkpoint_path=self.checkpoint_path,
                            checkpoint_every=self.checkpoint_every,
                            resume_from=resume, obs=obs, log=self._log,
                            **run_kwargs)
                    except Preempted:
                        raise
                    except Exception as e:  # noqa: BLE001 — filtered below
                        # retryability is RANK-AGREED: rank 0's
                        # classification is broadcast so every process
                        # of a multi-host pack takes the same branch
                        # (a split here issues mismatched collectives)
                        retryable = is_oom(e) or (
                            self.kind == "sharded" and is_device_loss(e))
                        if not self._agree(retryable) \
                                or self.attempts > self.max_retries:
                            raise
                        self._handle_oom(e)
                        if self._skip_resume:
                            resume = None
                        elif self.checkpoint_path and \
                                os.path.isdir(self.checkpoint_path):
                            resume = self.checkpoint_path
                        # else: keep the caller's resume_from (the OOM
                        # hit before the first snapshot landed) — never
                        # silently abandon a snapshot we were asked to
                        # recover from
                        if resume is None:
                            self.log("no snapshot yet; restarting the "
                                     "run from the initial states")
        finally:
            self._journal.close()

    def _handle_oom(self, exc):
        # injected OOMs were journaled as `fault` events by the engine's
        # observer at fire time; journal real ones here so the journal
        # always explains the retry that follows
        self._skip_resume = False
        if not isinstance(exc, InjectedFault):
            self._jwrite("fault", what="oom", site="run")
        if self.kind == "sharded":
            self._degrade_sharded(exc)
            self._backoff_and_journal()
            return
        if self.kind != "paged" and self.tile // 2 >= self.min_tile:
            old, self.tile = self.tile, self.tile // 2
            self.degrades.append(("tile", old, self.tile))
            self._jwrite("degrade", what="tile",
                         **{"from": old, "to": self.tile})
            self.log(f"OOM ({exc}): degrading tile {old} -> {self.tile}")
        elif self.kind != "paged":
            self.degrades.append(("engine", "device", "paged"))
            self._jwrite("degrade", what="engine",
                         **{"from": "device", "to": "paged"})
            self.kind = "paged"
            self.log(f"OOM ({exc}): tile floor {self.min_tile} reached; "
                     f"falling back to the host-paged engine")
        else:
            self.log(f"OOM ({exc}): already on the paged engine; "
                     f"plain retry")
        self._backoff_and_journal()

    def _degrade_sharded(self, exc):
        """The mesh-aware ladder (ISSUE 5): per-shard tile halving ->
        mesh shrink to the largest usable power-of-two device count ->
        single-device paged fallback.  Device-loss failures skip the
        tile rung (a smaller tile does not bring a device back); the
        paged rung converts the sharded snapshot in place so the
        fallback resumes with the run's progress."""
        dev_lost = is_device_loss(exc) and not is_oom(exc)
        what = "device loss" if dev_lost else "OOM"
        if not dev_lost and self.tile // 2 >= self.min_tile:
            old, self.tile = self.tile, self.tile // 2
            self.degrades.append(("tile", old, self.tile))
            self._jwrite("degrade", what="tile",
                         **{"from": old, "to": self.tile})
            self.log(f"{what} ({exc}): degrading per-shard tile "
                     f"{old} -> {self.tile}")
        elif self.n_dev > max(1, self.min_devices):
            old = self.n_dev
            self.n_dev = max(self.min_devices, _pow2_below(self.n_dev))
            self.degrades.append(("mesh", old, self.n_dev))
            self._jwrite("degrade", what="mesh",
                         **{"from": old, "to": self.n_dev})
            self.log(f"{what} ({exc}): shrinking mesh {old} -> "
                     f"{self.n_dev} devices (resume re-hash-partitions "
                     f"the snapshot)")
        else:
            self.degrades.append(("engine", "sharded", "paged"))
            self._jwrite("degrade", what="engine",
                         **{"from": "sharded", "to": "paged"})
            self.kind = "paged"
            # sharded-only knobs (bucket_cap, axis, exchange_*, sleep,
            # check_deadlock, ...) never reach the paged constructor:
            # keep only what PagedBFS.__init__ actually accepts, so
            # the final ladder rung cannot die on a TypeError
            import inspect

            from ..engine.device_bfs import DeviceBFS
            from ..engine.paged_bfs import PagedBFS
            accepted = set()
            for cls in (DeviceBFS, PagedBFS):   # paged delegates *args
                for name, p in inspect.signature(
                        cls.__init__).parameters.items():
                    if p.kind not in (p.VAR_POSITIONAL, p.VAR_KEYWORD):
                        accepted.add(name)
            accepted.discard("self")
            # names what the sharded capacities were sized by: nothing
            # the paged engine is asked to provide
            accepted.discard("requires")
            for k in [k for k in self._engine_kwargs
                      if k not in accepted]:
                self._engine_kwargs.pop(k)
            self.log(f"{what} ({exc}): mesh floor reached; falling "
                     f"back to the single-device paged engine")
            if self.checkpoint_path and \
                    os.path.isdir(self.checkpoint_path):
                try:
                    from ..parallel.sharded_bfs import \
                        convert_sharded_snapshot
                    convert_sharded_snapshot(self.checkpoint_path,
                                             self.spec, log=self._log)
                except Exception as ce:  # noqa: BLE001 — keep degrading
                    self._skip_resume = True
                    self.log(f"sharded snapshot conversion failed "
                             f"({type(ce).__name__}: {ce}); the paged "
                             f"fallback restarts from the initial "
                             f"states")

    def _backoff_and_journal(self):
        from .backoff import backoff_delay
        backoff = backoff_delay(self.attempts, self.backoff_base,
                                self.backoff_cap)
        self._jwrite("retry", attempt=self.attempts,
                     backoff_s=round(backoff, 3))
        self.log(f"retry {self.attempts}/{self.max_retries} "
                 f"in {backoff:.1f}s")
        if backoff > 0:
            self._sleep(backoff)

    # ------------------------------------------------------------------
    # library mode (ISSUE 6 satellite): a worker process hosting MANY
    # jobs cannot let one preemption own the process exit — run() still
    # raises Preempted for the CLI (byte-identical behavior), while
    # run_to_outcome() folds every ending into an Outcome value.
    # ------------------------------------------------------------------
    def run_to_outcome(self, **run_kwargs) -> "Outcome":
        """``run()`` with every ending reified as an :class:`Outcome`
        instead of an exception/exit-code side channel:

        * clean fixpoint          -> ``done`` (EX_OK)
        * invariant/deadlock      -> ``violated`` (EX_VIOLATION)
        * ``Preempted``           -> ``preempted-requeued``
          (EX_RESUMABLE) with the rescue snapshot attached
        * anything non-retryable  -> ``failed`` (EX_SOFTWARE)

        The state strings ARE the service job terminal states — the
        mapping lives in ``tpuvsr.exitcodes.JOB_STATE`` and nowhere
        else."""
        try:
            res = self.run(**run_kwargs)
        except Preempted as p:
            return Outcome(
                state=job_state(EX_RESUMABLE), exit_code=EX_RESUMABLE,
                rescue={"path": p.path, "depth": p.depth,
                        "distinct": p.distinct, "signal": p.signal},
                summary=self.summary())
        except Exception as e:  # noqa: BLE001 — reified, not swallowed
            return Outcome(state=job_state(EX_SOFTWARE),
                           exit_code=EX_SOFTWARE,
                           error=f"{type(e).__name__}: {e}",
                           summary=self.summary())
        code = EX_OK if res.ok else EX_VIOLATION
        return Outcome(state=job_state(code), exit_code=code,
                       result=res, error=res.error,
                       summary=self.summary())


@dataclass
class Outcome:
    """The reified ending of a supervised run (library mode).

    ``state`` is a service job state (``done`` / ``violated`` /
    ``failed`` / ``preempted-requeued``) and ``exit_code`` the matching
    entry of the unified contract (tpuvsr/exitcodes.py) — the pair is
    always consistent by construction."""

    state: str
    exit_code: int
    result: object = None    # CheckResult when the run finished
    error: str = None
    rescue: dict = None      # {path, depth, distinct, signal} on preemption
    summary: dict = field(default_factory=dict)

    @property
    def resumable(self):
        return self.exit_code == EX_RESUMABLE


def run_supervised(spec, *, run_kwargs=None, **supervisor_kwargs):
    """One-call library entry: build a :class:`Supervisor` over `spec`
    and run it to an :class:`Outcome` — the worker-process twin of the
    CLI's ``-supervise`` path, returning instead of ``sys.exit``-ing so
    one process can host many jobs (tpuvsr/service/worker.py)."""
    sup = Supervisor(spec, **supervisor_kwargs)
    out = sup.run_to_outcome(**(run_kwargs or {}))
    out.supervisor = sup
    return out
