"""Mesh scheduler + elastic placement for the dispatch service.

Three concerns (ISSUE 6 tentpole, ROADMAP item 3):

* **Device ledger** (:class:`DevicePool`) — how many accelerator
  devices exist and which job holds how many.  The worker allocates at
  claim time and releases at job end; sharded jobs' allocations ARE
  their mesh sizes.
* **Packing + elasticity** (:class:`Scheduler`) — greedy bin-pack by
  requested device count (priority first, then submission order —
  exactly the order ``JobQueue.claim_next`` pops), plus the two LIVE
  reshape rules evaluated at every level boundary of a running job
  (the worker's observer tick):

  - *shrink / yield*: a higher-priority job arrived.  The running job
    is preempted through the ordinary rescue-checkpoint path
    (``request_preemption`` — the engines poll the same flag SIGTERM
    sets), and, when the arrival does not fit beside it, an elastic
    sharded job is requeued with a SMALLER mesh so both eventually
    pack.  The resume re-hash-partitions the snapshot onto the new
    mesh (PR 5 reshard-on-load) — nothing is lost but the in-flight
    level.
  - *grow*: a previously-shrunken elastic job is running below its
    requested device count and devices have freed up.  Preempt-to-grow
    requeues it with the bigger mesh; the elastic resume grows the
    same way it shrank.

* **Cross-backend placement advisory** (:func:`advise_backend`) — the
  cpu-vs-tpu call, using the same logic ``scripts/compare_bench.py``
  applies across backends: measured ``distinct_per_s`` from the
  newest usable bench documents decides, and tiny jobs stay on CPU
  (device compile time dominates them).  Advisory because every tier-1
  environment is CPU-only; the decision is recorded on the job's
  ``job_started`` event either way.
"""

from __future__ import annotations

import glob
import json
import os
from dataclasses import dataclass

from .queue import CLAIMABLE


def pow2_floor(n):
    """Largest power of two <= n (n >= 1)."""
    n = int(n)
    if n < 1:
        return 1
    p = 1
    while p * 2 <= n:
        p *= 2
    return p


def _clamp(n, lo, hi):
    return max(lo, min(hi, n))


class DevicePool:
    """Slot ledger over `total` devices.  Allocation is bookkeeping —
    jax device selection happens in the worker (`jax.devices()[:n]`) —
    but the ledger is what packing decisions read."""

    def __init__(self, total):
        self.total = int(total)
        self._alloc = {}

    @property
    def free(self):
        return self.total - sum(self._alloc.values())

    def held(self, job_id):
        return self._alloc.get(job_id, 0)

    def alloc(self, job_id, n):
        self._alloc[job_id] = int(n)

    def release(self, job_id):
        self._alloc.pop(job_id, None)

    def snapshot(self):
        return {"total": self.total, "free": self.free,
                "alloc": dict(self._alloc)}


@dataclass
class Decision:
    """One live-reshape decision for the currently running job."""
    action: str          # "shrink" | "grow" | "yield"
    devices: int         # the job's NEXT mesh size (requeue devices)
    reason: str


class Scheduler:
    def __init__(self, pool, elastic_grow=True, policy=None):
        self.pool = pool
        self.elastic_grow = elastic_grow
        #: fair-share policy (tpuvsr/serve/fairshare.py) — when set,
        #: every priority comparison below uses AGED priorities, so a
        #: long-waiting low-priority job eventually wins preemption
        #: decisions too, not just pop order (ISSUE 14)
        self.policy = policy

    def _prio(self, job):
        if self.policy is not None:
            return self.policy.effective_priority(job)
        return job.priority

    # -- claim-time placement -----------------------------------------
    def alloc_for(self, job):
        """Device count for a job being claimed: its current
        ``devices`` (the scheduler rewrites that field on elastic
        requeues), clamped to the pool and — for sharded jobs — to a
        power of two (the mesh-shrink contract)."""
        n = _clamp(int(job.devices or 1), 1, self.pool.total)
        if job.engine == "sharded":
            n = pow2_floor(n)
        return n

    def _bounds(self, job):
        lo = int(job.devices_min or 1)
        # the grow ceiling must NOT read job.devices — the scheduler
        # itself rewrites that on a shrink requeue; the preserved
        # original request is the fallback ceiling
        hi = int(job.devices_max
                 or job.flags.get("devices_requested")
                 or job.devices or 1)
        return max(1, lo), _clamp(hi, 1, self.pool.total)

    # -- level-boundary reshape ---------------------------------------
    def rebalance(self, running, jobs):
        """The live grow/shrink call, evaluated at a running job's
        level boundaries.  Returns a :class:`Decision` (the worker
        preempts and requeues with ``decision.devices``) or None.

        Shrink/yield: the highest-priority CLAIMABLE job outranking
        `running` preempts it; if that job cannot fit beside the
        current allocation, an elastic victim also gives up devices —
        down to the largest power of two that leaves room, floored at
        ``devices_min``.  Grow: an elastic job running BELOW its
        requested mesh (an earlier shrink) reclaims freed devices up
        to ``devices_max``."""
        cur = self.pool.held(running.job_id) or running.devices or 1
        waiting = sorted(
            (j for j in jobs
             if j.state in CLAIMABLE and j.job_id != running.job_id),
            key=lambda j: (-self._prio(j), j.seq))
        for j in waiting:
            if self._prio(j) <= self._prio(running):
                break
            new = cur
            if j.devices > self.pool.total - cur and running.elastic:
                lo, hi = self._bounds(running)
                room = max(1, self.pool.total - j.devices)
                # the power-of-two clamp is the SHARDED mesh-shrink
                # contract; a walker fleet (kind="sim") runs on any
                # device count — don't strand devices it could use
                if running.engine == "sharded":
                    room = pow2_floor(room)
                new = _clamp(room, lo, hi)
            if new < cur:
                return Decision("shrink", new,
                                f"make room for {j.job_id} "
                                f"(priority {self._prio(j)})")
            return Decision("yield", cur,
                            f"yield to {j.job_id} "
                            f"(priority {self._prio(j)})")
        if self.elastic_grow and running.elastic:
            lo, hi = self._bounds(running)
            requested = int(running.flags.get("devices_requested")
                            or running.devices or 1)
            # reserve capacity for everything still waiting at >= our
            # priority before taking the rest of the pool
            reserved = sum(j.devices for j in waiting
                           if self._prio(j) >= self._prio(running))
            room = max(1, self.pool.total - reserved)
            if running.engine == "sharded":
                room = pow2_floor(room)
            target = _clamp(room, lo, hi)
            if cur < requested and target > cur:
                return Decision("grow", target,
                                f"devices freed up ({cur} -> {target})")
        return None

    # -- queue-level packing view -------------------------------------
    def plan(self, jobs):
        """Greedy bin-pack preview for ``status``: which claimable
        jobs fit the free pool right now, in pop order."""
        free = self.pool.free
        placed, waiting = [], []
        for j in sorted((j for j in jobs if j.state in CLAIMABLE),
                        key=lambda j: (-self._prio(j), j.seq)):
            need = self.alloc_for(j)
            if need <= free:
                placed.append((j.job_id, need))
                free -= need
            else:
                waiting.append((j.job_id, need))
        return {"placed": placed, "waiting": waiting, "free": free}


# ---------------------------------------------------------------------
# cross-backend placement advisory (compare_bench logic)
# ---------------------------------------------------------------------

#: below this many states a run is compile-dominated on an accelerator
SMALL_JOB_STATES = 50_000

#: CAPACITY.md tier math: ~16 GB of HBM holds ~8e8 fingerprint slots
#: (16 B/state, the device_bfs scale note) — the per-device distinct-
#: state capacity the admission gate prices a requested tier at.
#: Jobs carrying an explicit ``flags.tier_states`` override it.
TIER_STATES_PER_DEVICE = 800_000_000


def tier_states_for(job):
    """Distinct-state capacity of the tier a job requested:
    ``flags.tier_states`` when explicit, else requested devices x the
    CAPACITY.md per-device FPSet price.  The bounds-pass admission
    gate (worker._admit_one, ISSUE 13) rejects jobs whose static
    ``state_bound`` provably exceeds it — before any device time."""
    t = job.flags.get("tier_states")
    if t is not None:
        return int(t)
    return max(1, int(job.devices or 1)) * TIER_STATES_PER_DEVICE


def _doc_throughput(doc):
    """distinct_per_s of one bench/metrics document — the same lookup
    order ``scripts/compare_bench.py`` uses (gauges.distinct_per_s,
    then distinct/elapsed, then the legacy bench ``value``).  The
    repo's BENCH_r*.json files wrap the bench RESULT line under a
    ``parsed`` key ({n, cmd, rc, tail, parsed}); unwrap it first."""
    if not isinstance(doc, dict):
        return None
    if isinstance(doc.get("parsed"), dict):
        doc = doc["parsed"]
    m = doc if doc.get("schema") == "tpuvsr-metrics/1" else None
    if m is None and isinstance(doc.get("metrics"), dict) \
            and doc["metrics"].get("schema") == "tpuvsr-metrics/1":
        m = doc["metrics"]
    if m is not None:
        g = m.get("gauges", {})
        if "distinct_per_s" in g:
            return float(g["distinct_per_s"])
        if m.get("elapsed_s") and m.get("distinct") is not None:
            return float(m["distinct"]) / float(m["elapsed_s"])
    if "value" in doc:
        try:
            return float(doc["value"])
        except (TypeError, ValueError):
            return None
    return None


def bench_throughputs(bench_dir):
    """Newest usable per-backend distinct/s from the repo's BENCH_r*
    documents: ``{"cpu": x, "tpu": y}`` (either may be absent)."""
    out = {}
    for path in sorted(glob.glob(os.path.join(bench_dir,
                                              "BENCH_r*.json"))):
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        tp = _doc_throughput(doc)
        if tp is None:
            continue
        if isinstance(doc.get("parsed"), dict):
            doc = doc["parsed"]
        backend = str(doc.get("backend", "")).lower()
        key = "tpu" if "tpu" in backend and "fallback" not in backend \
            else "cpu"
        out[key] = tp              # sorted order: newest round wins
    return out


def advise_backend(job, *, tpu_devices=0, bench_dir=None):
    """cpu-vs-tpu placement for one job: ``(backend, reason)``.

    TPU only when it is actually reachable AND the job is big enough
    to amortize device compile AND the measured cross-backend
    throughput (newest bench documents, compare_bench semantics)
    favors it; cross-backend numbers are ADVISORY, like
    ``compare_bench`` treats them, so ties and missing data fall back
    to CPU."""
    if tpu_devices <= 0:
        return "cpu", "no tpu devices reachable"
    est = job.flags.get("maxstates") or job.flags.get("est_states")
    if est is not None and int(est) < SMALL_JOB_STATES:
        return "cpu", (f"small job ({est} states < "
                       f"{SMALL_JOB_STATES}): compile-dominated")
    if bench_dir is None:
        bench_dir = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
    tps = bench_throughputs(bench_dir)
    if "tpu" in tps and "cpu" in tps and tps["tpu"] > tps["cpu"]:
        return "tpu", (f"bench advisory: {tps['tpu']:.0f} vs "
                       f"{tps['cpu']:.0f} distinct/s")
    if "tpu" in tps and "cpu" not in tps:
        return "tpu", "bench advisory: only tpu rounds recorded"
    return "cpu", "bench advisory: no measured tpu advantage"


def detect_tpu_devices():
    """TPU device count for the placement advisory: the
    ``TPUVSR_TPU_DEVICES`` env override, else 0 — no probe here
    (`serve` must stay responsive, and a probing child could not have
    a chip its parent holds).  The platform a job really ran on is on
    its ``job_started`` event (``models.registry.device_doc``)."""
    env = os.environ.get("TPUVSR_TPU_DEVICES")
    if env:
        try:
            return max(0, int(env))
        except ValueError:
            pass
    return 0
