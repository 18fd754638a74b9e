"""Fault-injection smoke matrix on the inline stub spec (ISSUE 3).

Runs every resilience path end to end, in-process, through the REAL
engine loops driven by the stub kernel (tpuvsr/testing.py) — no
reference mount, no TPU, seconds on the CPU backend:

  oom-degrade        injected RESOURCE_EXHAUSTED at a mid level ->
                     supervisor halves the tile, retries from the
                     snapshot, completes with the exact fixpoint
  oom-paged-fallback repeated OOMs at the tile floor -> hbm -> paged
                     engine fallback, still the exact fixpoint
  kill-rescue        injected SIGTERM under a PreemptionGuard ->
                     rescue checkpoint at the level boundary,
                     Preempted raised; -recover reproduces the
                     uninterrupted run's counts exactly
  pack-kill-rescue   same kill with the packed frontier ON (ISSUE 9):
                     the rescue snapshot loads as DENSE planes, and
                     both a packed and a -pack off engine resume it to
                     the exact fixpoint
  corrupt-ckpt       crash-corrupted snapshot write (payload truncated,
                     .old kept) -> load_checkpoint falls back to .old
                     and the resumed run still reaches the fixpoint
  garble-ckpt        bit-rot snapshot write (payload bytes XOR-flipped
                     in place, size preserved — only the manifest
                     CRC32 can catch it) -> CRC verify fails, .old
                     fallback, resumed run reaches the fixpoint
  exchange-drop      transient sharded-exchange failure -> journaled
                     retry, level step re-issued, exact fixpoint
  exchange-drop-retry persistent exchange-drop:3 -> three journaled
                     retries with exponential backoff, then the level
                     step goes through; exact fixpoint (and a drop
                     count beyond the budget fails loudly)
  oom-mesh-degrade   injected OOM on a supervised SHARDED run at the
                     tile floor -> mesh shrink 4 -> 2 devices, elastic
                     resume re-hash-partitions the snapshot, exact
                     fixpoint (ISSUE 5 mesh degrade ladder)
  kill-elastic-resume injected SIGTERM on a 4-device sharded run ->
                     rescue checkpoint; resumed on a 2-device mesh ->
                     journaled reshard, exact fixpoint
  pipeline-faults    oom + kill injected into -pipeline 4 runs ->
                     the dispatch window drains, the supervisor/rescue
                     paths behave exactly as at -pipeline 1
  service-preempt-requeue SIGTERM-style kill under the DISPATCHER
                     (tpuvsr/service, ISSUE 6) -> job requeued with
                     its rescue checkpoint, reclaimed, resumed to the
                     exact fixpoint; job_* transitions journaled
  service-oom-degrade injected OOM under the dispatcher -> the
                     per-job supervisor degrades the tile inside ONE
                     job run (no requeue), exact fixpoint
  sim-oom-shrink     injected OOM inside a walker-fleet chunk
                     (ISSUE 7) -> the fleet halves its walker count
                     (degrade {what:"walkers"}), redraws the round,
                     and the trace matches the degraded-count oracle
  kill-hunt-resume   SIGTERM mid-hunt -> walker-frontier rescue
                     snapshot + Preempted; the resumed hunt's deduped
                     violation set and headline trace are
                     bit-identical to an uninterrupted oracle hunt
  kill-canon-resume  SIGTERM mid-run with symmetry canonicalization
                     ON (ISSUE 11) -> rescue snapshot recording the
                     canon spec; a -symmetry off engine REFUSES it
                     (policy error) and a symmetry-on engine resumes
                     to the exact orbit fixpoint
  kill-spill-resume  SIGTERM on a paged run spilling to DISK level
                     files (ISSUE 11, 2-row RAM budget) -> rescue
                     checkpoint; the resume reloads the frontier
                     through the tier and completes the exact fixpoint
  kill-bounds-resume SIGTERM mid-run under bounds-TIGHTENED packing
                     (ISSUE 13) -> rescue snapshot recording the
                     facts digest; tightened AND untightened (bounds
                     off) kill/resume pairs both reach the exact
                     fixpoint, and a flipped -bounds resume is
                     REFUSED (policy error)
  kill-por-resume    SIGTERM mid-run with the ample-set reduction
                     live (ISSUE 16) -> rescue snapshot recording the
                     independence facts digest; the matched resume
                     completes the exact REDUCED fixpoint, and a
                     flipped -por resume is REFUSED in both
                     directions
  kill-validate-resume  SIGTERM mid-batch on a kind="validate" job
                     (ISSUE 8) -> candidate-frontier rescue at the
                     committed chunk boundary, preempt-requeue through
                     the queue, and the resumed attempt's divergence
                     report is bit-identical to an undisturbed oracle
                     job's
  kill-aggregator-mid-tail  SIGKILL the telemetry aggregator mid-tail
                     (ISSUE 17) -> the spool stays fully servable: the
                     torn breach-journal tail is held back, a fresh
                     aggregator refolds from byte 0, and two fresh
                     folds are bit-identical (the fold is a pure
                     function of the journal bytes)
  kill-worker-mid-event  SIGKILL a worker mid-run under
                     TPUVSR_JOURNAL_FSYNC=1 (ISSUE 17) -> the dead
                     worker's journal is a valid prefix (every
                     complete line parses), the live aggregator folds
                     it, the survivor resumes the job, and the
                     incremental fold reconverges exactly with a
                     from-scratch fold
  flood-rate-limit   a flooding tenant hammers the hardened HTTP
                     front door (ISSUE 18) -> bounded 429s with
                     Retry-After, every denial journaled; a legit
                     tenant's job still completes with the exact
                     stub fixpoint
  breaker-crash-loop a crash-looping (tenant, spec) trips the
                     circuit breaker after K failures -> later
                     submissions fail fast with reason breaker-open
                     (no subprocess spawned); a clean run after the
                     cooldown closes it via the half-open probe —
                     both transitions journaled, telemetry fold
                     restart-convergent
  slow-loris-reap    a client that sends half a request line and
                     stalls is reaped by the per-connection read
                     timeout; the service stays fully responsive
  host-death-failover  an ENTIRE host (pool parent + worker, one
                     process) is SIGKILLed mid-sharded-job and its
                     local checkpoint dir dies with it (ISSUE 20) ->
                     the survivor host's recover_stale sweeps the dead
                     host's claims by its stale LEASE, restores the
                     rescue from the quorum driver's blob store, and
                     resumes to a verdict bit-identical to an oracle's
  spool-replica-loss one replica of the quorum spool deleted
                     mid-drain (ISSUE 20) -> the service is unaffected
                     (appends still reach write quorum), replica_lost
                     journaled; recreating the dir heals via
                     anti-entropy — replica_rejoin journaled, replica
                     logs byte-identical
  zombie-fence       a recovered-then-revived worker tries to commit
                     its stale terminal state (ISSUE 20) -> the
                     claim-epoch fence rejects the append
                     (FencedError, journaled ``fence``); the
                     successor's verdict stands: exactly-once
  kill-liveness-resume  SIGTERM mid-graph-build on a STREAMED temporal
                     run (ISSUE 15: edges flowing out of the fused
                     commit) -> rescue snapshot carrying gid column +
                     edge rows + retained levels; the resumed run's
                     CSR, verdict and lasso trace are bit-identical
                     to an uninterrupted oracle's

Prints one JSON object; exit 0 iff every scenario passed.  Run by
tests/test_resilience.py under tier-1 and standalone:

    python scripts/fault_matrix.py
"""

import json
import os
import shutil
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

if __name__ == "__main__":
    # standalone: force the virtual-device CPU backend BEFORE any jax
    # import (under pytest, tests/conftest.py already did this)
    os.environ["JAX_PLATFORMS"] = "cpu"
    _fl = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _fl:
        os.environ["XLA_FLAGS"] = (
            _fl + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, REPO)


def _oracle():
    from tpuvsr.testing import STUB_DISTINCT, STUB_LEVELS
    return {"distinct": STUB_DISTINCT, "levels": STUB_LEVELS}


def _factory(spec):
    from tpuvsr.testing import stub_engine_factory
    return stub_engine_factory(spec)


def _events(path):
    from tpuvsr.obs import read_journal
    return [e["event"] for e in read_journal(path)]


def scenario_oom_degrade(tmp):
    ORACLE = _oracle()
    from tpuvsr.resilience import faults
    from tpuvsr.resilience.supervisor import Supervisor
    from tpuvsr.testing import counter_spec
    spec = counter_spec()
    jp = os.path.join(tmp, "oom.jsonl")
    faults.install("oom@level=3")
    try:
        sup = Supervisor(spec, checkpoint_path=os.path.join(tmp, "ck"),
                         journal_path=jp, engine_factory=_factory(spec),
                         tile_size=4, min_tile=2, backoff_base=0.0,
                         sleep=lambda s: None)
        res = sup.run()
    finally:
        faults.clear()
    ev = _events(jp)
    return {
        "ok": (res.ok and res.distinct_states == ORACLE["distinct"]
               and res.levels == ORACLE["levels"] and sup.attempts == 2
               and ("tile", 4, 2) in sup.degrades
               and "fault" in ev and "retry" in ev and "degrade" in ev),
        "attempts": sup.attempts, "degrades": sup.degrades,
        "distinct": res.distinct_states,
    }


def scenario_oom_paged_fallback(tmp):
    ORACLE = _oracle()
    from tpuvsr.resilience import faults
    from tpuvsr.resilience.supervisor import Supervisor
    from tpuvsr.testing import counter_spec
    spec = counter_spec()
    jp = os.path.join(tmp, "paged.jsonl")
    # tile 4 with floor 4: the first OOM exhausts the halving ladder
    # and falls straight to the paged engine; later OOMs retry there
    faults.install("oom@level=2,oom@level=3,oom@level=4")
    try:
        sup = Supervisor(spec, checkpoint_path=os.path.join(tmp, "ck"),
                         journal_path=jp, engine_factory=_factory(spec),
                         tile_size=4, min_tile=4, backoff_base=0.0,
                         sleep=lambda s: None)
        res = sup.run()
    finally:
        faults.clear()
    return {
        "ok": (res.ok and res.distinct_states == ORACLE["distinct"]
               and res.levels == ORACLE["levels"]
               and sup.kind == "paged"
               and ("engine", "device", "paged") in sup.degrades),
        "attempts": sup.attempts, "engine": sup.kind,
        "distinct": res.distinct_states,
    }


def scenario_kill_rescue(tmp):
    ORACLE = _oracle()
    from tpuvsr.obs import RunObserver
    from tpuvsr.resilience import faults
    from tpuvsr.resilience.supervisor import (Preempted,
                                              PreemptionGuard)
    from tpuvsr.testing import stub_device_engine
    ck = os.path.join(tmp, "kill-ck")
    jp = os.path.join(tmp, "kill.jsonl")
    faults.install("kill@level=3")
    preempted = None
    try:
        with PreemptionGuard():
            try:
                stub_device_engine().run(
                    checkpoint_path=ck,
                    obs=RunObserver(journal_path=jp))
            except Preempted as p:
                preempted = p
    finally:
        faults.clear()
    if preempted is None:
        return {"ok": False, "why": "no Preempted raised"}
    res2 = stub_device_engine().run(resume_from=ck)
    ev = _events(jp)
    return {
        "ok": (preempted.depth == 3 and res2.ok
               and res2.distinct_states == ORACLE["distinct"]
               and res2.levels == ORACLE["levels"]
               and "rescue_checkpoint" in ev and "fault" in ev),
        "rescue_depth": preempted.depth,
        "distinct_after_recover": res2.distinct_states,
    }


def scenario_pack_kill_rescue(tmp):
    """ISSUE 9 satellite: kill mid-run with the packed frontier ON ->
    rescue checkpoint (loaded DENSE, the interchange format), then BOTH
    a packed and a dense engine resume it to the exact fixpoint — the
    packed at-rest representation is invisible across the rescue
    seam."""
    ORACLE = _oracle()
    from tpuvsr.obs import RunObserver
    from tpuvsr.resilience import faults
    from tpuvsr.resilience.supervisor import (Preempted,
                                              PreemptionGuard)
    from tpuvsr.testing import stub_device_engine
    ck = os.path.join(tmp, "pack-ck")
    jp = os.path.join(tmp, "pack.jsonl")
    faults.install("kill@level=3")
    preempted = None
    try:
        with PreemptionGuard():
            try:
                eng = stub_device_engine()      # pack defaults ON
                assert eng._pk is not None
                eng.run(checkpoint_path=ck,
                        obs=RunObserver(journal_path=jp))
            except Preempted as p:
                preempted = p
    finally:
        faults.clear()
    if preempted is None:
        return {"ok": False, "why": "no Preempted raised"}
    res_packed = stub_device_engine().run(resume_from=ck)
    res_dense = stub_device_engine(pack=False).run(resume_from=ck)
    from tpuvsr.obs import read_journal
    starts = [e for e in read_journal(jp) if e["event"] == "run_start"]
    return {
        "ok": (preempted.depth == 3
               and res_packed.ok and res_dense.ok
               and res_packed.distinct_states == ORACLE["distinct"]
               and res_dense.distinct_states == ORACLE["distinct"]
               and res_packed.levels == ORACLE["levels"]
               and res_dense.levels == ORACLE["levels"]
               and all(e.get("pack") for e in starts)),
        "rescue_depth": preempted.depth,
        "distinct_packed": res_packed.distinct_states,
        "distinct_dense": res_dense.distinct_states,
    }


def scenario_kill_fused_commit_resume(tmp):
    """ISSUE 10 satellite: kill mid-chunk with packing AND the fused
    (occupancy-packed single-insert) commit on -> rescue checkpoint,
    then a fused resume AND a per-action resume both reach the exact
    uninterrupted fixpoint — the three-stage commit restructure is
    invisible across the rescue seam, and the journal's run_start rows
    carry the commit key."""
    ORACLE = _oracle()
    from tpuvsr.obs import RunObserver
    from tpuvsr.resilience import faults
    from tpuvsr.resilience.supervisor import (Preempted,
                                              PreemptionGuard)
    from tpuvsr.testing import stub_device_engine
    ck = os.path.join(tmp, "fused-ck")
    jp = os.path.join(tmp, "fused.jsonl")
    faults.install("kill@level=3")
    preempted = None
    try:
        with PreemptionGuard():
            try:
                eng = stub_device_engine()      # commit defaults fused
                assert eng.commit == "fused" and eng._pk is not None
                eng.run(checkpoint_path=ck,
                        obs=RunObserver(journal_path=jp))
            except Preempted as p:
                preempted = p
    finally:
        faults.clear()
    if preempted is None:
        return {"ok": False, "why": "no Preempted raised"}
    res_fused = stub_device_engine().run(resume_from=ck)
    res_pa = stub_device_engine(
        commit="per-action").run(resume_from=ck)
    from tpuvsr.obs import read_journal
    starts = [e for e in read_journal(jp) if e["event"] == "run_start"]
    return {
        "ok": (preempted.depth == 3
               and res_fused.ok and res_pa.ok
               and res_fused.distinct_states == ORACLE["distinct"]
               and res_pa.distinct_states == ORACLE["distinct"]
               and res_fused.levels == ORACLE["levels"]
               and res_pa.levels == ORACLE["levels"]
               and all(e.get("commit") == "fused" for e in starts)),
        "rescue_depth": preempted.depth,
        "distinct_fused": res_fused.distinct_states,
        "distinct_per_action": res_pa.distinct_states,
    }


def scenario_kill_canon_resume(tmp):
    """ISSUE 11 satellite: kill mid-run with symmetry canonicalization
    ON -> rescue checkpoint recording the canon spec, then (a) a
    symmetry-on engine resumes to the exact orbit fixpoint, (b) a
    symmetry-off engine REFUSES the snapshot (policy error — the
    stored fingerprints live in the canonical space)."""
    from tpuvsr.core.values import TLAError
    from tpuvsr.obs import RunObserver, read_journal
    from tpuvsr.resilience import faults
    from tpuvsr.resilience.supervisor import (Preempted,
                                              PreemptionGuard)
    from tpuvsr.testing import SYMPAIR_ORBIT_LEVELS, SYMPAIR_ORBITS, \
        stub_sym_engine
    ck = os.path.join(tmp, "canon-ck")
    jp = os.path.join(tmp, "canon.jsonl")
    faults.install("kill@level=2")
    preempted = None
    try:
        with PreemptionGuard():
            try:
                eng = stub_sym_engine()         # symmetry auto -> ON
                assert eng._canon is not None
                eng.run(checkpoint_path=ck,
                        obs=RunObserver(journal_path=jp))
            except Preempted as p:
                preempted = p
    finally:
        faults.clear()
    if preempted is None:
        return {"ok": False, "why": "no Preempted raised"}
    refused = False
    try:
        stub_sym_engine(symmetry=False).run(resume_from=ck)
    except TLAError as e:
        refused = "symmetry canonicalization" in str(e)
    res = stub_sym_engine().run(resume_from=ck)
    starts = [e for e in read_journal(jp)
              if e["event"] == "run_start"]
    return {
        "ok": (refused and res.ok
               and res.distinct_states == SYMPAIR_ORBITS
               and res.levels == SYMPAIR_ORBIT_LEVELS
               and all(e.get("symmetry") for e in starts)),
        "rescue_depth": preempted.depth, "flip_refused": refused,
        "distinct": res.distinct_states,
    }


def scenario_kill_spill_resume(tmp):
    """ISSUE 11 satellite: kill a paged run whose frontier is spilling
    to DISK level files (2-row RAM budget) -> rescue checkpoint, then
    the resumed run reloads the frontier THROUGH the tier and
    completes the exact fixpoint."""
    ORACLE = _oracle()
    from tpuvsr.engine.paged_bfs import PagedBFS
    from tpuvsr.obs import RunObserver, read_journal
    from tpuvsr.resilience import faults
    from tpuvsr.resilience.supervisor import (Preempted,
                                              PreemptionGuard)
    from tpuvsr.testing import stub_device_engine
    ck = os.path.join(tmp, "spill-ck")
    jp = os.path.join(tmp, "spill.jsonl")
    sd = os.path.join(tmp, "spill-tier")
    faults.install("kill@level=4")
    preempted = None
    try:
        with PreemptionGuard():
            try:
                stub_device_engine(
                    cls=PagedBFS, spill_dir=sd, spill_ram_rows=2,
                    chunk_tiles=1).run(
                    checkpoint_path=ck,
                    obs=RunObserver(journal_path=jp))
            except Preempted as p:
                preempted = p
    finally:
        faults.clear()
    if preempted is None:
        return {"ok": False, "why": "no Preempted raised"}
    res = stub_device_engine(cls=PagedBFS, spill_dir=sd,
                             spill_ram_rows=2,
                             chunk_tiles=1).run(resume_from=ck)
    disk = [e for e in read_journal(jp)
            if e["event"] == "spill" and e.get("tier") == "disk"]
    return {
        "ok": (res.ok and res.distinct_states == ORACLE["distinct"]
               and res.levels == ORACLE["levels"] and len(disk) > 0),
        "rescue_depth": preempted.depth,
        "disk_spills": len(disk),
        "distinct": res.distinct_states,
    }


def scenario_kill_bounds_resume(tmp):
    """ISSUE 13 satellite: kill mid-run under bounds-TIGHTENED packing
    -> rescue checkpoint recording the facts digest; the tightened
    resume completes the exact fixpoint, a flipped -bounds resume is
    REFUSED (policy error), and an untightened (bounds-off) kill/
    resume pair is bit-identical too."""
    ORACLE = _oracle()
    from tpuvsr.core.values import TLAError
    from tpuvsr.obs import RunObserver, read_journal
    from tpuvsr.resilience import faults
    from tpuvsr.resilience.supervisor import (Preempted,
                                              PreemptionGuard)
    from tpuvsr.testing import stub_device_engine

    def kill_run(ck, jp, **kw):
        faults.install("kill@level=3")
        preempted = None
        try:
            with PreemptionGuard():
                try:
                    eng = stub_device_engine(**kw)
                    eng.run(checkpoint_path=ck,
                            obs=RunObserver(journal_path=jp))
                except Preempted as p:
                    preempted = p
        finally:
            faults.clear()
        return preempted

    ck_on = os.path.join(tmp, "bounds-on-ck")
    jp = os.path.join(tmp, "bounds.jsonl")
    p_on = kill_run(ck_on, jp)                 # bounds default ON
    if p_on is None:
        return {"ok": False, "why": "no Preempted raised (on leg)"}
    eng_on = stub_device_engine()
    assert eng_on._pk.total_bits < eng_on._pk_decl.total_bits
    res_on = eng_on.run(resume_from=ck_on)
    flipped = False
    try:
        stub_device_engine(bounds=False).run(resume_from=ck_on)
    except TLAError:
        flipped = True
    ck_off = os.path.join(tmp, "bounds-off-ck")
    p_off = kill_run(ck_off, os.path.join(tmp, "bounds-off.jsonl"),
                     bounds=False)
    if p_off is None:
        return {"ok": False, "why": "no Preempted raised (off leg)"}
    res_off = stub_device_engine(bounds=False).run(resume_from=ck_off)
    starts = [e for e in read_journal(jp)
              if e["event"] == "run_start"]
    return {
        "ok": (p_on.depth == 3 and res_on.ok and res_off.ok
               and res_on.distinct_states == ORACLE["distinct"]
               and res_off.distinct_states == ORACLE["distinct"]
               and res_on.levels == ORACLE["levels"]
               and res_off.levels == ORACLE["levels"]
               and flipped
               and all((e.get("bounds") or {}).get("tightened")
                       for e in starts)),
        "rescue_depth": p_on.depth,
        "distinct_tightened": res_on.distinct_states,
        "distinct_untightened": res_off.distinct_states,
        "flip_refused": flipped,
    }


def scenario_kill_por_resume(tmp):
    """ISSUE 16 satellite: kill mid-run with the ample-set reduction
    live -> rescue checkpoint recording the independence facts digest;
    the matched resume completes the exact REDUCED fixpoint
    bit-identically, and a flipped -por resume is REFUSED in both
    directions (on-snapshot -> off engine, off-snapshot -> on
    engine)."""
    from tpuvsr.core.values import TLAError
    from tpuvsr.obs import RunObserver, read_journal
    from tpuvsr.resilience import faults
    from tpuvsr.resilience.supervisor import (Preempted,
                                              PreemptionGuard)
    from tpuvsr.testing import (POR_STUB_DISTINCT, POR_STUB_LEVELS,
                                counter_spec, stub_device_engine)

    def kill_run(ck, jp, **kw):
        faults.install("kill@level=3")
        preempted = None
        try:
            with PreemptionGuard():
                try:
                    eng = stub_device_engine(
                        spec=counter_spec(inv_free=True), **kw)
                    eng.run(checkpoint_path=ck,
                            obs=RunObserver(journal_path=jp))
                except Preempted as p:
                    preempted = p
        finally:
            faults.clear()
        return preempted

    ck_on = os.path.join(tmp, "por-on-ck")
    jp = os.path.join(tmp, "por.jsonl")
    p_on = kill_run(ck_on, jp, por="on")
    if p_on is None:
        return {"ok": False, "why": "no Preempted raised (on leg)"}
    res_on = stub_device_engine(spec=counter_spec(inv_free=True),
                                por="on").run(resume_from=ck_on)
    flip_off = False
    try:
        stub_device_engine(spec=counter_spec(inv_free=True)).run(
            resume_from=ck_on)
    except TLAError:
        flip_off = True
    ck_off = os.path.join(tmp, "por-off-ck")
    p_off = kill_run(ck_off, os.path.join(tmp, "por-off.jsonl"))
    if p_off is None:
        return {"ok": False, "why": "no Preempted raised (off leg)"}
    flip_on = False
    try:
        stub_device_engine(spec=counter_spec(inv_free=True),
                           por="on").run(resume_from=ck_off)
    except TLAError:
        flip_on = True
    starts = [e for e in read_journal(jp)
              if e["event"] == "run_start"]
    return {
        "ok": (p_on.depth == 3 and res_on.ok
               and res_on.distinct_states == POR_STUB_DISTINCT
               and res_on.levels == POR_STUB_LEVELS
               and flip_off and flip_on
               and all((e.get("por") or {}).get("eligible_actions")
                       == 2 for e in starts)),
        "rescue_depth": p_on.depth,
        "distinct_reduced": res_on.distinct_states,
        "flip_off_refused": flip_off,
        "flip_on_refused": flip_on,
    }


def scenario_corrupt_ckpt(tmp):
    ORACLE = _oracle()
    from tpuvsr.resilience import faults
    from tpuvsr.testing import stub_device_engine
    ck = os.path.join(tmp, "corrupt-ck")
    # every-level checkpoints; the level-3 write is crash-corrupted
    # (frontier.npz truncated, the level-2 snapshot kept as .old)
    faults.install("corrupt-ckpt:frontier.npz@level=3")
    try:
        res1 = stub_device_engine().run(max_depth=3,
                                        checkpoint_path=ck)
    finally:
        faults.clear()
    old_ok = os.path.isdir(ck + ".old")
    res2 = stub_device_engine().run(resume_from=ck)
    return {
        "ok": (bool(res1.error) and old_ok and res2.ok
               and res2.distinct_states == ORACLE["distinct"]
               and res2.levels == ORACLE["levels"]),
        "old_present": old_ok,
        "distinct_after_recover": res2.distinct_states,
    }


def scenario_garble_ckpt(tmp):
    ORACLE = _oracle()
    from tpuvsr.resilience import faults
    from tpuvsr.testing import stub_device_engine
    ck = os.path.join(tmp, "garble-ck")
    # every-level checkpoints; the level-3 write is bit-rotted in place
    # (fpset.npz garbled, size preserved — only the CRC catches it)
    faults.install("garble-ckpt:fpset.npz@level=3")
    try:
        res1 = stub_device_engine().run(max_depth=3,
                                        checkpoint_path=ck)
    finally:
        faults.clear()
    old_ok = os.path.isdir(ck + ".old")
    # the garbled payload is np.load-able garbage of the right size:
    # only the manifest CRC32 distinguishes it from a good snapshot
    logs = []
    res2 = stub_device_engine().run(resume_from=ck,
                                    log=logs.append)
    crc_seen = any("CRC32 mismatch" in m for m in logs)
    return {
        "ok": (bool(res1.error) and old_ok and crc_seen and res2.ok
               and res2.distinct_states == ORACLE["distinct"]
               and res2.levels == ORACLE["levels"]),
        "old_present": old_ok, "crc_detected": crc_seen,
        "distinct_after_recover": res2.distinct_states,
    }


def scenario_pipeline_faults(tmp):
    """oom + kill landing while a -pipeline 4 window is in flight:
    the drain-and-replay contract must leave the supervisor/rescue
    paths bit-identical to the synchronous engine."""
    ORACLE = _oracle()
    from tpuvsr.obs import RunObserver
    from tpuvsr.resilience import faults
    from tpuvsr.resilience.supervisor import (Preempted,
                                              PreemptionGuard,
                                              Supervisor)
    from tpuvsr.testing import counter_spec, stub_device_engine, \
        stub_engine_factory
    spec = counter_spec()
    # oom mid-run under the supervisor, window depth 4
    faults.install("oom@level=3")
    try:
        sup = Supervisor(spec, checkpoint_path=os.path.join(tmp, "ck"),
                         engine_factory=stub_engine_factory(
                             spec, pipeline=4),
                         tile_size=4, min_tile=2, backoff_base=0.0,
                         sleep=lambda s: None)
        res = sup.run()
    finally:
        faults.clear()
    oom_ok = (res.ok and res.distinct_states == ORACLE["distinct"]
              and res.levels == ORACLE["levels"])
    # kill mid-run, window depth 4: rescue at the (drained) boundary
    ck = os.path.join(tmp, "kill-ck")
    jp = os.path.join(tmp, "kill.jsonl")
    faults.install("kill@level=3")
    preempted = None
    try:
        with PreemptionGuard():
            try:
                stub_device_engine(pipeline=4).run(
                    checkpoint_path=ck,
                    obs=RunObserver(journal_path=jp))
            except Preempted as p:
                preempted = p
    finally:
        faults.clear()
    res2 = stub_device_engine(pipeline=4).run(resume_from=ck) \
        if preempted else None
    kill_ok = (preempted is not None and res2 is not None and res2.ok
               and res2.distinct_states == ORACLE["distinct"]
               and res2.levels == ORACLE["levels"])
    return {"ok": oom_ok and kill_ok, "oom_ok": oom_ok,
            "kill_ok": kill_ok}


def scenario_exchange_drop(tmp):
    ORACLE = _oracle()
    import jax
    if len(jax.devices()) < 2:
        return {"ok": True, "skipped": "needs 2 virtual devices"}
    import numpy as np
    from jax.sharding import Mesh
    from tpuvsr.obs import RunObserver, read_journal
    from tpuvsr.parallel.sharded_bfs import ShardedBFS
    from tpuvsr.resilience import faults
    from tpuvsr.testing import counter_spec, stub_model_factory
    jp = os.path.join(tmp, "exchange.jsonl")
    mesh = Mesh(np.array(jax.devices()[:2]), ("d",))
    faults.install("exchange-drop@shard=0@level=2")
    try:
        eng = ShardedBFS(counter_spec(), mesh, tile=4, bucket_cap=64,
                         next_capacity=1 << 6, fpset_capacity=1 << 8,
                         model_factory=stub_model_factory())
        res = eng.run(obs=RunObserver(journal_path=jp))
    finally:
        faults.clear()
    events = read_journal(jp)
    kinds = [e["event"] for e in events]
    return {
        "ok": (res.ok and res.distinct_states == ORACLE["distinct"]
               and res.levels == ORACLE["levels"]
               and "fault" in kinds and "retry" in kinds),
        "distinct": res.distinct_states,
    }


def scenario_exchange_drop_retry(tmp):
    """Persistent exchange-drop:3 (a flaky ICI link): three journaled
    retries with exponential backoff, then the level step goes
    through — the exact fixpoint either way (ISSUE 5)."""
    ORACLE = _oracle()
    import jax
    if len(jax.devices()) < 2:
        return {"ok": True, "skipped": "needs 2 virtual devices"}
    from tpuvsr.obs import RunObserver, read_journal
    from tpuvsr.resilience import faults
    from tpuvsr.testing import stub_sharded_engine
    jp = os.path.join(tmp, "xretry.jsonl")
    faults.install("exchange-drop:3@shard=0@level=2")
    try:
        eng = stub_sharded_engine(n_devices=2, sleep=lambda s: None)
        res = eng.run(obs=RunObserver(journal_path=jp))
    finally:
        faults.clear()
    retries = [e for e in read_journal(jp) if e["event"] == "retry"]
    backoffs = [e["backoff_s"] for e in retries]
    return {
        "ok": (res.ok and res.distinct_states == ORACLE["distinct"]
               and res.levels == ORACLE["levels"]
               and [e["attempt"] for e in retries] == [1, 2, 3]
               and all(e.get("what") == "exchange" for e in retries)
               and backoffs == sorted(backoffs)),
        "retries": [(e["attempt"], e["backoff_s"]) for e in retries],
        "distinct": res.distinct_states,
    }


def scenario_oom_mesh_degrade(tmp):
    """Supervised sharded run, injected OOM at the tile floor: the
    mesh degrade ladder shrinks 4 -> 2 devices and the elastic resume
    re-hash-partitions the snapshot — exact fixpoint (ISSUE 5)."""
    ORACLE = _oracle()
    import jax
    if len(jax.devices()) < 4:
        return {"ok": True, "skipped": "needs 4 virtual devices"}
    from tpuvsr.obs import read_journal
    from tpuvsr.resilience import faults
    from tpuvsr.resilience.supervisor import Supervisor
    from tpuvsr.testing import counter_spec, stub_sharded_factory
    spec = counter_spec()
    jp = os.path.join(tmp, "mesh.jsonl")
    faults.install("oom@level=3")
    try:
        sup = Supervisor(spec, engine="sharded", mesh_devices=4,
                         checkpoint_path=os.path.join(tmp, "ck"),
                         journal_path=jp,
                         engine_factory=stub_sharded_factory(spec),
                         tile_size=4, min_tile=4, backoff_base=0.0,
                         sleep=lambda s: None)
        res = sup.run()
    finally:
        faults.clear()
    ev = [e["event"] for e in read_journal(jp)]
    return {
        "ok": (res.ok and res.distinct_states == ORACLE["distinct"]
               and res.levels == ORACLE["levels"]
               and ("mesh", 4, 2) in sup.degrades
               and sup.summary()["resharded_from"] == 4
               and "degrade" in ev and "retry" in ev
               and "reshard" in ev),
        "degrades": sup.degrades, "mesh_devices": sup.n_dev,
        "distinct": res.distinct_states,
    }


def scenario_kill_elastic_resume(tmp):
    """SIGTERM on a 4-device sharded run -> rescue checkpoint; the
    resume comes back on HALF the mesh (a lost pod slice) and the
    snapshot is re-hash-partitioned at load — exact fixpoint, reshard
    journaled (ISSUE 5)."""
    ORACLE = _oracle()
    import jax
    if len(jax.devices()) < 4:
        return {"ok": True, "skipped": "needs 4 virtual devices"}
    from tpuvsr.obs import RunObserver, read_journal
    from tpuvsr.resilience import faults
    from tpuvsr.resilience.supervisor import (Preempted,
                                              PreemptionGuard)
    from tpuvsr.testing import stub_sharded_engine
    ck = os.path.join(tmp, "kill-ck")
    jp = os.path.join(tmp, "kill.jsonl")
    faults.install("kill@level=3")
    preempted = None
    try:
        with PreemptionGuard():
            try:
                stub_sharded_engine(n_devices=4).run(
                    checkpoint_path=ck,
                    obs=RunObserver(journal_path=jp))
            except Preempted as p:
                preempted = p
    finally:
        faults.clear()
    if preempted is None:
        return {"ok": False, "why": "no Preempted raised"}
    eng2 = stub_sharded_engine(n_devices=2)
    res2 = eng2.run(resume_from=ck,
                    obs=RunObserver(journal_path=jp))
    ev = [e["event"] for e in read_journal(jp)]
    return {
        "ok": (preempted.depth == 3 and res2.ok
               and res2.distinct_states == ORACLE["distinct"]
               and res2.levels == ORACLE["levels"]
               and eng2.resharded_from == 4
               and "rescue_checkpoint" in ev and "reshard" in ev),
        "rescue_depth": preempted.depth,
        "resharded_from": eng2.resharded_from,
        "distinct_after_recover": res2.distinct_states,
    }


def scenario_service_preempt_requeue(tmp):
    """A SIGTERM-style preemption UNDER THE DISPATCHER (ISSUE 6): the
    injected kill fires mid-run inside the service worker, the job is
    requeued with its rescue checkpoint attached, and the same drain
    claims it again and resumes to the exact fixpoint — every
    transition visible in the job's own journal."""
    ORACLE = _oracle()
    from tpuvsr.obs import read_journal
    from tpuvsr.service.queue import JobQueue
    from tpuvsr.service.worker import Worker
    q = JobQueue(os.path.join(tmp, "spool"))
    job = q.submit("<stub>", engine="device",
                   flags={"stub": True, "inject": "kill@level=3"})
    Worker(q, devices=1).drain()
    done = q.get(job.job_id)
    ev = [e["event"] for e in read_journal(q.journal_path(job.job_id))]
    starts = [e for e in read_journal(q.journal_path(job.job_id))
              if e["event"] == "job_started"]
    return {
        "ok": (done.state == "done" and done.attempts == 2
               and done.result["distinct"] == ORACLE["distinct"]
               and done.result["levels"] == ORACLE["levels"]
               and "job_requeued" in ev and "rescue_checkpoint" in ev
               and "job_done" in ev and len(starts) == 2),
        "state": done.state, "attempts": done.attempts,
        "distinct": done.result["distinct"],
    }


def scenario_service_oom_degrade(tmp):
    """An injected OOM under the dispatcher: the per-job supervisor
    degrades (tile halving) INSIDE one job run — the job never leaves
    ``running``, completes with the exact fixpoint, and the degrade is
    journaled in the job's own journal (ISSUE 6)."""
    ORACLE = _oracle()
    from tpuvsr.obs import read_journal
    from tpuvsr.service.queue import JobQueue
    from tpuvsr.service.worker import Worker
    q = JobQueue(os.path.join(tmp, "spool"))
    job = q.submit("<stub>", engine="device",
                   flags={"stub": True, "inject": "oom@level=3",
                          "supervisor": {"tile_size": 4, "min_tile": 2,
                                         "backoff_base": 0.0}})
    Worker(q, devices=1).drain()
    done = q.get(job.job_id)
    ev = [e["event"] for e in read_journal(q.journal_path(job.job_id))]
    degrades = [e for e in read_journal(q.journal_path(job.job_id))
                if e["event"] == "degrade"]
    return {
        "ok": (done.state == "done" and done.attempts == 1
               and done.result["distinct"] == ORACLE["distinct"]
               and done.result["levels"] == ORACLE["levels"]
               and "fault" in ev and "retry" in ev
               and any(d["what"] == "tile" and d["from"] == 4
                       and d["to"] == 2 for d in degrades)
               and "job_requeued" not in ev),
        "state": done.state, "attempts": done.attempts,
        "degrades": [(d["what"], d["from"], d["to"]) for d in degrades],
    }


#: the kill-one-of-N dead worker: claims ONE job off the spool and
#: SIGKILLs itself (no rescue, no atexit — a genuinely dead process)
#: at the depth-2 tick, after the level-1 checkpoint has landed (the
#: unique-witness violation itself lands at depth 3 — the kill must
#: precede it)
_DOOMED_WORKER = """\
import os, signal, sys
from tpuvsr.service.queue import JobQueue
from tpuvsr.service.worker import Worker

def on_level(worker, job, depth):
    if depth >= 2:
        os.kill(os.getpid(), signal.SIGKILL)

Worker(JobQueue(sys.argv[1]), devices=1, owner="wA",
       on_level=on_level, light_threads=0).drain(max_jobs=1)
"""


def scenario_kill_one_of_n_workers(tmp):
    """ISSUE 14: N workers share one spool; one is SIGKILLed mid-job
    (dead pid, claim file left, per-level checkpoints on disk).  The
    SURVIVOR's ordinary drain loop recovers the stale claim — the
    worker-id/host-aware liveness judgment — requeues the job WITH
    the rescue snapshot, resumes it, and reports the violation with a
    trace BIT-IDENTICAL to an uninterrupted oracle.  The survivor
    also drains the dead worker's unclaimed backlog."""
    import subprocess
    from tpuvsr.engine.device_bfs import DeviceBFS
    from tpuvsr.obs import read_journal
    from tpuvsr.service.queue import JobQueue
    from tpuvsr.service.worker import Worker, result_summary
    from tpuvsr.testing import (counter_spec, stub_model_factory,
                                subprocess_env)
    spool = os.path.join(tmp, "spool")
    q = JobQueue(spool)
    doomed = q.submit("<stub:doomed>", engine="device",
                      flags={"stub": True, "inv_x_bound": 2})
    other = q.submit("<stub:other>", engine="device",
                     flags={"stub": True})
    p = subprocess.run(
        [sys.executable, "-c", _DOOMED_WORKER, spool],
        env=subprocess_env(), capture_output=True, text=True,
        timeout=300)
    killed = p.returncode in (-9, 137)
    claim_left = os.path.exists(
        os.path.join(q.claims_dir, f"{doomed.job_id}.claim"))
    # the survivor: recover_stale runs inside its ordinary drain loop
    Worker(q, devices=1, owner="wB", light_threads=0).drain()
    jd, jo = q.get(doomed.job_id), q.get(other.job_id)
    evs = read_journal(q.journal_path(doomed.job_id))
    req = [e for e in evs if e["event"] == "job_requeued"]
    workers = [e["worker"] for e in evs
               if e["event"] == "sched_decision"]
    oracle = result_summary(
        DeviceBFS(counter_spec(inv_x_bound=2),
                  model_factory=stub_model_factory(inv_x_bound=2),
                  hash_mode="full", tile_size=4,
                  fpset_capacity=1 << 8, next_capacity=1 << 6).run())
    ok = (killed and claim_left
          and jd.state == "violated" and jd.attempts == 2
          and len(req) == 1 and "worker-died" in req[0]["reason"]
          and (req[0].get("rescue") or {}).get("depth", 0) >= 1
          and jd.result["violated"] == oracle["violated"] == "Bound"
          and jd.result["trace"] == oracle["trace"]
          and jd.result["distinct"] == oracle["distinct"]
          and jo.state == "done"
          and jo.result["distinct"] == _oracle()["distinct"]
          and workers == ["wA", "wB"])
    return {
        "ok": ok, "killed_rc": p.returncode,
        "claim_left_behind": claim_left,
        "doomed": {"state": jd.state, "attempts": jd.attempts,
                   "requeue_reason": req[0]["reason"] if req else None,
                   "rescue_depth": (req[0].get("rescue") or {}).get(
                       "depth") if req else None,
                   "trace_identical": (jd.result or {}).get("trace")
                   == oracle["trace"]},
        "survivor_finished_backlog": jo.state,
        "workers_seen": workers,
    }


#: the killed telemetry aggregator: tails the spool in a tight poll
#: loop under a microscopic queue-wait SLO (so it journals
#: ``slo_breach`` lines to its own telemetry/events.jsonl), then
#: SIGKILLs itself after the first poll that folded events — offsets
#: lost, breach journal mid-life
_DOOMED_AGGREGATOR = """\
import os, signal, sys, time
from tpuvsr.obs.telemetry import TelemetryAggregator

agg = TelemetryAggregator(sys.argv[1], window_s=1.0,
                          slo={"queue_wait_p99_s": 1e-9})
while True:
    agg.poll()
    if agg.snapshot()["events"] > 0:
        os.kill(os.getpid(), signal.SIGKILL)
    time.sleep(0.05)
"""


def scenario_kill_aggregator_mid_tail(tmp):
    """ISSUE 17: the telemetry aggregator is a pure READER — SIGKILL
    it mid-tail (in-memory offsets lost, its breach journal possibly
    torn mid-append) and the spool must stay fully servable: a torn
    events.jsonl tail is held back by the \\n-holdback discipline, a
    fresh aggregator refolds from byte 0 without error, and two
    independent fresh folds are IDENTICAL (the fold is a pure
    function of the journal bytes — nothing the dead reader held
    mattered)."""
    import subprocess
    from tpuvsr.obs.telemetry import TelemetryAggregator
    from tpuvsr.service.queue import JobQueue
    from tpuvsr.service.worker import Worker
    from tpuvsr.testing import subprocess_env
    spool = os.path.join(tmp, "spool")
    q = JobQueue(spool)
    q.submit("<stub>", engine="device", flags={"stub": True})
    Worker(q, devices=1).drain()
    p = subprocess.run(
        [sys.executable, "-c", _DOOMED_AGGREGATOR, spool],
        env=subprocess_env(), capture_output=True, text=True,
        timeout=300)
    killed = p.returncode in (-9, 137)
    # simulate the worst kill point: a half-appended breach line with
    # no terminating newline left on the aggregator's own journal
    evp = os.path.join(spool, "telemetry", "events.jsonl")
    breach_lines = 0
    if os.path.exists(evp):
        with open(evp) as f:
            breach_lines = sum(1 for ln in f if ln.endswith("\n"))
        with open(evp, "a") as f:
            f.write('{"event": "slo_br')
    # more fleet activity lands AFTER the reader died
    j2 = q.submit("<stub:after>", engine="device",
                  flags={"stub": True})
    Worker(q, devices=1).drain()
    a1 = TelemetryAggregator(spool, journal_breaches=False)
    a1.poll()
    a2 = TelemetryAggregator(spool, journal_breaches=False)
    a2.poll()
    s1, s2 = a1.snapshot(), a2.snapshot()
    done = q.get(j2.job_id)
    ok = (killed and breach_lines >= 1 and s1 == s2
          and s1["counters"]["jobs_submitted"] == 2
          and s1["counters"]["slo_breaches"] >= 1
          and done.state == "done")
    return {
        "ok": ok, "killed_rc": p.returncode,
        "breach_lines_journaled": breach_lines,
        "events_folded": s1["events"],
        "slo_breaches": s1["counters"]["slo_breaches"],
        "reconverged": s1 == s2,
    }


def scenario_kill_worker_mid_event(tmp):
    """ISSUE 17: a worker SIGKILLed mid-run under
    ``TPUVSR_JOURNAL_FSYNC=1`` leaves a journal that is a valid
    prefix — every complete line parses, at most the last line is
    torn — the live aggregator folds it without error, the survivor
    recovers and finishes the job, and the killed-then-resumed
    incremental fold reconverges EXACTLY with a from-scratch fold."""
    import subprocess
    from tpuvsr.obs.telemetry import TelemetryAggregator
    from tpuvsr.service.queue import JobQueue
    from tpuvsr.service.worker import Worker
    from tpuvsr.testing import subprocess_env
    spool = os.path.join(tmp, "spool")
    q = JobQueue(spool)
    job = q.submit("<stub>", engine="device", flags={"stub": True})
    p = subprocess.run(
        [sys.executable, "-c", _DOOMED_WORKER, spool],
        env=subprocess_env({"TPUVSR_JOURNAL_FSYNC": "1"}),
        capture_output=True, text=True, timeout=300)
    killed = p.returncode in (-9, 137)
    # the dead worker's journal: every \n-terminated line is valid
    # JSON (fsync-per-event means nothing buffered was lost)
    torn, parsed = 0, []
    with open(q.journal_path(job.job_id)) as f:
        for line in f:
            if not line.endswith("\n"):
                torn += 1
                continue
            parsed.append(json.loads(line))
    mid = TelemetryAggregator(spool, journal_breaches=False)
    mid.poll()
    mid_events = mid.snapshot()["events"]
    # the survivor's ordinary drain recovers the stale claim
    Worker(q, devices=1, owner="wB", light_threads=0).drain()
    done = q.get(job.job_id)
    mid.poll()                 # the mid-kill aggregator keeps tailing
    fresh = TelemetryAggregator(spool, journal_breaches=False)
    fresh.poll()
    s_resumed, s_fresh = mid.snapshot(), fresh.snapshot()
    ok = (killed and torn <= 1 and len(parsed) >= 3
          and mid_events >= len(parsed)
          and done.state == "done"
          and s_resumed == s_fresh
          and s_fresh["counters"]["requeues"] >= 1
          and s_fresh["jobs_by_state"].get("done") == 1)
    return {
        "ok": ok, "killed_rc": p.returncode,
        "torn_lines": torn, "parsed_lines": len(parsed),
        "state": done.state,
        "incremental_fold_reconverged": s_resumed == s_fresh,
    }


def scenario_sim_oom_shrink(tmp):
    """Injected OOM inside a fleet chunk (ISSUE 7): the fleet's own
    degrade ladder halves the walker count, journals
    ``degrade {what: "walkers"}`` + ``retry``, redraws the round, and
    the run still completes — per-walk determinism makes the redraw
    exact."""
    from tpuvsr.obs import RunObserver, read_journal
    from tpuvsr.resilience import faults
    from tpuvsr.testing import stub_fleet
    jp = os.path.join(tmp, "sim-oom.jsonl")
    faults.install("oom@level=2")
    try:
        sim = stub_fleet(walkers=32, n_devices=2, inv_x_bound=2)
        res = sim.run(num=64, depth=8, seed=3,
                      obs=RunObserver(journal_path=jp))
    finally:
        faults.clear()
    # oracle at the DEGRADED walker count: the redraw must match it
    oracle = stub_fleet(walkers=16, n_devices=2, inv_x_bound=2).run(
        num=64, depth=8, seed=3)
    ev = [e["event"] for e in read_journal(jp)]
    degr = [(e["what"], e["from"], e["to"])
            for e in read_journal(jp) if e["event"] == "degrade"]
    same = (res.violated_invariant == oracle.violated_invariant
            and [(t.action_name, t.state) for t in res.trace]
            == [(t.action_name, t.state) for t in oracle.trace])
    return {
        "ok": (not res.ok and sim.walkers == 16 and same
               and ("walkers", 32, 16) in degr
               and "fault" in ev and "retry" in ev),
        "walkers": sim.walkers, "degrades": degr,
        "trace_matches_degraded_oracle": same,
    }


def scenario_kill_hunt_resume(tmp):
    """SIGTERM mid-hunt under the fleet (ISSUE 7): rescue snapshot of
    the walker frontier at the committed chunk boundary, exit-75-style
    Preempted; the resumed hunt's unique-violation set and headline
    trace are bit-identical to an uninterrupted oracle hunt."""
    from tpuvsr.obs import RunObserver, read_journal
    from tpuvsr.resilience import faults
    from tpuvsr.resilience.supervisor import (Preempted,
                                              PreemptionGuard)
    from tpuvsr.sim.hunt import run_hunt, sim_result_summary
    from tpuvsr.testing import counter_spec, stub_model_factory
    spec = counter_spec(inv_x_bound=2)
    factory = stub_model_factory(inv_x_bound=2)
    kw = dict(walkers=32, n_devices=2, depth=8, seed=5, num=64,
              chunk_steps=4, model_factory=factory)
    oracle = sim_result_summary(run_hunt(spec, **kw))
    ck = os.path.join(tmp, "hunt-ck")
    jp = os.path.join(tmp, "hunt.jsonl")
    faults.install("kill@level=1")
    preempted = None
    try:
        with PreemptionGuard():
            try:
                run_hunt(spec, checkpoint_path=ck,
                         obs=RunObserver(journal_path=jp), **kw)
            except Preempted as p:
                preempted = p
    finally:
        faults.clear()
    if preempted is None:
        return {"ok": False, "why": "no Preempted raised"}
    res2 = sim_result_summary(run_hunt(
        spec, resume_from=ck, obs=RunObserver(journal_path=jp), **kw))
    ev = [e["event"] for e in read_journal(jp)]
    return {
        "ok": (res2["violations"] == oracle["violations"]
               and res2["trace"] == oracle["trace"]
               and res2["walks"] == oracle["walks"]
               and "rescue_checkpoint" in ev and "fault" in ev
               and "sim_chunk" in ev and "hunt_violation" in ev),
        "unique_violations": len(res2["violations"]),
        "walks": res2["walks"],
    }


def scenario_kill_validate_resume(tmp):
    """SIGTERM mid-batch on a ``kind="validate"`` job (ISSUE 8): the
    batch validator rescues its committed candidate frontier at the
    chunk boundary and raises Preempted; the worker maps that to
    preempted-requeued, the next claim resumes from the rescue, and
    the final divergence report (trace id, step, enabled set) is
    bit-identical to an undisturbed oracle job's."""
    from tpuvsr.obs import read_journal
    from tpuvsr.service.queue import JobQueue
    from tpuvsr.service.worker import Worker
    from tpuvsr.testing import stub_trace_records
    from tpuvsr.validate.traces import save_traces
    q = JobQueue(os.path.join(tmp, "spool"))
    tp = os.path.join(tmp, "traces.jsonl")
    save_traces(tp, stub_trace_records(n=64, depth=6, seed=5,
                                       mutate=(40, 3)))
    flags = {"stub": True, "traces": tp, "batch": 16,
             "chunk_steps": 2}
    oracle = q.submit("<stub:v-oracle>", kind="validate",
                      flags=dict(flags))
    kill = q.submit("<stub:v-kill>", kind="validate",
                    flags=dict(flags, inject="kill@level=1"))
    Worker(q, devices=2).drain()
    jo, jk = q.get(oracle.job_id), q.get(kill.job_id)
    if jo.state != "violated" or jk.state != "violated":
        return {"ok": False, "oracle_state": jo.state,
                "kill_state": jk.state,
                "why": (jk.reason or jo.reason)}
    ev = [e["event"] for e in read_journal(q.journal_path(jk.job_id))]
    fd = jk.result["first_divergence"]
    return {
        "ok": (jk.attempts == 2
               and jk.result["divergences"] == jo.result["divergences"]
               and fd["trace"] == "t-0040" and fd["step"] == 3
               and "rescue_checkpoint" in ev and "job_requeued" in ev
               and "validate_chunk" in ev and "divergence" in ev),
        "attempts": jk.attempts,
        "divergences": len(jk.result["divergences"]),
        "traces": jk.result["traces"],
    }


def scenario_kill_liveness_resume(tmp):
    """ISSUE 15 satellite: SIGTERM-kill mid-graph-build on a STREAMED
    temporal run (the behavior graph flowing out of the fused commit)
    -> rescue snapshot carrying the gid column, the drained edge rows
    and the retained level blocks; the resumed run completes with a
    CSR, verdict and lasso trace bit-identical to an uninterrupted
    oracle's."""
    from tpuvsr.engine.device_liveness import DeviceGraph
    from tpuvsr.engine.liveness import liveness_check
    from tpuvsr.obs import RunObserver
    from tpuvsr.resilience import faults
    from tpuvsr.resilience.supervisor import (Preempted,
                                              PreemptionGuard)
    from tpuvsr.testing import (canon_csr, stub_ticker_factory,
                                ticker_spec)
    spec = ticker_spec(modulus=8)        # 16 states, 9 levels
    kw = dict(tile_size=2, chunk_tiles=1, next_capacity=16,
              fpset_capacity=1 << 8, hash_mode="full",
              model_factory=stub_ticker_factory(modulus=8))
    canon = canon_csr
    oracle = DeviceGraph(spec, mode="stream", **kw)
    r_o = liveness_check(spec, graph=oracle)

    ck = os.path.join(tmp, "liveness-ck")
    jp = os.path.join(tmp, "liveness.jsonl")
    faults.install("kill@level=4")
    preempted = None
    try:
        with PreemptionGuard():
            try:
                DeviceGraph(spec, mode="stream", checkpoint_path=ck,
                            obs=RunObserver(journal_path=jp), **kw)
            except Preempted as p:
                preempted = p
    finally:
        faults.clear()
    if preempted is None:
        return {"ok": False, "why": "no Preempted raised"}
    g2 = DeviceGraph(spec, mode="stream", resume_from=ck, **kw)
    r2 = liveness_check(spec, graph=g2)
    ev = _events(jp)

    def trace(r):
        return [(e.action_name, e.state) for e in r.trace]
    return {
        "ok": (preempted.depth == 4
               and g2.n == oracle.n
               and canon(g2) == canon(oracle)
               and all(g2.states[s] == oracle.states[s]
                       for s in range(g2.n))
               and (r2.ok, r2.property_name) == (r_o.ok,
                                                 r_o.property_name)
               and trace(r2) == trace(r_o)
               and r2.cycle_start == r_o.cycle_start
               and "rescue_checkpoint" in ev and "fault" in ev),
        "rescue_depth": preempted.depth,
        "states": g2.n,
        "edges": int(g2.csr[1].shape[0]),
        "verdict_ok": r2.ok,
    }


def scenario_flood_rate_limit(tmp):
    """ISSUE 18: a flooding tenant hammers the hardened HTTP front
    door -> the per-tenant token bucket turns the flood into bounded
    429s carrying Retry-After (every denial journaled as
    rate_limited), an unauthenticated probe bounces 401, and the
    legit tenant's job still completes with the EXACT stub
    fixpoint — abuse never changes a verdict."""
    import http.client
    ORACLE = _oracle()
    from tpuvsr.obs import read_journal
    from tpuvsr.serve.guard import Guard
    from tpuvsr.serve.http import ServiceHTTP
    from tpuvsr.service.queue import JobQueue
    from tpuvsr.service.worker import Worker
    from tpuvsr.testing import true_argv
    spool = os.path.join(tmp, "spool")
    os.makedirs(spool, exist_ok=True)
    with open(os.path.join(spool, "tokens.json"), "w") as f:
        json.dump({"legit": "tok-l", "flood": "tok-f"}, f)
    guard = Guard(spool, rate=0.5, burst=2.0)
    svc = ServiceHTTP(spool, guard=guard).start()

    def post(token, body):
        conn = http.client.HTTPConnection("127.0.0.1", svc.port,
                                          timeout=10)
        hdrs = {"Content-Type": "application/json"}
        if token:
            hdrs["Authorization"] = f"Bearer {token}"
        conn.request("POST", "/v1/jobs",
                     body=json.dumps(body).encode(), headers=hdrs)
        resp = conn.getresponse()
        doc = json.loads(resp.read() or b"{}")
        ra = resp.getheader("Retry-After")
        conn.close()
        return resp.status, doc, ra

    try:
        code, doc, _ = post("tok-l", {"spec": "<stub>",
                                      "engine": "device",
                                      "flags": {"stub": True}})
        legit_id = doc.get("job_id")
        flood = [post("tok-f", {"spec": "SPAM", "kind": "shell",
                                "flags": {"argv": true_argv()}})
                 for _ in range(10)]
        denied = [f for f in flood if f[0] == 429]
        noauth = post(None, {"spec": "X", "kind": "shell",
                             "flags": {"argv": true_argv()}})[0]
        q = JobQueue(spool)
        Worker(q, devices=1).drain()
        done = q.get(legit_id)
    finally:
        svc.stop()
    ev = [e["event"]
          for e in read_journal(os.path.join(spool, "guard.jsonl"))]
    return {
        "ok": (code == 200 and done.state == "done"
               and done.result["distinct"] == ORACLE["distinct"]
               and done.result["levels"] == ORACLE["levels"]
               and len(denied) >= 7
               and all(f[2] is not None for f in denied)
               and noauth == 401
               and ev.count("rate_limited") == len(denied)
               and "auth_denied" in ev),
        "flood_429s": len(denied), "noauth": noauth,
        "legit_state": done.state,
        "distinct": done.result["distinct"],
    }


def scenario_breaker_crash_loop(tmp):
    """ISSUE 18: a crash-looping (tenant, spec) trips the circuit
    breaker after K=2 failures -> the next submissions fail FAST with
    reason breaker-open (no subprocess spawned), a clean run after
    the cooldown closes it via the half-open probe, both transitions
    are journaled, and two fresh telemetry folds of the guard journal
    are identical (restart-convergent)."""
    import time
    from tpuvsr.obs import read_journal
    from tpuvsr.obs.telemetry import TelemetryAggregator
    from tpuvsr.serve.guard import Guard, spec_digest
    from tpuvsr.service.queue import JobQueue
    from tpuvsr.service.worker import Worker
    from tpuvsr.testing import true_argv
    spool = os.path.join(tmp, "spool")
    q = JobQueue(spool)
    guard = Guard(spool, breaker_k=2, breaker_cooldown=1.0)
    w = Worker(q, devices=1, light_threads=0, policy=None,
               owner="w-brk", guard=guard)
    fail = [sys.executable, "-c", "import sys; sys.exit(3)"]
    for i in range(4):
        q.submit("CRASH", kind="shell", tenant="a",
                 flags={"argv": fail, "timeout": 30}, job_id=f"c{i}")
    w.drain(idle_exit=True)
    jobs = {j.job_id: j for j in q.jobs()}
    digest = spec_digest("CRASH", None)
    opened = guard.breaker_state("a", digest) == "open"
    time.sleep(1.2)                # past the cooldown: half-open
    q.submit("CRASH", kind="shell", tenant="a",
             flags={"argv": true_argv(), "timeout": 30},
             job_id="probe")
    w.drain(idle_exit=True)
    closed = guard.breaker_state("a", digest) == "closed"
    ev = [e["event"]
          for e in read_journal(os.path.join(spool, "guard.jsonl"))]
    a1 = TelemetryAggregator(spool, journal_breaches=False)
    a1.poll()
    a2 = TelemetryAggregator(spool, journal_breaches=False)
    a2.poll()
    g1 = a1.snapshot()["guard"]
    g2 = a2.snapshot()["guard"]
    return {
        "ok": (jobs["c0"].reason == "rc=3"
               and jobs["c1"].reason == "rc=3"
               and jobs["c2"].reason == "breaker-open"
               and jobs["c3"].reason == "breaker-open"
               and opened and closed
               and q.get("probe").state == "done"
               and ev.count("breaker_open") == 1
               and ev.count("breaker_close") == 1
               and g1 == g2 and g1["breaker_trips"] == 1
               and g1["breaker_closes"] == 1
               and g1["open_breakers"] == []),
        "fast_fail_reasons": [jobs["c2"].reason, jobs["c3"].reason],
        "probe_state": q.get("probe").state,
        "fold_reconverged": g1 == g2,
    }


def scenario_slow_loris_reap(tmp):
    """ISSUE 18: a client that sends half a request line and stalls
    holds a connection slot until the per-connection read timeout
    reaps it (server closes; recv returns b''); the service answers
    the next well-formed request immediately."""
    import http.client
    import socket
    from tpuvsr.serve.http import ServiceHTTP
    spool = os.path.join(tmp, "spool")
    os.makedirs(spool, exist_ok=True)
    svc = ServiceHTTP(spool, request_timeout=0.5).start()
    try:
        s = socket.create_connection(("127.0.0.1", svc.port),
                                     timeout=10)
        s.sendall(b"POST /v1/jobs HT")      # ...and stall forever
        s.settimeout(10)
        try:
            reaped = s.recv(64) == b""      # server hung up on us
        except ConnectionError:
            reaped = True
        s.close()
        conn = http.client.HTTPConnection("127.0.0.1", svc.port,
                                          timeout=10)
        conn.request("GET", "/healthz")
        healthy = conn.getresponse().status == 200
        conn.close()
    finally:
        svc.stop()
    return {"ok": reaped and healthy, "reaped": reaped,
            "healthz_after": healthy}


def _spool_events(spool):
    from tpuvsr.obs import read_journal
    path = os.path.join(spool, "spool.jsonl")
    return read_journal(path) if os.path.exists(path) else []


#: the doomed POOL PARENT (host-death-failover): registers its host's
#: lease through the spool driver, then runs its worker — and SIGKILLs
#: the whole process at the depth-2 tick, after the level-1 checkpoint
#: has landed locally AND the same tick's replicate_snapshot() shipped
#: it into the driver blob store (fake host identity via TPUVSR_HOST)
_DOOMED_POOL = """\
import os, signal, sys
from tpuvsr.service.queue import JobQueue
from tpuvsr.service.worker import Worker

q = JobQueue(sys.argv[1])
q.host_heartbeat()                 # the pool parent's host lease

def on_level(worker, job, depth):
    if depth >= 2:
        os.kill(os.getpid(), signal.SIGKILL)

Worker(q, devices=2, owner="poolA-w0",
       on_level=on_level, light_threads=0).drain(max_jobs=1)
"""


def scenario_host_death_failover(tmp):
    """ISSUE 20: an ENTIRE HOST dies mid-sharded-job — the pool
    parent (which wrote host-lease heartbeats through the spool
    driver) and its worker are one SIGKILLed process, and the host's
    local checkpoint directory AND its spool replica die with it
    (the quorum keeps serving on the remaining majority).  The
    survivor host's
    ``recover_stale`` judges the dead host by its stale LEASE (claim
    heartbeats are irrelevant: heartbeat_timeout is an hour), sweeps
    its claim in one pass, restores the rescue from the DRIVER-HELD
    snapshot blob, and resumes the sharded job to a verdict
    bit-identical to an undisturbed oracle job's."""
    import subprocess
    import time
    from tpuvsr.obs import read_journal
    from tpuvsr.service.queue import JobQueue
    from tpuvsr.service.worker import Worker
    from tpuvsr.testing import subprocess_env
    flags = {"stub": True, "inv_x_bound": 2}
    spool = os.path.join(tmp, "spool")
    q = JobQueue(spool, driver="quorum", host_lease_timeout=1.0,
                 heartbeat_timeout=3600.0)
    doomed = q.submit("<stub:doomed>", engine="sharded", devices=2,
                      flags=dict(flags))
    env = subprocess_env({"TPUVSR_HOST": "hostA"})
    p = subprocess.run([sys.executable, "-c", _DOOMED_POOL, spool],
                       env=env, capture_output=True, text=True,
                       timeout=300)
    killed = p.returncode in (-9, 137)
    # hostA's disk dies with the host: the job's local checkpoint
    # directory is gone — only the driver-held blob can seed a rescue
    shutil.rmtree(q.checkpoint_path(doomed.job_id),
                  ignore_errors=True)
    # ... and so does hostA's spool replica: the quorum keeps serving
    # (and the replicated blob survives) on the remaining majority
    shutil.rmtree(os.path.join(spool, "replicas", "r0"),
                  ignore_errors=True)
    blob_held = q.drv.get_blob(f"ckpt-{doomed.job_id}.tar") is not None
    time.sleep(1.2)                    # hostA's lease goes stale
    os.environ["TPUVSR_HOST"] = "hostB"
    try:
        qb = JobQueue(spool, host_lease_timeout=1.0,
                      heartbeat_timeout=3600.0)
        qb.host_heartbeat()
        dead = sorted(qb.dead_hosts())
        recovered = qb.recover_stale()
        Worker(qb, devices=2, owner="poolB-w0",
               light_threads=0).drain()
    finally:
        os.environ.pop("TPUVSR_HOST", None)
    jd = qb.get(doomed.job_id)
    evs = read_journal(qb.journal_path(doomed.job_id))
    req = [e for e in evs if e["event"] == "job_requeued"]
    # the undisturbed oracle: the same sharded job on a fresh spool
    qo = JobQueue(os.path.join(tmp, "oracle"))
    oj = qo.submit("<stub:oracle>", engine="sharded", devices=2,
                   flags=dict(flags))
    Worker(qo, devices=2, light_threads=0).drain()
    oracle = qo.get(oj.job_id)
    live = (qb.spool_status()["replicas"] or {}).get("live")
    ok = (killed and blob_held and dead == ["hostA"]
          and live == 2
          and doomed.job_id in recovered
          and jd.state == "violated" and jd.attempts == 2
          and len(req) == 1 and req[0].get("dead_host") == "hostA"
          and (req[0].get("rescue") or {}).get("depth", 0) >= 1
          and oracle.state == "violated"
          and jd.result["violated"] == oracle.result["violated"]
          and jd.result["trace"] == oracle.result["trace"]
          and jd.result["distinct"] == oracle.result["distinct"])
    return {
        "ok": ok, "killed_rc": p.returncode, "blob_held": blob_held,
        "replicas_live": live,
        "dead_hosts": dead, "state": jd.state,
        "attempts": jd.attempts,
        "dead_host_in_requeue": req[0].get("dead_host") if req
        else None,
        "rescue_depth": (req[0].get("rescue") or {}).get("depth")
        if req else None,
        "trace_identical": (jd.result or {}).get("trace")
        == (oracle.result or {}).get("trace"),
    }


def scenario_spool_replica_loss(tmp):
    """ISSUE 20: one replica of the quorum spool is DELETED mid-drain.
    The service is unaffected (appends still reach write quorum, jobs
    keep completing with the exact fixpoint), the loss is journaled as
    ``replica_lost`` in the spool's own journal, and recreating the
    replica directory lets anti-entropy heal it back — journaled
    ``replica_rejoin``, replica log byte-identical to a surviving
    one's."""
    ORACLE = _oracle()
    from tpuvsr.service.queue import JobQueue
    from tpuvsr.service.worker import Worker
    spool = os.path.join(tmp, "spool")
    q = JobQueue(spool, driver="quorum")
    j1 = q.submit("<stub:1>", engine="device", flags={"stub": True})
    j2 = q.submit("<stub:2>", engine="device", flags={"stub": True})
    Worker(q, devices=1, light_threads=0).drain(max_jobs=1)
    r1 = os.path.join(spool, "replicas", "r1")
    shutil.rmtree(r1)                  # mid-drain: one replica dies
    # the ordinary drain loop keeps going — recover_stale inside it
    # runs the driver's housekeeping, which detects the loss
    Worker(q, devices=1, light_threads=0).drain()
    st_lost = q.spool_status()["replicas"]
    j3 = q.submit("<stub:3>", engine="device", flags={"stub": True})
    Worker(q, devices=1, light_threads=0).drain()
    jobs = [q.get(j.job_id) for j in (j1, j2, j3)]
    # rejoin: the operator recreates the directory; the next sweep's
    # anti-entropy copies the missing frames back, prefix-preserving
    os.makedirs(r1)
    q.recover_stale()
    st_back = q.spool_status()["replicas"]
    with open(os.path.join(spool, "replicas", "r0",
                           "jobs.jsonl"), "rb") as f:
        b0 = f.read()
    with open(os.path.join(r1, "jobs.jsonl"), "rb") as f:
        b1 = f.read()
    ev = [e["event"] for e in _spool_events(spool)]
    ok = (st_lost and st_lost["live"] == 2 and st_lost["total"] == 3
          and all(j.state == "done"
                  and j.result["distinct"] == ORACLE["distinct"]
                  and j.result["levels"] == ORACLE["levels"]
                  for j in jobs)
          and st_back and st_back["live"] == 3
          and b0 == b1 and len(b0) > 0
          and "replica_lost" in ev and "replica_rejoin" in ev)
    return {
        "ok": ok, "replicas_after_loss": st_lost,
        "replicas_after_rejoin": st_back,
        "jobs_done_through_loss": [j.state for j in jobs],
        "replica_log_byte_identical": b0 == b1,
        "spool_events": [e for e in ev
                         if e in ("replica_lost", "replica_rejoin")],
    }


def scenario_zombie_fence(tmp):
    """ISSUE 20: a worker that was recovered (its claim swept, the
    job re-run by a successor) REVIVES and tries to commit its stale
    outcome.  Claim-epoch fencing rejects the zombie's terminal
    append — FencedError, a ``fence`` event in the spool journal —
    so the successor's verdict stands untouched: exactly-once."""
    ORACLE = _oracle()
    import time
    from tpuvsr.service.queue import FencedError, JobQueue
    from tpuvsr.service.worker import Worker
    spool = os.path.join(tmp, "spool")
    q1 = JobQueue(spool, driver="objstore", heartbeat_timeout=0.2)
    job = q1.submit("<stub>", engine="device", flags={"stub": True})
    q1.transition(job.job_id, "admitted")
    # the zombie claims from a "remote" host (a same-host claim would
    # be judged by its live pid, not by heartbeat staleness)...
    os.environ["TPUVSR_HOST"] = "hostZ"
    try:
        claimed = q1.claim(job.job_id, owner="wZ") is not None
    finally:
        os.environ.pop("TPUVSR_HOST", None)
    time.sleep(0.3)                    # ...then stalls: no heartbeat
    q2 = JobQueue(spool, heartbeat_timeout=0.2)
    recovered = q2.recover_stale()
    # the zombie revives mid-successor-run — the exact danger window
    # (running -> failed is a LEGAL transition; only the epoch fence
    # can tell the stale holder from the live one)
    state = {"fenced": None}

    def on_level(worker, jb, depth):
        if state["fenced"] is None and depth >= 1:
            try:
                q1.finish(job.job_id, "failed",
                          reason="zombie-says-so")
                state["fenced"] = False
            except FencedError:
                state["fenced"] = True

    Worker(q2, devices=1, owner="wB", on_level=on_level,
           light_threads=0).drain()
    done = q2.get(job.job_id)
    fenced = state["fenced"] is True
    q2.refresh()
    final = q2.get(job.job_id)
    fences = [e for e in _spool_events(spool)
              if e["event"] == "fence"]
    ok = (claimed and job.job_id in recovered and fenced
          and done.state == "done" and done.attempts == 2
          and final.state == "done"
          and final.result["distinct"] == ORACLE["distinct"]
          and final.result["levels"] == ORACLE["levels"]
          and len(fences) >= 1
          and fences[0]["job_id"] == job.job_id)
    return {
        "ok": ok, "zombie_claimed": claimed,
        "recovered": recovered, "zombie_fenced": fenced,
        "final_state": final.state, "attempts": final.attempts,
        "fence_events": [(e["job_id"], e["epoch"]) for e in fences],
    }


SCENARIOS = [
    ("oom-degrade", scenario_oom_degrade),
    ("oom-paged-fallback", scenario_oom_paged_fallback),
    ("kill-rescue", scenario_kill_rescue),
    ("pack-kill-rescue", scenario_pack_kill_rescue),
    ("kill-fused-commit-resume", scenario_kill_fused_commit_resume),
    ("kill-canon-resume", scenario_kill_canon_resume),
    ("kill-spill-resume", scenario_kill_spill_resume),
    ("kill-bounds-resume", scenario_kill_bounds_resume),
    ("kill-por-resume", scenario_kill_por_resume),
    ("corrupt-ckpt", scenario_corrupt_ckpt),
    ("garble-ckpt", scenario_garble_ckpt),
    ("exchange-drop", scenario_exchange_drop),
    ("exchange-drop-retry", scenario_exchange_drop_retry),
    ("oom-mesh-degrade", scenario_oom_mesh_degrade),
    ("kill-elastic-resume", scenario_kill_elastic_resume),
    ("pipeline-faults", scenario_pipeline_faults),
    ("service-preempt-requeue", scenario_service_preempt_requeue),
    ("service-oom-degrade", scenario_service_oom_degrade),
    ("kill-one-of-n-workers", scenario_kill_one_of_n_workers),
    ("kill-aggregator-mid-tail", scenario_kill_aggregator_mid_tail),
    ("kill-worker-mid-event", scenario_kill_worker_mid_event),
    ("sim-oom-shrink", scenario_sim_oom_shrink),
    ("kill-hunt-resume", scenario_kill_hunt_resume),
    ("kill-validate-resume", scenario_kill_validate_resume),
    ("kill-liveness-resume", scenario_kill_liveness_resume),
    ("flood-rate-limit", scenario_flood_rate_limit),
    ("breaker-crash-loop", scenario_breaker_crash_loop),
    ("slow-loris-reap", scenario_slow_loris_reap),
    ("host-death-failover", scenario_host_death_failover),
    ("spool-replica-loss", scenario_spool_replica_loss),
    ("zombie-fence", scenario_zombie_fence),
]


def main(argv=None):
    only = (argv or [None])[0] if argv else None
    out = {}
    tmp = tempfile.mkdtemp(prefix="tpuvsr-fault-matrix-")
    try:
        for name, fn in SCENARIOS:
            if only and only not in name:
                continue
            sdir = os.path.join(tmp, name)
            os.makedirs(sdir, exist_ok=True)
            try:
                out[name] = fn(sdir)
            except Exception as e:  # noqa: BLE001 — report, don't die
                out[name] = {"ok": False,
                             "error": f"{type(e).__name__}: {e}"}
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    ok = all(v.get("ok") for v in out.values()) and bool(out)
    print(json.dumps({"ok": ok, "scenarios": out}, indent=1,
                     default=str))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
