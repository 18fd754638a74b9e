"""Dispatch-service tests (ISSUE 6): queue durability, speclint
admission, elastic scheduling, outcome mapping, CLI round-trip.

Everything runs tier-1 on the stub harness (``tpuvsr/testing.py``) —
the REAL device/paged/sharded engine loops on the inline counter
spec, no reference mount, virtual 8-device CPU mesh (conftest).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from tpuvsr.exitcodes import (EX_OK, EX_RESUMABLE, EX_VIOLATION,
                              JOB_STATE, job_state)
from tpuvsr.obs import read_journal
from tpuvsr.service import (CLAIMABLE, TERMINAL, DevicePool, JobQueue,
                            QueueError, Scheduler, Worker, pow2_floor)
from tpuvsr.testing import STUB_DISTINCT, STUB_LEVELS

ORACLE_DISTINCT = STUB_DISTINCT
ORACLE_LEVELS = STUB_LEVELS


def _events(q, job_id):
    return [e["event"] for e in read_journal(q.journal_path(job_id))]


# ---------------------------------------------------------------------
# queue mechanics (no engines)
# ---------------------------------------------------------------------
def test_queue_state_machine_and_durability(tmp_path):
    q = JobQueue(str(tmp_path / "spool"))
    j = q.submit("X.tla", engine="device", priority=3, devices=2)
    assert j.state == "queued"
    with pytest.raises(QueueError):
        q.transition(j.job_id, "running")     # queued -> running illegal
    q.transition(j.job_id, "admitted")
    assert q.claim(j.job_id) is not None
    assert q.get(j.job_id).state == "running"
    assert q.get(j.job_id).attempts == 1
    # claiming a non-claimable job is a LOST RACE, not an error (two
    # workers over one spool race routinely)
    assert q.claim(j.job_id) is None
    q.requeue(j.job_id, reason="test", rescue={"path": "p", "depth": 2,
                                               "distinct": 6},
              devices=1)
    job = q.get(j.job_id)
    assert job.state == "preempted-requeued" and job.devices == 1
    assert job.rescue["depth"] == 2

    # a fresh JobQueue over the same spool folds to the same state
    q2 = JobQueue(str(tmp_path / "spool"))
    j2 = q2.get(j.job_id)
    assert (j2.state, j2.devices, j2.attempts, j2.rescue) == \
        ("preempted-requeued", 1, 1, job.rescue)
    assert q2.claim_next() is not None        # requeued jobs reclaim


def test_queue_claim_priority_order_and_atomicity(tmp_path):
    q = JobQueue(str(tmp_path / "spool"))
    lo = q.submit("lo.tla", priority=0)
    hi = q.submit("hi.tla", priority=9)
    for j in (lo, hi):
        q.transition(j.job_id, "admitted")
    assert q.claim_next().job_id == hi.job_id
    # the claim FILE is the arbiter: a second queue view over the same
    # spool cannot double-claim
    q2 = JobQueue(str(tmp_path / "spool"))
    assert q2.claim(lo.job_id) is not None
    assert q.claim_next() is None


def test_concurrent_claims_exactly_once(tmp_path):
    """ISSUE 14 satellite: 3+ worker PROCESSES race ``claim_next``
    over one spool (the multiprocessing harness in
    ``tpuvsr/testing.py``); every job must be claimed exactly once —
    the union of the racers' hauls covers the queue and their hauls
    are disjoint (the O_CREAT|O_EXCL claim files arbitrate)."""
    from tpuvsr.testing import claim_race
    spool = str(tmp_path / "spool")
    q = JobQueue(spool)
    submitted = []
    for i in range(36):
        j = q.submit(f"job-{i:03d}.tla", tenant=f"t{i % 4}",
                     priority=i % 3)
        q.transition(j.job_id, "admitted")
        submitted.append(j.job_id)
    hauls = claim_race(spool, workers=3)
    assert len(hauls) == 3
    all_claimed = [jid for got in hauls.values() for jid in got]
    assert sorted(all_claimed) == sorted(submitted)      # no dupes,
    assert len(set(all_claimed)) == len(submitted)       # no losses
    q.refresh()
    assert all(j.state == "done" for j in q.jobs())
    # the race was real: no racer swept the whole queue alone
    assert max(len(got) for got in hauls.values()) < len(submitted)


def test_tenant_field_durable_across_fold(tmp_path):
    """The tenant rides the durable job record: a fresh JobQueue over
    the same spool folds it back, and legacy records without one load
    as the anonymous tenant."""
    spool = str(tmp_path / "spool")
    q = JobQueue(spool)
    j = q.submit("X.tla", tenant="acme")
    assert JobQueue(spool).get(j.job_id).tenant == "acme"
    # a legacy submit record (pre-ISSUE 14: no tenant key) still folds
    legacy = q.get(j.job_id).to_dict()
    legacy.pop("tenant")
    legacy.update(job_id="legacy-1", seq=99)
    with open(q.log_path, "a") as f:
        f.write(json.dumps({"op": "submit", "job": legacy}) + "\n")
    assert JobQueue(spool).get("legacy-1").tenant is None


def test_queue_cross_process_refresh(tmp_path):
    """A long-running worker's queue view picks up jobs submitted by
    ANOTHER JobQueue instance over the same spool (the live-serve
    contract)."""
    spool = str(tmp_path / "spool")
    q1 = JobQueue(spool)
    q2 = JobQueue(spool)
    j = q2.submit("other.tla")
    assert q1.claim_next() is None            # not admitted yet
    assert q1.get(j.job_id).state == "queued"  # but visible


def test_torn_spool_tail_does_not_eat_next_record(tmp_path):
    """A writer killed mid-append leaves a newline-less fragment; the
    next append must not merge with it (which would silently drop the
    new record from every future fold)."""
    spool = str(tmp_path / "spool")
    q = JobQueue(spool)
    j = q.submit("X.tla")
    with open(q.log_path, "a") as f:
        f.write('{"op": "state", "job_id": "torn')     # no newline
    q2 = JobQueue(spool)
    q2.transition(j.job_id, "admitted")
    assert JobQueue(spool).get(j.job_id).state == "admitted"


def test_malformed_job_flags_fail_the_job_not_the_worker(tmp_path):
    q = JobQueue(str(tmp_path / "spool"))
    bad_sup = q.submit("<stub>", flags={"stub": True,
                                        "supervisor": {"bogus": 1}})
    bad_inj = q.submit("<stub>", flags={"stub": True,
                                        "inject": "not-a-fault"})
    ok = q.submit("<stub>", flags={"stub": True})
    w = Worker(q, devices=1)
    w.drain()                                  # must not raise
    assert q.get(bad_sup.job_id).state == "failed"
    assert "job-setup" in q.get(bad_sup.job_id).reason
    assert q.get(bad_inj.job_id).state == "failed"
    assert q.get(ok.job_id).state == "done"    # the worker lived on


def test_orphan_claim_of_never_started_job_is_cleared(tmp_path):
    """A worker killed between claim-file creation and the `running`
    transition must not wedge the job: recover_stale clears the
    dead-pid claim and the job stays claimable."""
    q = JobQueue(str(tmp_path / "spool"))
    j = q.submit("X.tla")
    q.transition(j.job_id, "admitted")
    with open(os.path.join(q.claims_dir, f"{j.job_id}.claim"),
              "w") as f:
        json.dump({"pid": _dead_pid(), "owner": "gone"}, f)
    assert q.claim(j.job_id) is None          # wedged without recovery
    q.recover_stale()
    assert q.get(j.job_id).state == "admitted"
    assert q.claim_next().job_id == j.job_id


def test_exit_code_table_is_the_single_contract():
    from tpuvsr.resilience.supervisor import EXIT_RESUMABLE
    assert EXIT_RESUMABLE == EX_RESUMABLE == 75
    assert job_state(EX_OK) == "done"
    assert job_state(EX_VIOLATION) == "violated"
    assert job_state(EX_RESUMABLE) == "preempted-requeued"
    assert job_state(137) == "failed"          # unknown code: failed
    # terminal states of the service ARE the table's image (+cancelled)
    assert set(JOB_STATE.values()) - {"preempted-requeued"} \
        <= TERMINAL


# ---------------------------------------------------------------------
# run_supervised library mode (ISSUE 6 satellite)
# ---------------------------------------------------------------------
def test_run_supervised_returns_outcome_not_exit(tmp_path):
    from tpuvsr.resilience import faults
    from tpuvsr.resilience.supervisor import run_supervised
    from tpuvsr.testing import counter_spec, stub_service_factory
    spec = counter_spec()
    ck = str(tmp_path / "ck")
    faults.install("kill@level=3")
    try:
        out = run_supervised(spec, engine="device",
                             checkpoint_path=ck,
                             engine_factory=stub_service_factory(spec),
                             backoff_base=0.0)
    finally:
        faults.clear()
    assert out.state == "preempted-requeued" and out.resumable
    assert out.exit_code == EX_RESUMABLE
    assert out.rescue["path"] == ck and out.rescue["depth"] == 3
    # the same process hosts the next run: resume to the fixpoint
    out2 = run_supervised(spec, engine="device", checkpoint_path=ck,
                          engine_factory=stub_service_factory(spec),
                          backoff_base=0.0,
                          run_kwargs={"resume_from": ck})
    assert out2.state == "done" and out2.exit_code == EX_OK
    assert out2.result.distinct_states == ORACLE_DISTINCT
    assert out2.result.levels == ORACLE_LEVELS


def test_run_supervised_violation_outcome():
    from tpuvsr.resilience.supervisor import run_supervised
    from tpuvsr.testing import counter_spec, stub_service_factory
    spec = counter_spec(inv_bound=2)
    out = run_supervised(
        spec, engine="device",
        engine_factory=stub_service_factory(spec, inv_bound=2),
        backoff_base=0.0)
    assert out.state == "violated" and out.exit_code == EX_VIOLATION
    assert out.result.violated_invariant == "Bound"
    assert out.result.trace


# ---------------------------------------------------------------------
# worker end-to-end: durability across a killed worker
# ---------------------------------------------------------------------
def test_killed_worker_job_requeued_and_bit_identical(tmp_path):
    """ISSUE 6 acceptance: a worker dies mid-job (dead-pid claim file
    left behind, checkpoint on disk).  recover_stale requeues the job
    WITH the rescue handoff, and the resumed run's violation trace is
    bit-identical to an uninterrupted oracle (the unique-witness
    invariant, PR 4/5 equivalence pattern)."""
    from tpuvsr.engine.device_bfs import DeviceBFS
    from tpuvsr.service.worker import result_summary
    from tpuvsr.testing import counter_spec, stub_model_factory
    spool = str(tmp_path / "spool")
    q = JobQueue(spool)
    j = q.submit("<stub>", engine="device",
                 flags={"stub": True, "inv_x_bound": 2})
    q.transition(j.job_id, "admitted")

    # simulate the killed worker: run the engine HALFWAY (depth limit),
    # leaving its checkpoint in the job's ckpt dir, with a claim file
    # whose pid is dead
    eng = DeviceBFS(counter_spec(inv_x_bound=2),
                    model_factory=stub_model_factory(inv_x_bound=2),
                    hash_mode="full", tile_size=4,
                    fpset_capacity=1 << 8, next_capacity=1 << 6)
    half = eng.run(max_depth=2, checkpoint_path=q.checkpoint_path(j.job_id))
    assert half.ok and half.error          # depth-bounded, no violation yet
    q.transition(j.job_id, "running", attempts=1)
    with open(os.path.join(q.claims_dir, f"{j.job_id}.claim"),
              "w") as f:
        json.dump({"pid": _dead_pid(), "owner": "gone"}, f)

    recovered = q.recover_stale()
    assert recovered == [j.job_id]
    job = q.get(j.job_id)
    assert job.state == "preempted-requeued"
    assert job.rescue and job.rescue["depth"] == 2

    # drain: the job resumes from the rescue and reports the violation
    Worker(q, devices=1).drain()
    job = q.get(j.job_id)
    assert job.state == "violated"

    # uninterrupted oracle, serialized identically
    oracle = result_summary(
        DeviceBFS(counter_spec(inv_x_bound=2),
                  model_factory=stub_model_factory(inv_x_bound=2),
                  hash_mode="full", tile_size=4,
                  fpset_capacity=1 << 8, next_capacity=1 << 6).run())
    assert job.result["violated"] == oracle["violated"] == "Bound"
    assert job.result["trace"] == oracle["trace"]
    assert job.result["distinct"] == oracle["distinct"]
    ev = _events(q, j.job_id)
    assert "job_done" in ev and "run_start" in ev


def _dead_pid():
    """A pid guaranteed dead: spawn-and-reap a child."""
    p = subprocess.Popen([sys.executable, "-c", "pass"])
    p.wait()
    return p.pid


def test_speclint_rejected_job_never_reaches_running(tmp_path):
    q = JobQueue(str(tmp_path / "spool"))
    j = q.submit("<bad>", engine="device",
                 flags={"stub": True, "stub_bad": True})
    Worker(q, devices=1).drain()
    job = q.get(j.job_id)
    assert job.state == "failed" and job.reason == "speclint"
    assert job.attempts == 0
    assert any("frames" in f for f in job.result["speclint"])
    ev = _events(q, j.job_id)
    assert "job_started" not in ev and "run_start" not in ev
    # the spool log never shows a running transition either
    recs = [json.loads(line) for line in open(q.log_path)]
    assert all(r.get("state") != "running" for r in recs)


def test_preempt_requeue_under_dispatcher(tmp_path):
    """kill@level=3 inside the worker: exit-75 contract -> requeue
    with rescue, same drain resumes to the exact fixpoint."""
    q = JobQueue(str(tmp_path / "spool"))
    j = q.submit("<stub>", engine="device",
                 flags={"stub": True, "inject": "kill@level=3"})
    Worker(q, devices=1).drain()
    job = q.get(j.job_id)
    assert job.state == "done" and job.attempts == 2
    assert job.result["distinct"] == ORACLE_DISTINCT
    assert job.result["levels"] == ORACLE_LEVELS
    evs = read_journal(q.journal_path(j.job_id))
    kinds = [e["event"] for e in evs]
    assert "job_requeued" in kinds and "rescue_checkpoint" in kinds
    req = next(e for e in evs if e["event"] == "job_requeued")
    assert req["rescue"]["depth"] == 3
    starts = [e for e in evs if e["event"] == "job_started"]
    assert [s["attempt"] for s in starts] == [1, 2]


# ---------------------------------------------------------------------
# scheduler: elastic shrink-then-grow of a live sharded job
# ---------------------------------------------------------------------
@pytest.mark.skipif(len(__import__("jax").devices()) < 8,
                    reason="needs 8 virtual devices")
def test_scheduler_shrink_then_grow_live_sharded_job(tmp_path):
    """ISSUE 6 acceptance: a live sharded job on the 4-2-8 stub
    meshes.  A higher-priority arrival mid-run shrinks it (preempt +
    elastic resume on 2 devices); once the pool frees up the
    scheduler grows it back (elastic resume on 8); the final fixpoint
    is exact and both reshards are journaled."""
    q = JobQueue(str(tmp_path / "spool"))
    a = q.submit("<stub:A>", engine="sharded", devices=4,
                 devices_min=2, devices_max=8, flags={"stub": True})
    state = {"submitted": False}

    def on_level(worker, job, depth):
        if job.job_id == a.job_id and depth >= 2 \
                and not state["submitted"]:
            state["submitted"] = True
            q.submit("<stub:B>", engine="device", priority=10,
                     devices=6, flags={"stub": True})

    Worker(q, devices=8, on_level=on_level).drain()
    job = q.get(a.job_id)
    assert job.state == "done"
    assert job.result["distinct"] == ORACLE_DISTINCT
    assert job.result["levels"] == ORACLE_LEVELS
    evs = read_journal(q.journal_path(a.job_id))
    meshes = [e["devices"] for e in evs if e["event"] == "job_started"]
    reshards = [(e["from_shards"], e["to_shards"])
                for e in evs if e["event"] == "reshard"]
    assert meshes == [4, 2, 8]
    assert reshards == [(4, 2), (2, 8)]
    # the high-priority job ran to completion in between
    b = [x for x in q.jobs() if x.job_id != a.job_id][0]
    assert b.state == "done" and b.result["distinct"] == ORACLE_DISTINCT


def test_scheduler_units():
    pool = DevicePool(8)
    s = Scheduler(pool)
    assert pow2_floor(7) == 4 and pow2_floor(8) == 8 \
        and pow2_floor(1) == 1
    plan = s.plan([])
    assert plan == {"placed": [], "waiting": [], "free": 8}
    pool.alloc("a", 4)
    assert pool.free == 4
    pool.release("a")
    assert pool.free == 8


def test_grow_without_devices_max_uses_original_request():
    """The grow ceiling falls back to the preserved original request
    (flags.devices_requested), not job.devices — which the scheduler
    itself rewrote on the shrink."""
    from tpuvsr.service import Job
    pool = DevicePool(8)
    s = Scheduler(pool)
    job = Job(job_id="a", spec="s", engine="sharded", devices=2,
              devices_min=2, devices_max=None, state="running",
              flags={"devices_requested": 4})
    pool.alloc("a", 2)
    dec = s.rebalance(job, [job])
    assert dec is not None and dec.action == "grow" \
        and dec.devices == 4


def test_bench_throughputs_reads_repo_bench_wrapper(tmp_path):
    """The repo's BENCH_r*.json wrap the RESULT line under `parsed`
    ({n, cmd, rc, tail, parsed}); the advisory must unwrap it."""
    from tpuvsr.service.scheduler import bench_throughputs
    (tmp_path / "BENCH_r01.json").write_text(json.dumps(
        {"n": 1, "cmd": "bench", "rc": 0, "tail": "",
         "parsed": {"backend": "cpu", "value": 1200.0}}))
    (tmp_path / "BENCH_r02.json").write_text(json.dumps(
        {"n": 2, "cmd": "bench", "rc": 0, "tail": "",
         "parsed": {"backend": "tpu (v5e)", "value": 9000.0}}))
    tps = bench_throughputs(str(tmp_path))
    assert tps == {"cpu": 1200.0, "tpu": 9000.0}
    # a directory without bench documents advises nothing
    assert bench_throughputs(str(tmp_path / "empty")) == {}


def test_detect_tpu_devices(monkeypatch):
    """The env override is the only signal: no flag file, no probe."""
    from tpuvsr.service import detect_tpu_devices
    monkeypatch.delenv("TPUVSR_TPU_DEVICES", raising=False)
    assert detect_tpu_devices() == 0
    monkeypatch.setenv("TPUVSR_TPU_DEVICES", "8")
    assert detect_tpu_devices() == 8
    monkeypatch.setenv("TPUVSR_TPU_DEVICES", "-3")
    assert detect_tpu_devices() == 0
    monkeypatch.setenv("TPUVSR_TPU_DEVICES", "many")
    assert detect_tpu_devices() == 0


def test_advise_backend_cpu_fallbacks(tmp_path):
    from tpuvsr.service import Job, advise_backend
    j = Job(job_id="x", spec="s", flags={})
    b, why = advise_backend(j, tpu_devices=0)
    assert b == "cpu" and "no tpu" in why
    j2 = Job(job_id="y", spec="s", flags={"maxstates": 100})
    b2, why2 = advise_backend(j2, tpu_devices=4,
                              bench_dir=str(tmp_path))
    assert b2 == "cpu" and "compile-dominated" in why2
    # with a tpu bench doc beating the cpu one, tpu wins
    (tmp_path / "BENCH_r01.json").write_text(json.dumps(
        {"backend": "cpu", "value": 900.0}))
    (tmp_path / "BENCH_r02.json").write_text(json.dumps(
        {"backend": "tpu (v5e)", "value": 9000.0}))
    j3 = Job(job_id="z", spec="s", flags={})
    b3, why3 = advise_backend(j3, tpu_devices=4,
                              bench_dir=str(tmp_path))
    assert b3 == "tpu" and "advisory" in why3


# ---------------------------------------------------------------------
# cancel: queued and live
# ---------------------------------------------------------------------
def test_cancel_running_job_rescues_at_level_boundary(tmp_path):
    q = JobQueue(str(tmp_path / "spool"))
    j = q.submit("<stub>", engine="device", flags={"stub": True})

    def on_level(worker, job, depth):
        if depth == 2:
            q.cancel(job.job_id)

    Worker(q, devices=1, on_level=on_level).drain()
    job = q.get(j.job_id)
    assert job.state == "cancelled"
    assert job.result["rescue"]["depth"] >= 2   # progress preserved


def test_operator_sigterm_requeues_and_stops_drain(tmp_path):
    """A REAL SIGTERM to the serve process (not a scheduler tick, not
    an injected drill) must requeue the running job AND stop the drain
    loop — otherwise `serve` re-claims the job instantly and can never
    be stopped gracefully.  A later drain resumes and completes."""
    import signal as _signal
    q = JobQueue(str(tmp_path / "spool"))
    j = q.submit("<stub>", engine="device", flags={"stub": True})

    def on_level(worker, job, depth):
        if depth == 2 and job.attempts == 1:
            os.kill(os.getpid(), _signal.SIGTERM)

    w = Worker(q, devices=1, on_level=on_level)
    runs = w.drain()
    assert w._shutdown and runs == 1
    assert q.get(j.job_id).state == "preempted-requeued"
    assert q.get(j.job_id).rescue["depth"] >= 2
    # the next serve resumes it to the exact fixpoint
    Worker(q, devices=1).drain()
    job = q.get(j.job_id)
    assert job.state == "done"
    assert job.result["distinct"] == ORACLE_DISTINCT


def test_shell_exit75_requeue_is_bounded(tmp_path):
    """A shell child that always exits 75 must not hot-loop: the
    requeue respects the attempt budget, then the job fails."""
    q = JobQueue(str(tmp_path / "spool"))
    j = q.submit("always-75", kind="shell",
                 flags={"argv": [sys.executable, "-c",
                                 "import sys; sys.exit(75)"],
                        "timeout": 30, "max_attempts": 2})
    Worker(q, devices=1).drain()
    job = q.get(j.job_id)
    assert job.state == "failed" and job.attempts == 2
    assert "exit-75" in job.reason and "exhausted" in job.reason


def test_cancel_running_shell_job_kills_subprocess(tmp_path):
    """cancel of a live kind=shell job lands mid-run: the worker's
    poll slice sees the marker (written by a SECOND queue view, the
    cross-process path), SIGTERMs the process group, and the job ends
    cancelled instead of running out its full timeout."""
    import threading
    import time as _time
    spool = str(tmp_path / "spool")
    q = JobQueue(spool)
    j = q.submit("sleeper", kind="shell",
                 flags={"argv": [sys.executable, "-c",
                                 "import time; time.sleep(120)"],
                        "timeout": 120})
    w = Worker(q, devices=1)
    t = threading.Thread(target=w.drain)
    t.start()
    view = JobQueue(spool)
    try:
        for _ in range(400):
            view.refresh()
            if view.get(j.job_id).state == "running":
                break
            _time.sleep(0.05)
        assert view.get(j.job_id).state == "running"
        view.cancel(j.job_id)
    finally:
        t.join(60)
    assert not t.is_alive()
    q.refresh()
    assert q.get(j.job_id).state == "cancelled"


# ---------------------------------------------------------------------
# CLI round-trip: submit / status / cancel / serve
# ---------------------------------------------------------------------
def test_cli_submit_status_cancel_round_trip(tmp_path, capsys):
    from tpuvsr.service.api import main as api_main
    spool = str(tmp_path / "spool")
    assert api_main(["submit", "--stub", "--priority", "5",
                     "--spool", spool, "--json"]) == 0
    job = json.loads(capsys.readouterr().out.strip())
    assert job["state"] == "queued" and job["priority"] == 5
    assert job["flags"]["stub"] is True

    assert api_main(["status", "--spool", spool, "--json"]) == 0
    st = json.loads(capsys.readouterr().out.strip())
    assert st["stats"]["queued"] == 1 and len(st["jobs"]) == 1

    assert api_main(["cancel", job["job_id"], "--spool", spool,
                     "--json"]) == 0
    out = json.loads(capsys.readouterr().out.strip())
    assert out["state"] == "cancelled"

    assert api_main(["status", job["job_id"], "--spool", spool,
                     "--json", "--tail", "5"]) == 0
    doc = json.loads(capsys.readouterr().out.strip())
    assert doc["state"] == "cancelled"
    assert [e["event"] for e in doc["journal_tail"]] == \
        ["job_submitted"]
    # unknown job: usage error, not a traceback
    assert api_main(["status", "nope", "--spool", spool]) == 2
    # malformed --flag: same usage-error code, no traceback
    assert api_main(["submit", "--stub", "--flag", "nope",
                     "--spool", spool]) == 2


def test_cli_serve_drains_stub_job(tmp_path, capsys):
    from tpuvsr.service.api import main as api_main
    spool = str(tmp_path / "spool")
    api_main(["submit", "--stub", "--spool", spool])
    capsys.readouterr()
    assert api_main(["serve", "--drain", "--devices", "1",
                     "--spool", spool, "--quiet"]) == 0
    out = json.loads(capsys.readouterr().out.strip())
    assert out["runs"] == 1 and out["stats"]["done"] == 1
    q = JobQueue(spool)
    job = q.jobs()[0]
    assert job.result["distinct"] == ORACLE_DISTINCT


def test_cli_verb_dispatch_subprocess(tmp_path):
    """`python -m tpuvsr submit/status` routes to the service before
    the TLC parser (and stays fast: no jax import)."""
    spool = str(tmp_path / "spool")
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
           "PYTHONPATH": "/root/repo", "HOME": "/root"}
    r = subprocess.run(
        [sys.executable, "-m", "tpuvsr", "submit", "--stub",
         "--spool", spool, "--json"],
        capture_output=True, text=True, timeout=120, env=env)
    assert r.returncode == 0, r.stderr
    job = json.loads(r.stdout.strip())
    r2 = subprocess.run(
        [sys.executable, "-m", "tpuvsr", "status", job["job_id"],
         "--spool", spool, "--json"],
        capture_output=True, text=True, timeout=120, env=env)
    assert r2.returncode == 0, r2.stderr
    assert json.loads(r2.stdout.strip())["state"] == "queued"


def test_serve_demo_smoke(capsys):
    """The full serving-tier drill under tier-1 (ISSUE 14 + 18
    acceptance): lifecycle, the 3-tenant/4-kind saturation queue over
    2 worker processes, the >= 1.6x 2-worker scaling gate, the
    multi-worker-vs-serial bit-identity oracle, and the abuse drill
    (401/413/429 at the hardened front door, legit verdicts
    exact)."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "scripts"))
    import serve_demo
    assert serve_demo.main() == 0
    out = json.loads(capsys.readouterr().out)
    assert out["ok"] and all(out["checks"].values())
    assert out["saturation"]["jobs"] > 150
    assert out["saturation"]["kinds"] == ["check", "shell", "sim",
                                          "validate"]
    assert out["scaling"]["ratio"] >= 1.6
    assert out["bit_identity"]["diffs"] == {}
    assert out["abuse"]["flood_429s"] >= 7
    assert out["abuse"]["legit_state"] == "done"


# ---------------------------------------------------------------------
# journal schema: the job_* events validate
# ---------------------------------------------------------------------
def test_job_journal_validates_and_interleaves(tmp_path):
    q = JobQueue(str(tmp_path / "spool"))
    j = q.submit("<stub>", engine="device", flags={"stub": True})
    Worker(q, devices=1).drain()
    evs = read_journal(q.journal_path(j.job_id))   # validates each line
    kinds = [e["event"] for e in evs]
    assert kinds[0] == "job_submitted"
    assert kinds[-1] == "job_done"
    # engine events interleave in the SAME file
    assert "run_start" in kinds and "level_done" in kinds
    done = evs[-1]
    assert done["state"] == "done" and done["job_id"] == j.job_id
    # metrics doc exists per job (the status query surface)
    assert os.path.exists(q.metrics_path(j.job_id))
    with open(q.metrics_path(j.job_id)) as f:
        assert json.load(f)["schema"] == "tpuvsr-metrics/1"


# ---------------------------------------------------------------------
# ISSUE 20: the same durability contract holds over the quorum driver,
# including with one replica directory destroyed mid-lifecycle
# ---------------------------------------------------------------------
def test_queue_durability_over_quorum_driver(tmp_path):
    import shutil

    spool = str(tmp_path / "spool")
    q = JobQueue(spool, driver="quorum")
    j = q.submit("X.tla", engine="device", priority=3)
    q.transition(j.job_id, "admitted")
    assert q.claim(j.job_id) is not None
    q.finish(j.job_id, "done", result={"distinct": 7, "ok": True})

    # losing a minority replica must not lose the fold
    shutil.rmtree(os.path.join(spool, "replicas", "r0"))
    q2 = JobQueue(spool)                      # auto-detects quorum
    j2 = q2.get(j.job_id)
    assert j2.state == "done" and j2.attempts == 1
    assert j2.result == {"distinct": 7, "ok": True}
    assert q2.spool_status()["driver"] == "quorum"


def test_a_finished_jobs_engine_is_freed_when_the_job_ends(
        tmp_path, capsys, monkeypatch):
    """The engine of a finished job sits in reference cycles and holds
    its device buffers and the level program's executable; `run_one`
    collects at the job's end, so the next job never loads its own
    beside them (ISSUE 43: a second served job's executable load took
    a second longer whenever the cyclic collector had not yet run a
    full pass).  With the collector off, only that call can free it."""
    import gc
    import weakref

    from tpuvsr.engine.device_bfs import DeviceBFS
    from tpuvsr.service.api import main as api_main
    born = []
    init = DeviceBFS.__init__

    def spy(self, *args, **kw):
        born.append(weakref.ref(self))
        init(self, *args, **kw)
    monkeypatch.setattr(DeviceBFS, "__init__", spy)
    spool = str(tmp_path / "spool")
    api_main(["submit", "--stub", "--spool", spool])
    gc.collect()
    gc.disable()
    try:
        assert api_main(["serve", "--drain", "--devices", "1",
                         "--spool", spool, "--quiet"]) == 0
        alive = [r for r in born if r() is not None]
    finally:
        gc.enable()
    capsys.readouterr()
    assert born and not alive
