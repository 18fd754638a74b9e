"""Batched trace validation tests (tpuvsr/validate, ISSUE 8).

Everything runs tier-1 on the stub harness (``tpuvsr/testing.py``) —
the REAL vmapped/shard_mapped validation chunk kernel, the
interpreter reference validator, the CLI ``-validate`` flag and the
``kind="validate"`` service path on the inline counter spec, virtual
8-device CPU mesh (conftest).

The load-bearing battery is the determinism contract restated from
the ISSUE 8 acceptance: a single-mutation trace batch reports the
SAME first divergence (trace id, event step, candidate count, spec-
side enabled set) bit-identically across mesh sizes 1/2/4, across
batch sizes, and across a SIGTERM/exit-75 rescue-resume seam; a
partial-observation trace (dropped variables, fully-blanked events)
stays accepted with the candidate set doing the nondeterminism
bookkeeping (arxiv 2404.16075).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

import jax

from tpuvsr.core.values import TLAError
from tpuvsr.obs import RunObserver, read_journal, validate_journal_line
from tpuvsr.resilience import faults
from tpuvsr.resilience.supervisor import Preempted, PreemptionGuard
from tpuvsr.service.queue import JobQueue
from tpuvsr.service.worker import Worker
from tpuvsr.testing import (COUNTER, COUNTER_CFG, counter_spec,
                            stub_trace_records, stub_validator)
from tpuvsr.validate import (load_traces, save_traces, validate_trace)
from tpuvsr.validate.host import host_validate_batch
from tpuvsr.validate.traces import (trace_from_record,
                                    traces_from_records)


def mk_traces(spec=None, **kw):
    spec = spec or counter_spec()
    return traces_from_records(stub_trace_records(spec=spec, **kw),
                               spec)


def div_sig(res):
    """Comparable identity of a divergence report list."""
    return json.dumps(res.divergences, sort_keys=True)


# ---------------------------------------------------------------------
# the TRACE.jsonl format
# ---------------------------------------------------------------------
def test_traces_roundtrip(tmp_path):
    spec = counter_spec()
    recs = stub_trace_records(n=4, depth=5, seed=0)
    path = str(tmp_path / "t.jsonl")
    save_traces(path, recs)
    traces = load_traces(path, spec)
    assert [t.tid for t in traces] == [r["trace"] for r in recs]
    assert [t.to_record() for t in traces] == recs
    # values round-trip through TLA+ expression strings: ints stay
    # ints after a save of the PARSED traces
    save_traces(path, traces)
    again = load_traces(path, spec)
    assert [t.to_record() for t in again] == recs


def test_trace_unknown_names_fail_loudly():
    spec = counter_spec()
    with pytest.raises(TLAError, match="unknown to the spec"):
        trace_from_record({"init": {"z": 0}, "events": []}, spec)
    with pytest.raises(TLAError, match="not a spec action"):
        trace_from_record(
            {"events": [{"action": "Nope", "vars": {"x": 1}}]}, spec)
    with pytest.raises(TLAError, match="unknown to the spec"):
        trace_from_record({"events": [{"vars": {"zz": 1}}]}, spec)


# ---------------------------------------------------------------------
# the interpreter reference validator
# ---------------------------------------------------------------------
def test_host_accepts_genuine_walks():
    spec = counter_spec()
    res = host_validate_batch(spec, mk_traces(n=16, depth=6, seed=0))
    assert res.ok and res.accepted == res.traces_checked == 16
    assert not res.divergences


def test_host_divergence_at_exact_mutated_step():
    spec = counter_spec()
    res = host_validate_batch(
        spec, mk_traces(n=8, depth=6, seed=1, mutate=(5, 3)))
    assert not res.ok and res.accepted == 7
    rec = res.first_divergence
    assert rec["trace"] == "t-0005" and rec["step"] == 3
    assert rec["candidates"] >= 1
    # the spec-side enabled set carries action + location metadata
    assert {e["action"] for e in rec["enabled"]} <= {"IncX", "IncY"}
    assert all(e["location"] for e in rec["enabled"])


def test_host_partial_observation_stays_accepted():
    """Dropping a variable from every observation and blanking every
    third event entirely leaves the trace under-determined but
    consistent — the candidate set grows past 1 and the batch still
    accepts (the paper's nondeterminism handling)."""
    spec = counter_spec()
    traces = mk_traces(n=8, depth=6, seed=2, drop_vars=("y",),
                       blank_every=3)
    res = host_validate_batch(spec, traces)
    assert res.ok and res.accepted == 8
    v = validate_trace(spec, traces[0])
    assert v.ok and v.max_candidates > 1


def test_host_no_init_state_is_a_step0_divergence():
    spec = counter_spec()
    traces = traces_from_records(
        [{"trace": "bad-init", "init": {"x": "5"}, "events": []}],
        spec)
    res = host_validate_batch(spec, traces)
    rec = res.first_divergence
    assert rec["trace"] == "bad-init" and rec["step"] == 0
    assert rec["reason"] == "no-init-state" and rec["enabled"] == []


def test_host_invariant_metadata_on_conforming_trace():
    """A trace the implementation really took can still walk into an
    invariant-violating region: conformance holds (accepted), but the
    verdict carries the certainly-bad-state metadata."""
    spec = counter_spec(inv_x_bound=2)
    rec = {"trace": "t-inv", "init": {"x": "0", "y": "0"},
           "events": [{"action": "IncX", "vars": {"x": str(i)}}
                      for i in (1, 2, 3)]}
    v = validate_trace(spec, trace_from_record(rec, spec))
    assert v.ok
    assert v.violated_invariant == "Bound" and v.violated_at == 2


def test_next_action_record_is_action_unobserved():
    """A recorded action naming the composite next-state relation
    ("Next") pins nothing: it normalizes to action-unobserved at load,
    so a genuine step stays accepted by BOTH validators instead of
    host-diverging / device-erroring on a lane-less name."""
    spec = counter_spec()
    recs = stub_trace_records(n=4, depth=6, seed=0)
    for r in recs:
        for ev in r["events"]:
            if "action" in ev:
                ev["action"] = "Next"
    traces = traces_from_records(recs, spec)
    assert all(e.action is None for t in traces for e in t.events)
    assert host_validate_batch(spec, traces).ok
    assert stub_validator(batch=4).run(traces).ok


def test_deadline_stop_is_incomplete_not_diverged():
    """A -maxseconds stop with zero divergences keeps ok=True with
    error="deadline" (the BFS time-budget contract): a timed-out
    clean batch must not exit 12 or settle a service job
    "violated"."""
    spec = counter_spec()
    traces = mk_traces(n=32, depth=6, seed=0)
    hres = host_validate_batch(spec, traces, max_seconds=1e-9)
    assert hres.error == "deadline" and hres.ok
    assert hres.traces_checked < 32
    bres = stub_validator(batch=8, chunk_steps=2).run(
        traces, max_seconds=1e-9)
    assert bres.error == "deadline" and bres.ok


def test_host_candidate_cap_is_a_policy_error():
    spec = counter_spec()
    # fully-unobserved events over the whole spec: the candidate set
    # is the reachable frontier, which exceeds a tiny cap
    traces = traces_from_records(
        [{"trace": "wide", "events": [{}, {}, {}]}], spec)
    with pytest.raises(TLAError, match="candidate set exceeds"):
        validate_trace(spec, traces[0], max_candidates=2)


# ---------------------------------------------------------------------
# the batch validator vs the interpreter oracle
# ---------------------------------------------------------------------
def test_batch_matches_host_oracle():
    spec = counter_spec()
    traces = mk_traces(n=48, depth=6, seed=3, mutate=(31, 4))
    hres = host_validate_batch(spec, traces)
    bres = stub_validator(batch=16, n_devices=2).run(traces)
    assert bres.traces_checked == hres.traces_checked == 48
    assert bres.accepted == hres.accepted == 47
    bd, hd = bres.first_divergence, hres.first_divergence
    assert (bd["trace"], bd["step"], bd["candidates"]) \
        == (hd["trace"], hd["step"], hd["candidates"]) \
        == ("t-0031", 4, 1)
    assert [e["action"] for e in bd["enabled"]] \
        == [e["action"] for e in hd["enabled"]]


def test_batch_partial_observation_stays_accepted():
    spec = counter_spec()
    traces = mk_traces(n=16, depth=6, seed=2, drop_vars=("y",),
                       blank_every=3)
    res = stub_validator(batch=16, n_devices=2).run(traces)
    assert res.ok and res.accepted == 16
    # blanked events really grow the device-side candidate sets: the
    # cap had to grow past the constructor's 1
    bv = stub_validator(batch=16, n_devices=2, cand_cap=1)
    r2 = bv.run(traces)
    assert r2.ok and bv.K > 1


def test_batch_cand_cap_growth_is_journaled(tmp_path):
    jp = str(tmp_path / "j.jsonl")
    spec = counter_spec()
    traces = mk_traces(n=8, depth=6, seed=2, blank_every=2)
    bv = stub_validator(batch=8, n_devices=1, cand_cap=1)
    res = bv.run(traces, obs=RunObserver(journal_path=jp))
    assert res.ok
    grows = [e for e in read_journal(jp) if e["event"] == "grow"
             and e["what"] == "cand_cap"]
    assert grows and grows[-1]["to"] == bv.K > 1


# ---------------------------------------------------------------------
# the determinism contract (ISSUE 8 acceptance, stub-spec form)
# ---------------------------------------------------------------------
def test_divergence_identical_across_mesh_sizes():
    spec = counter_spec()
    traces = mk_traces(n=64, depth=6, seed=1, mutate=(17, 2))
    sigs = {}
    for D in (1, 2, 4):
        res = stub_validator(batch=32, n_devices=D).run(traces)
        assert res.accepted == 63
        assert res.first_divergence["trace"] == "t-0017"
        assert res.first_divergence["step"] == 2
        sigs[D] = div_sig(res)
    assert sigs[1] == sigs[2] == sigs[4]


def test_divergence_identical_across_batch_sizes():
    spec = counter_spec()
    traces = mk_traces(n=64, depth=6, seed=1, mutate=(40, 5))
    sigs = {B: div_sig(stub_validator(batch=B, n_devices=2).run(traces))
            for B in (8, 32, 64)}
    assert sigs[8] == sigs[32] == sigs[64]


def test_rescue_resume_divergence_bit_identical(tmp_path):
    """SIGTERM mid-batch -> CRC'd candidate-frontier rescue at the
    committed chunk boundary -> the resumed run (same or DIFFERENT
    mesh size) reports the identical divergence list."""
    ck = str(tmp_path / "ck")
    jp = str(tmp_path / "j.jsonl")
    spec = counter_spec()
    traces = mk_traces(n=64, depth=6, seed=1, mutate=(49, 4))
    kw = dict(batch=16, chunk_steps=2)
    oracle = stub_validator(n_devices=2, **kw).run(traces)
    faults.install("kill@level=2")
    preempted = None
    try:
        with PreemptionGuard():
            try:
                stub_validator(n_devices=2, **kw).run(
                    traces, checkpoint_path=ck,
                    obs=RunObserver(journal_path=jp))
            except Preempted as p:
                preempted = p
    finally:
        faults.clear()
    assert preempted is not None and preempted.path == ck
    # the manifest is readable by the service's cheap rescue reader
    from tpuvsr.engine.checkpoint import snapshot_info
    info = snapshot_info(ck)
    assert info and info["depth"] == preempted.depth
    for D in (2, 4):
        res = stub_validator(n_devices=D, **kw).run(
            traces, resume_from=ck,
            obs=RunObserver(journal_path=jp) if D == 2 else None)
        assert div_sig(res) == div_sig(oracle)
        assert res.traces_checked == 64 and res.accepted == 63
    evs = [e["event"] for e in read_journal(jp)]
    assert "rescue_checkpoint" in evs and "fault" in evs
    assert "validate_chunk" in evs and "divergence" in evs


def test_resume_on_non_dividing_mesh_repads(tmp_path):
    """A rescue written on one mesh resumes on a device count that
    does NOT divide the batch: the committed candidate frontier is
    re-padded to the new mesh's T_pad (added/dropped rows are always
    dead pad slots) and the report stays bit-identical."""
    ck = str(tmp_path / "ck")
    spec = counter_spec()
    traces = mk_traces(n=32, depth=6, seed=1, mutate=(20, 3))
    kw = dict(batch=16, chunk_steps=2)
    oracle = stub_validator(n_devices=2, **kw).run(traces)
    faults.install("kill@level=1")
    try:
        with PreemptionGuard():
            with pytest.raises(Preempted):
                stub_validator(n_devices=2, **kw).run(
                    traces, checkpoint_path=ck)
    finally:
        faults.clear()
    res = stub_validator(n_devices=3, **kw).run(   # T_pad 18 != 16
        traces, resume_from=ck)
    assert div_sig(res) == div_sig(oracle)
    assert res.traces_checked == 32 and res.accepted == 31


def test_resume_rescales_to_requested_batch_after_rescued_round(
        tmp_path):
    """The elastic --batch-per-device contract: a resume finishes the
    rescued round at the snapshot's batch, then rescales to the
    requested one for the rest of the run — it must not stay pinned
    to the old allocation's round size."""
    ck = str(tmp_path / "ck")
    spec = counter_spec()
    traces = mk_traces(n=64, depth=6, seed=1, mutate=(49, 4))
    kw = dict(n_devices=2, chunk_steps=2)
    oracle = stub_validator(batch=16, **kw).run(traces)
    faults.install("kill@level=1")
    try:
        with PreemptionGuard():
            with pytest.raises(Preempted):
                stub_validator(batch=16, **kw).run(
                    traces, checkpoint_path=ck)
    finally:
        faults.clear()
    bv = stub_validator(batch=32, **kw)
    res = bv.run(traces, resume_from=ck)
    assert bv.batch == 32            # rescaled after the rescued round
    assert res.batch == 32
    assert div_sig(res) == div_sig(oracle)
    assert res.traces_checked == 64 and res.accepted == 63


def test_resume_refuses_different_trace_batch(tmp_path):
    ck = str(tmp_path / "ck")
    spec = counter_spec()
    traces = mk_traces(n=32, depth=6, seed=1)
    faults.install("kill@level=1")
    try:
        with PreemptionGuard():
            with pytest.raises(Preempted):
                stub_validator(batch=16, chunk_steps=2).run(
                    traces, checkpoint_path=ck)
    finally:
        faults.clear()
    other = mk_traces(n=32, depth=6, seed=9)
    with pytest.raises(ValueError, match="different trace batch"):
        stub_validator(batch=16, chunk_steps=2).run(
            other, resume_from=ck)


def test_acceptance_1024_traces_mesh_batch_and_seam():
    """The ISSUE 8 acceptance criterion, stub-spec form: >= 1024
    traces, one mutated, the SAME first divergence (trace id, step,
    action set, candidates) bit-identically across mesh sizes 1/2/4,
    across batch sizes, and across a SIGTERM/exit-75 resume seam."""
    import tempfile
    spec = counter_spec()
    traces = mk_traces(n=1024, depth=6, seed=11, mutate=(777, 3))
    sigs = {}
    for name, bv in (("d1", stub_validator(batch=1024, n_devices=1)),
                     ("d2", stub_validator(batch=1024, n_devices=2)),
                     ("d4", stub_validator(batch=1024, n_devices=4)),
                     ("b256", stub_validator(batch=256, n_devices=4))):
        res = bv.run(traces)
        assert res.traces_checked == 1024 and res.accepted == 1023
        rec = res.first_divergence
        assert rec["trace"] == "t-0777" and rec["step"] == 3
        sigs[name] = div_sig(res)
    assert len(set(sigs.values())) == 1
    # the resume seam, on a different mesh than the kill
    ck = os.path.join(tempfile.mkdtemp(prefix="tpuvsr-v1024-"), "ck")
    faults.install("kill@level=1")
    try:
        with PreemptionGuard():
            with pytest.raises(Preempted):
                stub_validator(batch=256, n_devices=4).run(
                    traces, checkpoint_path=ck)
    finally:
        faults.clear()
    res = stub_validator(batch=256, n_devices=2).run(
        traces, resume_from=ck)
    assert div_sig(res) == sigs["d1"]


# ---------------------------------------------------------------------
# degrade ladder + journal schema
# ---------------------------------------------------------------------
def test_oom_halves_batch_and_redraws(tmp_path):
    jp = str(tmp_path / "j.jsonl")
    spec = counter_spec()
    traces = mk_traces(n=32, depth=6, seed=1, mutate=(20, 1))
    oracle = stub_validator(batch=32, n_devices=2).run(traces)
    faults.install("oom@level=1")
    try:
        bv = stub_validator(batch=32, n_devices=2)
        res = bv.run(traces, obs=RunObserver(journal_path=jp))
    finally:
        faults.clear()
    assert bv.batch == 16           # halved once
    assert div_sig(res) == div_sig(oracle)
    evs = read_journal(jp)
    degr = [e for e in evs if e["event"] == "degrade"]
    assert degr and degr[0]["what"] == "validate_batch"
    assert (degr[0]["from"], degr[0]["to"]) == (32, 16)


def test_oom_ladder_is_bounded():
    spec = counter_spec()
    traces = mk_traces(n=16, depth=6, seed=1)
    faults.install("oom@level=1,oom@level=1,oom@level=1")
    try:
        with pytest.raises(Exception, match="RESOURCE_EXHAUSTED"):
            stub_validator(batch=16, min_batch=8).run(traces)
    finally:
        faults.clear()


def test_validate_journal_events_validate(tmp_path):
    """Every new event passes the tpuvsr-journal/1 validator
    (EVENT_REQUIRED keys in obs/journal.py + SCHEMA.md)."""
    jp = str(tmp_path / "j.jsonl")
    spec = counter_spec()
    traces = mk_traces(n=8, depth=6, seed=1, mutate=(3, 2))
    stub_validator(batch=8).run(traces,
                                obs=RunObserver(journal_path=jp))
    evs = read_journal(jp)
    kinds = {e["event"] for e in evs}
    assert {"validate_chunk", "divergence", "run_start",
            "run_end"} <= kinds
    with open(jp) as f:
        for line in f:
            validate_journal_line(json.loads(line))
    end = [e for e in evs if e["event"] == "run_end"][-1]
    assert end["traces"] == 8 and end["divergences"] == 1
    viol = [e for e in evs if e["event"] == "violation"]
    assert viol and viol[0]["kind"] == "divergence"


# ---------------------------------------------------------------------
# CLI flag contract + end to end
# ---------------------------------------------------------------------
def _run_cli(*argv, timeout=420):
    return subprocess.run(
        [sys.executable, "-m", "tpuvsr", *argv],
        capture_output=True, text=True, timeout=timeout,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "PYTHONPATH": "/root/repo", "HOME": "/root"})


@pytest.mark.parametrize("bad", [
    ["-validate", "t.jsonl", "-simulate"],
    ["-validate", "t.jsonl", "-supervise"],
    ["-validate", "t.jsonl", "-deadlock"],
    ["-validate", "t.jsonl", "-maxstates", "10"],
    ["-validate", "t.jsonl", "-checkpoint", "5"],
    ["-validate", "t.jsonl", "-engine", "sharded"],
    ["-validate", "t.jsonl", "-fpset", "hbm"],
    ["-batch", "64"],
    ["-validate", "t.jsonl", "-batch", "0"],
], ids=["simulate", "supervise", "deadlock", "maxstates",
        "checkpoint", "sharded", "fpset-hbm", "batch-no-validate",
        "zero-batch"])
def test_cli_validate_flag_conflicts_exit_2(bad):
    r = _run_cli("X.tla", *bad)
    assert r.returncode == 2, (r.stdout, r.stderr)
    assert "usage" in r.stderr or "error" in r.stderr


def test_cli_validate_end_to_end(tmp_path):
    """-validate through the real CLI on the inline counter spec (no
    device kernel registered -> the interpreter validator): a clean
    batch exits 0, a mutated one exits 12 with the divergence and the
    enabled set on stderr."""
    (tmp_path / "ObsCounter.tla").write_text(COUNTER)
    (tmp_path / "ObsCounter.cfg").write_text(COUNTER_CFG)
    good = str(tmp_path / "good.jsonl")
    save_traces(good, stub_trace_records(n=6, depth=6, seed=0))
    bad = str(tmp_path / "bad.jsonl")
    save_traces(bad, stub_trace_records(n=6, depth=6, seed=0,
                                        mutate=(2, 3)))
    r = _run_cli(str(tmp_path / "ObsCounter.tla"), "-validate", good,
                 "-json")
    assert r.returncode == 0, r.stderr
    doc = json.loads(r.stdout.strip().splitlines()[-1])
    assert doc["mode"] == "validate" and doc["ok"] \
        and doc["accepted"] == 6
    r = _run_cli(str(tmp_path / "ObsCounter.tla"), "-validate", bad,
                 "-json")
    assert r.returncode == 12, (r.stdout, r.stderr)
    doc = json.loads(r.stdout.strip().splitlines()[-1])
    assert doc["divergences"] == 1
    assert doc["first_divergence"]["trace"] == "t-0002"
    assert doc["first_divergence"]["step"] == 3
    assert "diverges at event 3" in r.stderr
    assert "enabled actions" in r.stderr


# ---------------------------------------------------------------------
# the service path (kind="validate")
# ---------------------------------------------------------------------
def _submit_validate(q, tmp_path, name, recs, **flags):
    tp = str(tmp_path / f"{name}.jsonl")
    save_traces(tp, recs)
    base = {"stub": True, "traces": tp, "batch": 16, "chunk_steps": 2}
    base.update(flags)
    return q.submit(f"<stub:{name}>", kind="validate", flags=base)


def test_validate_job_lifecycle_and_kill_resume_bit_identical(
        tmp_path):
    q = JobQueue(str(tmp_path / "spool"))
    recs = stub_trace_records(n=32, depth=6, seed=1, mutate=(11, 2))
    clean = _submit_validate(q, tmp_path, "clean", recs)
    kill = _submit_validate(q, tmp_path, "kill", recs,
                            inject="kill@level=1")
    ok = _submit_validate(q, tmp_path, "ok",
                          stub_trace_records(n=16, depth=6, seed=2))
    bad = q.submit("<stub:bad>", kind="validate",
                   flags={"stub": True, "stub_bad": True,
                          "traces": str(tmp_path / "clean.jsonl")})
    Worker(q, devices=2).drain()
    jc, jk, jo, jb = (q.get(j.job_id) for j in (clean, kill, ok, bad))
    assert jc.state == "violated" and jc.attempts == 1
    assert jk.state == "violated" and jk.attempts == 2
    assert jo.state == "done" and jo.result["ok"]
    assert jb.state == "failed" and jb.reason == "speclint" \
        and jb.attempts == 0
    assert jc.result["traces"] == 32 and jc.result["accepted"] == 31
    fd = jc.result["first_divergence"]
    assert fd["trace"] == "t-0011" and fd["step"] == 2
    # the preempted job's report is bit-identical to the clean one's
    assert jk.result["divergences"] == jc.result["divergences"]
    evs = [e["event"]
           for e in read_journal(q.journal_path(jk.job_id))]
    assert "job_requeued" in evs and "rescue_checkpoint" in evs
    assert "validate_chunk" in evs and "divergence" in evs
    assert evs[-1] == "job_done"


def test_dead_worker_validate_job_recovers_with_rescue(tmp_path):
    """recover_stale reads the validate snapshot manifest through the
    same checkpoint.snapshot_info handoff BFS and sim jobs use."""
    q = JobQueue(str(tmp_path / "spool"))
    recs = stub_trace_records(n=32, depth=6, seed=1, mutate=(11, 2))
    j = _submit_validate(q, tmp_path, "dead", recs)
    oracle = _submit_validate(q, tmp_path, "oracle", recs)
    ck = q.checkpoint_path(j.job_id)
    traces = traces_from_records(recs, counter_spec())
    faults.install("kill@level=1")
    try:
        with PreemptionGuard():
            with pytest.raises(Preempted):
                stub_validator(batch=16, n_devices=2,
                               chunk_steps=2).run(
                    traces, checkpoint_path=ck)
    finally:
        faults.clear()
    q.transition(j.job_id, "admitted")
    q.transition(j.job_id, "running", attempts=1)
    p = subprocess.Popen([sys.executable, "-c", "pass"])
    p.wait()
    with open(os.path.join(q.claims_dir, f"{j.job_id}.claim"),
              "w") as f:
        json.dump({"pid": p.pid, "owner": "gone"}, f)
    assert q.recover_stale() == [j.job_id]
    assert q.get(j.job_id).rescue["path"] == ck
    Worker(q, devices=2).drain()
    job, oj = q.get(j.job_id), q.get(oracle.job_id)
    assert job.state == oj.state == "violated"
    assert job.result["divergences"] == oj.result["divergences"]


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs 8 virtual devices")
def test_scheduler_shrinks_live_validate_job(tmp_path):
    """Elastic trace-batch placement: a higher-priority arrival
    preempts the elastic validate job at a validate_chunk boundary;
    it resumes on the smaller allocation (batch follows
    batch_per_device on the new mesh) and the divergence report stays
    bit-identical to an undisturbed oracle job."""
    q = JobQueue(str(tmp_path / "spool"))
    recs = stub_trace_records(n=96, depth=6, seed=1, mutate=(90, 4))
    tp = str(tmp_path / "A.jsonl")
    save_traces(tp, recs)
    # devices_max pins the post-shrink allocation (no grow-back mid
    # test), like the sim twin of this test
    a = q.submit("<stub:A>", kind="validate", devices=4,
                 devices_min=2, devices_max=2,
                 flags={"stub": True, "traces": tp,
                        "batch_per_device": 8, "chunk_steps": 2})
    state = {"submitted": False}

    def on_level(worker, job, depth):
        if job.job_id == a.job_id and not state["submitted"]:
            state["submitted"] = True
            q.submit("<stub:B>", engine="device", priority=10,
                     devices=6, flags={"stub": True})

    Worker(q, devices=8, on_level=on_level).drain()
    job = q.get(a.job_id)
    assert job.state == "violated"
    evs = read_journal(q.journal_path(a.job_id))
    kinds = [e["event"] for e in evs]
    assert "job_requeued" in kinds and "rescue_checkpoint" in kinds
    allocs = [e["devices"] for e in evs
              if e["event"] == "job_started"]
    assert allocs == [4, 2]
    b = [x for x in q.jobs() if x.job_id != a.job_id][0]
    assert b.state == "done"
    oracle = stub_validator(batch=32, n_devices=4, chunk_steps=2).run(
        traces_from_records(recs, counter_spec()))
    assert job.result["divergences"] == oracle.divergences


def test_status_surfaces_validate_progress(tmp_path, capsys):
    from tpuvsr.service import api
    spool = str(tmp_path / "spool")
    q = JobQueue(spool)
    j = _submit_validate(q, tmp_path, "st",
                         stub_trace_records(n=32, depth=6, seed=1,
                                            mutate=(11, 2)))
    Worker(q, devices=2).drain()
    rc = api.main(["status", j.job_id, "--spool", spool, "--json"])
    assert rc == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["kind"] == "validate"
    assert doc["validate"]["traces"] == 32
    assert doc["validate"]["divergences"] == 1
    assert doc["validate"]["first_divergence"]["trace"] == "t-0011"
    rc = api.main(["status", j.job_id, "--spool", spool])
    assert rc == 0
    out = capsys.readouterr().out
    assert "validate:" in out and "divergence" in out


def test_submit_validate_flag_contract(tmp_path, capsys):
    from tpuvsr.service import api
    spool = str(tmp_path / "spool")
    rc = api.main(["submit", "--stub", "--validate", "t.jsonl",
                   "--sim", "--spool", spool])
    assert rc == 2              # --validate and --sim conflict
    rc = api.main(["submit", "--stub", "--batch", "64",
                   "--spool", spool])
    assert rc == 2              # --batch without --validate
    rc = api.main(["submit", "--stub", "--validate", "t.jsonl",
                   "--batch", "64", "--spool", spool, "--json"])
    assert rc == 0
    doc = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert doc["kind"] == "validate"
    assert doc["flags"]["traces"] == "t.jsonl"
    assert doc["flags"]["batch"] == 64


# ---------------------------------------------------------------------
# tooling: demo drill + bench gate
# ---------------------------------------------------------------------
def test_validate_demo_smoke(capsys):
    """The accepted/mutated round-trip drill under tier-1 —
    hunt_demo's validation twin."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "scripts"))
    import validate_demo
    assert validate_demo.main([]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["ok"] and all(out["checks"].values())
    assert out["traces_per_s"] > 0


def test_compare_bench_gates_traces_per_s(tmp_path):
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "scripts"))
    import compare_bench

    def doc(traces_per_s, backend="cpu", value=100.0):
        return {"value": value,
                "validate_demo": {"traces_per_s": traces_per_s,
                                  "batch": 1024,
                                  "backend": backend}}

    def run(base, cand):
        bp, cp = str(tmp_path / "b.json"), str(tmp_path / "c.json")
        with open(bp, "w") as f:
            json.dump(base, f)
        with open(cp, "w") as f:
            json.dump(cand, f)
        return compare_bench.main([bp, cp, "--max-regression", "10"])

    assert run(doc(100.0), doc(95.0)) == 0        # in tolerance
    assert run(doc(100.0), doc(50.0)) == 1        # regression
    # cross-backend drop: advisory, like walks/s across fleet sizes
    assert run(doc(100.0, "tpu"), doc(50.0, "cpu")) == 0
