"""Resilience layer tests (ISSUE 3): fault injection, supervised
retry/degrade, preemption-safe checkpoints, checkpoint hardening.

Everything here runs tier-1 — no reference mount, no TPU: the real
Device/Paged/Sharded engine loops are driven by the stub kernel
(tpuvsr/testing.py) and failures are injected deterministically
through tpuvsr/resilience/faults.py.

Acceptance (ISSUE 3):
* a SIGTERM'd supervised run writes a rescue snapshot at the next
  level boundary, raises Preempted (CLI exit 75), and ``-recover``
  from that snapshot reproduces the uninterrupted run's fp_count and
  level_sizes exactly;
* an injected OOM at a mid level degrades (tile halving -> paged
  fallback) instead of aborting, with the fault/retry/degrade
  sequence visible in the journal.
"""

import json
import os
import shutil
import signal
import subprocess
import sys

import numpy as np
import pytest

from tpuvsr.core.values import TLAError
from tpuvsr.engine.checkpoint import (CheckpointCorrupt, PAYLOADS,
                                      load_checkpoint)
from tpuvsr.obs import RunObserver, read_journal, validate_journal_line
from tpuvsr.resilience import faults
from tpuvsr.resilience.faults import (FaultPlan, InjectedOOM,
                                      parse_fault)
from tpuvsr.resilience.supervisor import (EXIT_RESUMABLE, Preempted,
                                          PreemptionGuard, Supervisor,
                                          clear_preemption, is_oom,
                                          preempt_signal)
from tpuvsr.testing import (STUB_DISTINCT as ORACLE_DISTINCT,
                            STUB_LEVELS as ORACLE_LEVELS,
                            counter_spec, stub_device_engine,
                            stub_engine_factory as _stub_factory_for,
                            stub_model_factory)


@pytest.fixture(autouse=True)
def _clean_fault_state():
    yield
    faults.clear()
    clear_preemption()


# ---------------------------------------------------------------------
# fault spec grammar
# ---------------------------------------------------------------------
def test_fault_spec_grammar():
    plan = FaultPlan.parse(
        "oom@level=3, kill@level=5,"
        "corrupt-ckpt:frontier.npz@level=2;exchange-drop@shard=1")
    kinds = [f.kind for f in plan.faults]
    assert kinds == ["oom", "kill", "corrupt-ckpt", "exchange-drop"]
    assert plan.faults[0].site == "level" and plan.faults[0].level == 3
    assert plan.faults[2].payload == "frontier.npz"
    assert plan.faults[2].level == 2
    assert plan.faults[3].site == "exchange"
    assert plan.faults[3].shard == 1


@pytest.mark.parametrize("bad", [
    "explode@level=1",              # unknown kind
    "oom@when=3",                   # unknown parameter
    "corrupt-ckpt",                 # missing payload
    "oom@level=x",                  # non-integer
])
def test_fault_spec_rejects_malformed(bad):
    with pytest.raises(ValueError):
        parse_fault(bad)


def test_faults_are_one_shot():
    plan = FaultPlan.parse("oom@level=3")
    with pytest.raises(InjectedOOM):
        plan.fire("level", depth=3)
    assert plan.fire("level", depth=3) is None      # consumed
    assert not plan.pending()


def test_level_pinned_fault_only_fires_at_its_level():
    plan = FaultPlan.parse("oom@level=3")
    assert plan.fire("level", depth=2) is None
    assert plan.fire("checkpoint", depth=3) is None  # wrong site
    with pytest.raises(InjectedOOM):
        plan.fire("level", depth=3)


def test_env_var_arms_a_plan(monkeypatch):
    faults.clear()
    monkeypatch.setenv("TPUVSR_FAULT", "oom@level=7")
    plan = faults.active()
    assert plan is not None and plan.faults[0].level == 7
    faults.clear()
    monkeypatch.delenv("TPUVSR_FAULT")
    assert faults.active() is None


def test_is_oom_classification():
    assert is_oom(InjectedOOM("RESOURCE_EXHAUSTED: injected"))
    assert is_oom(RuntimeError(
        "RESOURCE_EXHAUSTED: Out of memory while trying to allocate"))
    assert is_oom(MemoryError())
    assert not is_oom(ValueError("nope"))


def test_new_journal_events_validate():
    base = {"ts": 0.0, "run_id": "r", "elapsed_s": 1.0}
    validate_journal_line(dict(base, event="fault", what="oom",
                               site="level"))
    validate_journal_line(dict(base, event="retry", attempt=1,
                               backoff_s=0.5))
    validate_journal_line(dict(base, event="degrade", what="tile",
                               **{"from": 128, "to": 64}))
    validate_journal_line(dict(base, event="rescue_checkpoint",
                               path="x", depth=3, distinct=9,
                               signal="SIGTERM"))
    validate_journal_line(dict(base, event="degrade", what="mesh",
                               **{"from": 8, "to": 4}))
    validate_journal_line(dict(base, event="reshard", from_shards=8,
                               to_shards=4, distinct=100))
    with pytest.raises(ValueError):
        validate_journal_line(dict(base, event="fault", what="oom"))
    with pytest.raises(ValueError):
        validate_journal_line(dict(base, event="reshard",
                                   from_shards=8))


# ---------------------------------------------------------------------
# checkpoint hardening: CRCs recorded, corruption matrix, .old fallback
# ---------------------------------------------------------------------
@pytest.fixture(scope="module")
def snapshot(tmp_path_factory):
    """A depth-3 stub-engine snapshot (written with every-level
    cadence) plus its pristine load."""
    ck = str(tmp_path_factory.mktemp("resil") / "snap")
    res = stub_device_engine().run(max_depth=3, checkpoint_path=ck)
    assert res.error                       # depth-limited
    pristine = load_checkpoint(ck)
    return ck, pristine


def _copy_snapshot(snapshot, tmp_path, with_old=False):
    ck, _ = snapshot
    dst = str(tmp_path / "snap")
    shutil.copytree(ck, dst)
    if with_old:
        shutil.copytree(ck, dst + ".old")
    return dst


def test_manifest_records_payload_crcs(snapshot):
    ck, pristine = snapshot
    with open(os.path.join(ck, "manifest.json")) as f:
        manifest = json.load(f)
    crcs = manifest["payload_crc32"]
    assert set(crcs) == set(PAYLOADS)
    assert all(isinstance(v, int) for v in crcs.values())
    assert pristine["depth"] == 3
    assert pristine["restored_from"] == ck


def _truncate(path):
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.truncate(max(1, size // 2))


def _rewrite_valid_npz(path):
    # a perfectly loadable npz with the WRONG content: only the CRC
    # check can catch this one
    np.savez_compressed(path, slots=np.zeros((4, 5), np.uint32))


CORRUPTIONS = [
    ("truncated-npz", lambda d: _truncate(
        os.path.join(d, "frontier.npz"))),
    ("bad-crc-loadable-npz", lambda d: _rewrite_valid_npz(
        os.path.join(d, "fpset.npz"))),
    ("missing-payload", lambda d: os.remove(
        os.path.join(d, "trace.npz"))),
    ("garbage-manifest", lambda d: open(
        os.path.join(d, "manifest.json"), "w").write("{not json")),
]


@pytest.mark.parametrize("name,corrupt", CORRUPTIONS,
                         ids=[c[0] for c in CORRUPTIONS])
def test_corruption_falls_back_to_old(snapshot, tmp_path, name,
                                      corrupt):
    dst = _copy_snapshot(snapshot, tmp_path, with_old=True)
    corrupt(dst)
    logs = []
    ck = load_checkpoint(dst, log=logs.append)
    assert ck["restored_from"] == dst + ".old"
    assert ck["fp_count"] == snapshot[1]["fp_count"]
    assert ck["level_sizes"] == snapshot[1]["level_sizes"]
    assert logs and "falling back" in logs[0]


@pytest.mark.parametrize("name,corrupt", CORRUPTIONS,
                         ids=[c[0] for c in CORRUPTIONS])
def test_corruption_without_old_raises_clearly(snapshot, tmp_path,
                                               name, corrupt):
    dst = _copy_snapshot(snapshot, tmp_path)
    corrupt(dst)
    with pytest.raises(CheckpointCorrupt):
        load_checkpoint(dst)


def test_stale_old_is_not_preferred(snapshot, tmp_path):
    # primary intact, .old corrupted: the primary must load
    dst = _copy_snapshot(snapshot, tmp_path, with_old=True)
    _truncate(os.path.join(dst + ".old", "frontier.npz"))
    ck = load_checkpoint(dst)
    assert ck["restored_from"] == dst
    assert ck["fp_count"] == snapshot[1]["fp_count"]


def test_digest_mismatch_never_falls_back(snapshot, tmp_path):
    # policy errors must not be masked by the .old fallback
    dst = _copy_snapshot(snapshot, tmp_path, with_old=True)
    with pytest.raises(ValueError, match="different spec"):
        load_checkpoint(dst, expect_digest="0123456789abcdef")


def test_bad_crc_recovers_through_engine_resume(snapshot, tmp_path):
    """The seed bug this hardening fixes: a corrupt payload with an
    intact manifest used to raise deep inside np.load on -recover;
    now the engine resumes from .old and still reaches the exact
    fixpoint."""
    dst = _copy_snapshot(snapshot, tmp_path, with_old=True)
    _truncate(os.path.join(dst, "fpset.npz"))
    res = stub_device_engine().run(resume_from=dst)
    assert res.ok and res.distinct_states == ORACLE_DISTINCT
    assert res.levels == ORACLE_LEVELS


# ---------------------------------------------------------------------
# garble-ckpt: in-place byte garbling — the direct CRC-path fault
# (ISSUE 4 satellite)
# ---------------------------------------------------------------------
def test_garble_ckpt_spec_grammar():
    f = parse_fault("garble-ckpt:fpset.npz@level=3")
    assert f.kind == "garble-ckpt" and f.site == "checkpoint"
    assert f.payload == "fpset.npz" and f.level == 3
    with pytest.raises(ValueError):
        parse_fault("garble-ckpt")           # missing payload


def test_garble_ckpt_preserves_size_and_breaks_only_crc(tmp_path):
    """The flavor's whole point: the garbled payload stays np.load-able
    garbage of the ORIGINAL size, so the manifest CRC32 is the only
    line of defense — and it fires."""
    ck = str(tmp_path / "snap")
    pristine = str(tmp_path / "pristine")
    res0 = stub_device_engine().run(max_depth=2, checkpoint_path=pristine)
    assert res0.error
    faults.install("garble-ckpt:fpset.npz@level=2")
    res1 = stub_device_engine().run(max_depth=2, checkpoint_path=ck)
    faults.clear()
    assert res1.error                        # depth-limited
    g = os.path.join(ck, "fpset.npz")
    p = os.path.join(pristine, "fpset.npz")
    assert os.path.getsize(g) == os.path.getsize(p)   # size preserved
    # the fault keeps the previous snapshot as .old (the crash window);
    # drop it to face the CRC check head-on
    shutil.rmtree(ck + ".old")
    with pytest.raises(CheckpointCorrupt, match="CRC32 mismatch"):
        load_checkpoint(ck)


def test_garble_ckpt_journals_and_falls_back_to_old(tmp_path):
    ck = str(tmp_path / "snap")
    jp = str(tmp_path / "j.jsonl")
    # every-level cadence: the level-3 write is garbled, level-2 stays
    # behind as .old
    faults.install("garble-ckpt:frontier.npz@level=3")
    res1 = stub_device_engine().run(
        max_depth=3, checkpoint_path=ck,
        obs=RunObserver(journal_path=jp))
    faults.clear()
    assert res1.error
    events = read_journal(jp)
    garbles = [e for e in events if e["event"] == "fault"
               and e["what"] == "garble-ckpt"]
    assert garbles and garbles[0]["payload"] == "frontier.npz"
    assert os.path.isdir(ck + ".old")
    logs = []
    res2 = stub_device_engine().run(resume_from=ck, log=logs.append)
    assert any("CRC32 mismatch" in m and "falling back" in m
               for m in logs)
    assert res2.ok and res2.distinct_states == ORACLE_DISTINCT
    assert res2.levels == ORACLE_LEVELS


# ---------------------------------------------------------------------
# preemption: SIGTERM -> rescue checkpoint -> resumable -> equivalence
# ---------------------------------------------------------------------
def test_preemption_guard_flag_and_restore():
    before = signal.getsignal(signal.SIGTERM)
    with PreemptionGuard():
        assert preempt_signal() is None
        os.kill(os.getpid(), signal.SIGTERM)
        assert preempt_signal() == "SIGTERM"
    assert preempt_signal() is None
    assert signal.getsignal(signal.SIGTERM) is before


def test_sigterm_rescue_and_recover_equivalence(tmp_path):
    """ISSUE 3 acceptance: kill -TERM of a supervised checkpointed run
    exits resumable (Preempted -> CLI exit 75) having written a rescue
    snapshot at the next level boundary, and -recover reproduces the
    uninterrupted run's fp_count and level_sizes exactly."""
    assert EXIT_RESUMABLE == 75
    spec = counter_spec()
    ck = str(tmp_path / "ck")
    jp = str(tmp_path / "run.jsonl")
    faults.install("kill@level=3")      # SIGTERM mid-run, via injection
    sup = Supervisor(spec, checkpoint_path=ck, journal_path=jp,
                     engine_factory=_stub_factory_for(spec),
                     tile_size=4)
    with pytest.raises(Preempted) as pi:
        sup.run()
    p = pi.value
    assert p.path == ck and p.depth == 3 and p.signal == "SIGTERM"
    assert os.path.isdir(ck)

    # the resume (-recover) continues the same journal
    res2 = stub_device_engine().run(
        resume_from=ck, obs=RunObserver(journal_path=jp))
    oracle = stub_device_engine().run()
    assert res2.ok
    assert res2.distinct_states == oracle.distinct_states \
        == ORACLE_DISTINCT
    assert res2.levels == oracle.levels == ORACLE_LEVELS

    events = read_journal(jp)
    kinds = [e["event"] for e in events]
    assert "fault" in kinds and "rescue_checkpoint" in kinds
    rescue = next(e for e in events
                  if e["event"] == "rescue_checkpoint")
    assert rescue["signal"] == "SIGTERM" and rescue["depth"] == 3
    starts = [e for e in events if e["event"] == "run_start"]
    assert [s["resumed"] for s in starts] == [False, True]
    # cumulative elapsed across the rescue/recover seam
    ends = [e for e in events if e["event"] == "run_end"]
    assert ends and ends[-1]["distinct"] == ORACLE_DISTINCT


# ---------------------------------------------------------------------
# OOM: degrade ladder + journal visibility
# ---------------------------------------------------------------------
def test_oom_mid_level_degrades_and_journals(tmp_path):
    """ISSUE 3 acceptance: an injected OOM at a mid level degrades
    (tile halving) instead of aborting, resumes from the snapshot, and
    the fault -> degrade -> retry sequence is visible in the journal."""
    spec = counter_spec()
    jp = str(tmp_path / "oom.jsonl")
    faults.install("oom@level=3")
    sup = Supervisor(spec, checkpoint_path=str(tmp_path / "ck"),
                     journal_path=jp,
                     engine_factory=_stub_factory_for(spec),
                     tile_size=4, min_tile=2, backoff_base=0.0,
                     sleep=lambda s: None)
    res = sup.run()
    assert res.ok and res.distinct_states == ORACLE_DISTINCT
    assert res.levels == ORACLE_LEVELS
    assert sup.attempts == 2
    assert sup.degrades == [("tile", 4, 2)]
    kinds = [e["event"] for e in read_journal(jp)]
    assert kinds.index("fault") < kinds.index("degrade") \
        < kinds.index("retry")
    # the resumed attempt announces itself
    events = read_journal(jp)
    starts = [e for e in events if e["event"] == "run_start"]
    assert [s["resumed"] for s in starts] == [False, True]


def test_oom_ladder_falls_back_to_paged(tmp_path):
    spec = counter_spec()
    jp = str(tmp_path / "paged.jsonl")
    faults.install("oom@level=2,oom@level=4")
    sup = Supervisor(spec, checkpoint_path=str(tmp_path / "ck"),
                     journal_path=jp,
                     engine_factory=_stub_factory_for(spec),
                     tile_size=4, min_tile=4,     # floor: no halving room
                     backoff_base=0.0, sleep=lambda s: None)
    res = sup.run()
    assert res.ok and res.distinct_states == ORACLE_DISTINCT
    assert res.levels == ORACLE_LEVELS
    assert sup.kind == "paged"
    assert ("engine", "device", "paged") in sup.degrades
    degr = [e for e in read_journal(jp) if e["event"] == "degrade"]
    assert {"what": "engine", "from": "device", "to": "paged"}.items() \
        <= degr[0].items()


def test_non_oom_errors_propagate_unretried(tmp_path):
    spec = counter_spec()
    calls = []

    def factory(kind, tile):
        calls.append((kind, tile))

        class Boom:
            def run(self, **kw):
                raise TLAError("not an OOM")
        return Boom()

    sup = Supervisor(spec, engine_factory=factory, tile_size=4,
                     sleep=lambda s: None)
    with pytest.raises(TLAError, match="not an OOM"):
        sup.run()
    assert len(calls) == 1              # no retry ladder for real bugs


def test_oom_retries_are_bounded(tmp_path):
    spec = counter_spec()

    def factory(kind, tile):
        class AlwaysOOM:
            def run(self, **kw):
                raise InjectedOOM("RESOURCE_EXHAUSTED: forever")
        return AlwaysOOM()

    sup = Supervisor(spec, engine_factory=factory, tile_size=4,
                     max_retries=3, backoff_base=0.0,
                     sleep=lambda s: None)
    with pytest.raises(InjectedOOM):
        sup.run()
    assert sup.attempts == 4            # initial + 3 retries


# ---------------------------------------------------------------------
# sharded resume validation (satellite)
# ---------------------------------------------------------------------
def _sharded_engine(mesh):
    from tpuvsr.parallel.sharded_bfs import ShardedBFS
    return ShardedBFS(counter_spec(), mesh, tile=4, bucket_cap=64,
                      next_capacity=1 << 6, fpset_capacity=1 << 8,
                      model_factory=stub_model_factory())


@pytest.mark.skipif(len(__import__("jax").devices()) < 4,
                    reason="needs 4 virtual devices")
def test_sharded_recover_rejects_mismatched_shard_layout(tmp_path):
    import jax
    from jax.sharding import Mesh
    ck = str(tmp_path / "shard-ck")
    mesh2 = Mesh(np.array(jax.devices()[:2]), ("d",))
    r1 = _sharded_engine(mesh2).run(max_depth=3, checkpoint_path=ck)
    assert r1.error                     # depth-limited
    pristine = str(tmp_path / "pristine")
    shutil.copytree(ck, pristine)

    # (a) same mesh, tampered per-shard counts: clear TLAError instead
    # of an index error in the frontier re-scatter
    mf_path = os.path.join(ck, "manifest.json")
    with open(mf_path) as f:
        mf = json.load(f)
    mf["extra"]["shard_counts"][0] += 2
    with open(mf_path, "w") as f:
        json.dump(mf, f)
    with pytest.raises(TLAError, match="shard layout"):
        _sharded_engine(mesh2).run(resume_from=ck)

    # (b) a mesh-size mismatch is no longer a refusal (ISSUE 5 elastic
    # resume) — but an INCONSISTENT snapshot still is: garble the
    # manifest fp_count so the pooled FPSet rows cannot match it
    with open(os.path.join(pristine, "manifest.json")) as f:
        mf2 = json.load(f)
    mf2["fp_count"] += 5
    with open(os.path.join(pristine, "manifest.json"), "w") as f:
        json.dump(mf2, f)
    mesh4 = Mesh(np.array(jax.devices()[:4]), ("d",))
    with pytest.raises(TLAError, match="inconsistent"):
        _sharded_engine(mesh4).run(resume_from=pristine)


# ---------------------------------------------------------------------
# elastic resume (ISSUE 5 tentpole): a D-shard snapshot resumed on an
# M-device mesh — both shrink and grow — reproduces the uninterrupted
# run exactly, with the reshard journaled
# ---------------------------------------------------------------------
def _stub_sharded(n, **kw):
    from tpuvsr.testing import stub_sharded_engine
    return stub_sharded_engine(n_devices=n, **kw)


@pytest.mark.skipif(len(__import__("jax").devices()) < 8,
                    reason="needs 8 virtual devices")
@pytest.mark.parametrize("m_dev", [2, 8], ids=["shrink-4to2",
                                               "grow-4to8"])
def test_elastic_resume_equivalence(tmp_path, m_dev):
    """ISSUE 5 acceptance: checkpoint on a 4-shard mesh, resume on
    M < D and M > D; distinct/generated/level_sizes match the
    uninterrupted run exactly and the journal records the reshard."""
    ck = str(tmp_path / "ck")
    jp = str(tmp_path / "elastic.jsonl")
    r1 = _stub_sharded(4).run(max_depth=3, checkpoint_path=ck)
    assert r1.error                     # depth-limited
    eng = _stub_sharded(m_dev)
    res = eng.run(resume_from=ck, obs=RunObserver(journal_path=jp))
    oracle = _stub_sharded(4).run()
    assert res.ok
    assert res.distinct_states == oracle.distinct_states \
        == ORACLE_DISTINCT
    assert eng.level_sizes == oracle.levels == ORACLE_LEVELS
    assert res.states_generated == oracle.states_generated
    assert eng.resharded_from == 4
    events = read_journal(jp)
    rs = [e for e in events if e["event"] == "reshard"]
    assert len(rs) == 1
    assert rs[0]["from_shards"] == 4 and rs[0]["to_shards"] == m_dev
    assert rs[0]["distinct"] == r1.distinct_states
    # the metrics gauges carry the mesh identity for compare_bench
    assert res.metrics["gauges"]["mesh_devices"] == m_dev
    assert res.metrics["gauges"]["resharded_from"] == 4


@pytest.mark.skipif(len(__import__("jax").devices()) < 8,
                    reason="needs 8 virtual devices")
def test_elastic_resume_trace_bit_identical(tmp_path):
    """The unique-witness invariant (x <= 2: the only violation at its
    BFS level is (3,0), reached one way) must surface the bit-identical
    counterexample trace from every mesh size AND from an elastic
    resume that crossed mesh sizes mid-run."""
    def trace_of(res):
        assert not res.ok and res.violated_invariant == "Bound"
        return [tuple(sorted(s.state.items())) for s in res.trace]

    golden = trace_of(_stub_sharded(1, inv_x_bound=2).run())
    for m in (2, 4, 8):
        assert trace_of(_stub_sharded(m, inv_x_bound=2).run()) == golden

    # checkpoint at depth 2 on 4 devices, resume on 2: same witness
    ck = str(tmp_path / "ck")
    r1 = _stub_sharded(4, inv_x_bound=2).run(max_depth=2,
                                             checkpoint_path=ck)
    assert r1.error and r1.ok           # depth-limited, no viol yet
    eng = _stub_sharded(2, inv_x_bound=2)
    res = eng.run(resume_from=ck)
    assert eng.resharded_from == 4
    assert trace_of(res) == golden


@pytest.mark.skipif(len(__import__("jax").devices()) < 4,
                    reason="needs 4 virtual devices")
def test_sharded_mesh_degrade_ladder_to_paged(tmp_path):
    """ISSUE 5 acceptance: injected OOMs walk the full mesh ladder —
    per-shard tile halving, mesh shrink 4 -> 2 -> 1, single-device
    paged fallback (snapshot converted in place) — and the run still
    reaches the exact fixpoint with every rung journaled."""
    from tpuvsr.resilience.supervisor import Supervisor
    from tpuvsr.testing import stub_sharded_factory
    spec = counter_spec()
    jp = str(tmp_path / "ladder.jsonl")
    faults.install("oom@level=2,oom@level=3,oom@level=4,"
                   "oom@level=5,oom@level=6")
    sup = Supervisor(spec, engine="sharded", mesh_devices=4,
                     checkpoint_path=str(tmp_path / "ck"),
                     journal_path=jp,
                     engine_factory=stub_sharded_factory(spec),
                     tile_size=8, min_tile=4, backoff_base=0.0,
                     sleep=lambda s: None)
    res = sup.run()
    assert res.ok and res.distinct_states == ORACLE_DISTINCT
    assert res.levels == ORACLE_LEVELS
    assert ("tile", 8, 4) in sup.degrades
    assert ("mesh", 4, 2) in sup.degrades
    assert ("mesh", 2, 1) in sup.degrades
    assert ("engine", "sharded", "paged") in sup.degrades
    assert sup.kind == "paged"
    degr = [e for e in read_journal(jp) if e["event"] == "degrade"]
    assert [d["what"] for d in degr] == ["tile", "mesh", "mesh",
                                         "engine"]
    assert {"what": "mesh", "from": 4, "to": 2}.items() \
        <= degr[1].items()


@pytest.mark.skipif(len(__import__("jax").devices()) < 2,
                    reason="needs 2 virtual devices")
def test_exchange_retry_is_bounded(tmp_path):
    """A drop count beyond the retry budget must fail loudly (bounded
    retry, not an infinite re-issue spin)."""
    jp = str(tmp_path / "x.jsonl")
    faults.install("exchange-drop:9@shard=0")
    eng = _stub_sharded(2, sleep=lambda s: None)
    with pytest.raises(TLAError, match="giving up"):
        eng.run(obs=RunObserver(journal_path=jp))
    retries = [e for e in read_journal(jp) if e["event"] == "retry"]
    assert [e["attempt"] for e in retries] == [1, 2, 3, 4, 5]
    backoffs = [e["backoff_s"] for e in retries]
    assert backoffs == sorted(backoffs)     # exponential, capped


def test_exchange_drop_count_grammar():
    plan = FaultPlan.parse("exchange-drop:3@shard=1")
    f = plan.faults[0]
    assert f.kind == "exchange-drop" and f.count == 3 and f.shard == 1
    assert repr(f) == "exchange-drop:3@shard=1"
    # fires exactly count times, then clears
    from tpuvsr.resilience.faults import InjectedExchangeDrop
    for _ in range(3):
        with pytest.raises(InjectedExchangeDrop):
            plan.fire("exchange", shard=1)
    assert plan.fire("exchange", shard=1) is None
    assert not plan.pending()
    with pytest.raises(ValueError, match="integer count"):
        parse_fault("exchange-drop:x")
    with pytest.raises(ValueError, match="count must be"):
        parse_fault("exchange-drop:0")


def test_oom_shard_scoped_fault():
    """oom@shard=S fires at the level site only for the matching host
    process (None context — a single-process mesh — matches any)."""
    plan = FaultPlan.parse("oom@shard=1")
    assert plan.fire("level", depth=2, shard=0) is None
    with pytest.raises(InjectedOOM):
        plan.fire("level", depth=2, shard=1)
    plan2 = FaultPlan.parse("oom@shard=1")
    with pytest.raises(InjectedOOM):    # single-process: any shard
        plan2.fire("level", depth=2, shard=None)


# ---------------------------------------------------------------------
# the full injection matrix (scripts/fault_matrix.py) under tier-1
# ---------------------------------------------------------------------
def test_fault_matrix_smoke(capsys):
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "scripts"))
    import fault_matrix
    assert fault_matrix.main([]) == 0
    out = json.loads(capsys.readouterr().out)
    # 31 scenarios since ISSUE 20 (host-death-failover +
    # spool-replica-loss + zombie-fence)
    assert out["ok"] and len(out["scenarios"]) == 31


# ---------------------------------------------------------------------
# CLI contract
# ---------------------------------------------------------------------
def _cli(args):
    return subprocess.run(
        [sys.executable, "-m", "tpuvsr"] + args,
        capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "PYTHONPATH": os.path.dirname(os.path.dirname(
                 os.path.abspath(__file__))),
             "HOME": "/root"})


@pytest.mark.parametrize("bad", [
    ["-supervise", "-simulate"],
    ["-supervise", "-engine", "interp"],
    ["-supervise", "-fpset", "host"],
    ["-inject", "explode@level=1"],
    ["-engine", "sharded", "-simulate"],
    ["-engine", "sharded", "-fpset", "paged"],
    ["-inject", "exchange-drop:x@shard=0"],
], ids=["simulate", "interp", "host-fpset", "bad-inject",
        "sharded-simulate", "sharded-fpset", "bad-drop-count"])
def test_cli_supervise_and_inject_flag_validation(bad):
    r = _cli(["X.tla"] + bad)
    assert r.returncode == 2, r.stderr
    assert "usage" in r.stderr or "error" in r.stderr
