"""Occupancy-packed level-kernel commit tests (ISSUE 10).

The tentpole restructures the device tile pass from n_actions serial
phases into the three-stage fused commit — chunk-wide guard matrix,
work-queue compaction, single-commit tiles (ONE FPSet insert batch +
ONE scatter per tile) — and the contract is BIT-IDENTITY with the
historical per-action body.  The whole existing tier-1 suite already
pins the fused default against fixed oracles (fused is the engine
default since ISSUE 10); this module adds the per-action comparison
legs and the seams the restructure touches:

* fused vs per-action bit-identity on the device/paged/sharded
  engines, including violation traces and a growth-pause re-entry at
  a mid-chunk boundary;
* the run_chained level-boundary rescue seam (satellite): cadence
  checkpoints, SIGTERM rescue, resume through run() bit-identical to
  the uninterrupted oracle, and the supervisor's chained mode degrade;
* exact-count cap growth + level-boundary calibration host logic;
* the obs surface: run_start `commit` key (key-set parity), and the
  `occupancy` / `inserts_per_tile` / `commit_mode` gauges.

An extended (pack x pipeline) per-action cross runs under -m slow —
the fused half of that cross is what every other module runs tier-1.
"""

import os
import signal

import numpy as np
import pytest

from tpuvsr.testing import (STUB_DISTINCT, STUB_LEVELS, counter_spec,
                            stub_device_engine, stub_engine_factory,
                            stub_sharded_engine)


def _trace_tuples(res):
    return [(t.action_name, tuple(sorted(t.state.items())))
            for t in (res.trace or [])]


# ---------------------------------------------------------------------
# fused vs per-action bit-identity
# ---------------------------------------------------------------------
def test_device_fused_vs_per_action_bit_identical():
    """Counts, level sizes and per-action expansion counters agree
    between the two commit modes (K=2 window, packed frontier); the
    fused run's need vector holds the exact per-action enabled maxima
    the chunk-wide guard matrix measured."""
    ea = stub_device_engine(pipeline=2)
    ra = ea.run()
    eb = stub_device_engine(pipeline=2, commit="per-action")
    rb = eb.run()
    assert ea.commit == "fused" and eb.commit == "per-action"
    assert ra.distinct_states == rb.distinct_states == STUB_DISTINCT
    assert ra.states_generated == rb.states_generated
    assert ea.level_sizes == eb.level_sizes == STUB_LEVELS
    assert list(ea._act_counts) == list(eb._act_counts)
    # exact counts: the widest level [(0,3),(1,2),(2,1),(3,0)] has 3
    # IncX-enabled and 3 IncY-enabled states in its (single) tile
    assert list(ea._need_seen) == [3, 3]


def test_device_violation_trace_bit_identical():
    """A reachable violation yields the SAME counterexample trace —
    same states, same actions — under both commit modes (the fused
    queue's first-occurrence dedup reproduces the per-action commit
    order for cross-action duplicate successors)."""
    ra = stub_device_engine(inv_bound=4).run()
    rb = stub_device_engine(inv_bound=4, commit="per-action").run()
    assert not ra.ok and not rb.ok
    assert ra.violated_invariant == rb.violated_invariant
    assert _trace_tuples(ra) == _trace_tuples(rb)
    assert ra.distinct_states == rb.distinct_states


def test_growth_pause_reentry_mid_chunk_bit_identical():
    """A next-buffer growth pause mid-chunk (next_capacity sized so
    the headroom gate trips mid-level) re-enters at the paused tile
    and still produces identical results in both modes (K=1, dense
    frontier — the other corner of the pack x pipeline cross)."""
    ea = stub_device_engine(pipeline=1, pack=False, next_capacity=8)
    ra = ea.run()
    eb = stub_device_engine(pipeline=1, pack=False, next_capacity=8,
                            commit="per-action")
    rb = eb.run()
    assert ra.distinct_states == rb.distinct_states == STUB_DISTINCT
    assert ra.states_generated == rb.states_generated
    assert ea.level_sizes == eb.level_sizes == STUB_LEVELS


@pytest.mark.slow
def test_paged_per_action_matches_oracle():
    """The paged engine shares the level kernel verbatim: its
    per-action leg stays pinned to the oracle (the fused leg runs all
    over tests/test_paged.py as the tier-1 default, and the device
    per-action leg above covers the shared body)."""
    from tpuvsr.engine.paged_bfs import PagedBFS
    e = stub_device_engine(cls=PagedBFS, chunk_tiles=2,
                           commit="per-action")
    r = e.run()
    assert r.distinct_states == STUB_DISTINCT
    assert e.level_sizes == STUB_LEVELS


def test_sharded_fused_vs_per_action_violation_bit_identical():
    """The sharded step's guard-compacted expansion (fused) buckets,
    dedups and traces exactly like the step_all dense expansion
    (per-action) — asserted on the unique-witness violation so the
    counterexample trace is compared too."""
    ra = stub_sharded_engine(n_devices=2, inv_x_bound=1).run()
    rb = stub_sharded_engine(n_devices=2, inv_x_bound=1,
                             commit="per-action").run()
    assert not ra.ok and not rb.ok
    assert ra.violated_invariant == rb.violated_invariant
    assert ra.distinct_states == rb.distinct_states
    assert _trace_tuples(ra) == _trace_tuples(rb)


# ---------------------------------------------------------------------
# exact-count growth + calibration (host logic; no engine run)
# ---------------------------------------------------------------------
def test_exact_growth_and_calibration():
    class _Obs:
        def __init__(self):
            self.grows = []

        def grow(self, what, to):
            self.grows.append((what, to))

    e = stub_device_engine(tile_size=16)
    obs = _Obs()
    # headroom growth: observed need 11 for action 0 -> cap
    # align8(4*11)=48 clamped to T*L_a=16; action 1 (need 2, cap 8)
    # untouched
    e._need_seen = np.array([11, 2], np.int64)
    e.expand_caps = [8, 8]
    e._grow_expand(0, obs, lambda m: None)
    assert e.expand_caps[0] == 16 and e.expand_caps[1] == 8
    assert ("expand_buffer", 16) in obs.grows
    # calibration shrinks over-grown caps onto 4x the observed maxima
    # only when a representative level was measured and >= 20% of
    # lanes are saved — and never below the static start (CAP_START
    # lanes per state; here clamped to the full T*L_a=16)
    e.expand_caps = [16, 16]
    e._need_seen = np.array([3, 3], np.int64)
    assert not e._calibrate_caps(obs, lambda m: None,
                                 level_states=16)   # < 4*tile
    assert not e._calibrate_caps(obs, lambda m: None, level_states=64)
    assert e.expand_caps == [16, 16]
    # a wider kernel (64 lanes/action: full T*L_a=1024) whose caps
    # grew to 512 calibrates down to max(static start 4*16, 4*need)
    e.kern._lane_count = lambda name: 64
    e.expand_caps = [512, 512]
    e._need_seen = np.array([3, 40], np.int64)
    assert e._calibrate_caps(obs, lambda m: None, level_states=64)
    assert e.expand_caps == [64, 160]
    # never shrinks below observation: a second call is a no-op
    assert not e._calibrate_caps(obs, lambda m: None, level_states=64)


# ---------------------------------------------------------------------
# run_chained rescue seam (satellite)
# ---------------------------------------------------------------------
def test_chained_checkpoint_seam_resumes_through_run(tmp_path):
    ck = str(tmp_path / "ck")
    e = stub_device_engine(chunk_tiles=1)
    r = e.run_chained(checkpoint_path=ck, checkpoint_every=0.0)
    assert r.ok and r.distinct_states == STUB_DISTINCT
    assert os.path.isdir(ck)
    e2 = stub_device_engine()
    r2 = e2.run(resume_from=ck)
    assert r2.ok and r2.distinct_states == STUB_DISTINCT
    assert e2.level_sizes == STUB_LEVELS


def test_chained_preempt_rescue_bit_identical(tmp_path):
    """A pending SIGTERM makes the chained window finish the in-flight
    level, write a run()-format rescue snapshot at the boundary, and
    exit resumable; the resumed run reaches the exact fixpoint."""
    from tpuvsr.resilience.supervisor import (Preempted,
                                              PreemptionGuard)
    ck = str(tmp_path / "rescue-ck")
    preempted = None
    with PreemptionGuard():
        os.kill(os.getpid(), signal.SIGTERM)
        try:
            stub_device_engine(chunk_tiles=1).run_chained(
                checkpoint_path=ck)
        except Preempted as p:
            preempted = p
    assert preempted is not None and preempted.path == ck
    res = stub_device_engine().run(resume_from=ck)
    assert res.ok and res.distinct_states == STUB_DISTINCT
    # the resumed trajectory is the uninterrupted one


def test_supervisor_chained_mode_degrades_on_resume(tmp_path):
    """-supervise + chained: a retry that has a snapshot resumes
    through the chunked engine, journaled as a mode degrade exactly
    like the fused one (ISSUE 10 satellite)."""
    from tpuvsr.resilience.supervisor import Supervisor
    spec = counter_spec()
    # the degrade path: feed it a resume snapshot
    e = stub_device_engine()
    e.run(checkpoint_path=str(tmp_path / "ck2"))
    sup2 = Supervisor(spec, engine="device", chained=True,
                      checkpoint_path=str(tmp_path / "ck2"),
                      engine_factory=stub_engine_factory(spec))
    res2 = sup2.run(resume_from=str(tmp_path / "ck2"))
    assert res2.ok and res2.distinct_states == STUB_DISTINCT
    assert sup2.summary()["chained"] is True
    assert ("mode", "chained", "chunked") in [
        tuple(d) for d in sup2.degrades]
    with pytest.raises(ValueError):
        Supervisor(spec, engine="device", fused=True, chained=True)


# ---------------------------------------------------------------------
# obs surface
# ---------------------------------------------------------------------
def test_commit_key_and_gauges(tmp_path):
    """run_start carries the commit key with key-set parity across
    engines (device: "fused"; interp: null), and the fused run reports
    occupancy / inserts_per_tile == 1 / commit_mode gauges."""
    from tpuvsr.engine.bfs import bfs_check
    from tpuvsr.obs import RunObserver, read_journal
    jp = str(tmp_path / "j.jsonl")
    e = stub_device_engine()
    r = e.run(obs=RunObserver(journal_path=jp))
    bfs_check(counter_spec(), obs=RunObserver(journal_path=jp))
    starts = [ev for ev in read_journal(jp)
              if ev["event"] == "run_start"]
    assert len(starts) == 2
    assert starts[0]["commit"] == "fused"
    assert "commit" in starts[1] and starts[1]["commit"] is None
    assert set(starts[0]) == set(starts[1])
    g = r.metrics["gauges"]
    assert g["inserts_per_tile"] == 1
    assert g["commit_mode"] == "fused"
    assert 0.0 < g["occupancy"] <= 1.0


# ---------------------------------------------------------------------
# extended cross (slow): per-action across modes x pack x K — the
# fused half of this cross is every other module's tier-1 default
# ---------------------------------------------------------------------
@pytest.mark.slow
@pytest.mark.parametrize("mode", ["run", "run_fused", "run_chained"])
@pytest.mark.parametrize("pack", [True, False], ids=["pack", "dense"])
@pytest.mark.parametrize("k", [1, 2])
def test_per_action_cross_matches_oracle(mode, pack, k):
    e = stub_device_engine(pipeline=k, pack=("auto" if pack else False),
                           chunk_tiles=2, commit="per-action")
    r = getattr(e, mode)()
    assert r.ok and r.distinct_states == STUB_DISTINCT
    assert e.level_sizes == STUB_LEVELS
