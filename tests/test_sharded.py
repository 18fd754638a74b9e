"""Multi-device tests on the virtual 8-device CPU mesh: the sharded
BFS driver (frontier data-parallel, fingerprint-ownership-partitioned
FPSet, single state+fp all_to_all exchange) must agree with the
single-device engine level by level.
"""

import os

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from tests.conftest import (reference_available, requires_reference,
                            vsr_spec)
from tpuvsr.engine.device_bfs import DeviceBFS
from tpuvsr.parallel.sharded_bfs import ShardedBFS

pytestmark = pytest.mark.skipif(len(jax.devices()) < 8,
                                reason="needs 8 virtual devices")

SMALL_CFG = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "examples", "VSR_small.cfg")


def _mesh8():
    return Mesh(np.array(jax.devices()[:8]), ("d",))


def _small_spec():
    """`vsr_spec()`'s constants are examples/VSR_small.cfg's.  The
    tests that compare counts alone need no AST: where the reference
    corpus is not mounted they take the kernel-native spec of that cfg
    instead of skipping."""
    if reference_available():
        return vsr_spec()
    from tpuvsr.engine.spec import load_spec
    return load_spec("VSR", SMALL_CFG)


def test_sharded_bfs_levels_match_single_device():
    """The full multi-chip BFS driver must produce identical per-level
    frontier sizes and distinct-state counts as the single-device
    engine.  Depth 8 with tile 8 forces MULTI-TILE levels (per-device
    frontier > tile from level ~5): r2-r4 carried a dedup regression
    where each tile inserted into the step's constant table argument
    instead of the carried one, so tile t+1 re-admitted tile t's
    successors — invisible at single-tile depths (the old depth-4
    version of this test)."""
    spec = _small_spec()
    sbfs = ShardedBFS(spec, _mesh8(), tile=8, bucket_cap=512,
                      next_capacity=1 << 10, fpset_capacity=1 << 12)
    res = sbfs.run(max_depth=8)
    eng = DeviceBFS(spec, tile_size=64)
    res1 = eng.run(max_depth=8)
    assert sbfs.level_sizes == eng.level_sizes
    assert res.distinct_states == res1.distinct_states
    assert res.states_generated == res1.states_generated
    # exchange metric: every distinct non-init state crossed the wire
    # exactly once as a useful row (init states are placed, not sent);
    # wire volume is the static full-bucket traffic and bounds it
    ex = res.exchange
    assert ex["useful_rows"] >= res.distinct_states - 1
    assert ex["wire_rows"] >= ex["useful_rows"]
    assert ex["useful_bytes"] == ex["useful_rows"] * ex["row_bytes"]


@requires_reference
@pytest.mark.slow
def test_sharded_bfs_finds_violation_with_trace():
    """A seeded violation must surface from the sharded driver with a
    replayable trace that the interpreter confirms.  (slow: the
    two-invariant kernels are a separate multi-minute CPU compile)"""
    spec = vsr_spec(values=("v1",), timer=1,
                    invariants=["AcknowledgedWritesExistOnMajority",
                                "AcknowledgedWriteNotLost"])
    sbfs = ShardedBFS(spec, _mesh8(), tile=16, bucket_cap=512,
                      next_capacity=1 << 10, fpset_capacity=1 << 12)
    res = sbfs.run(max_depth=12)
    # the small config violates AcknowledgedWritesExistOnMajority (a
    # committed write exists on primary+1 backup = majority of 3, so it
    # does NOT violate; guard against silent pass by checking both ways
    # against the single-device engine)
    eng = DeviceBFS(spec, tile_size=64)
    res1 = eng.run(max_depth=12)
    assert res.ok == res1.ok
    if not res.ok:
        # engines may surface different same-depth witnesses; each must
        # be interpreter-confirmed (exploration order differs)
        assert res.violated_invariant is not None
        assert res.trace is not None
        assert spec.check_invariants(res.trace[-1].state) is not None


@pytest.mark.slow
def test_sharded_bfs_fixpoint_small():
    """Sharded fixpoint on the shrunken flagship config matches the
    golden distinct-state count (43,941; BASELINE.json configs[0])."""
    spec = _small_spec()
    sbfs = ShardedBFS(spec, _mesh8(), tile=64, bucket_cap=4096,
                      next_capacity=1 << 13, fpset_capacity=1 << 14)
    res = sbfs.run()
    assert res.error is None
    assert res.ok
    assert res.distinct_states == 43941
    assert res.diameter == 24


def test_sharded_checkpoint_resume(tmp_path):
    """Kill-and-resume parity (VERDICT r3 item 7): a sharded run
    checkpointed at a level boundary must, resumed in a FRESH driver,
    reach the same per-level frontier sizes and distinct count as an
    uninterrupted sharded run."""
    ckpt = str(tmp_path / "sharded.ckpt")
    spec = _small_spec()
    s1 = ShardedBFS(spec, _mesh8(), tile=16, bucket_cap=512,
                    next_capacity=1 << 10, fpset_capacity=1 << 12)
    r1 = s1.run(max_depth=3, checkpoint_path=ckpt)
    assert r1.error                       # depth-limited
    sizes_at_kill = list(s1.level_sizes)

    s2 = ShardedBFS(_small_spec(), _mesh8(), tile=16, bucket_cap=512,
                    next_capacity=1 << 10, fpset_capacity=1 << 12)
    r2 = s2.run(max_depth=5, resume_from=ckpt)
    s3 = ShardedBFS(_small_spec(), _mesh8(), tile=16, bucket_cap=512,
                    next_capacity=1 << 10, fpset_capacity=1 << 12)
    r3 = s3.run(max_depth=5)
    assert s2.level_sizes == s3.level_sizes
    assert s2.level_sizes[:len(sizes_at_kill)] == sizes_at_kill
    assert r2.distinct_states == r3.distinct_states
    assert r2.states_generated == r3.states_generated


@requires_reference
def test_sharded_elastic_resume_across_mesh_sizes(tmp_path):
    """ISSUE 5: a 4-shard checkpoint of the real VSR spec resumed on
    M = 2 (shrink) and M = 8 (grow) devices reproduces the
    uninterrupted run's per-level frontier sizes and distinct/generated
    counts exactly — the reshard-on-load path on a real kernel."""
    ckpt = str(tmp_path / "elastic.ckpt")
    spec = vsr_spec()
    mesh4 = Mesh(np.array(jax.devices()[:4]), ("d",))
    s1 = ShardedBFS(spec, mesh4, tile=16, bucket_cap=512,
                    next_capacity=1 << 10, fpset_capacity=1 << 12)
    r1 = s1.run(max_depth=3, checkpoint_path=ckpt)
    assert r1.error                       # depth-limited

    oracle = ShardedBFS(vsr_spec(), mesh4, tile=16, bucket_cap=512,
                        next_capacity=1 << 10, fpset_capacity=1 << 12)
    ro = oracle.run(max_depth=5)
    for m in (2, 8):
        mesh = Mesh(np.array(jax.devices()[:m]), ("d",))
        s2 = ShardedBFS(vsr_spec(), mesh, tile=16, bucket_cap=512,
                        next_capacity=1 << 10, fpset_capacity=1 << 12)
        r2 = s2.run(max_depth=5, resume_from=ckpt)
        assert s2.resharded_from == 4
        assert s2.level_sizes == oracle.level_sizes
        assert r2.distinct_states == ro.distinct_states
        assert r2.states_generated == ro.states_generated


@requires_reference
def test_sharded_checkpoint_rejects_wrong_spec(tmp_path):
    ckpt = str(tmp_path / "sharded.ckpt")
    spec = vsr_spec()
    s1 = ShardedBFS(spec, _mesh8(), tile=16, bucket_cap=512,
                    next_capacity=1 << 10, fpset_capacity=1 << 12)
    s1.run(max_depth=3, checkpoint_path=ckpt)
    other = vsr_spec(values=("v1", "v2"))
    s2 = ShardedBFS(other, _mesh8(), tile=16, bucket_cap=512,
                    next_capacity=1 << 10, fpset_capacity=1 << 12)
    with pytest.raises(ValueError, match="different spec"):
        s2.run(resume_from=ckpt)


@requires_reference
@pytest.mark.slow
def test_sharded_deadlock_reporting():
    """The sharded driver must surface a deadlock (a state with no
    enabled successor) with a replayable trace whose final state the
    interpreter confirms has no successors — parity with the
    single-device engine's -deadlock path."""
    spec = vsr_spec(values=("v1",), timer=0)
    eng = DeviceBFS(spec, tile_size=8)
    r1 = eng.run(check_deadlock=True)
    sbfs = ShardedBFS(vsr_spec(values=("v1",), timer=0), _mesh8(),
                      tile=8, bucket_cap=256, next_capacity=1 << 8,
                      fpset_capacity=1 << 10, check_deadlock=True)
    r2 = sbfs.run()
    assert (r1.error == "deadlock") == (r2.error == "deadlock")
    if r2.error == "deadlock":
        assert r2.deadlock_state is not None
        assert not list(spec.successors(r2.deadlock_state))
        assert r2.trace is not None
        # the trace must replay to the deadlocked state
        from tests.conftest import state_key
        assert state_key(r2.trace[-1].state) == state_key(
            r2.deadlock_state)


@requires_reference
@pytest.mark.slow
def test_sharded_recovery_era_spec_levels():
    """A recovery-era spec (CP06, 22 actions, checkpoint shapes — the
    layout stress test) through the sharded driver: per-level parity
    with the single-device engine (VERDICT r3 item 7)."""
    from tpuvsr.engine.spec import load_spec
    spec = load_spec(
        "/root/reference/vsr-revisited/paper/analysis/"
        "06-replica-recovery-cp/VR_REPLICA_RECOVERY_CP.tla",
        "examples/VR_REPLICA_RECOVERY_CP_small.cfg")
    sbfs = ShardedBFS(spec, _mesh8(), tile=16, bucket_cap=1024,
                      next_capacity=1 << 10, fpset_capacity=1 << 12)
    res = sbfs.run(max_depth=4)
    spec2 = load_spec(
        "/root/reference/vsr-revisited/paper/analysis/"
        "06-replica-recovery-cp/VR_REPLICA_RECOVERY_CP.tla",
        "examples/VR_REPLICA_RECOVERY_CP_small.cfg")
    eng = DeviceBFS(spec2, tile_size=64)
    res1 = eng.run(max_depth=4)
    assert sbfs.level_sizes == eng.level_sizes
    assert res.distinct_states == res1.distinct_states
    assert res.states_generated == res1.states_generated
