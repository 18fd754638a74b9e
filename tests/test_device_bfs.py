"""Differential tests: device BFS engine vs the interpreter oracle.

Distinct-state counts, per-level frontier sizes, diameters, and
invariant verdicts must agree between the TPU pipeline (dense kernel +
128-bit FPSet dedup) and the exact interpreter BFS (canonical-value
dedup) on small configs — the framework's analog of matching TLC's
distinct-state counts (SURVEY.md §4.7).
"""

import numpy as np
import pytest

from tests.conftest import (REFERENCE, explore_states, requires_reference,
                            vsr_spec)
from tpuvsr.core.values import ModelValue
from tpuvsr.engine.device_bfs import DeviceBFS, device_bfs_check
from tpuvsr.engine.fpset import dedup_batch, empty_table, insert_batch
from tpuvsr.engine.spec import SpecModel
from tpuvsr.frontend.cfg import parse_cfg_file
from tpuvsr.frontend.parser import parse_module_file


# ---------------------------------------------------------------------
# FPSet unit tests
# ---------------------------------------------------------------------
def test_fpset_insert_and_dup():
    rng = np.random.default_rng(7)
    fps = rng.integers(0, 2**32, size=(512, 4), dtype=np.uint64).astype(
        np.uint32)
    table = empty_table(1 << 12)
    mask = np.ones((512,), bool)
    table, fresh, ovf = insert_batch(table, fps, mask)
    assert not bool(ovf) and np.asarray(fresh).all()
    # same batch again: nothing fresh
    table, fresh2, _ = insert_batch(table, fps.copy(), mask)
    assert not np.asarray(fresh2).any()
    # half old, half new
    fps3 = np.concatenate([fps[:256], rng.integers(
        0, 2**32, size=(256, 4), dtype=np.uint64).astype(np.uint32)])
    table, fresh3, _ = insert_batch(table, fps3, mask)
    f3 = np.asarray(fresh3)
    assert not f3[:256].any() and f3[256:].all()


def test_fpset_grow_preserves_membership_with_zero_word0():
    # a fingerprint whose word 0 is 0 is claim-tag-remapped to 1; the
    # probe chain must be derived from the remapped key so a table
    # rebuilt by grow() still recognizes it as a duplicate
    from tpuvsr.engine.fpset import grow
    fps = np.array([[0, 11, 22, 33], [7, 1, 2, 3]], dtype=np.uint32)
    mask = np.ones((2,), bool)
    table = empty_table(1 << 8)
    table, fresh, _ = insert_batch(table, fps, mask)
    assert np.asarray(fresh).all()
    table = grow(table)
    table, fresh2, _ = insert_batch(table, fps.copy(), mask)
    assert not np.asarray(fresh2).any()


def test_fpset_overflow_reports_unresolved():
    # over-full table: insert reports ovf and the unresolved lanes are
    # NOT marked fresh (the engine grows the table and re-inserts)
    rng = np.random.default_rng(3)
    fps = rng.integers(0, 2**32, size=(128, 4), dtype=np.uint64).astype(
        np.uint32)
    mask = np.ones((128,), bool)
    table = empty_table(64)
    table, fresh, ovf = insert_batch(table, fps, mask)
    assert bool(ovf)
    n1 = int(np.asarray(fresh).sum())
    assert n1 < 128
    # grow + re-insert resolves the rest exactly once
    from tpuvsr.engine.fpset import grow
    table = grow(table)
    table, fresh2, ovf2 = insert_batch(table, fps.copy(), mask)
    assert not bool(ovf2)
    assert int(np.asarray(fresh2).sum()) == 128 - n1
    assert not (np.asarray(fresh) & np.asarray(fresh2)).any()


def test_fpset_dedup_batch():
    fps = np.array([[1, 2, 3, 4], [5, 6, 7, 8], [1, 2, 3, 4], [9, 9, 9, 9],
                    [5, 6, 7, 8]], dtype=np.uint32)
    mask = np.array([True, True, True, False, True])
    perm, keep = dedup_batch(fps, mask)
    kept = set(map(tuple, np.asarray(fps)[np.asarray(perm)][np.asarray(keep)]))
    assert kept == {(1, 2, 3, 4), (5, 6, 7, 8)}
    assert int(np.asarray(keep).sum()) == 2


# ---------------------------------------------------------------------
# engine differential tests
# ---------------------------------------------------------------------


def _interp_levels(spec, max_depth=None):
    """Exact per-level BFS frontier sizes via the interpreter."""
    seen = set()
    frontier = []
    for st in spec.init_states():
        k = spec.view_value(st)
        if k not in seen:
            seen.add(k)
            frontier.append(st)
    sizes = [len(frontier)]
    depth = 0
    while frontier and (max_depth is None or depth < max_depth):
        depth += 1
        nxt = []
        for st in frontier:
            for _a, succ in spec.successors(st):
                k = spec.view_value(succ)
                if k not in seen:
                    seen.add(k)
                    nxt.append(succ)
        frontier = nxt
        if nxt:
            sizes.append(len(nxt))
    return sizes, len(seen), depth


@requires_reference
def test_device_bfs_fixpoint_no_viewchange():
    # timer=0: only the normal-op sub-protocol is reachable
    spec = vsr_spec(values=("v1",), timer=0)
    sizes, total, diameter = _interp_levels(spec)
    eng = DeviceBFS(spec, tile_size=8)
    res = eng.run()
    assert res.ok and res.error is None
    assert res.distinct_states == total
    assert eng.level_sizes == sizes
    assert res.diameter == diameter


@requires_reference
def test_device_bfs_message_table_grows_in_place():
    # deliberately undersized message table: the engine must grow it
    # mid-run (padding preserves fingerprints) and still reach the same
    # fixpoint; the restart-era config puts fresh lanes at the top of
    # the (re-laid-out) lane space, catching stale lane bookkeeping
    spec = vsr_spec(values=("v1",), timer=0, restarts=1)
    sizes, total, _ = _interp_levels(spec)
    eng = DeviceBFS(spec, tile_size=8, max_msgs=2)
    res = eng.run()
    assert res.ok and res.distinct_states == total
    assert eng.level_sizes == sizes
    assert eng.codec.shape.MAX_MSGS > 2


@requires_reference
def test_device_bfs_incremental_hash_mode():
    spec = vsr_spec(values=("v1",), timer=0)
    _sizes, total, _ = _interp_levels(spec)
    eng = DeviceBFS(spec, tile_size=8, hash_mode="incremental")
    res = eng.run()
    assert res.ok and res.distinct_states == total


@requires_reference
def test_device_bfs_with_tiny_fpset_grows():
    # force FPSet growth mid-run; counts must be unaffected
    spec = vsr_spec(values=("v1",), timer=0)
    sizes, total, _ = _interp_levels(spec)
    eng = DeviceBFS(spec, tile_size=8, fpset_capacity=16)
    res = eng.run()
    assert res.ok and res.distinct_states == total
    assert eng.level_sizes == sizes


@requires_reference
@pytest.mark.slow
def test_device_bfs_levels_with_viewchange():
    spec = vsr_spec(values=("v1",), timer=1)
    sizes, total, _ = _interp_levels(spec, max_depth=5)
    eng = DeviceBFS(spec, tile_size=32)
    res = eng.run(max_depth=5)
    assert res.ok
    assert eng.level_sizes[:6] == sizes[:6]
    assert res.distinct_states == total


@requires_reference
@pytest.mark.slow
def test_device_bfs_recovery_fixpoint():
    # exercises RestartEmpty/Recovery*/CompleteRecovery and tombstone
    # revival on device to fixpoint
    spec = vsr_spec(values=("v1",), timer=0, restarts=1)
    sizes, total, _ = _interp_levels(spec)
    eng = DeviceBFS(spec, tile_size=32)
    res = eng.run()
    assert res.ok and res.error is None
    assert res.distinct_states == total
    assert eng.level_sizes == sizes


@requires_reference
@pytest.mark.slow
def test_device_bfs_symmetry_levels():
    # |Values|=2 with Permutations symmetry: device min-over-perm
    # fingerprints must induce the same partition as the interpreter's
    # canonical min-permutation view values
    spec = vsr_spec(values=("v1", "v2"), timer=1, symmetry=True)
    sizes, total, _ = _interp_levels(spec, max_depth=4)
    eng = DeviceBFS(spec, tile_size=32)
    res = eng.run(max_depth=4)
    assert res.ok
    assert eng.level_sizes[:5] == sizes[:5]
    assert res.distinct_states == total


@requires_reference
def test_invariant_kernels_match_interpreter():
    spec = vsr_spec(values=("v1", "v2"), timer=1)
    eng = DeviceBFS(spec)
    kern, codec = eng.kern, eng.codec
    states = explore_states(spec, 120)[::3]
    import jax
    for name in ("AcknowledgedWriteNotLost",
                 "AcknowledgedWritesExistOnMajority", "NoLogDivergence"):
        fn = jax.jit(kern.invariant_fn([name]))
        for st in states:
            dense = codec.encode(st)
            got = bool(fn({k: np.asarray(v) for k, v in dense.items()}))
            want = spec.eval_predicate(name, st)
            assert got == want, f"{name} differs"


def test_fpset_insert_duplicates_single_fresh():
    # claim-based insert must resolve intra-batch duplicate
    # fingerprints to exactly ONE fresh lane (losers must re-check the
    # contested slot, not probe past it — the round-2 lost-claim bug)
    rng = np.random.default_rng(11)
    base = rng.integers(1, 2**32, size=(64, 4), dtype=np.uint64).astype(
        np.uint32)
    fps = np.repeat(base, 4, axis=0)
    fps = fps[rng.permutation(len(fps))]
    mask = np.ones((len(fps),), bool)
    table = empty_table(1 << 10)
    table, fresh, ovf = insert_batch(table, fps, mask)
    fresh = np.asarray(fresh)
    assert not bool(ovf)
    assert int(fresh.sum()) == 64
    seen = set()
    for i in range(len(fps)):
        if fresh[i]:
            key = tuple(int(x) for x in fps[i])
            assert key not in seen
            seen.add(key)
    # nothing fresh on re-insert
    _, fresh2, _ = insert_batch(table, fps, mask)
    assert not np.asarray(fresh2).any()


# ---------------------------------------------------------------------
# checkpoint/resume
# ---------------------------------------------------------------------
@requires_reference
def test_checkpoint_resume_reaches_same_frontier(tmp_path):
    """Kill-and-resume: a run checkpointed at a level boundary must,
    after resuming in a FRESH engine, reach the same per-level frontier
    sizes and distinct count as an uninterrupted run (SURVEY.md §5
    checkpoint/resume; reference README:20 multi-day guidance)."""
    ckpt = str(tmp_path / "vsr.ckpt")
    spec = vsr_spec()
    eng1 = DeviceBFS(spec, tile_size=64)
    res1 = eng1.run(max_depth=5, checkpoint_path=ckpt)
    assert res1.error          # depth-limited, not fixpoint
    sizes_at_kill = list(eng1.level_sizes)

    # "crash": new engine object, resume from disk, continue deeper
    eng2 = DeviceBFS(vsr_spec(), tile_size=64)
    res2 = eng2.run(max_depth=9, resume_from=ckpt)
    # oracle: one uninterrupted run to the same depth
    eng3 = DeviceBFS(vsr_spec(), tile_size=64)
    res3 = eng3.run(max_depth=9)
    assert eng2.level_sizes == eng3.level_sizes
    assert eng2.level_sizes[:len(sizes_at_kill)] == sizes_at_kill
    assert res2.distinct_states == res3.distinct_states
    assert res2.states_generated == res3.states_generated


# ---------------------------------------------------------------------
# trace-once stages of the level body (ISSUE 26), on the committed
# native small check: no reference mount
# ---------------------------------------------------------------------
def _trace_level(eng):
    """Trace `eng`'s level program on shapes alone, under a build
    meter of its own; returns (shared calls, shared traces)."""
    import jax
    import jax.numpy as jnp
    from tpuvsr.obs import builds
    bufs = eng._alloc_bufs(eng.next_cap)
    i32 = jnp.zeros((), jnp.int32)
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype),
        ({"slots": empty_table(eng.fpset_capacity)["slots"]},
         bufs[0], i32, i32, *bufs, i32, jnp.zeros((), bool)))
    meter = builds.BuildMeter()
    previous = builds.attach(meter)
    try:
        eng._level.trace(*args, None, None, i32)
    finally:
        builds.detach(previous)
    return meter.shared_calls, meter.shared_traces


@pytest.mark.parametrize("hash_mode", [None, "incremental"])
@pytest.mark.parametrize("commit", ["fused", "per-action"])
def test_level_trace_runs_each_shared_stage_once(small_native, commit,
                                                 hash_mode):
    """19 actions use the fingerprint and the invariant function: 38
    uses, and the Python body of each runs once.  An engine at its
    defaults hashes whole successors (ISSUE 52); the incremental hash,
    which only `hash_mode` asks for now, is shared the same way."""
    kw = {} if hash_mode is None else {"hash_mode": hash_mode}
    eng = DeviceBFS(small_native, commit=commit, **kw)
    assert len(eng.kern.action_names) == 19
    assert eng._fp_incremental == (hash_mode == "incremental")
    assert _trace_level(eng) == (38, 2)


# what only the incremental hash leaves in a block stage's trace, by
# the names of the functions its equations come from: the scratch keys
# `seed_touch` adds, the slot writes `_touch` records in them inside
# the action functions, and the hash itself (the parts it starts from
# are the tile's, `kern.parent_parts` outside the block: an argument of
# the stage, `parts_row`)
INCREMENTAL_ONLY = {"seed_touch", "_touch", "fingerprint_incremental"}


def _default_engine(which, spec):
    """One engine of each class at its OWN defaults: no `hash_mode`
    given (the sharded engines take none)."""
    import jax
    from jax.sharding import Mesh
    from tpuvsr.engine.paged_bfs import PagedBFS
    from tpuvsr.parallel.sharded_bfs import ShardedBFS
    from tpuvsr.testing import stub_sharded_engine
    if which == "sharded-stub":
        return stub_sharded_engine()
    if which == "sharded":
        return ShardedBFS(spec, Mesh(np.array(jax.devices()[:2]), ("d",)))
    if which == "incremental":
        return DeviceBFS(spec, hash_mode="incremental")
    return {"device": DeviceBFS, "paged": PagedBFS}[which](spec)


@pytest.mark.parametrize("which", ["device", "paged", "sharded",
                                   "sharded-stub", "incremental"])
def test_every_engine_hashes_whole_successors_by_default(small_native,
                                                         which):
    """ISSUE 52: `Stage2` is built with the full hash by every engine
    at its defaults, and the trace of one block stage (an action that
    sends, under `vmap`; traced on types, nothing compiled) holds
    nothing of the incremental one: no parts among its arguments, no
    touch bookkeeping in the action function.  `hash_mode=
    "incremental"` is the control: the same search finds all of it."""
    import jax
    from tpuvsr.engine.device_bfs import I32
    eng = _default_engine(which, small_native)
    stage2, names = eng._stage2, eng.kern.action_names
    wanted = which == "incremental"
    assert stage2.incremental == wanted
    assert (stage2.parts_row is not None) == wanted
    aid, rows = (names.index("SendDVC") if "SendDVC" in names else 0), 8

    def batch(s):
        return jax.ShapeDtypeStruct((rows,) + s.shape, s.dtype)
    traced = stage2.expand_stage(aid, rows).trace(
        jax.tree_util.tree_map(batch, stage2.row),
        jax.tree_util.tree_map(batch, stage2.parts_row),
        jax.ShapeDtypeStruct((rows,), I32))
    text = traced.jaxpr.pretty_print(source_info=True)
    # an equation's source reads "file:line:col (Class.function)", or
    # "(Class.function.<locals>.inner)"
    found = {w for w in INCREMENTAL_ONLY
             if f".{w})" in text or f".{w}." in text}
    assert found == (INCREMENTAL_ONLY if wanted else set())


def test_grow_msgs_rebuilds_the_shared_stages(small_native):
    """A grown message table is a new kernel: its stages are made
    anew and traced anew, never carried over."""
    eng = DeviceBFS(small_native, max_msgs=8)
    assert _trace_level(eng) == (38, 2)
    old_kern, old_fp, old_inv = eng.kern, eng._fp_stage, eng._inv_stage
    assert old_fp.__wrapped__.__self__ is old_kern
    eng._grow_msgs([])
    assert eng.codec.shape.MAX_MSGS == 16 and eng.kern is not old_kern
    assert eng._fp_stage is not old_fp and eng._inv_stage is not old_inv
    assert eng._fp_stage.__wrapped__.__self__ is eng.kern
    assert eng._inv_stage.__wrapped__ is eng._inv
    assert _trace_level(eng) == (38, 2)


@pytest.mark.parametrize("hash_mode", ["incremental", "full"])
@pytest.mark.parametrize("commit", ["fused", "per-action"])
def test_small_check_exact_counts(small_native, small_pin, commit,
                                  hash_mode, empty_store):
    """The small check to its fixpoint under both tile bodies and both
    hash modes: the pinned level sizes, 43,941 distinct, diameter 24.
    The capacities are sized so that no buffer grows: every growth is
    one more build of the level program, most of this test's time.
    From an empty store, so that the program is traced here."""
    eng = DeviceBFS(small_native, commit=commit, hash_mode=hash_mode,
                    next_capacity=1 << 16, expand_mult=4)
    res = eng.run()
    assert res.ok and res.error is None
    assert list(eng.level_sizes) == small_pin
    assert (res.distinct_states, res.diameter) == (43941, 24)
    c = res.metrics["counters"]
    assert c["build_shared_calls"] % 38 == 0
    assert c["build_shared_traces"] == 2
