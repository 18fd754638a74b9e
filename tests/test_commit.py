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
* exact-count cap growth + level-boundary calibration host logic;
* the obs surface: run_start `commit` key (key-set parity), and the
  `occupancy` / `commit_mode` gauges.

An extended (pack x pipeline) per-action cross runs under -m slow —
the fused half of that cross is what every other module runs tier-1.
"""

import os

import numpy as np
import pytest

from tpuvsr.testing import (STUB_DISTINCT, STUB_LEVELS, counter_spec,
                            stub_device_engine, stub_sharded_engine)


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
# stage 2 in blocks (ISSUE 28): only the blocks of an action's segment
# that hold an enabled lane are expanded
# ---------------------------------------------------------------------
def _pointers(e):
    e._flush_pointers()
    return [np.concatenate([np.asarray(x) for x in h]).tolist()
            for h in (e._h_parent, e._h_action, e._h_param)]


# (tile, block, engine keywords).  The stub's actions have one lane a
# state, so an action's cap is the tile and its count the states of
# the tile that enable it: 1, 2, 3, 3, 2, 1, 0 down the levels.
BLOCK_CASES = {
    # cap 4 = two blocks: counts 2 (== block) and 3 (== block + 1)
    "cap4-block2": (4, 2, {}),
    # cap 3 is no multiple: the second block is clamped onto rows 1-2
    "cap3-block2-clamped": (3, 2, {}),
    # count 3 == block, the clamped second block never runs
    "cap4-block3": (4, 3, {}),
    "cap4-block1": (4, 1, {}),
    # Jump is never enabled: its segment runs no block in any tile
    "dead-action": (4, 2, {"dead_action": True, "bounds": False}),
    # the next-buffer pause and re-entry of the test above, in blocks
    "pause-reentry": (4, 2, {"pipeline": 1, "pack": False,
                             "next_capacity": 8}),
    "pause-reentry-clamped": (3, 2, {"pipeline": 1, "pack": False,
                                     "next_capacity": 8}),
}


@pytest.mark.parametrize("case", list(BLOCK_CASES))
def test_expand_blocks_bit_identical(monkeypatch, case):
    """Fused in blocks against per-action: levels, counters and trace
    pointers, and the counterexample of a reachable violation."""
    from tpuvsr.engine import device_bfs
    tile, block, kw = BLOCK_CASES[case]
    monkeypatch.setattr(device_bfs, "EXPAND_BLOCK", block)
    ea = stub_device_engine(tile_size=tile, **kw)
    ra = ea.run()
    eb = stub_device_engine(tile_size=tile, commit="per-action", **kw)
    rb = eb.run()
    assert ra.distinct_states == rb.distinct_states == STUB_DISTINCT
    assert ra.states_generated == rb.states_generated
    assert ea.level_sizes == eb.level_sizes == STUB_LEVELS
    assert list(ea._act_counts) == list(eb._act_counts)
    assert _pointers(ea) == _pointers(eb)
    blocks = list(ea._blocks_act)
    assert all(b > 0 for b in blocks[:2])
    if kw.get("dead_action"):
        assert ea.kern.action_names[2] == "Jump" and blocks[2] == 0
    if case == "cap4-block2":
        # one tile a level: ceil(count / 2) = 1, 1, 2, 2, 1, 1, 0
        assert blocks == [8, 8]
    va = stub_device_engine(tile_size=tile, inv_bound=4, **kw).run()
    vb = stub_device_engine(tile_size=tile, inv_bound=4,
                            commit="per-action", **kw).run()
    assert not va.ok and va.violated_invariant == vb.violated_invariant
    assert _trace_tuples(va) == _trace_tuples(vb)
    assert va.distinct_states == vb.distinct_states


# DeviceBFS on examples/VSR_small.cfg to depth 6 at the parent of
# ISSUE 28 (one vmap over every cap lane of every action): sha256 of
# its parent / action / lane trace pointers, and its per-action
# expansion counts
SMALL_POINTERS_DEPTH6 = (
    "2647ffe144f8c1e07342594cd9e8b40581865af1ce7b7ce1b6a3aee3f915edb8")
SMALL_ACTS_DEPTH6 = [28, 198, 566, 333, 10, 69, 0, 0, 13, 35, 34, 17,
                     0, 0, 0, 0, 0, 0, 0]


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _small_engine():
    from tpuvsr.engine.device_bfs import DeviceBFS
    from tpuvsr.engine.spec import load_spec
    return DeviceBFS(load_spec(
        "VSR", os.path.join(REPO, "examples", "VSR_small.cfg")))


def _assert_small_depth6(eng, res):
    """The pinned levels, per-action counts and pointer digest of the
    small check to depth 6."""
    import hashlib
    import json
    with open(os.path.join(REPO, "scripts",
                           "pinned_levels_small.json")) as f:
        pin = json.load(f)["level_sizes"][:7]
    assert res.ok and list(eng.level_sizes) == pin
    assert res.distinct_states == sum(pin)
    assert list(eng._act_counts) == SMALL_ACTS_DEPTH6
    digest = hashlib.sha256()
    for plane in _pointers(eng):
        digest.update(np.asarray(plane, np.int64).tobytes())
    assert digest.hexdigest() == SMALL_POINTERS_DEPTH6


@pytest.mark.parametrize("block", [8, 40])
def test_expand_blocks_small_check_pinned(monkeypatch, block):
    """The pinned small check in blocks of 8 (which divides the caps
    of 384 and 512) and of 40 (which does not: the last block is
    clamped): segments of many blocks, most of them never run."""
    from tpuvsr.engine import device_bfs
    monkeypatch.setattr(device_bfs, "EXPAND_BLOCK", block)
    eng = _small_engine()
    res = eng.run(max_depth=6)
    _assert_small_depth6(eng, res)
    caps = eng._expand_caps()
    assert any(cap % block for cap in caps) == (block == 40)
    per_tile = sum(-(-cap // block) for cap in caps)
    blocks = eng._blocks_act
    assert blocks.max() > eng._tiles_done        # many blocks a tile
    assert blocks.sum() * 4 < eng._tiles_done * per_tile
    assert [b == 0 for b in blocks] == [a == 0 for a in eng._act_counts]


# ---------------------------------------------------------------------
# stage 3 in pieces (ISSUE 30): stage 2 appends its blocks, packed, at
# the running end of one dense commit queue, and the written prefix is
# committed COMMIT_PIECE lanes at a time
# ---------------------------------------------------------------------
def _jump_in_the_middle(**kw):
    """The dead-action kernel with its actions ordered IncX, Jump,
    IncY: in every tile the never-enabled Jump writes no block between
    two actions that do."""
    from tpuvsr.testing import stub_model_factory
    make = stub_model_factory(dead_action=True, **kw)
    order = [0, 2, 1]

    def factory(spec, max_msgs=None):
        codec, kern = make(spec, max_msgs=max_msgs)

        class Mid(type(kern)):
            action_names = [kern.action_names[i] for i in order]

            def _guard_fns(self):
                fns = super()._guard_fns()
                return [fns[i] for i in order]

            def _action_fns(self):
                fns = super()._action_fns()
                return [fns[i] for i in order]

        return codec, Mid()
    return factory


# (tile, piece, block or None for the module's, engine keywords).  The
# stub's queue holds the two caps of 4 = 8 lanes: a block of 4 makes an
# action's region 4 lanes wide wherever a state of the tile enables it.
# Level 1 holds (1,0) and (0,1): IncX gives (2,0), (1,1) and IncY gives
# (1,1), (0,2), so at pieces of 2 or 3 the fingerprint of (1,1) comes
# in two different pieces and the earlier one, IncX's, has to win: the
# trace pointers say so.
PIECE_CASES = {
    # a queue no wider than one piece: a static shape and no loop
    "one-piece": (4, 8, None, {}),
    "pieces-of-2": (4, 2, None, {}),
    # the piece boundaries 3 and 6 fall inside the second blocks of
    # IncX's and IncY's regions; the queue is padded to 9
    "boundary-inside-an-action": (4, 3, 2, {}),
    # tile 3: caps of 3, blocks of 2, the clamped last block, pieces
    # of 4 over a queue of 6 padded to 8
    "clamped-block": (3, 4, 2, {}),
    "zero-blocks-between": (4, 2, None, {
        "jump_mid": True, "bounds": False}),
    # a table of 2 slots holds Init and IncX's successor of piece 0:
    # IncY's, in piece 1, finds no slot in MAX_PROBES, the table grows
    # and the tile is entered again
    "probe-overflow-in-second-piece": (4, 4, None, {
        "fpset_capacity": 2}),
    # tile 2: a queue of 4, and a next buffer of 4 rows that the
    # second tile of a level finds without room for them
    "next-buffer-pause-reentry": (2, 2, None, {
        "pipeline": 1, "pack": False, "next_capacity": 4}),
}


def _piece_engine(tile, inv_bound=None, jump_mid=False, **kw):
    if not jump_mid:
        return stub_device_engine(tile_size=tile, inv_bound=inv_bound,
                                  **kw)
    from tpuvsr.engine.device_bfs import DeviceBFS
    return DeviceBFS(
        counter_spec(inv_bound, dead_action=True),
        model_factory=_jump_in_the_middle(inv_bound=inv_bound),
        hash_mode="full", tile_size=tile, fpset_capacity=1 << 8,
        next_capacity=1 << 6, **kw)


@pytest.mark.parametrize("case", list(PIECE_CASES))
def test_commit_pieces_bit_identical(monkeypatch, case):
    """Fused with stage 3 in pieces against per-action: levels,
    counters and trace pointers, and the counterexample of a reachable
    violation."""
    from tpuvsr.engine import device_bfs
    tile, piece, block, kw = PIECE_CASES[case]
    monkeypatch.setattr(device_bfs, "COMMIT_PIECE", piece)
    if block:
        monkeypatch.setattr(device_bfs, "EXPAND_BLOCK", block)
    ea = _piece_engine(tile, **kw)
    ra = ea.run()
    eb = _piece_engine(tile, commit="per-action", **kw)
    rb = eb.run()
    assert ra.distinct_states == rb.distinct_states == STUB_DISTINCT
    assert ra.states_generated == rb.states_generated
    assert ea.level_sizes == eb.level_sizes == STUB_LEVELS
    assert list(ea._act_counts) == list(eb._act_counts)
    assert _pointers(ea) == _pointers(eb)
    total = sum(ea._expand_caps())
    width = min(piece, total)
    assert ea._commit_run % width == 0
    pieces = ea._commit_run // width
    if case == "one-piece":
        assert pieces == ea._tiles_done == 7
    else:
        assert pieces > ea._tiles_done      # several pieces a tile
    if case == "zero-blocks-between":
        assert ea.kern.action_names == ["IncX", "Jump", "IncY"]
        assert list(ea._blocks_act > 0) == [True, False, True]
    for r in (ra, rb):
        grew = r.metrics["counters"].get("grow_fpset", 0)
        assert (grew > 0) == (case == "probe-overflow-in-second-piece")
        assert (r.metrics["counters"].get("grow_next_buffer", 0) > 0) \
            == (case == "next-buffer-pause-reentry")
    va = _piece_engine(tile, inv_bound=4, **kw).run()
    vb = _piece_engine(tile, inv_bound=4, commit="per-action", **kw).run()
    assert not va.ok and va.violated_invariant == vb.violated_invariant
    assert _trace_tuples(va) == _trace_tuples(vb)
    assert va.distinct_states == vb.distinct_states


@pytest.mark.parametrize("paged", [False, True], ids=["run", "paged"])
def test_commit_pieces_por(monkeypatch, paged):
    """`-por on` at a piece smaller than the queue: the proviso probes
    the whole queue on the table as it stood before the first piece
    committed, so the reduced run is the one-piece run."""
    from tpuvsr.engine import device_bfs
    from tpuvsr.engine.paged_bfs import PagedBFS
    from tpuvsr.testing import (POR_STUB_DISTINCT, POR_STUB_FULL,
                                POR_STUB_KEPT, POR_STUB_LEVELS)
    runs = []
    for piece in (8, 2):
        monkeypatch.setattr(device_bfs, "COMMIT_PIECE", piece)
        e = stub_device_engine(cls=PagedBFS if paged else None,
                               spec=counter_spec(inv_free=True),
                               por="on")
        r = e.run(check_deadlock=True)
        assert r.error == "deadlock"
        assert r.distinct_states == POR_STUB_DISTINCT
        assert r.levels == POR_STUB_LEVELS
        assert (e._por_kept, e._por_full) == (POR_STUB_KEPT,
                                              POR_STUB_FULL)
        runs.append((_pointers(e), list(e._act_counts), e._por_amp,
                     _trace_tuples(r)))
    assert runs[0] == runs[1]


@pytest.mark.parametrize("over", [
    {},
    # pauses with edges appended past `edge_n`: a tiny edge buffer
    # (R_EDGE_FLUSH), a tiny FPSet (R_FPSET_GROW), a tiny next buffer
    dict(edge_capacity=16, fpset_capacity=1 << 4, next_capacity=1 << 4),
], ids=["roomy", "tiny-buffers"])
def test_commit_pieces_edges_csr(monkeypatch, over):
    """`PagedBFS(edges=True)` at a piece smaller than the queue: every
    lane resolves its `dst` after its own piece's insert, and the CSR
    and the gid order are the one-piece build's."""
    from tpuvsr.engine import device_bfs
    from tpuvsr.engine.device_liveness import DeviceGraph
    from tpuvsr.testing import canon_csr, stub_ticker_factory, ticker_spec
    spec = ticker_spec(modulus=6)
    kw = dict(tile_size=4, chunk_tiles=2, next_capacity=32,
              fpset_capacity=1 << 8, hash_mode="full",
              model_factory=stub_ticker_factory(modulus=6))
    kw.update(over)
    graphs = []
    for piece in (1 << 10, 3):
        monkeypatch.setattr(device_bfs, "COMMIT_PIECE", piece)
        graphs.append(DeviceGraph(spec, mode="stream", **kw))
    whole, pieces = graphs
    assert pieces.n == whole.n == 12
    assert canon_csr(pieces) == canon_csr(whole)
    assert [pieces.states[i] for i in range(pieces.n)] == \
        [whole.states[i] for i in range(whole.n)]


@pytest.fixture(scope="module")
def small_engine():
    """One DeviceBFS on examples/VSR_small.cfg for the module: a new
    COMMIT_PIECE makes a new level program, on the block functions the
    kernel has traced."""
    return _small_engine()


@pytest.mark.parametrize("piece", [384, 1000])
def test_commit_pieces_small_check_pinned(monkeypatch, small_engine,
                                          piece):
    """The pinned small check at pieces of 384 (23 of them in the
    queue of 8,832) and of 1,000 (the queue is padded to 9,000): the
    levels, the per-action counts and the pointer digest that
    `test_expand_blocks_small_check_pinned` holds, which the parent of
    ISSUE 28 gave with one batch over every cap lane."""
    from tpuvsr.engine import device_bfs
    monkeypatch.setattr(device_bfs, "COMMIT_PIECE", piece)
    eng = small_engine
    eng._level_jit = None
    eng._fresh_jit = True
    res = eng.run(max_depth=6)
    _assert_small_depth6(eng, res)
    total = sum(eng._expand_caps())
    assert total == 8832 and (total % piece != 0) == (piece == 1000)
    c, g = res.metrics["counters"], res.metrics["gauges"]
    assert c["commit_lanes_cap"] == eng._tiles_done * total
    # a tile's written prefix is a few blocks of 128: most of the
    # queue's pieces never run
    assert eng._tiles_done * piece < c["commit_lanes_run"]
    assert c["commit_lanes_run"] * 3 < c["commit_lanes_cap"]
    assert g["commit_occupancy"] == round(
        sum(SMALL_ACTS_DEPTH6) / c["commit_lanes_run"], 4)


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
# obs surface
# ---------------------------------------------------------------------
def test_commit_key_and_gauges(tmp_path):
    """run_start carries the commit key with key-set parity across
    engines (device: "fused"; interp: null), and the fused run reports
    occupancy / commit_mode gauges."""
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
    assert g["commit_mode"] == "fused"
    assert "inserts_per_tile" not in g     # a constant of commit_mode
    assert 0.0 < g["occupancy"] <= 1.0


@pytest.mark.parametrize("mode", ["run", "paged"])
def test_expand_block_counters(monkeypatch, mode):
    """`expand_blocks_run` of `expand_blocks_cap`, and occupancy over
    the lanes the device expanded, from both loops that run the level
    program."""
    from tpuvsr.engine import device_bfs
    from tpuvsr.engine.paged_bfs import PagedBFS
    block = 2
    monkeypatch.setattr(device_bfs, "EXPAND_BLOCK", block)
    e = stub_device_engine(cls=PagedBFS if mode == "paged" else None,
                           dead_action=True, bounds=False,
                           chunk_tiles=2)
    r = e.run()
    assert r.ok and r.distinct_states == STUB_DISTINCT
    c, g = r.metrics["counters"], r.metrics["gauges"]
    acts = g["action_expansions"]
    assert c["expand_blocks_run"] == int(e._blocks_act.sum()) == 16
    # 7 tiles (one a level), three caps of 4 lanes = 2 blocks each
    assert c["expand_blocks_cap"] == 7 * 3 * 2
    assert c["expand_blocks_run"] <= c["expand_blocks_cap"]
    assert g["occupancy"] == round(
        sum(acts.values()) / (c["expand_blocks_run"] * block), 4)
    assert acts["Jump"] == 0 and e._blocks_act[2] == 0
    # per-action expands every cap lane of every tile, in no blocks
    rp = stub_device_engine(dead_action=True, bounds=False,
                            commit="per-action").run()
    assert "expand_blocks_run" not in rp.metrics["counters"]
    assert rp.metrics["gauges"]["occupancy"] == round(
        sum(acts.values()) / (7 * 3 * 4), 4)


@pytest.mark.parametrize("mode", ["run", "paged"])
def test_commit_lane_counters(monkeypatch, mode):
    """`commit_lanes_run` of `commit_lanes_cap`, and `commit_occupancy`
    = items over the lanes stage 3 walked, from both loops that run
    the level program."""
    from tpuvsr.engine import device_bfs
    from tpuvsr.engine.paged_bfs import PagedBFS
    monkeypatch.setattr(device_bfs, "COMMIT_PIECE", 2)
    e = stub_device_engine(cls=PagedBFS if mode == "paged" else None,
                           chunk_tiles=2)
    r = e.run()
    assert r.ok and r.distinct_states == STUB_DISTINCT
    c, g = r.metrics["counters"], r.metrics["gauges"]
    # 7 tiles (one a level) of two caps of 4 lanes; in the first six
    # both actions write their block of 4 = four pieces of 2, and the
    # last state enables nothing: no piece
    assert c["commit_lanes_cap"] == 7 * 8
    assert c["commit_lanes_run"] == e._commit_run == 6 * 4 * 2
    assert c["commit_lanes_run"] <= c["commit_lanes_cap"]
    items = sum(g["action_expansions"].values())
    assert g["commit_occupancy"] == round(
        items / c["commit_lanes_run"], 4)
    # per-action commits action by action: no queue, no pieces
    rp = stub_device_engine(commit="per-action").run()
    assert "commit_lanes_run" not in rp.metrics["counters"]
    assert "commit_occupancy" not in rp.metrics["gauges"]


# ---------------------------------------------------------------------
# extended cross (slow): per-action across pack x K — the fused half
# of this cross is every other module's tier-1 default
# ---------------------------------------------------------------------
@pytest.mark.slow
@pytest.mark.parametrize("pack", [True, False], ids=["pack", "dense"])
@pytest.mark.parametrize("k", [1, 2])
def test_per_action_cross_matches_oracle(pack, k):
    e = stub_device_engine(pipeline=k, pack=("auto" if pack else False),
                           chunk_tiles=2, commit="per-action")
    r = e.run()
    assert r.ok and r.distinct_states == STUB_DISTINCT
    assert e.level_sizes == STUB_LEVELS
