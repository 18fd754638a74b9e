"""The sharded step's stage 2 is the one-chip level program's (ISSUE
50): per action only the blocks of its segment that hold an enabled
lane are expanded, into one dense queue (`engine/device_bfs.Stage2`).

On two of the virtual CPU devices, at the defect cfg (the four-chip
cell's) and at the shipped cfg with symmetry on, the fused sharded run
is held to `commit="per-action"` (the dense `step_all` expansion, the
bit-for-bit oracle: levels, counts, pointer planes, the rows and the
FPSet entries each shard holds) and to `DeviceBFS`.  A tile of 16
states gives caps of 64 (M-lane actions: two blocks of 32) and 48
(R-lane actions: the second block clamped onto the first), so most
tiles skip blocks; the caps are then cut to the exact per-tile needs
the first run observed, so that a tile fills a cap to its last slot,
and one below that, so that the step pauses with R_EXPAND_GROW.
"""

import glob
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from tpuvsr.engine.device_bfs import (EXPAND_BLOCK, _align8, block_rows,
                                      static_cap)
from tpuvsr.engine.spec import load_spec
from tpuvsr.obs import spans
from tpuvsr.parallel.sharded_bfs import ShardedBFS

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
D = 2
TILE = 16
# cfg, max_msgs, depth compared (defect: 1,145 states; shipped: 1,776
# orbits)
CASES = {"defect": ("vsr-defect.cfg", 32, 5),
         "shipped": ("vsr-shipped.cfg", 32, 6)}

pytestmark = pytest.mark.skipif(len(jax.devices()) < D,
                                reason=f"needs {D} virtual devices")


def _engine(spec, name, **kw):
    _cfg, max_msgs, _depth = CASES[name]
    return ShardedBFS(
        spec, Mesh(np.array(jax.devices()[:D]), ("d",)), max_msgs=max_msgs,
        tile=TILE, bucket_cap=128, next_capacity=1 << 10,
        fpset_capacity=1 << 12, **kw)


def _recording_needs(eng, into):
    """Every dispatch's `need` output ([D, A]: the largest per-tile
    count of each action a shard saw) appended to `into`."""
    step = eng._step

    def recorded(*args):
        out = step(*args)
        into.append(out[13])
        return out

    eng._step = recorded


class Ran:
    """What one run of an engine leaves: the result, the levels, the
    per-action counts, the three pointer planes, and the snapshot of
    its end (the rows and FPSet entries each shard holds)."""

    def __init__(self, eng, depth, path):
        from tpuvsr.engine.checkpoint import load_checkpoint
        self.log = []
        self.res = eng.run(max_depth=depth, checkpoint_path=path,
                           log=self.log.append)
        self.levels = list(eng.level_sizes)
        self.acts = [int(x) for x in eng._act_counts]
        self.pointers = [np.concatenate(h) for h in (
            eng._h_parent, eng._h_action, eng._h_param)]
        ck = load_checkpoint(path)
        self.shard_counts = list(ck["extra"]["shard_counts"])
        self.frontier = {k: np.asarray(v) for k, v in ck["frontier"].items()}
        self.slots = np.asarray(ck["slots"])

    def assert_equal(self, other):
        assert self.levels == other.levels
        assert self.acts == other.acts
        assert self.res.distinct_states == other.res.distinct_states
        assert self.res.states_generated == other.res.states_generated
        for mine, theirs in zip(self.pointers, other.pointers):
            assert np.array_equal(mine, theirs)
        assert self.shard_counts == other.shard_counts
        assert sorted(self.frontier) == sorted(other.frontier)
        for k, v in self.frontier.items():
            assert np.array_equal(v, other.frontier[k]), k
        # the same fingerprints in each shard's table (two inserts of
        # one batch may probe to different slots; the sets are equal)
        for mine, theirs in zip(self.slots, other.slots):
            assert (sorted(map(tuple, mine[mine[:, 0] != 0][:, :4]))
                    == sorted(map(tuple, theirs[theirs[:, 0] != 0][:, :4])))


class Built:
    """The fused engine of a cfg and its first run from Init, with the
    per-tile needs it observed; the per-action engine's run beside
    it."""

    def __init__(self, name, tmp):
        cfg, _max_msgs, self.depth = CASES[name]
        self.name, self.tmp = name, tmp
        self.spec = load_spec("VSR", os.path.join(BENCH, "configs", cfg))
        self.fused = _engine(self.spec, name)
        needs = []
        _recording_needs(self.fused, needs)
        self.first = Ran(self.fused, self.depth, str(tmp / "fused.ckpt"))
        # [dispatches, A]: the most lanes of each action one tile of
        # one shard held, dispatch by dispatch in breadth-first order
        self.needs = np.max([np.asarray(n) for n in needs],
                            axis=1).astype(np.int64)
        self.oracle = Ran(_engine(self.spec, name, commit="per-action"),
                          self.depth, str(tmp / "dense.ckpt"))

    def rerun_with_caps(self, caps, tag):
        """The fused engine's step rebuilt at `caps`, and run again."""
        self.fused.expand_caps = [int(c) for c in caps]
        self.fused._need_seen[:] = 0
        self.fused._make_step()
        return Ran(self.fused, self.depth, str(self.tmp / f"{tag}.ckpt"))


@pytest.fixture(scope="module", params=sorted(CASES))
def built(request, tmp_path_factory):
    return Built(request.param, tmp_path_factory.mktemp(request.param))


def test_blocks_are_smaller_than_the_caps(built):
    """The shape the other tests run at: every segment is two blocks
    or more, and the R-lane actions' last block is clamped."""
    caps = built.fused._caps()
    assert sorted(set(caps)) == [48, 64]
    assert {block_rows(c) for c in caps} == {EXPAND_BLOCK // 4}
    assert built.first.res.metrics["counters"].get("grows", 0) == 0


def test_fused_equals_per_action_bit_for_bit(built):
    assert built.first.res.ok
    assert built.first.res.error == f"depth limit {built.depth} reached"
    built.first.assert_equal(built.oracle)
    with open(os.path.join(BENCH, "oracles", {
            "defect": "defect_window.json",
            "shipped": "shipped_levels.json"}[built.name])) as f:
        assert built.first.levels == \
            json.load(f)["level_sizes"][:built.depth + 1]


def test_block_counters_and_occupancy(built):
    """`expand_blocks_run` of `expand_blocks_cap`, and the `occupancy`
    gauge over the slots of the blocks that ran, recomputed here."""
    res, eng = built.first.res, built.fused
    c, g = res.metrics["counters"], res.metrics["gauges"]
    block = EXPAND_BLOCK // 4
    assert c["expand_blocks_run"] == int(eng._blocks_act.sum()) > 0
    assert c["expand_blocks_run"] <= c["expand_blocks_cap"]
    # two blocks a segment, every tile of every shard
    assert c["expand_blocks_cap"] == \
        eng._tiles_done * 2 * len(eng.kern.action_names)
    real = sum(g["action_expansions"].values())
    assert real == sum(built.first.acts)
    assert g["occupancy"] == round(
        real / (c["expand_blocks_run"] * block), 4)
    # some tiles skip blocks: under a third of them ran, and an action
    # that never fired ran none
    assert c["expand_blocks_run"] * 3 < c["expand_blocks_cap"]
    assert [b == 0 for b in eng._blocks_act] == \
        [a == 0 for a in built.first.acts]
    # the dense step expands every lane of every tile, in no blocks
    dense = built.oracle.res.metrics
    assert "expand_blocks_run" not in dense["counters"]
    assert dense["gauges"]["occupancy"] == round(
        real / (eng._tiles_done * TILE * eng.kern.n_lanes), 4)


@pytest.mark.parametrize("built", ["defect"], indirect=True)
def test_a_tile_that_fills_a_cap_and_an_exact_expand_grow(built):
    """At caps equal to the observed needs a tile fills a segment to
    its last slot; one slot fewer pauses the step with R_EXPAND_GROW,
    whose `need` is the exact count of the tile that overflowed.  One
    rebuilt step shows both: the action cut short is one whose need is
    first met in the last dispatches, after other actions have met
    theirs."""
    need = built.needs.max(axis=0)
    # the dispatch in which each action's largest tile comes
    met = (built.needs == need).argmax(axis=0)
    a = max(range(len(need)), key=lambda i: (need[i] > 8, met[i], need[i]))
    assert need[a] > 8
    full_before = [b for b in range(len(need))
                   if need[b] >= 8 and met[b] < met[a]]
    assert full_before

    caps = np.maximum(8, need)
    caps[a] -= 1
    grown = built.rerun_with_caps(caps, "short")
    assert len([m for m in grown.log if "expand caps grown" in m]) == 1
    # the exact need of the tile that overflowed, and of no later one
    seen = built.fused._need_seen
    assert seen[a] == need[a] and (seen <= need).all()
    assert built.fused._caps()[a] >= 2 * (need[a] - 1)
    grown.assert_equal(built.oracle)


@pytest.mark.parametrize("built", ["defect"], indirect=True)
def test_a_second_engine_reads_the_step_from_the_store(built):
    """The sharded step goes through the store of traced programs
    (engine/program_store.py): the engine that ran first left its
    export there, and an engine built after it traces nothing, lowers
    a wrapper and commits the same rows.  A stub kernel's step, whose
    class the package's source does not determine, never enters."""
    from tpuvsr.testing import stub_sharded_engine
    assert built.fused._step_key_doc() is not None
    second = _engine(built.spec, "defect")
    ran = Ran(second, built.depth, str(built.tmp / "second.ckpt"))
    c = ran.res.metrics["counters"]
    assert (c["build_export_hits"], c.get("build_export_misses", 0)) == (1, 0)
    assert c.get("build_shared_traces", 0) == 0
    ran.assert_equal(built.oracle)
    assert stub_sharded_engine(n_devices=D)._step_key_doc() is None


# ---------------------------------------------------------------------
# structure: no action function over a whole cap
# ---------------------------------------------------------------------
def _expand_eqns(jaxpr, loops, into):
    """(enclosing `while` loops, largest leading dimension) of every
    equation under the scope of the action functions, in a jaxpr and
    the jaxprs nested in its equations' parameters."""
    for eqn in jaxpr.eqns:
        if spans.EXPAND in str(eqn.source_info.name_stack):
            lead = max([v.aval.shape[0] for v in eqn.outvars
                        if getattr(v.aval, "shape", ())] + [0])
            into.append((loops, lead))
        inner = loops + (eqn.primitive.name == "while")
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else (p,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _expand_eqns(sub, inner, into)
    return into


@pytest.mark.parametrize("built", ["defect"], indirect=True)
def test_no_action_function_runs_over_a_whole_cap(built):
    """In the jaxpr of the sharded step every equation of an action
    function sits inside a block loop inside the tile loop, and makes
    nothing with more rows than a block."""
    eng = built.fused
    eng.expand_caps = None
    eng._build(CASES["defect"][1])       # the static caps again
    sh = eng._sh

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    per_dev = arg((D,), jnp.int32)
    rows = arg((D * eng.N, eng._pk.words), jnp.uint32)
    col = arg((D * eng.N,), jnp.int32)
    traced = eng._step.trace(
        {"slots": arg((D, eng.fp_cap, 5), jnp.uint32)}, rows, per_dev,
        per_dev, rows, col, col, col, per_dev, per_dev)
    found = _expand_eqns(traced.jaxpr.jaxpr, 0, [])
    assert len(found) > 1000
    block = EXPAND_BLOCK // 4
    assert min(loops for loops, _lead in found) == 2
    assert max(lead for _loops, lead in found) == block
    assert block < min(eng._caps())


def test_one_chip_configurations_keep_their_block():
    """`block_rows` is EXPAND_BLOCK at every cap a committed one-chip
    configuration starts with (and calibration never cuts a cap below
    its start), and a quarter of it at the caps each four-chip
    configuration starts with (its own tile read)."""
    from tpuvsr.engine.checked import CheckedModel
    seen, four_chip = set(), {}
    for path in sorted(glob.glob(os.path.join(BENCH, "configs", "*.json"))):
        with open(path) as f:
            doc = json.load(f)
        spec = load_spec(doc["module"], os.path.join(BENCH, doc["cfg"]))
        for kind, kw in doc["assumed"]["engine"].items():
            model = CheckedModel(spec)
            model.build(kw.get("max_msgs"))
            kern = model.kern
            if kind == "sharded":
                # the caps `ShardedBFS._build` starts this configuration
                # with: the static ones of its tile
                tile = kw.get("tile", 32)
                caps = [static_cap(tile, tile * kern._lane_count(n))
                        for n in kern.action_names]
                if "4chip" in doc["name"]:
                    four_chip[doc["name"]] = sorted(set(caps))
                    assert {block_rows(c) for c in caps} == {32}
                continue
            tile, mults = kw.get("tile_size", 128), kw.get("expand_mults", {})
            for n in kern.action_names:
                full = tile * kern._lane_count(n)
                cap = static_cap(tile, full)
                if n in mults:
                    cap = max(cap, min(full, _align8(tile * mults[n])))
                assert cap >= 384 and block_rows(cap) == EXPAND_BLOCK == 128
            seen.add(doc["name"])
    assert len(seen) >= 9 and "vr-replica-recovery-async-log" in seen
    # a shard's tile of 32 states starts at 4 lanes a state, and no
    # four-chip configuration has counted a need beyond it
    assert four_chip == {"vsr-defect-4chip": [96, 128],
                         "vr-replica-recovery-cp-4chip": [96, 128]}
    assert block_rows(129) == 128
    assert [block_rows(c) for c in (128, 96, 33, 32, 24, 8)] == \
        [32, 32, 32, 32, 24, 8]
