"""ShardedBFS against the references this repo has without the AST:
the pinned level sizes under benchmark/oracles/ and the single-device
engine, on the kernel-native spec (`load_spec("VSR", cfg)`) and 4 of
the 8 virtual CPU devices.  One built engine per cfg for the module.

The start of a run (ISSUE 27) packs the rows that exist — the init
states, or a snapshot's frontier — into a buffer of the packed zero
row; `_old_start_frontier` keeps the construction it replaced (a dense
D x N zero frontier, every row packed), as the bit-for-bit oracle.
"""

import json
import os

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from tpuvsr.engine.spec import load_spec
from tpuvsr.parallel.sharded_bfs import ShardedBFS

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
D = 4
# cfg, oracle file, depth compared (small: 5,646 states; defect: 4,095),
# depth of the mid-run snapshot
CASES = {
    "small": ("vsr-small.cfg", "pinned_levels_small.json", 10, 5),
    "defect": ("vsr-defect.cfg", "defect_window.json", 6, 3),
}

pytestmark = pytest.mark.skipif(len(jax.devices()) < D,
                                reason=f"needs {D} virtual devices")


def _pinned(name):
    _cfg, oracle, depth, _mid = CASES[name]
    with open(os.path.join(BENCH, "oracles", oracle)) as f:
        return json.load(f)["level_sizes"][:depth + 1]


class Built:
    """One engine and its first run from Init, shared by the module."""

    def __init__(self, name):
        cfg, _oracle, self.depth, self.mid = CASES[name]
        self.name = name
        self.spec = load_spec("VSR", os.path.join(BENCH, "configs", cfg))
        # capacities that hold the compared depth with no growth: a
        # growth is a rebuild of the step
        self.engine = ShardedBFS(
            self.spec, Mesh(np.array(jax.devices()[:D]), ("d",)),
            max_msgs=32 if name == "defect" else None, tile=32,
            bucket_cap=128, next_capacity=1 << 11,
            fpset_capacity=1 << 13)
        self.first = self.engine.run(max_depth=self.depth)
        self.first_levels = list(self.engine.level_sizes)


@pytest.fixture(scope="module", params=sorted(CASES))
def built(request):
    return Built(request.param)


def test_levels_equal_the_pinned_sizes(built):
    res = built.first
    assert res.ok and res.error == f"depth limit {built.depth} reached"
    assert built.first_levels == _pinned(built.name)
    assert res.distinct_states == sum(built.first_levels)
    assert res.metrics["counters"].get("grows", 0) == 0


def test_start_counts_the_init_rows_not_the_capacity(built):
    """VSR has one Init state: the host packs 1 row, not D x N."""
    assert built.engine.D * built.engine.N == D << 11
    assert built.first.metrics["counters"]["init_packed_rows"] == 1


def test_shard_and_exchange_gauges(built):
    g = built.first.metrics["gauges"]
    shard = g["shard_distinct"]
    assert sum(shard) == built.first.distinct_states
    assert g["shard_skew"] == round(max(shard) / (sum(shard) / D), 4)
    assert 1.0 <= g["shard_skew"] < 1.5
    # D-1 of a sender's D buckets leave the chip
    assert g["exchange_offchip_bytes"] * D == \
        g["exchange_wire_bytes"] * (D - 1)
    assert g["exchange_wire_bytes"] == \
        g["exchange_wire_rows"] * g["exchange_row_bytes"]
    # every state but Init reached its owner through the exchange
    assert g["exchange_useful_rows"] >= built.first.distinct_states - 1


def test_second_run_of_the_same_engine(built):
    again = built.engine.run(max_depth=built.depth)
    assert list(built.engine.level_sizes) == built.first_levels
    assert again.distinct_states == built.first.distinct_states
    assert again.states_generated == built.first.states_generated
    assert again.metrics["counters"]["init_packed_rows"] == 1
    # every program it needs is the engine's, built by the first run
    assert again.metrics["counters"]["build_programs"] == 0


def test_resume_from_a_mid_run_snapshot(built, tmp_path):
    ckpt = str(tmp_path / "mid.ckpt")
    cut = built.engine.run(max_depth=built.mid, checkpoint_path=ckpt)
    assert cut.error == f"depth limit {built.mid} reached"
    resumed = built.engine.run(max_depth=built.depth, resume_from=ckpt)
    assert list(built.engine.level_sizes) == built.first_levels
    assert resumed.distinct_states == built.first.distinct_states
    assert resumed.states_generated == built.first.states_generated
    # the host packed the snapshot's frontier, and no more
    assert resumed.metrics["counters"]["init_packed_rows"] == \
        built.first_levels[built.mid]
    # the exchange's totals carry over the snapshot
    for key in ("useful_rows", "wire_bytes", "offchip_bytes"):
        assert resumed.exchange[key] == built.first.exchange[key], key


@pytest.mark.parametrize("built", ["defect"], indirect=True)
def test_defect_counts_equal_the_single_device_engine(built):
    from tpuvsr.engine.device_bfs import DeviceBFS
    one = DeviceBFS(built.spec, max_msgs=32, tile_size=64)
    res = one.run(max_depth=built.depth)
    assert list(one.level_sizes) == built.first_levels
    assert res.distinct_states == built.first.distinct_states
    assert res.states_generated == built.first.states_generated
    # per action too, and the sharded step ran them in the blocks of
    # the four-chip cell's shape (caps of 128 and 96 slots, blocks of
    # 32: ISSUE 50), most of which it skipped
    acts = built.first.metrics["gauges"]["action_expansions"]
    assert acts == res.metrics["gauges"]["action_expansions"]
    from tpuvsr.engine.device_bfs import block_rows
    assert sorted({(c, block_rows(c)) for c in built.engine._caps()}) == \
        [(96, 32), (128, 32)]
    c = built.first.metrics["counters"]
    assert 0 < c["expand_blocks_run"] < c["expand_blocks_cap"] / 3
    assert built.first.metrics["gauges"]["occupancy"] == round(
        sum(acts.values()) / (c["expand_blocks_run"] * 32), 4)


def _old_start_frontier(engine, rows, counts0):
    """`ShardedBFS.run`'s start before ISSUE 27: dense D x N zero
    planes, the rows written one by one, every row packed."""
    F = engine.N
    zero = engine.codec.zero_state()
    host_front = {k: np.zeros((engine.D * F,) + np.shape(v), np.int32)
                  for k, v in zero.items()}
    pos = 0
    for d in range(engine.D):
        for j in range(int(counts0[d])):
            for k in host_front:
                host_front[k][d * F + j] = rows[k][pos]
            pos += 1
    return engine._pk.pack_np(host_front)


@pytest.mark.parametrize("start", ["init", "snapshot"])
def test_start_frontier_is_bit_identical_to_the_old_one(built, start,
                                                        tmp_path):
    from tpuvsr.engine.checkpoint import load_checkpoint
    from tpuvsr.obs import RunObserver
    eng = built.engine
    if start == "init":
        dense = [eng.codec.encode(st) for st in built.spec.init_states()]
        rows = {k: np.stack([d[k] for d in dense]) for k in dense[0]}
        counts0 = np.bincount([2], minlength=D)     # any one owner
    else:
        ckpt = str(tmp_path / "mid.ckpt")
        eng.run(max_depth=built.mid, checkpoint_path=ckpt)
        ck = load_checkpoint(ckpt)
        rows = ck["frontier"]
        counts0 = np.asarray(ck["extra"]["shard_counts"])
        assert counts0.sum() == built.first_levels[built.mid]
        assert len(set(counts0)) > 1        # uneven shards
    obs = RunObserver.ensure(None, "sharded", built.spec)
    new = np.asarray(eng._start_frontier(rows, counts0, obs))
    old = _old_start_frontier(eng, rows, counts0)
    assert new.dtype == old.dtype and new.shape == old.shape
    assert np.array_equal(new, old)
    assert obs.metrics.counters["init_packed_rows"] == counts0.sum()
    # the padding is the packed zero row, and that is not zero words
    pad = new[int(counts0[0]):eng.N]
    assert (pad == eng._pk.zero_row).all()
    assert eng._pk.zero_row.any()


@pytest.mark.parametrize("config", ["vsr-defect-4chip",
                                    "vr-replica-recovery-cp-4chip"])
def test_four_chip_configuration_names_what_the_engine_provides(config):
    """A four-chip configuration's file hands its capacities to the
    constructor as they stand; an engine without a property it
    requires (start_packs_live_rows: every commit before ISSUE 27;
    commit_stats_at_owner, which the CP06 cell's kernel readers rest
    on: every commit before ISSUE 55) refuses before it builds."""
    import inspect

    from tpuvsr.core.values import TLAError
    with open(os.path.join(BENCH, "configs", config + ".json")) as f:
        kw = json.load(f)["assumed"]["engine"]["sharded"]
    assert set(kw) <= set(inspect.signature(ShardedBFS).parameters)
    assert kw["requires"] == ["start_packs_live_rows"] + \
        ["commit_stats_at_owner"] * (config != "vsr-defect-4chip")
    assert set(kw["requires"]) <= ShardedBFS.PROVIDES
    with pytest.raises(TLAError, match="does not provide .'host_free"):
        ShardedBFS(None, None, requires=["host_free_levels"])
