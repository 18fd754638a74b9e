"""What a kernel asks to have counted over the states a run commits
(``commit_stats``), counted by `ShardedBFS` behind the exchange (ISSUE
55): a successor's stat vector rides in its bucket to the shard that
owns its fingerprint, and that shard reduces it over the rows its
insert finds fresh.

On the virtual CPU devices, at small capacities, on the three kernel
classes that carry the hook (`CP06Kernel`: ten entries, seven sums and
three maxima; `VSRKernel` at ``RestartEmptyLimit = 1`` with symmetry
on; `ST03Kernel`), a sharded run's counters and gauges are held to
`DeviceBFS`'s to the unit, the per-shard vectors to their total, and
the CP06 run to the plain reference's counts at the depth
(`benchmark/oracles/checkpoint_recovery_levels_deep.json`, `through`).
One step on a frontier made by hand shows a successor that two shards
generate in one tile counted once, by its owner.
"""

import json
import os

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from tpuvsr.engine.device_bfs import DeviceBFS
from tpuvsr.engine.spec import load_spec
from tpuvsr.parallel.sharded_bfs import ShardedBFS

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
TILE = 32
# module, cfg, max_msgs, the depth compared on each mesh size (cp06 on
# four shards: 141,618 states, the first depth at which a replica waits
# on a part-filled quorum and a DoViewChange receive-set holds a
# record; on two: 21,813, for the suite's wall; restart: 8,318 orbits;
# st03: 36,564 states)
CASES = {
    "cp06": ("VR_REPLICA_RECOVERY_CP", "vr-replica-recovery-cp.cfg", 24,
             {2: 7, 4: 9}),
    "restart": ("VSR", "vsr-shipped-restart.cfg", 32, {4: 6}),
    "st03": ("VR_STATE_TRANSFER", "vr-state-transfer.cfg", 24, {2: 8}),
}
SHARDED = [(name, d) for name, case in CASES.items() for d in case[3]]

pytestmark = pytest.mark.skipif(len(jax.devices()) < 4,
                                reason="needs 4 virtual devices")


def _spec(name):
    module, cfg, _max_msgs, _depths = CASES[name]
    return load_spec(module, os.path.join(BENCH, "configs", cfg))


def _sharded(name, devices, **kw):
    kw = dict(dict(tile=TILE, bucket_cap=256, next_capacity=1 << 16,
                   fpset_capacity=1 << 18), **kw)
    return ShardedBFS(_spec(name), Mesh(np.array(jax.devices()[:devices]),
                                        ("d",)),
                      max_msgs=CASES[name][2], **kw)


def _counted(eng, res):
    """What a run leaves of what this file compares: levels, the
    per-action counts and every entry of the kernel's COMMIT_STATS
    under the name and kind (counter or gauge) it is written as."""
    assert res.ok and res.error.startswith("depth limit"), res.error
    counters, gauges = res.metrics["counters"], res.metrics["gauges"]
    return {"levels": [int(x) for x in eng.level_sizes],
            "distinct": int(res.distinct_states),
            "generated": int(res.states_generated),
            "actions": gauges["action_expansions"],
            "stats": {name: int((counters if how == "sum" else gauges)
                                .get(name, 0))
                      for name, how in eng.kern.COMMIT_STATS}}


@pytest.fixture(scope="module")
def one_chip():
    """(name, depth) -> what `DeviceBFS` counts to the depth, run on
    demand and kept; one engine a name, whose second run builds
    nothing."""
    engines, done = {}, {}

    def run(name, depth):
        if (name, depth) not in done:
            if name not in engines:
                engines[name] = DeviceBFS(
                    _spec(name), max_msgs=CASES[name][2],
                    next_capacity=1 << 17, fpset_capacity=1 << 19)
            eng = engines[name]
            done[name, depth] = _counted(eng, eng.run(max_depth=depth))
        return done[name, depth]
    return run


@pytest.fixture(scope="module")
def sharded_runs():
    """(name, D) -> (engine, result of its run to the mesh's depth)."""
    done = {}

    def run(name, devices):
        if (name, devices) not in done:
            eng = _sharded(name, devices)
            done[name, devices] = (
                eng, eng.run(max_depth=CASES[name][3][devices]))
        return done[name, devices]
    return run


@pytest.mark.parametrize("name, devices", SHARDED)
def test_counts_behind_the_exchange_equal_one_chips(name, devices,
                                                    sharded_runs, one_chip):
    eng, res = sharded_runs(name, devices)
    got, want = _counted(eng, res), one_chip(name, CASES[name][3][devices])
    assert got == want
    names = [n for n, _how in eng.kern.COMMIT_STATS]
    assert len(names) == {"cp06": 10, "restart": 2, "st03": 6}[name]
    assert sum(got["stats"].values()) > 0
    # every shard commits its own states and counts them: the shards'
    # vectors add up (or peak) to the run's, and no shard holds it all
    shards = eng._stat_shard
    assert shards.shape == (devices, len(names))
    for i, (stat, how) in enumerate(eng.kern.COMMIT_STATS):
        column = shards[:, i]
        assert (column.sum() if how == "sum" else column.max()) \
            == got["stats"][stat], stat
        if how == "sum" and got["stats"][stat] > 100 * devices:
            assert 0 < column.min() and column.max() < got["stats"][stat]
    # the stat words are on the wire, and the gauges say so
    gauges = res.metrics["gauges"]
    assert gauges["exchange_row_bytes"] == \
        eng.model.row_bytes() + 16 + 1 + 12 + 4 * len(names)
    assert gauges["exchange_offchip_bytes"] > 0 == \
        gauges["exchange_offchip_bytes"] % gauges["exchange_row_bytes"]


@pytest.mark.parametrize("devices", CASES["cp06"][3])
def test_cp06_counts_equal_the_plain_references(devices, sharded_runs):
    """The deep oracle's `through` is what the plain reference had
    counted at each depth's end (scripts/cp06_deep_oracle.py)."""
    depth = CASES["cp06"][3][devices]
    with open(os.path.join(BENCH, "oracles",
                           "checkpoint_recovery_levels_deep.json")) as f:
        oracle = json.load(f)
    want = oracle["through"][depth - 1]
    eng, res = sharded_runs("cp06", devices)
    got = _counted(eng, res)
    assert got["levels"] == oracle["level_sizes"][:depth + 1]
    assert (got["distinct"], got["generated"]) == (want["distinct"],
                                                   want["generated"])
    assert got["actions"] == want["action_expansions"]
    assert got["stats"] == {n: want["committed"][n] for n in got["stats"]}
    # every entry but the two that need what this cfg never reaches
    # (a replica in StateTransfer; a StartViewChange quorum of more
    # than one record) has something to count at depth 9
    if depth == 9:
        assert sorted(n for n, v in got["stats"].items() if v == 0) == \
            ["state_transfer_states", "svc_quorum_waiting_states"]


def test_a_kernel_without_the_hook_carries_no_stat_word():
    """`VSRKernel.commit_stats` is None where the cfg cannot restart
    (the defect cell's shape): the step has its 17 outputs and the
    wire its 505-byte row."""
    eng = ShardedBFS(
        load_spec("VSR", os.path.join(BENCH, "configs", "vsr-defect.cfg")),
        Mesh(np.array(jax.devices()[:2]), ("d",)), max_msgs=32, tile=16,
        bucket_cap=128, next_capacity=1 << 10, fpset_capacity=1 << 12)
    assert eng._stat_fn is None and eng._stage2.stat_fn is None
    res = eng.run(max_depth=3)
    gauges = res.metrics["gauges"]
    assert gauges["exchange_row_bytes"] == 119 * 4 + 16 + 1 + 12 == 505
    assert eng._stat_shard.shape == (2, 0)
    assert "recovering_states" not in res.metrics["counters"]
    assert "dvc_set_peak" not in gauges


# ---------------------------------------------------------------------
# one step, by hand: a successor two shards generate in one tile
# ---------------------------------------------------------------------
def _step_once(eng, rows, counts):
    """One dispatch of `eng`'s step from an empty table on the dense
    batch `rows`, `counts[d]` of them on shard d: its outputs."""
    from tpuvsr.obs import RunObserver
    obs = RunObserver.ensure(None, "sharded", eng.spec)
    D = eng.D
    counts = np.asarray(counts)
    tables = {"slots": eng._zeros((D, eng.fp_cap, 5), np.uint32, obs)}
    front = eng._start_frontier(rows, counts, obs)
    zero = lambda: eng._put(np.zeros(D, np.int32))
    nb, nbp, nba, nbprm = eng._alloc_frontier(eng.N, obs)
    return eng._step(tables, front, eng._put(counts.astype(np.int32)),
                     zero(), nb, nbp, nba, nbprm, zero(), zero())


def test_a_duplicate_from_two_shards_in_one_tile_is_counted_once(
        sharded_runs):
    eng, _res = sharded_runs("cp06", 2)
    kern, codec = eng.kern, eng.codec
    planes = sorted(codec.zero_state())
    init = codec.encode(next(iter(eng.spec.init_states())))
    batch = {k: np.asarray(init[k])[None] for k in planes}

    def successors(batch):
        """The enabled successors of a dense batch, row by row."""
        succ, en = kern.step_batch(batch)
        en = np.asarray(en)
        fps = np.asarray(kern.fingerprint_batch(
            {k: np.asarray(v).reshape((-1,) + np.shape(v)[2:])
             for k, v in succ.items() if not k.startswith("_")})
        ).reshape(en.shape + (4,))
        return [({k: np.asarray(succ[k])[i][en[i]] for k in planes},
                 [tuple(fp) for fp in fps[i][en[i]]])
                for i in range(en.shape[0])]

    (level1, _fps), = successors(batch)
    assert len(level1["status"]) == 7
    children = successors(level1)
    # two states of level 1 with a successor in common, one a shard:
    # the frontier's rows need not be where their fingerprints live
    a, b = next((a, b) for a in range(7) for b in range(a + 1, 7)
                if set(children[a][1]) & set(children[b][1]))
    common = set(children[a][1]) & set(children[b][1])
    rows = {k: level1[k][[a, b]] for k in planes}
    out = _step_once(eng, rows, [1, 1])
    assert [int(x) for x in np.asarray(out[7])] == [0, 0]   # RUNNING
    sent = int(np.asarray(out[10]).sum())
    fresh = int(np.asarray(out[5]).sum())
    union = set(children[a][1]) | set(children[b][1])
    # each shard ships its own successors, locally distinct; the owner
    # of a common one gets it twice in this tile and commits it once
    assert sent == len(set(children[a][1])) + len(set(children[b][1]))
    assert fresh == len(union) == sent - len(common) < sent
    # the step's stat vectors against `commit_stats` of the rows the
    # shards hold afterwards
    nn = np.asarray(out[5])
    held = eng._pk.unpack_np(np.concatenate(
        [np.asarray(out[1])[d * eng.N:d * eng.N + int(nn[d])]
         for d in range(eng.D)]))
    per_row = np.asarray(jax.vmap(kern.commit_stats)(
        {k: np.asarray(v) for k, v in held.items()}), np.int64)
    assert per_row.shape == (len(union), len(kern.COMMIT_STATS))
    cs = np.asarray(out[17], np.int64)
    assert cs.shape == (eng.D, len(kern.COMMIT_STATS))
    want = np.where(eng._stat_sums, per_row.sum(0), per_row.max(0))
    got = np.where(eng._stat_sums, cs.sum(0), cs.max(0))
    assert got.tolist() == want.tolist()
    # ... where counting at the senders would have read more
    assert per_row[:, 1].sum() > 0 and sent > fresh

