"""The restart era from committed files: `RestartEmptyLimit = 1` on the
kernel-native spec, where `rep_dvc_recv` holds more than one record a
source (models/vsr.py, layout: K slots a (dest, source) pair, set
semantics, canonical order) and the four recovery actions fire.

The small cfg (examples/VSR_small.cfg with the knob at 1: Values =
{v1}, timer 1) through the three BFS engines to depth 10, and the
kernel held, state by state, to the plain reference of the nine
actions that touch the receive-sets or belong to recovery
(benchmark/tools/recovery_reference.py: host values, frozensets,
`len`, `min`).  No `.tla`, no interpreter: nothing here is
`requires_reference`.  The parent stopped this cfg in level 9 with a
slot collision; levels 0-8 are the sizes it reached, 9-10 are pinned
from the three engines agreeing.
"""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from tpuvsr.core.values import FnVal, TLAError, mk_record
from tpuvsr.engine.spec import load_spec

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark", "tools"))
import recovery_reference as reference  # noqa: E402

DEPTH = 10
LEVELS = [1, 6, 25, 91, 302, 928, 2588, 6485, 14532, 29287, 53872]
# levels whose every state is compared with the reference; of the
# deeper ones, the states that hold two records of one source
EVERY_STATE_THROUGH = 7
ENGINES = ("device", "paged", "sharded")
# what one tile of 32 states needs beyond the static 4 lanes a state
# (need_seen of a run to depth 10): a growth is a rebuild
CAPS = {"ReceiveMatchingSVC": 5}


@pytest.fixture(scope="module")
def spec(tmp_path_factory):
    with open(os.path.join(REPO, "examples", "VSR_small.cfg")) as f:
        text = f.read()
    assert "RestartEmptyLimit = 0" in text
    path = tmp_path_factory.mktemp("cfg") / "VSR_small_restart.cfg"
    path.write_text(text.replace("RestartEmptyLimit = 0",
                                 "RestartEmptyLimit = 1"))
    return load_spec("VSR", str(path))


def _build(name, spec):
    if name == "device":
        from tpuvsr.engine.device_bfs import DeviceBFS
        return DeviceBFS(spec, tile_size=32, next_capacity=1 << 16,
                         fpset_capacity=1 << 19, expand_mults=CAPS)
    if name == "paged":
        from tpuvsr.engine.paged_bfs import PagedBFS
        return PagedBFS(spec, tile_size=32, next_capacity=1 << 16,
                        fpset_capacity=1 << 19, expand_mults=CAPS,
                        retain_levels=True)
    from tpuvsr.parallel.sharded_bfs import ShardedBFS
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    return ShardedBFS(spec, Mesh(np.array(jax.devices()[:4]), ("d",)),
                      tile=32, bucket_cap=128,
                      next_capacity=1 << 15, fpset_capacity=1 << 17)


@pytest.fixture(scope="module")
def runs(spec):
    """name -> (engine, result of its run to DEPTH), built on demand
    and kept: the later tests read the paged run's levels and start
    the same programs again from another state."""
    done = {}

    def run(name):
        if name not in done:
            eng = _build(name, spec)
            done[name] = (eng, eng.run(max_depth=DEPTH))
        return done[name]
    return run


# ---------------------------------------------------------------------
# (a) the three engines, level for level
# ---------------------------------------------------------------------
@pytest.mark.parametrize("name", ENGINES)
def test_levels_through_depth_10(name, runs):
    eng, res = runs(name)
    assert eng.kern.K == 3 and eng.codec.shape.restart_limit == 1
    assert res.ok and res.error == f"depth limit {DEPTH} reached"
    assert list(eng.level_sizes) == LEVELS
    assert res.distinct_states == sum(LEVELS)
    counters = res.metrics["counters"]
    assert counters.get("grow_message_table", 0) == 0
    if name == "sharded":
        return
    fired = res.metrics["gauges"]["action_expansions"]
    for action in ("RestartEmpty", "ReceivesRecoveryMsg",
                   "ReceivesRecoveryResponseMsg", "CompleteRecovery"):
        assert fired[action] > 0
    # counted on the device over the states the run committed
    assert 0 < counters["recovering_states"] < res.distinct_states
    assert res.metrics["gauges"]["dvc_set_peak"] == 2


# ---------------------------------------------------------------------
# (b) the kernel against the plain reference, state by state
# ---------------------------------------------------------------------
def _two_of_one_source(block):
    """Rows of a dense level block in which some (dest, source) pair
    holds two DoViewChange records."""
    held = (block["dvc"] == 1).sum(-1)                  # [n, R, R]
    return np.flatnonzero(held.reshape(len(held), -1).max(-1) >= 2)


def _padded(block, rows, size):
    """`rows` of a level block as a batch of `size` (the last row
    repeated), so every batch traces one program."""
    take = np.concatenate([rows, np.full(size - len(rows), rows[-1])])
    return {k: v[take] for k, v in block.items()}


def test_kernel_successors_equal_the_reference(spec, runs):
    eng, _res = runs("paged")
    codec, kern = eng.codec, eng.kern
    names = kern.action_names
    lane_action = np.asarray(kern.lane_action)
    nine = np.isin(lane_action, [names.index(a) for a in reference.ACTIONS])
    guards = kern._guard_fns()

    def guard_lanes(st):
        return jnp.concatenate([
            jax.vmap(lambda ln, g=g: g(st, ln))(
                jnp.arange(kern._lane_count(n), dtype=jnp.int32))
            for n, g in zip(names, guards)])
    guard_batch = jax.jit(jax.vmap(guard_lanes))

    assert len(eng.level_blocks) == DEPTH
    B = 256
    compared = with_two = 0
    for depth, block in enumerate(eng.level_blocks):
        rows = (np.arange(len(block["status"]))
                if depth <= EVERY_STATE_THROUGH
                else _two_of_one_source(block))
        for lo in range(0, len(rows), B):
            part = rows[lo:lo + B]
            batch = _padded(block, part, B)
            succs, en = kern.step_batch(batch)
            en = np.asarray(en)
            # every guard is its action's `en`, on all 19 actions
            assert np.array_equal(np.asarray(guard_batch(batch)), en)
            succs = {k: np.asarray(v) for k, v in succs.items()}
            for i in range(len(part)):
                state = codec.decode({k: v[i] for k, v in batch.items()})
                got = set()
                for lane in np.flatnonzero(en[i] & nine):
                    assert succs["err"][i, lane] == 0
                    got.add((names[lane_action[lane]],
                             reference.record_of(codec.decode(
                                 {k: v[i, lane]
                                  for k, v in succs.items()}))))
                want = reference.successors(state, spec.cfg.constants)
                assert got == want, (depth, int(part[i]),
                                     sorted(a for a, _ in got ^ want))
                compared += 1
                with_two += any(
                    len({m.apply("source") for m in dvcs}) < len(dvcs)
                    for _r, dvcs in state["rep_dvc_recv"].items)
    assert compared > sum(LEVELS[:EVERY_STATE_THROUGH + 1])
    assert with_two > 0


# ---------------------------------------------------------------------
# (d) a set one record too large stops every engine, loudly
# ---------------------------------------------------------------------
def _overfull_start(state, constants):
    """`state` (Init) with replica 2, primary of view 2, in a view
    change it has K = 3 different DoViewChange records of replica 3
    for, and a fourth in the bag with a delivery pending."""
    def entry(view):
        return mk_record(view_number=view, operation=min(
            constants["Values"], key=repr), client_id=1, request_number=1)

    def dvc(commit, log):
        return mk_record(type=constants["DoViewChangeMsg"], view_number=2,
                         log=FnVal((i + 1, e) for i, e in enumerate(log)),
                         last_normal_vn=1, op_number=len(log),
                         commit_number=commit, dest=2, source=3)
    records = [dvc(0, []), dvc(0, [entry(1)]), dvc(1, [entry(1)]),
               dvc(0, [entry(2)])]
    out = dict(state)
    out["rep_view_number"] = state["rep_view_number"].updated(2, 2)
    out["rep_status"] = state["rep_status"].updated(
        2, constants["ViewChange"])
    out["rep_dvc_recv"] = state["rep_dvc_recv"].updated(
        2, frozenset(records[:3]))
    out["messages"] = FnVal([(records[3], 1)])
    return out


@pytest.mark.parametrize("name", ENGINES)
def test_a_set_too_large_for_k_stops_the_run(name, spec, runs,
                                             monkeypatch):
    eng, _res = runs(name)
    (init,) = spec.init_states()
    start = _overfull_start(init, spec.cfg.constants)
    # the layout holds the start state (three records of one source) ...
    assert eng.codec.decode(eng.codec.encode(start)) == start
    # ... and not the set its ReceiveMatchingDVC makes
    monkeypatch.setattr(spec, "init_states", lambda: iter([start]))
    with pytest.raises(TLAError, match="more than 3 different "
                       "DoViewChange.*RestartEmptyLimit = 1"):
        eng.run(max_depth=2)
