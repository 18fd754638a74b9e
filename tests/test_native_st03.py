"""VR_STATE_TRANSFER (ST03) through the native door, from committed
files: `load_spec("VR_STATE_TRANSFER", cfg)` with the committed init
state (examples/VR_STATE_TRANSFER_init_trace.txt), the kernel held
state by state to the plain reference of its 16 actions
(benchmark/tools/state_transfer_reference.py: host values, its own
breadth-first loop, nothing of tpuvsr imported), and the engines held
to the reference's level sizes at the constants of the benchmark's cell
(benchmark/configs/vr-state-transfer.cfg: |Values| = 2, timer 2).

The reference itself is held to the two records this repository has of
the real `.tla`: the interpreter's fixpoint and level sizes at
|Values| = 1, timer 1.  No `.tla`, no interpreter: nothing here is
`requires_reference`.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from tests.st03_reference import (MODULE, REPO, STATS, explore,
                                  make_compare, quorum_counts, reference,
                                  to_tlc)
from tpuvsr.core.values import TLAError
from tpuvsr.engine.spec import load_spec

Msg = reference.Msg
CFG = os.path.join(REPO, "benchmark", "configs", "vr-state-transfer.cfg")
MAX_MSGS = 24
# the reference's level sizes at the cell's constants (depth 8)
LEVELS = [1, 4, 17, 63, 238, 851, 2814, 8564, 24012]
EVERY_STATE_THROUGH = 7
DEPTH = 8
TRIO = set(reference.STATE_TRANSFER_ACTIONS)
BATCH = 256


@pytest.fixture(scope="module")
def spec():
    return load_spec(MODULE, CFG)


@pytest.fixture(scope="module")
def constants():
    c, invariants = reference.read_cfg(CFG)
    assert c == reference.Constants(3, ("v1", "v2"), 2, 0)
    assert invariants == ("NoLogDivergence", "AcknowledgedWriteNotLost",
                          "CommitNumberNeverHigherThanOpNumber")
    return c


@pytest.fixture(scope="module")
def model(spec):
    """(codec, kernel) at the cell's message-table bound."""
    codec, kern, _inv = spec.model(MAX_MSGS)
    return codec, kern


@pytest.fixture(scope="module")
def ref_run(constants):
    """The reference's own breadth-first run to DEPTH, levels kept."""
    return reference.bfs(constants, reference.INVARIANTS, max_depth=DEPTH,
                         keep_levels=True)


# ---------------------------------------------------------------------
# the door
# ---------------------------------------------------------------------
def test_init_is_the_codecs_zero_state_in_view_1(spec, model, constants):
    from tpuvsr.models.native import INIT_TRACES
    from tpuvsr.models.st03_kernel import ACTION_NAMES
    codec, _kern = model
    assert spec.native and spec.module.name == MODULE
    assert os.path.dirname(INIT_TRACES[MODULE]) == os.path.join(
        REPO, "examples")
    (st,) = spec.init_states()
    zero = codec.zero_state()
    zero["view"][:] = 1
    assert codec.decode(zero) == st
    assert codec.decode(codec.encode(st)) == st
    assert reference.from_tlc(st, constants) == reference.init_state(
        constants)
    assert spec.check_invariants(st) is None
    assert spec.cfg.view == "view" and not spec.symmetry_perms
    assert [a.name for a in spec.actions] == list(ACTION_NAMES) \
        == list(reference.ACTIONS)
    loc = {a.name: a.location for a in spec.actions}
    assert loc["SendGetState"] == f"lines 407-447 of module {MODULE}"


@pytest.mark.parametrize("section", ["SYMMETRY symmValues",
                                     "PROPERTY AllReplicasMoveToSameView",
                                     "SPECIFICATION Spec"])
def test_sections_that_need_the_ast_stay_refused(section, tmp_path):
    with open(CFG) as f:
        text = f.read()
    if section.startswith("SPECIFICATION"):
        text = text.replace("INIT Init\nNEXT Next\n", "")
    cfg = tmp_path / "x.cfg"
    cfg.write_text(text + "\n" + section + "\n")
    with pytest.raises(TLAError, match="needs the .tla"):
        load_spec(MODULE, str(cfg))


# VR_REPLICA_RECOVERY_CP went through the door in PR 46 and
# VR_REPLICA_RECOVERY_ASYNC_LOG in PR 53 (tests/test_native_cp06.py and
# tests/test_native_al05.py hold them, and these four from their side)
@pytest.mark.parametrize("module", [
    "VR_ASSUME_NEWVIEWCHANGE", "VR_INC_RESEND", "VR_APP_STATE",
    "VR_REPLICA_RECOVERY"])
def test_the_four_other_modules_stay_shut(module):
    with pytest.raises(TLAError, match="no committed init trace"):
        load_spec(module, CFG)


# ---------------------------------------------------------------------
# (a) the reference against the two records of the real .tla
# ---------------------------------------------------------------------
def test_reference_reaches_the_interpreters_fixpoint():
    with open(os.path.join(REPO, "scripts", "lower_fixpoint.json")) as f:
        pin = json.load(f)["VR_ASSUME_NEWVIEWCHANGE"]   # BASELINE.md:36
    with open(os.path.join(REPO, "scripts", "fixpoints.json")) as f:
        fix = json.load(f)["03-state-transfer/VR_STATE_TRANSFER"]
    assert (fix["distinct"], fix["generated"], fix["diameter"]) == (
        42753, 106794, 24) and fix["fixpoint"]
    res = reference.bfs(reference.Constants(3, ("v1",), 1, 0),
                        reference.INVARIANTS)
    assert res["fixpoint"] and res["violation"] is None
    assert res["level_sizes"] == pin["level_sizes"]
    assert (res["distinct"], res["generated"],
            len(res["level_sizes"])) == (42753, 106794, 24)
    # no view met twice in one level under other auxiliaries: the
    # sizes do not depend on the order inside a level
    assert res["aux_conflicts"] == 0
    assert not any(res["action_expansions"][a] for a in TRIO)


def test_reference_levels_at_the_cells_constants(ref_run):
    assert ref_run["level_sizes"] == LEVELS
    assert ref_run["violation"] is None and ref_run["aux_conflicts"] == 0
    with open(os.path.join(REPO, "benchmark", "oracles",
                           "state_transfer_levels.json")) as f:
        oracle = json.load(f)
    assert oracle["level_sizes"][:DEPTH + 1] == LEVELS


# ---------------------------------------------------------------------
# (b), (c) the kernel against the reference, state by state
# ---------------------------------------------------------------------
@pytest.fixture(scope="module")
def compare(spec, model, constants):
    """The state-by-state comparison of `tests/st03_reference.py` at
    this file's cfg."""
    return make_compare(spec, model, constants, BATCH)


def test_kernel_equals_reference_on_levels_0_to_7(compare, ref_run):
    states = [s for level in ref_run["levels"][:EVERY_STATE_THROUGH + 1]
              for s in level]
    assert len(states) == sum(LEVELS[:EVERY_STATE_THROUGH + 1]) == 12552
    fired = compare(states)
    # breadth-first order reaches the state-transfer era too late
    assert fired == set(reference.ACTIONS) - TRIO - {"NoProgressChange"}


def _state_transfer_start(constants):
    """View 2 (primary 2) in normal operation with replica 3 left
    behind in view 1 with an empty log: 2 has prepared v1 and v2, 1
    has taken v1 and acknowledged it, and 3 has both Prepares pending,
    the second an op gap behind a higher view (the recipe of
    tests/test_lower.py::_craft_state_transfer_state, on host values,
    with the real primary and one timer left)."""
    def prepare(dest, op, value, count):
        return (Msg("PrepareMsg", 2, dest, 2, op_number=op,
                    commit_number=0, message=value), count)
    return reference.init_state(constants)._replace(
        rep_view_number=(2, 2, 1), rep_op_number=(1, 2, 0),
        rep_last_normal_view=(2, 2, 0),
        rep_log=(("v1",), ("v1", "v2"), ()),
        messages=frozenset({
            prepare(1, 1, "v1", 0), prepare(1, 2, "v2", 1),
            prepare(3, 1, "v1", 1), prepare(3, 2, "v2", 1),
            (Msg("PrepareOkMsg", 2, 2, 1, op_number=1), 1)}),
        aux_svc=1,
        aux_client_acked=frozenset({("v1", False), ("v2", False)}))


def test_state_transfer_subtree(compare, constants):
    """From the crafted state, every state within four steps and the
    state-transfer lines two steps below them: the trio fires between
    timers, view changes and normal operation, and a GetState
    addressed to AnyDest is delivered by each eligible replica but its
    sender."""
    start = _state_transfer_start(constants)
    states, by_action = explore(
        start, constants, 6,
        follow=lambda depth, action: depth < 4 or action in TRIO)
    assert TRIO <= set(by_action)
    # AnyDest: the GetState of replica 3 from the crafted state is
    # answered by 1 (one entry) and by 2 (two), never by 3 itself
    asked = next(succ for s, succ in by_action["SendGetState"]
                 if s == start)
    assert asked.rep_status[2] == reference.STATE_TRANSFER
    (ask,) = [m for m, _n in asked.messages if m.type == "GetStateMsg"]
    assert (ask.dest, ask.source, ask.view_number, ask.op_number) == (
        reference.ANY_DEST, 3, 2, 0)
    answers = sorted(
        next(m for m, _n in succ.messages if m.type == "NewStateMsg")
        for s, succ in by_action["ReceiveGetState"] if s == asked)
    assert [(m.source, m.dest, m.first_op, m.log) for m in answers] == [
        (1, 3, 1, ("v1",)), (2, 3, 1, ("v1", "v2"))]
    caught_up = {(succ.rep_status[2], succ.rep_view_number[2],
                  succ.rep_last_normal_view[2], succ.rep_log[2])
                 for _s, succ in by_action["ReceiveNewState"]}
    assert caught_up >= {(reference.NORMAL, 2, 2, ("v1",)),
                         (reference.NORMAL, 2, 2, ("v1", "v2"))}
    assert len(states) > 150
    fired = compare(states)
    assert TRIO <= fired and {"TimerSendSVC", "ReceiveHigherSVC",
                              "SendDVC", "ExecuteOp"} <= fired


# ---------------------------------------------------------------------
# (e) one crafted violating state per cfg invariant
# ---------------------------------------------------------------------
def _violating(name, constants):
    init = reference.init_state(constants)
    if name == "NoLogDivergence":
        return init._replace(
            rep_log=(("v1",), ("v2",), ()), rep_op_number=(1, 1, 0),
            rep_commit_number=(1, 1, 0),
            aux_client_acked=frozenset({("v1", False), ("v2", False)}))
    if name == "AcknowledgedWriteNotLost":
        return init._replace(aux_client_acked=frozenset({("v1", True)}))
    assert name == "CommitNumberNeverHigherThanOpNumber"
    return init._replace(rep_commit_number=(0, 1, 0))


def test_each_cfg_invariant_is_violated_by_its_crafted_state(
        spec, model, constants):
    codec, kern = model
    assert set(spec.cfg.invariants) <= set(kern.INVARIANT_FNS)
    for name in spec.cfg.invariants:
        state = _violating(name, constants)
        assert reference.violated(state, constants, [name]) == name
        others = [n for n in spec.cfg.invariants if n != name]
        if name != "CommitNumberNeverHigherThanOpNumber":
            assert reference.violated(state, constants, others) is None
        dense = codec.encode(to_tlc(state, spec))
        assert not bool(kern.invariant_fn([name])(dense))
        assert bool(kern.invariant_fn([name])(codec.encode(
            to_tlc(reference.init_state(constants), spec))))
    # the door's own host-side check names the first one broken
    assert spec.check_invariants(to_tlc(_violating(
        "AcknowledgedWriteNotLost", constants), spec)) \
        == "AcknowledgedWriteNotLost"


# ---------------------------------------------------------------------
# (d) the four engine paths, level for level
# ---------------------------------------------------------------------
# the per-action body builds sixteen programs, two minutes of compile on
# a cold cache whatever the depth: outside tier-1 (it ran to depth 13
# for the oracle, benchmark/oracles/state_transfer_levels.json)
ENGINES = ("device-fused",
           pytest.param("device-per-action", marks=pytest.mark.slow),
           "paged", "sharded")


def _build(name, spec):
    kw = dict(max_msgs=MAX_MSGS, next_capacity=1 << 16,
              fpset_capacity=1 << 18)
    if name.startswith("device"):
        from tpuvsr.engine.device_bfs import DeviceBFS
        return DeviceBFS(spec, commit=name[len("device-"):], **kw)
    if name == "paged":
        from tpuvsr.engine.paged_bfs import PagedBFS
        return PagedBFS(spec, **kw)
    from tpuvsr.parallel.sharded_bfs import ShardedBFS
    assert len(jax.devices()) >= 2      # tests/conftest.py makes 8
    return ShardedBFS(spec, Mesh(np.array(jax.devices()[:2]), ("d",)),
                      max_msgs=MAX_MSGS, tile=64, next_capacity=1 << 15,
                      fpset_capacity=1 << 17)


@pytest.mark.parametrize("name", ENGINES)
def test_engine_levels_equal_the_references(name, spec, ref_run,
                                            constants):
    eng = _build(name, spec)
    res = eng.run(max_depth=DEPTH)
    assert res.ok and res.error == f"depth limit {DEPTH} reached"
    assert list(eng.level_sizes) == ref_run["level_sizes"] == LEVELS
    assert res.distinct_states == ref_run["distinct"]
    counters = res.metrics["counters"]
    assert counters.get("grow_message_table", 0) == 0
    if name in ("device-fused", "paged"):
        # counted on the device, action by action, and over the
        # committed states (ST03Kernel.commit_stats)
        fired = res.metrics["gauges"]["action_expansions"]
        assert fired == ref_run["action_expansions"]
        assert sum(fired.values()) + 1 == res.states_generated \
            == ref_run["generated"]
        committed = [s for level in ref_run["levels"][1:] for s in level]
        assert counters["bag_slots"] == sum(
            len(s.messages) for s in committed)
        assert counters["bag_tombstones"] == sum(
            n == 0 for s in committed for _m, n in s.messages)
        assert counters.get("state_transfer_states", 0) == 0
        assert res.metrics["gauges"]["bag_peak"] \
            == ref_run["bag_peak"] <= MAX_MSGS
        # one record IS the StartViewChange quorum at three replicas:
        # nobody ever waits on one; on a DoViewChange quorum the new
        # primary does, with its own record in
        want = quorum_counts.committed(ref_run["levels"], constants)
        assert counters["quorum_waiting_states"] \
            == want["quorum_waiting_states"] > 0
        assert counters.get("svc_quorum_waiting_states", 0) \
            == want["svc_quorum_waiting_states"] == 0


def test_commit_stats_count_a_state_transfer_state(model, constants, spec):
    codec, kern = model
    names = [n for n, _how in kern.COMMIT_STATS]
    assert tuple(names) == STATS
    start = _state_transfer_start(constants)
    asked = next(succ for action, succ in
                 reference.successors(start, constants)
                 if action == "SendGetState")
    stats = jax.jit(kern.commit_stats)
    # replica 1's Prepare is delivered: no view change anywhere, so no
    # quorum is waited on
    for state, want in ((start, [0, 5, 1, 5, 0, 0]),
                        (asked, [1, 6, 1, 6, 0, 0])):
        assert list(np.asarray(stats(codec.encode(
            to_tlc(state, spec))))) == want


# ---------------------------------------------------------------------
# CLI and the served path, with no new option
# ---------------------------------------------------------------------
def test_cli_runs_the_module_by_name(capsys):
    from tpuvsr.cli.main import main
    # levels of one chunk end whole: past 1,000 states is depth 5
    rc = main([MODULE, "-config", CFG, "-maxstates", "1000", "-json"])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and out["ok"] and out["violated"] is None
    assert out["error"] == "state limit 1000 reached"
    assert (out["distinct_states"], out["diameter"]) == (
        sum(LEVELS[:6]), 5)
    assert out["metrics"]["gauges"]["bag_peak"] > 0


def test_served_job_runs_the_module_by_name(tmp_path, capsys):
    from tpuvsr.service.api import main as api_main
    depth = 5
    spool = str(tmp_path / "spool")
    assert api_main(["submit", MODULE, "-config", CFG, "--spool", spool,
                     "--flag", f"maxdepth={depth}", "--json"]) == 0
    job_id = json.loads(capsys.readouterr().out)["job_id"]
    assert api_main(["serve", "--drain", "--spool", spool,
                     "--quiet"]) == 0
    capsys.readouterr()
    assert api_main(["status", job_id, "--spool", spool, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["state"] == "done" and doc["result"]["ok"]
    assert doc["result"]["levels"] == LEVELS[:depth + 1]
    # a snapshot per level boundary (format 4), resumable by name
    with open(doc["metrics"]) as f:
        assert json.load(f)["counters"]["checkpoints"] == depth


# ---------------------------------------------------------------------
# (f) the program store tells the two kernel classes apart
# ---------------------------------------------------------------------
def test_program_key_tells_the_two_modules_apart(spec, tmp_path):
    from tpuvsr.engine import program_store
    from tpuvsr.engine.device_bfs import DeviceBFS
    vsr = DeviceBFS(load_spec("VSR", os.path.join(
        REPO, "benchmark", "configs", "vsr-shipped.cfg")),
        symmetry=False, max_msgs=MAX_MSGS)
    st03 = DeviceBFS(spec, max_msgs=MAX_MSGS)
    docs = [eng._level_key_doc() for eng in (vsr, st03)]
    assert all(d is not None for d in docs)     # both are stored
    signature = program_store._signature((jnp.zeros((), jnp.int32),))
    keys = [program_store.program_key(d, signature) for d in docs]
    assert keys[0] != keys[1]
    text = [json.dumps(d, sort_keys=True, default=str) for d in docs]
    assert "ST03Kernel" in text[1] and "ST03Kernel" not in text[0]
    assert "VSRKernel" in text[0] and "VSRKernel" not in text[1]
