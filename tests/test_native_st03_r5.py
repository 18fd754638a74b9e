"""VR_STATE_TRANSFER at ReplicaCount = 5 (f = 2) through the native
door, from committed files (benchmark/configs/vr-state-transfer-r5.cfg:
`vr-state-transfer.cfg` with one constant turned).

Every other state this repository commits has three replicas, where
every quorum of the protocol is one record: the first StartViewChange
a replica processes lets it send its DoViewChange, one PrepareOk
commits, the new primary picks its log among two DoViewChanges.  Here
a replica WAITS on a partly filled quorum, a commit needs the
acknowledgements of two different peers, and SendSV chooses among
three logs.  The kernel is held to the plain reference
(benchmark/tools/state_transfer_reference.py, which reads
`ReplicaCount` and writes every quorum in `replicas // 2`) state by
state on every state of levels 0-4 and on four crafted f = 2 subtrees,
each with its "fires / does not fire" pair asserted on the reference's
own successors; the engines to the reference's levels, per-action
counts and quorum counters (benchmark/tools/quorum_counts.py).

The comparison itself is `tests/st03_reference.py`'s, the one
tests/test_native_st03.py runs at three replicas.
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
CFG = os.path.join(REPO, "benchmark", "configs",
                   "vr-state-transfer-r5.cfg")
R3_CFG = os.path.join(REPO, "benchmark", "configs", "vr-state-transfer.cfg")
MAX_MSGS = 40           # the cell's (vr-state-transfer-r5.json)
# the reference's level sizes at the cell's constants (depth 5)
LEVELS = [1, 6, 44, 286, 1834, 11514]
EVERY_STATE_THROUGH = 4
DEPTH = 5
BATCH = 256
VIEW_CHANGE = reference.VIEW_CHANGE
counted = quorum_counts.counted


@pytest.fixture(scope="module")
def spec():
    return load_spec(MODULE, CFG)


@pytest.fixture(scope="module")
def constants():
    c, invariants = reference.read_cfg(CFG)
    assert c == reference.Constants(5, ("v1", "v2"), 2, 0)
    assert invariants == ("NoLogDivergence", "AcknowledgedWriteNotLost",
                          "CommitNumberNeverHigherThanOpNumber")
    return c


@pytest.fixture(scope="module")
def model(spec):
    codec, kern, _inv = spec.model(MAX_MSGS)
    return codec, kern


@pytest.fixture(scope="module")
def ref_run(constants):
    return reference.bfs(constants, reference.INVARIANTS, max_depth=DEPTH,
                         keep_levels=True)


@pytest.fixture(scope="module")
def compare(spec, model, constants):
    return make_compare(spec, model, constants, BATCH)


# ---------------------------------------------------------------------
# the door
# ---------------------------------------------------------------------
def test_init_at_five_replicas_is_the_references(spec, model, constants):
    codec, kern = model
    assert (kern.R, kern.shape.f, kern.n_lanes) == (5, 2, 622)
    (st,) = spec.init_states()
    assert st["replicas"] == frozenset(range(1, 6))
    assert reference.from_tlc(st, constants) == reference.init_state(
        constants)
    zero = codec.zero_state()
    zero["view"][:] = 1
    assert codec.decode(zero) == st == codec.decode(codec.encode(st))
    assert spec.check_invariants(st) is None
    assert [a.name for a in spec.actions] == list(reference.ACTIONS)


def test_init_at_three_replicas_is_the_committed_traces_entry_1():
    """The trace stays the anchor: the rule that gives Init at five
    replicas gives, at three, the committed trace's entry 1 value for
    value, and that entry is what the door yields there."""
    from tpuvsr.frontend.trace_parse import parse_trace_file
    from tpuvsr.models.native import INIT_AT_R, INIT_TRACES
    assert INIT_AT_R == {MODULE: (3, 5)}
    spec3 = load_spec(MODULE, R3_CFG)
    (entry,) = parse_trace_file(INIT_TRACES[MODULE], spec3)
    (st,) = spec3.init_states()
    assert st == entry.state
    codec, _kern, _inv = spec3.model(16)
    zero = codec.zero_state()
    zero["view"][:] = 1
    rule = codec.decode(zero)
    assert rule == entry.state


@pytest.mark.parametrize("module, cfg, replicas, message", [
    ("VSR", "examples/VSR_small.cfg", 5,
     r"does not fit this cfg's constants \(ReplicaCount = 5\)"),
    ("VR_REPLICA_RECOVERY_CP",
     "benchmark/configs/vr-replica-recovery-cp.cfg", 5,
     r"does not fit this cfg's constants \(ReplicaCount = 5\)"),
    (MODULE, "benchmark/configs/vr-state-transfer.cfg", 4,
     r"ReplicaCount = 4 is not admitted.*\[3, 5\]"),
    (MODULE, "benchmark/configs/vr-state-transfer.cfg", 7,
     r"ReplicaCount = 7 is not admitted.*\[3, 5\]"),
])
def test_every_other_replica_count_is_refused_by_name(
        module, cfg, replicas, message, tmp_path):
    """Only what a tier-1 test holds to a reference goes through: VSR
    and CP06 have no reference that reads R, an even R is no 2f + 1,
    and seven replicas have no test."""
    with open(os.path.join(REPO, cfg)) as f:
        text = f.read()
    assert "ReplicaCount = 3" in text
    path = tmp_path / "r.cfg"
    path.write_text(text.replace("ReplicaCount = 3",
                                 f"ReplicaCount = {replicas}"))
    with pytest.raises(TLAError, match=message):
        list(load_spec(module, str(path)).init_states())


def test_the_default_message_table_reads_the_replica_count(spec):
    """A broadcast is R - 1 records: the codec's default holds the
    counted peak at five replicas (32 through depth 8) and is what it
    was at three, for any timer limit."""
    from tpuvsr.models.st03 import shape_from_cfg
    five = dict(spec.cfg.constants)
    assert shape_from_cfg(five).MAX_MSGS == 48
    for timer in (1, 2, 3):
        three = dict(five, ReplicaCount=3, StartViewOnTimerLimit=timer)
        assert shape_from_cfg(three).MAX_MSGS == 8 * (1 + timer)
    assert shape_from_cfg(five, max_msgs=MAX_MSGS).MAX_MSGS == MAX_MSGS


# ---------------------------------------------------------------------
# the kernel against the reference, state by state
# ---------------------------------------------------------------------
def test_reference_levels_at_the_cells_constants(ref_run, constants):
    assert ref_run["level_sizes"] == LEVELS
    assert ref_run["violation"] is None and ref_run["aux_conflicts"] == 0
    with open(os.path.join(REPO, "benchmark", "oracles",
                           "state_transfer_r5_levels.json")) as f:
        oracle = json.load(f)
    assert oracle["level_sizes"][:DEPTH + 1] == LEVELS
    assert oracle["bag_peak"] <= MAX_MSGS
    # SendDVC first fires in level 4: two StartViewChanges processed
    assert ref_run["action_expansions"]["SendDVC"] > 0
    shallow = reference.bfs(constants, max_depth=3)
    assert shallow["action_expansions"]["SendDVC"] == 0


def test_kernel_equals_reference_on_levels_0_to_4(compare, ref_run):
    states = [s for level in ref_run["levels"][:EVERY_STATE_THROUGH + 1]
              for s in level]
    assert len(states) == sum(LEVELS[:EVERY_STATE_THROUGH + 1]) == 2171
    fired = compare(states)
    assert fired == {"TimerSendSVC", "ReceiveHigherSVC",
                     "ReceiveMatchingSVC", "SendDVC",
                     "ReceiveClientRequest", "ReceivePrepareMsg",
                     "ReceivePrepareOkMsg"}


# ---------------------------------------------------------------------
# crafted f = 2 subtrees: what breadth-first order reaches too late
# ---------------------------------------------------------------------
def take(state, constants, action, keeps):
    """The one successor of `state` by `action` that `keeps` holds
    for."""
    (succ,) = {s for a, s in reference.successors(state, constants)
               if a == action and keeps(s)}
    return succ


def by(action, state, constants):
    return [s for a, s in reference.successors(state, constants)
            if a == action]


def svc(view, dest, source):
    return Msg("StartViewChangeMsg", view, dest, source)


def _one_of_two_start_view_changes(constants):
    """Replicas 3 and 4 have timed out into view 2 (both timers spent)
    and broadcast; replica 5 has taken 3's StartViewChange
    (ReceiveHigherSVC: now in view 2 itself, one record counted) and
    has 4's pending.  Returns (the state before 5 moved, this one)."""
    init = reference.init_state(constants)
    timer_3 = take(init, constants, "TimerSendSVC",
                   lambda s: s.rep_view_number[2] == 2)
    timers = take(timer_3, constants, "TimerSendSVC",
                  lambda s: s.rep_view_number[3] == 2)
    return timers, take(timers, constants, "ReceiveHigherSVC",
                        lambda s: (svc(2, 5, 3), 0) in s.messages)


def test_send_dvc_needs_two_start_view_changes(compare, constants, model,
                                               spec):
    timers, one = _one_of_two_start_view_changes(constants)
    assert one.rep_status[4] == VIEW_CHANGE and not one.rep_sent_dvc[4]
    assert counted(one, 5, "StartViewChangeMsg") == 1
    assert (svc(2, 5, 4), 1) in one.messages
    # NOT after one: no SendDVC of any replica (3 and 4 have none yet)
    assert by("SendDVC", one, constants) == []
    two = take(one, constants, "ReceiveMatchingSVC",
               lambda s: (svc(2, 5, 4), 0) in s.messages)
    assert counted(two, 5, "StartViewChangeMsg") == 2
    (sent,) = by("SendDVC", two, constants)
    assert sent.rep_sent_dvc == (False,) * 4 + (True,)
    # to the primary of view 2, for delivery
    assert (Msg("DoViewChangeMsg", 2, 2, 5, op_number=0, commit_number=0,
                last_normal_vn=0, log=()), 1) in sent.messages
    # the counters: waiting with 1 of 2, not with 0 and not with 2
    codec, kern = model
    stats = jax.jit(kern.commit_stats)
    for state, waits in ((timers, 0), (one, 1), (two, 0)):
        got = dict(zip(STATS, np.asarray(stats(codec.encode(
            to_tlc(state, spec))))))
        assert (got["quorum_waiting_states"],
                got["svc_quorum_waiting_states"]) == (waits, waits)
        assert quorum_counts.waiting(state, constants) == (bool(waits),
                                                           False)
    states, by_action = explore(timers, constants, 3)
    assert one in states and two in states and len(states) > 300
    # wherever the reference sends a DoViewChange, two were counted
    for state, succ in by_action["SendDVC"]:
        (r,) = [i + 1 for i in range(5)
                if succ.rep_sent_dvc[i] and not state.rep_sent_dvc[i]]
        assert counted(state, r, "StartViewChangeMsg") >= 2
    fired = compare(states)
    assert {"ReceiveHigherSVC", "ReceiveMatchingSVC", "SendDVC"} <= fired


def test_the_counters_never_wait_on_a_start_view_change_at_three_replicas():
    """The same three steps at R = 3: the first StartViewChange a
    replica processes IS its quorum, so it never waits on one."""
    c3, _inv = reference.read_cfg(R3_CFG)
    spec3 = load_spec(MODULE, R3_CFG)
    codec, kern, _inv = spec3.model(24)
    init = reference.init_state(c3)
    timer = take(init, c3, "TimerSendSVC",
                 lambda s: s.rep_view_number[1] == 2)
    one = take(timer, c3, "ReceiveHigherSVC",
               lambda s: (svc(2, 3, 2), 0) in s.messages)
    assert counted(one, 3, "StartViewChangeMsg") == 1 == c3.replicas // 2
    assert len(by("SendDVC", one, c3)) == 1
    stats = np.asarray(jax.jit(kern.commit_stats)(codec.encode(
        to_tlc(one, spec3))))
    assert list(stats[-2:]) == [0, 0]
    assert quorum_counts.waiting(one, c3) == (False, False)


def _two_of_three_do_view_changes(constants):
    """View 2 (primary 2) half entered after v1 and v2 were prepared
    in view 1: replicas 2, 3 and 4 are in it with their DoViewChanges
    sent, 1 and 5 still Normal in view 1.  Primary 2 holds its own
    record (born delivered, log <<v1>>) and replica 3's (<<v1, v2>>);
    replica 4's (empty log) is pending: three records, three logs."""
    def dvc(source, log, count):
        return (Msg("DoViewChangeMsg", 2, 2, source, op_number=len(log),
                    commit_number=0, last_normal_vn=0, log=log), count)
    logs = (("v1", "v2"), ("v1",), ("v1", "v2"), (), ("v1",))
    return reference.init_state(constants)._replace(
        rep_status=("Normal", VIEW_CHANGE, VIEW_CHANGE, VIEW_CHANGE,
                    "Normal"),
        rep_view_number=(1, 2, 2, 2, 1), rep_log=logs,
        rep_op_number=tuple(map(len, logs)),
        rep_sent_dvc=(False, True, True, True, False),
        messages=frozenset({
            dvc(2, ("v1",), 0), dvc(3, ("v1", "v2"), 0), dvc(4, (), 1),
            (svc(2, 2, 3), 0), (svc(2, 2, 4), 0), (svc(2, 3, 2), 0),
            (svc(2, 3, 4), 0), (svc(2, 4, 2), 0), (svc(2, 4, 3), 0)}),
        aux_svc=1,
        aux_client_acked=frozenset({("v1", False), ("v2", False)}))


def test_send_sv_chooses_among_three_do_view_changes(compare, constants):
    two = _two_of_three_do_view_changes(constants)
    assert counted(two, 2, "DoViewChangeMsg") == 2
    # NOT with two, the quorum of three replicas
    assert by("SendSV", two, constants) == []
    assert quorum_counts.waiting(two, constants) == (False, True)
    three = take(two, constants, "ReceiveMatchingDVC", lambda s: True)
    assert counted(three, 2, "DoViewChangeMsg") == 3
    assert quorum_counts.waiting(three, constants) == (False, False)
    (sent,) = by("SendSV", three, constants)
    # the longest log wins, which is neither the primary's own nor the
    # last one in
    assert sent.rep_log[1] == ("v1", "v2") and sent.rep_op_number[1] == 2
    assert sent.rep_status[1] == "Normal" and sent.rep_sent_sv[1]
    views = sorted(m for m, n in sent.messages if m.type == "StartViewMsg")
    assert [(m.dest, m.log, n) for m in views
            for n in [dict(sent.messages)[m]]] == [
        (d, ("v1", "v2"), 1) for d in (1, 3, 4, 5)]
    states, by_action = explore(two, constants, 4)
    assert three in states and sent in states and len(states) > 500
    for state, succ in by_action["SendSV"]:
        (r,) = [i + 1 for i in range(5)
                if succ.rep_sent_sv[i] and not state.rep_sent_sv[i]]
        assert counted(state, r, "DoViewChangeMsg") >= 3
    # a replica still in view 1 and one in the view change both take it
    took = {(succ.rep_view_number, succ.rep_log)
            for _s, succ in by_action["ReceiveSV"]}
    assert len(took) > 1
    fired = compare(states)
    assert {"ReceiveMatchingDVC", "SendSV", "ReceiveSV"} <= fired


def _two_acknowledgements_pending(constants):
    """Normal operation in view 1: primary 1 has prepared v1 and v2,
    replica 2 has taken and acknowledged both, replica 3 the first;
    none of the three PrepareOks is delivered yet."""
    def prepare(dest, op, value, count):
        return (Msg("PrepareMsg", 1, dest, 1, op_number=op,
                    commit_number=0, message=value), count)

    def ok(source, op):
        return (Msg("PrepareOkMsg", 1, 1, source, op_number=op), 1)
    logs = (("v1", "v2"), ("v1", "v2"), ("v1",), (), ())
    return reference.init_state(constants)._replace(
        rep_log=logs, rep_op_number=tuple(map(len, logs)),
        messages=frozenset({
            prepare(2, 1, "v1", 0), prepare(2, 2, "v2", 0),
            prepare(3, 1, "v1", 0), prepare(3, 2, "v2", 1),
            prepare(4, 1, "v1", 1), prepare(4, 2, "v2", 1),
            ok(2, 1), ok(2, 2), ok(3, 1)}),
        aux_svc=2,
        aux_client_acked=frozenset({("v1", False), ("v2", False)}))


def test_execute_op_needs_two_different_peers(compare, constants):
    start = _two_acknowledgements_pending(constants)
    assert by("ExecuteOp", start, constants) == []
    first = take(start, constants, "ReceivePrepareOkMsg",
                 lambda s: s.rep_peer_op_number[0] == (0, 1, 0, 0, 0))
    assert by("ExecuteOp", first, constants) == []
    # NOT after two of one peer's
    same_peer = take(first, constants, "ReceivePrepareOkMsg",
                     lambda s: s.rep_peer_op_number[0] == (0, 2, 0, 0, 0))
    assert by("ExecuteOp", same_peer, constants) == []
    other_peer = take(first, constants, "ReceivePrepareOkMsg",
                      lambda s: s.rep_peer_op_number[0] == (0, 1, 1, 0, 0))
    (done,) = by("ExecuteOp", other_peer, constants)
    assert done.rep_commit_number == (1, 0, 0, 0, 0)
    assert ("v1", True) in done.aux_client_acked
    states, by_action = explore(start, constants, 6)
    assert {same_peer, other_peer, done} <= set(states)
    assert len(states) > 200
    for state, _succ in by_action["ExecuteOp"]:
        op = state.rep_commit_number[0] + 1
        assert sum(p >= op for p in state.rep_peer_op_number[0]) >= 2
    fired = compare(states)
    assert {"ReceivePrepareMsg", "ReceivePrepareOkMsg",
            "ExecuteOp"} <= fired


def _left_behind(constants):
    """View 2 (primary 2) in normal operation with replica 5 left
    behind in view 1 with an empty log (the recipe of
    tests/test_native_st03.py::_state_transfer_start at five
    replicas): 2 has prepared v1 and v2, 3 has taken both, 1 the first,
    4 none, and 5 has both Prepares pending, the second an op gap
    behind a higher view."""
    def prepare(dest, op, value, count):
        return (Msg("PrepareMsg", 2, dest, 2, op_number=op,
                    commit_number=0, message=value), count)
    logs = (("v1",), ("v1", "v2"), ("v1", "v2"), (), ())
    return reference.init_state(constants)._replace(
        rep_view_number=(2, 2, 2, 2, 1), rep_log=logs,
        rep_op_number=tuple(map(len, logs)),
        rep_last_normal_view=(2, 2, 2, 2, 0),
        messages=frozenset({
            prepare(1, 1, "v1", 0), prepare(1, 2, "v2", 1),
            prepare(3, 1, "v1", 0), prepare(3, 2, "v2", 0),
            prepare(4, 1, "v1", 1), prepare(4, 2, "v2", 1),
            prepare(5, 1, "v1", 1), prepare(5, 2, "v2", 1),
            (Msg("PrepareOkMsg", 2, 2, 3, op_number=2), 1)}),
        aux_svc=2,
        aux_client_acked=frozenset({("v1", False), ("v2", False)}))


def test_state_transfer_is_answered_by_a_non_primary(compare, constants):
    trio = set(reference.STATE_TRANSFER_ACTIONS)
    start = _left_behind(constants)
    asked = take(start, constants, "SendGetState",
                 lambda s: s.rep_status[4] == reference.STATE_TRANSFER)
    (ask,) = [m for m, _n in asked.messages if m.type == "GetStateMsg"]
    assert (ask.dest, ask.source, ask.view_number, ask.op_number) == (
        reference.ANY_DEST, 5, 2, 0)
    # AnyDest: every Normal replica of view 2 that is ahead answers,
    # the primary (2) and two that are not; 4 (nothing to give) and 5
    # (the asker) do not
    answers = sorted(
        next(m for m, _n in succ.messages if m.type == "NewStateMsg")
        for succ in by("ReceiveGetState", asked, constants))
    assert [(m.source, m.dest, m.first_op, m.log) for m in answers] == [
        (1, 5, 1, ("v1",)), (2, 5, 1, ("v1", "v2")),
        (3, 5, 1, ("v1", "v2"))]
    states, by_action = explore(
        start, constants, 6,
        follow=lambda depth, action: depth < 4 or action in trio)
    assert trio <= set(by_action) and len(states) > 250
    # ... and replica 5 installs what a non-primary sent it
    from_1 = [(s, succ) for s, succ in by_action["ReceiveNewState"]
              if succ.rep_log[4] == ("v1",)]
    assert from_1 and all(
        (succ.rep_status[4], succ.rep_view_number[4],
         succ.rep_last_normal_view[4]) == ("Normal", 2, 2)
        for _s, succ in from_1)
    fired = compare(states)
    assert trio <= fired


# ---------------------------------------------------------------------
# the engines, level for level, action for action, counter for counter
# ---------------------------------------------------------------------
# one tile of 128 states enables more lanes of these than the static
# cap of 4 a state (512): the cell's multipliers, so that nothing grows
# (vr-state-transfer-r5.json, assumed.sizing.expand_mults)
with open(os.path.join(REPO, "benchmark", "configs",
                       "vr-state-transfer-r5.json")) as _f:
    EXPAND_MULTS = json.load(_f)["assumed"]["engine"]["device"][
        "expand_mults"]

# the per-action body builds sixteen programs, a minute and more on a
# cold cache at 622 lanes, and no cell runs it: outside tier-1
ENGINES = ("device-fused", "sharded", "paged",
           pytest.param("device-per-action", marks=pytest.mark.slow))


def _build(name, spec):
    kw = dict(max_msgs=MAX_MSGS, next_capacity=1 << 15,
              fpset_capacity=1 << 17)
    if name.startswith("device"):
        from tpuvsr.engine.device_bfs import DeviceBFS
        return DeviceBFS(spec, commit=name[len("device-"):],
                         expand_mults=dict(EXPAND_MULTS), **kw)
    if name == "paged":
        from tpuvsr.engine.paged_bfs import PagedBFS
        return PagedBFS(spec, expand_mults=dict(EXPAND_MULTS), **kw)
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
    assert res.distinct_states == ref_run["distinct"] == 13685
    counters, gauges = res.metrics["counters"], res.metrics["gauges"]
    assert counters.get("grow_message_table", 0) == 0
    fired = gauges["action_expansions"]
    assert fired == ref_run["action_expansions"]
    assert sum(fired.values()) + 1 == res.states_generated \
        == ref_run["generated"]
    if name == "sharded":
        return      # its step carries no commit_stats (PERF.md 7)
    if name != "device-per-action":     # (its multipliers are tiles)
        assert counters.get("grows", 0) == 0
    assert gauges["bag_peak"] == ref_run["bag_peak"] == 20
    want = quorum_counts.committed(ref_run["levels"], constants)
    for stat in quorum_counts.COUNTERS:
        assert counters.get(stat, 0) == want[stat], stat
    # most committed states hold a replica that waits, nearly all of
    # them on a StartViewChange quorum: what three replicas never show
    assert want["svc_quorum_waiting_states"] * 10 > 8 * (
        ref_run["distinct"] - 1)
    assert want["quorum_waiting_states"] \
        >= want["svc_quorum_waiting_states"]


# ---------------------------------------------------------------------
# CLI and the served path, with no new option
# ---------------------------------------------------------------------
def test_cli_runs_five_replicas_by_name(capsys):
    from tpuvsr.cli.main import main
    rc = main([MODULE, "-config", CFG, "-maxstates", "40", "-json"])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and out["ok"] and out["violated"] is None
    assert out["error"] == "state limit 40 reached"
    assert (out["distinct_states"], out["diameter"]) == (
        sum(LEVELS[:3]), 2)
    assert out["metrics"]["counters"].get("grow_message_table", 0) == 0


def test_served_job_runs_five_replicas_by_name(tmp_path, capsys):
    from tpuvsr.service.api import main as api_main
    depth = 2
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
    with open(doc["metrics"]) as f:
        assert json.load(f)["counters"]["checkpoints"] == depth


# ---------------------------------------------------------------------
# the program store tells the two cluster sizes apart
# ---------------------------------------------------------------------
def test_program_key_tells_three_replicas_from_five(spec):
    from tpuvsr.engine import program_store
    from tpuvsr.engine.device_bfs import DeviceBFS
    docs = [DeviceBFS(s, max_msgs=24)._level_key_doc()
            for s in (load_spec(MODULE, R3_CFG), spec)]
    assert all(d is not None for d in docs)
    signature = program_store._signature((jnp.zeros((), jnp.int32),))
    keys = [program_store.program_key(d, signature) for d in docs]
    assert keys[0] != keys[1]
