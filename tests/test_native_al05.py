"""VR_REPLICA_RECOVERY_ASYNC_LOG (AL05) through the native door, from
committed files: `load_spec("VR_REPLICA_RECOVERY_ASYNC_LOG", cfg)` with
the committed init state
(examples/VR_REPLICA_RECOVERY_ASYNC_LOG_init_trace.txt), the kernel
held state by state to the plain reference of its 20 actions
(benchmark/tools/async_log_reference.py: host values, its own
breadth-first loop, nothing of tpuvsr imported), and the engines held
to the reference's level sizes, per-action counts and committed-state
counters at the constants of the benchmark's cell
(benchmark/configs/vr-replica-recovery-async-log.cfg: |Values| = 2,
timer 2, CrashLimit 1).

The reference itself is held to the one record this repository has of
the real `.tla`: the device engine's fixpoint with the real module
loaded, 2,316,959 / 5,123,247 / diameter 30 and its 30 level sizes at
|Values| = 1, timer 1, CrashLimit 1 (levels 0-12 here, the whole of it
under `slow`).  No `.tla`, no interpreter: nothing here is
`requires_reference`.
"""

import json
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
import async_log_reference as reference  # noqa: E402
from async_log_reference import (ANY_DEST, NIL, NORMAL,  # noqa: E402
                                 RECOVERING, STATE_TRANSFER, Msg)

MODULE = "VR_REPLICA_RECOVERY_ASYNC_LOG"
CFG = os.path.join(REPO, "benchmark", "configs",
                   "vr-replica-recovery-async-log.cfg")
SMALL_CFG = os.path.join(REPO, "examples",
                         "VR_REPLICA_RECOVERY_ASYNC_LOG_small.cfg")
ORACLE = os.path.join(REPO, "benchmark", "oracles",
                      "async_log_levels.json")
MAX_MSGS = 24
# the reference's level sizes at the cell's constants (depth 5)
LEVELS = [1, 7, 37, 171, 697, 2604]
EVERY_STATE_THROUGH = 4
DEPTH = 5
TRIO = set(reference.STATE_TRANSFER_ACTIONS)
CHAIN = set(reference.RECOVERY_ACTIONS)
BATCH = 128
# ST03's six, then AL05's five
STATS = ("state_transfer_states", "bag_slots", "bag_tombstones",
         "bag_peak", "quorum_waiting_states", "svc_quorum_waiting_states",
         "recovering_states", "prefix_survivor_states",
         "suffix_reply_states", "rec_set_peak", "dvc_set_peak")
NEW_COUNTERS = ("recovering_states", "prefix_survivor_states",
                "suffix_reply_states")


@pytest.fixture(scope="module")
def spec():
    return load_spec(MODULE, CFG)


@pytest.fixture(scope="module")
def constants():
    c, invariants = reference.read_cfg(CFG)
    assert c == reference.Constants(3, ("v1", "v2"), 2, 0, 1)
    assert invariants == reference.INVARIANTS
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
def test_init_is_the_zero_state_in_view_1_with_last_normal_view_1(
        spec, model, constants):
    """The codec's zero state in view 1, and last normal view 1: what
    the record of the real module forces (the reference's choice 10)."""
    from tpuvsr.models.al05_kernel import ACTION_NAMES
    from tpuvsr.models.native import INIT_TRACES
    codec, kern = model
    assert spec.native and spec.module.name == MODULE
    assert os.path.dirname(INIT_TRACES[MODULE]) == os.path.join(
        REPO, "examples")
    (st,) = spec.init_states()
    zero = codec.zero_state()
    zero["view"][:] = 1
    zero["lnv"][:] = 1
    assert "rec_ceil" in zero and not zero["rec_ceil"].any()
    assert codec.decode(zero) == st
    assert codec.decode(codec.encode(st)) == st
    # the module's own planes are in the committed trace
    assert {"rep_app_state", "rep_rec_number", "rep_rec_recv",
            "rep_recv_dvc", "aux_restart"} <= set(st)
    assert reference.from_tlc(st, constants) == reference.init_state(
        constants)
    assert spec.check_invariants(st) is None
    assert spec.cfg.view == "view" and not spec.symmetry_perms
    assert list(spec.cfg.invariants) == list(reference.INVARIANTS)
    assert [a.name for a in spec.actions] == list(ACTION_NAMES) \
        == list(reference.ACTIONS) and len(spec.actions) == 20
    assert "RetryRecovery" not in kern.action_names


@pytest.mark.parametrize("action", reference.ACTIONS)
def test_each_action_is_located_in_al05_or_nowhere(action, spec):
    """AL05's own lines for the four actions the module rewrites, else
    the generic location: never the lines of a base module."""
    from tpuvsr.models.al05_kernel import AL05Kernel
    lines = AL05Kernel.ACTION_LINES
    assert set(lines) == CHAIN and lines["Crash"] == (851, 885) \
        and lines["CompleteRecovery"] == (947, 977)
    loc = {a.name: a.location for a in spec.actions}[action]
    if action in lines:
        lo, hi = lines[action]
        assert 851 <= lo < hi < 992     # below Next (AL05:992-1017)
        assert loc == f"lines {lo}-{hi} of module {MODULE}"
    else:
        assert loc == f"native kernel of module {MODULE}"


def _cfg_text(**replaced):
    with open(CFG) as f:
        text = f.read()
    for old, new in replaced.items():
        assert old in text
        text = text.replace(old, new)
    return text


@pytest.mark.parametrize("section", ["SYMMETRY symmValues",
                                     "PROPERTY AllReplicasMoveToSameView",
                                     "SPECIFICATION Spec"])
def test_sections_that_need_the_ast_stay_refused(section, tmp_path):
    text = _cfg_text()
    if section.startswith("SPECIFICATION"):
        text = text.replace("INIT Init\nNEXT Next\n", "")
    cfg = tmp_path / "x.cfg"
    cfg.write_text(text + "\n" + section + "\n")
    with pytest.raises(TLAError, match="needs the .tla"):
        load_spec(MODULE, str(cfg))


def test_five_replicas_are_refused_with_the_count_named(tmp_path):
    cfg = tmp_path / "r5.cfg"
    cfg.write_text(_cfg_text(**{"ReplicaCount = 3": "ReplicaCount = 5"}))
    with pytest.raises(TLAError, match=r"ReplicaCount = 5"):
        list(load_spec(MODULE, str(cfg)).init_states())


@pytest.mark.parametrize("module", [
    "VR_ASSUME_NEWVIEWCHANGE", "VR_INC_RESEND", "VR_APP_STATE",
    "VR_REPLICA_RECOVERY"])
def test_the_four_other_modules_stay_shut(module):
    with pytest.raises(TLAError, match="no committed init trace"):
        load_spec(module, CFG)


def test_an_unknown_module_is_a_missing_file():
    with pytest.raises(FileNotFoundError):
        load_spec("VR_NO_SUCH_MODULE", CFG)


# ---------------------------------------------------------------------
# (a) the reference against the record of the real .tla
# ---------------------------------------------------------------------
def _record():
    with open(os.path.join(REPO, "scripts",
                           "recovery_fixpoints.json")) as f:
        return json.load(f)[MODULE]["single"]


def test_reference_reproduces_the_records_levels_0_to_12():
    c, invariants = reference.read_cfg(SMALL_CFG)
    assert c == reference.Constants(3, ("v1",), 1, 0, 1)
    res = reference.bfs(c, invariants, max_depth=12)
    pin = _record()
    assert (pin["distinct"], pin["generated"], pin["diameter"]) == (
        2316959, 5123247, 30) and pin["fixpoint"] and pin["ok"]
    assert res["level_sizes"] == pin["level_sizes"][:13]
    assert res["distinct"] == 105146 and res["generated"] == 251829
    assert res["violation"] is None and res["aux_conflicts"] == 0
    # the interpreter over the real module stopped inside level 15
    with open(os.path.join(REPO, "scripts", "fixpoints.json")) as f:
        fix = json.load(f)["05-replica-recovery/" + MODULE]
    assert not fix["fixpoint"] and sum(pin["level_sizes"][:15]) \
        < fix["distinct"] == 300004 < sum(pin["level_sizes"][:16])
    fired = res["action_expansions"]
    assert {a for a, n in fired.items() if not n} == TRIO | {
        "NoProgressChange"}


@pytest.mark.slow
def test_reference_reaches_the_records_fixpoint():
    """All 30 level sizes, 2,316,959 / 5,123,247: five minutes and
    6 GB (CHANGES.md, PR 53)."""
    c, invariants = reference.read_cfg(SMALL_CFG)
    res = reference.bfs(c, invariants)
    pin = _record()
    assert res["fixpoint"] and res["violation"] is None
    assert res["level_sizes"] == pin["level_sizes"]
    assert (res["distinct"], res["generated"],
            len(res["level_sizes"])) == (2316959, 5123247, 30)
    assert res["aux_conflicts"] == 0


def test_last_normal_view_0_at_init_contradicts_the_record(constants):
    """The door's first find: from the sibling modules' Init (last
    normal view 0) a replica that recovered in view 1 outranks, with an
    empty log, one that never left view 1 and has committed an entry.
    The reference walks it: the StartView of the recovered replica
    installs commit number 1 over op number 0."""
    c, _inv = reference.read_cfg(SMALL_CFG)
    init0 = reference.init_state(c)._replace(
        rep_last_normal_view=(0,) * 3)
    recovered = Msg("DoViewChangeMsg", 2, 2, view_number=2, op_number=0,
                    commit_number=0, last_normal_vn=1, log=())
    stayed = Msg("DoViewChangeMsg", 2, 1, view_number=2, op_number=1,
                 commit_number=1, last_normal_vn=0, log=("v1",))
    assert reference.highest_log({recovered, stayed}, c)[0] is recovered
    assert reference.highest_log(
        {recovered, stayed._replace(last_normal_vn=1)}, c)[0].source == 1
    state = init0._replace(
        rep_status=(reference.VIEW_CHANGE,) * 3, rep_view_number=(2,) * 3,
        rep_op_number=(1, 0, 1), rep_commit_number=(1, 0, 0),
        rep_last_normal_view=(0, 1, 0),
        rep_log=(("v1",), (), ("v1",)), rep_app_state=(("v1",), (), ()),
        rep_sent_dvc=(True, True, False),
        rep_recv_dvc=(frozenset(), frozenset({recovered, stayed}),
                      frozenset()),
        rep_rec_number=(0, 1, 0), aux_svc=1, aux_restart=1,
        aux_client_acked=frozenset({("v1", True)}))
    with pytest.raises(AssertionError):     # executes past the log's end
        reference.successors(state, c)
    mended = state._replace(
        rep_last_normal_view=(1, 1, 1),
        rep_recv_dvc=(frozenset(), frozenset(
            {recovered, stayed._replace(last_normal_vn=1)}), frozenset()))
    (sv,) = [s for a, s in reference.successors(mended, c)
             if a == "SendSV"]
    assert sv.rep_log[1] == ("v1",) and sv.rep_commit_number[1] == 1
    assert reference.violated(sv, c, reference.INVARIANTS) is None


def test_reference_levels_at_the_cells_constants(ref_run):
    assert ref_run["level_sizes"] == LEVELS
    assert ref_run["violation"] is None and ref_run["aux_conflicts"] == 0
    with open(ORACLE) as f:
        oracle = json.load(f)
    assert oracle["level_sizes"][:DEPTH + 1] == LEVELS
    assert set(oracle["action_expansions"]) == set(reference.ACTIONS)
    assert sum(oracle["action_expansions"].values()) + 1 \
        == oracle["generated"]
    assert sum(oracle["level_sizes"]) == oracle["distinct"]
    assert set(NEW_COUNTERS) <= set(oracle["committed"])
    with open(os.path.join(REPO, "benchmark", "configs",
                           "vr-replica-recovery-async-log.json")) as f:
        config = json.load(f)
    pin = config["oracle"]["levels"]["complete_through_depth"]
    assert len(oracle["level_sizes"]) == pin + 2    # one level past it
    assert config["reduced"] == ["depth"]
    assert config["assumed"]["engine"]["device"]["max_msgs"] == MAX_MSGS \
        == config["widths"]["max_msgs"]


# ---------------------------------------------------------------------
# (b) the codec: both RecoveryResponse shapes, rec_ceil, a re-based
# suffix
# ---------------------------------------------------------------------
def to_tlc(state, spec):
    """A reference `State` as the TLC-valued dict the codec encodes."""
    c = spec.cfg.constants
    value = {v.name: v for v in c["Values"]}
    reps = range(1, len(state.rep_status) + 1)

    def fn(values, conv=lambda x: x):
        return FnVal((r, conv(values[r - 1])) for r in reps)

    def log(entries, first=1):
        return FnVal((first + i, mk_record(operation=value[v]))
                     for i, v in enumerate(entries))

    def msg(m):
        f = dict(type=c[m.type], source=m.source,
                 dest=c["AnyDest"] if m.dest == ANY_DEST else m.dest)
        for k in ("view_number", "op_number", "commit_number",
                  "last_normal_vn", "first_op", "prefix_ceil", "x", "op"):
            if getattr(m, k) is not None:
                f[k] = getattr(m, k)
        if m.message is not None:
            f["message"] = mk_record(operation=value[m.message])
        if m.log is not None:
            f["log"] = log(m.log, m.first_op or 1)
        if m.log_suffix == NIL:
            f["log_suffix"] = c["Nil"]
        elif m.log_suffix is not None:
            f["log_suffix"] = log(m.log_suffix, m.prefix_ceil + 1)
        return mk_record(**f)

    return {
        "replicas": frozenset(reps),
        "rep_status": fn(state.rep_status, lambda s: c[s]),
        "rep_view_number": fn(state.rep_view_number),
        "rep_op_number": fn(state.rep_op_number),
        "rep_commit_number": fn(state.rep_commit_number),
        "rep_last_normal_view": fn(state.rep_last_normal_view),
        "rep_log": fn(state.rep_log, log),
        "rep_app_state": fn(state.rep_app_state, log),
        "rep_peer_op_number": fn(
            state.rep_peer_op_number,
            lambda row: FnVal((p, row[p - 1]) for p in reps)),
        "rep_sent_dvc": fn(state.rep_sent_dvc),
        "rep_sent_sv": fn(state.rep_sent_sv),
        "rep_recv_dvc": fn(state.rep_recv_dvc,
                           lambda s: frozenset(map(msg, s))),
        "rep_rec_number": fn(state.rep_rec_number),
        "rep_rec_recv": fn(state.rep_rec_recv,
                           lambda s: frozenset(map(msg, s))),
        "no_progress": fn(state.no_progress),
        "no_progress_ctr": state.no_progress_ctr,
        "messages": FnVal((msg(m), n) for m, n in state.messages),
        "aux_svc": state.aux_svc,
        "aux_client_acked": FnVal((value[v], a)
                                  for v, a in state.aux_client_acked),
        "aux_restart": state.aux_restart,
    }


def _two_replies(c):
    """3 is Recovering with its first entry kept; the primary's reply
    (prefix_ceil 1, the suffix above it) is held in its receive-set and
    a backup's (log_suffix = Nil) is pending in the bag."""
    suffix = Msg("RecoveryResponseMsg", 3, 1, view_number=1, x=1,
                 prefix_ceil=1, log_suffix=("v2",), op_number=2,
                 commit_number=1)
    nil = Msg("RecoveryResponseMsg", 3, 2, view_number=1, x=1,
              log_suffix=NIL)
    return reference.init_state(c)._replace(
        rep_status=(NORMAL, NORMAL, RECOVERING), rep_view_number=(1, 1, 0),
        rep_op_number=(2, 0, 1), rep_commit_number=(1, 0, 0),
        rep_last_normal_view=(1, 1, 0),
        rep_log=(("v1", "v2"), (), ("v1",)),
        rep_app_state=(("v1",), (), ()),
        rep_peer_op_number=((0, 0, 2), (0, 0, 0), (0, 0, 0)),
        rep_rec_number=(0, 0, 1),
        rep_rec_recv=(frozenset(), frozenset(), frozenset({suffix})),
        messages=frozenset({
            (nil, 1), (suffix, 0),
            (Msg("RecoveryMsg", 1, 3, x=1, op=1), 0),
            (Msg("RecoveryMsg", 2, 3, x=1, op=1), 0)}),
        aux_client_acked=frozenset({("v1", True), ("v2", False)}),
        aux_restart=1), suffix, nil


def test_codec_round_trips_both_reply_shapes(spec, model, constants):
    from tpuvsr.models.vsr import H_COMMIT, H_FIRST, H_OP, H_TYPE
    codec, _kern = model
    state, suffix, nil = _two_replies(constants)
    tlc = to_tlc(state, spec)
    dense = codec.encode(tlc)
    assert codec.decode(dense) == tlc
    assert reference.from_tlc(codec.decode(dense), constants) == state
    # the held reply: rec_ceil, and the suffix re-based at the ceiling
    assert dense["rec"][2].tolist() == [1, 0, 0]
    assert int(dense["rec_ceil"][2, 0]) == 1
    assert int(dense["rec_has_log"][2, 0]) == 1
    v2 = codec.value_id[next(v for v in spec.cfg.constants["Values"]
                             if v.name == "v2")]
    assert dense["rec_log"][2, 0].tolist() == [v2, 0]
    assert (int(dense["rec_op"][2, 0]), int(dense["rec_commit"][2, 0])) \
        == (2, 1)
    # the two shapes in the bag: H_OP = -1 marks the Nil form
    rows = {tuple(int(x) for x in (h[H_OP], h[H_COMMIT], h[H_FIRST])):
            dense["m_log"][k].tolist()
            for k, h in enumerate(dense["m_hdr"])
            if dense["m_present"][k] and h[H_TYPE] == 9}
    assert rows == {(2, 1, 1): [v2, 0], (-1, -1, 0): [0, 0]}
    for m in (suffix, nil):
        f = dict(to_tlc(state._replace(messages=frozenset({(m, 1)})),
                        spec)["messages"].items[0][0].items)
        assert ("prefix_ceil" in f) == ("op_number" in f) \
            == ("commit_number" in f) == (m is suffix)
    # a plain log entry is its value id: the packed (value, view) pair
    # of RR05 is undone
    assert codec._entry_code_hi(3) == 2
    bounds = codec.plane_bounds({})
    assert bounds["rec_ceil"] == (0, 2) and bounds["rec_log"] == (0, 2)


# ---------------------------------------------------------------------
# (c) the kernel against the reference, state by state
# ---------------------------------------------------------------------
@pytest.fixture(scope="module")
def compare(spec, model, constants):
    """compare(states): every state's kernel successors, as sets per
    action name, equal the reference's; every guard equals its
    action's enabling; every cfg invariant's kernel function and every
    entry of `commit_stats` equals the reference's; the codec round
    trips.  Returns the actions that fired."""
    codec, kern = model
    names = kern.action_names
    lane_action = np.asarray(kern.lane_action)
    guards = kern._guard_fns()
    assert tuple(n for n, _how in kern.COMMIT_STATS) == STATS

    def guard_lanes(st):
        return jnp.concatenate([
            jax.vmap(lambda ln, g=g: g(st, ln))(
                jnp.arange(kern._lane_count(n), dtype=jnp.int32))
            for n, g in zip(names, guards)])
    guard_batch = jax.jit(jax.vmap(guard_lanes))
    inv_names = list(spec.cfg.invariants)
    inv_batch = jax.jit(jax.vmap(lambda st: jnp.stack(
        [kern.invariant_fn([n])(st) for n in inv_names])))
    stat_batch = jax.jit(jax.vmap(kern.commit_stats))

    def run(states):
        fired = set()
        for lo in range(0, len(states), BATCH):
            part = states[lo:lo + BATCH]
            tlc = [to_tlc(s, spec) for s in part]
            dense = [codec.encode(t) for t in tlc]
            dense += [dense[-1]] * (BATCH - len(part))  # one program
            batch = {k: np.stack([d[k] for d in dense]) for k in dense[0]}
            succs, en = kern.step_batch(batch)
            en = np.asarray(en)
            assert np.array_equal(np.asarray(guard_batch(batch)), en)
            ok = np.asarray(inv_batch(batch))
            stats = np.asarray(stat_batch(batch))
            succs = {k: np.asarray(v) for k, v in succs.items()}
            for i, state in enumerate(part):
                assert codec.decode(dense[i]) == tlc[i]
                assert reference.from_tlc(tlc[i], constants) == state
                got = set()
                for lane in np.flatnonzero(en[i]):
                    assert succs["err"][i, lane] == 0
                    got.add((names[lane_action[lane]], reference.from_tlc(
                        codec.decode({k: v[i, lane]
                                      for k, v in succs.items()}),
                        constants)))
                want = set(reference.successors(state, constants))
                assert got == want, (state, sorted(
                    a for a, _ in got ^ want))
                fired |= {a for a, _ in want}
                assert list(ok[i]) == [
                    reference.INVARIANT_FNS[n](state, constants)
                    for n in inv_names], state
                host = reference.commit_stats(state)
                assert list(stats[i]) == [int(host[n]) for n in STATS], \
                    state
        return fired
    return run


def test_kernel_equals_reference_on_levels_0_to_4(compare, ref_run):
    states = [s for level in ref_run["levels"][:EVERY_STATE_THROUGH + 1]
              for s in level]
    assert len(states) == sum(LEVELS[:EVERY_STATE_THROUGH + 1]) == 913
    fired = compare(states)
    # breadth-first order reaches the view change's second half, the
    # end of a recovery and the state-transfer era too late
    assert fired == {
        "TimerSendSVC", "ReceiveHigherSVC", "ReceiveMatchingSVC",
        "SendDVC", "ReceiveHigherDVC", "ReceiveMatchingDVC",
        "ReceiveClientRequest", "ReceivePrepareMsg",
        "ReceivePrepareOkMsg", "PrimaryExecuteOp"} | CHAIN - {
        "CompleteRecovery"}


def _follow(state, c, *steps):
    """`state` after `steps`: each an action name and a test of the
    successor, the first successor of that action that passes."""
    for action, test in steps:
        state = next(s for a, s in reference.successors(state, c)
                     if a == action and test(s))
    return state


def _backup_with_two_entries(c):
    """View 1 from Init: the primary 1 has prepared v1 and v2 and
    committed v1; 3 holds both entries and has committed v1; 2 holds
    nothing (its Prepares are pending).  3 is the replica whose crash
    may keep 0, 1 or 2 entries."""
    return _follow(
        reference.init_state(c), c,
        ("ReceiveClientRequest", lambda s: s.rep_log[0] == ("v1",)),
        ("ReceivePrepareMsg", lambda s: s.rep_op_number[2] == 1),
        ("ReceivePrepareOkMsg", lambda s: s.rep_peer_op_number[0][2] == 1),
        ("PrimaryExecuteOp", lambda s: s.rep_commit_number[0] == 1),
        ("ReceiveClientRequest", lambda s: s.rep_log[0] == ("v1", "v2")),
        ("ReceivePrepareMsg", lambda s: s.rep_op_number[2] == 2))


def _left_behind(c):
    """View 2 (primary 2) in normal operation: 2 has committed v1 and
    v2, 1 holds both and has committed v1, 3 is left behind in view 1
    with an empty log and both Prepares pending, the second an op gap
    behind a higher view.  One timer and the one crash are left."""
    def prepare(dest, op, value, commit, count):
        return (Msg("PrepareMsg", dest, 2, view_number=2, op_number=op,
                    commit_number=commit, message=value), count)

    def ok(op):
        return (Msg("PrepareOkMsg", 2, 1, view_number=2, op_number=op), 0)
    return reference.init_state(c)._replace(
        rep_view_number=(2, 2, 1), rep_op_number=(2, 2, 0),
        rep_commit_number=(1, 2, 0), rep_last_normal_view=(2, 2, 1),
        rep_log=(("v1", "v2"), ("v1", "v2"), ()),
        rep_app_state=(("v1",), ("v1", "v2"), ()),
        rep_peer_op_number=((0, 0, 0), (2, 0, 0), (0, 0, 0)),
        messages=frozenset({
            prepare(1, 1, "v1", 0, 0), prepare(1, 2, "v2", 1, 0),
            prepare(3, 1, "v1", 0, 1), prepare(3, 2, "v2", 1, 1),
            ok(1), ok(2)}),
        aux_svc=1,
        aux_client_acked=frozenset({("v1", True), ("v2", True)}))


@pytest.fixture(scope="module")
def subtree(constants):
    """Every state within two steps of the crafted roots, and below
    them the lines of the state-transfer trio and of the crash /
    recovery chain alone, to a CompleteRecovery."""
    c = constants
    roots = [_backup_with_two_entries(c), _left_behind(c),
             _two_replies(c)[0]]
    seen = {s[:reference.N_VIEW] for s in roots}
    frontier, states, by_action = roots, list(roots), {}
    for depth in range(8):
        nxt = []
        for s in frontier:
            for action, succ in reference.successors(s, c):
                by_action.setdefault(action, []).append((s, succ))
                if succ[:reference.N_VIEW] in seen:
                    continue
                if depth >= 2 and action not in TRIO | CHAIN:
                    continue
                seen.add(succ[:reference.N_VIEW])
                nxt.append(succ)
        frontier = nxt
        states += nxt
    return roots, states, by_action


def test_crafted_subtree_fires_what_breadth_first_order_does_not(
        subtree, constants):
    c = constants
    (backup, behind, replies), states, by_action = subtree
    assert TRIO | CHAIN <= set(by_action)
    assert (backup.rep_op_number, backup.rep_commit_number) == (
        (2, 0, 2), (1, 0, 1))
    # a lossy crash of 3 at op 2: a prefix of 0, 1 or 2 entries and
    # nothing else, the floor min(commit, last_op) in its RecoveryMsg
    kept = sorted(
        (succ.rep_log[2], succ.rep_op_number[2], succ.rep_commit_number[2],
         succ.rep_app_state[2], succ.rep_view_number[2],
         {m.op for m, _n in succ.messages if m.type == "RecoveryMsg"})
        for s, succ in by_action["Crash"]
        if s == backup and succ.rep_status[2] == RECOVERING)
    assert kept == [((), 0, 0, (), 0, {0}), (("v1",), 1, 0, (), 0, {1}),
                    (("v1", "v2"), 2, 0, (), 0, {1})]
    # the primary answers the floor 1 with the suffix above it, a
    # backup with Nil and no op / commit / ceil
    answers = {(m.source, m.prefix_ceil, m.log_suffix, m.op_number,
                m.commit_number)
               for _s, succ in by_action["ReceiveRecoveryMsg"]
               for m, n in succ.messages
               if m.type == "RecoveryResponseMsg" and n}
    assert {(1, 1, ("v2",), 2, 1), (2, None, NIL, None, None),
            (1, 0, ("v1", "v2"), 2, 1)} <= answers
    # the splice: the own prefix through the ceiling, the primary's
    # suffix above it, executed through its commit number
    spliced = {(s.rep_log[2], succ.rep_log[2], succ.rep_app_state[2],
                succ.rep_commit_number[2], succ.rep_last_normal_view[2])
               for s, succ in by_action["CompleteRecovery"]
               if s.rep_status[2] == RECOVERING}
    assert ((("v1",), ("v1", "v2"), ("v1",), 1, 1) in spliced
            and ((), ("v1", "v2"), ("v1",), 1, 1) in spliced)
    assert any(reference.commit_stats(s)["suffix_reply_states"]
               and reference.commit_stats(s)["prefix_survivor_states"]
               for s in states)
    assert reference.commit_stats(replies) == dict(
        reference.commit_stats(replies), prefix_survivor_states=True,
        suffix_reply_states=True, recovering_states=True, rec_set_peak=1)
    # SendGetState of 3 asks in view 2 from its commit number, once;
    # 1 and 2 answer with their logs; 3 takes either in StateTransfer
    asked = next(succ for s, succ in by_action["SendGetState"]
                 if s == behind)
    assert asked.rep_status[2] == STATE_TRANSFER
    assert asked.rep_view_number[2] == 1
    (ask,) = [m for m, _n in asked.messages if m.type == "GetStateMsg"]
    assert (ask.dest, ask.source, ask.view_number, ask.op_number) == (
        ANY_DEST, 3, 2, 0)
    answers = sorted(
        next(m for m, _n in succ.messages if m.type == "NewStateMsg")
        for s, succ in by_action["ReceiveGetState"] if s == asked)
    assert [(m.source, m.first_op, m.op_number, m.commit_number, m.log)
            for m in answers] == [(1, 1, 2, 1, ("v1", "v2")),
                                  (2, 1, 2, 2, ("v1", "v2"))]
    caught_up = {(succ.rep_status[2], succ.rep_view_number[2],
                  succ.rep_log[2], succ.rep_app_state[2])
                 for _s, succ in by_action["ReceiveNewState"]}
    assert caught_up == {(NORMAL, 2, ("v1", "v2"), ("v1",)),
                         (NORMAL, 2, ("v1", "v2"), ("v1", "v2"))}
    assert not any(reference.violated(s, c, reference.INVARIANTS)
                   for s in states)
    assert 300 < len(states) < 6000


def test_kernel_equals_reference_on_the_crafted_subtree(compare, subtree):
    _roots, states, _by_action = subtree
    fired = compare(states)
    assert TRIO | CHAIN <= fired and {
        "TimerSendSVC", "ReceiveHigherSVC", "ReceivePrepareMsg",
        "PrimaryExecuteOp"} <= fired


# ---------------------------------------------------------------------
# one crafted violating state per cfg invariant
# ---------------------------------------------------------------------
def _violating(name, constants):
    init = reference.init_state(constants)
    both = frozenset({("v1", False), ("v2", False)})
    if name == "NoLogDivergence":
        return init._replace(
            rep_log=(("v1",), ("v2",), ()), rep_op_number=(1, 1, 0),
            rep_commit_number=(1, 1, 0),
            rep_app_state=(("v1",), ("v2",), ()), aux_client_acked=both)
    if name == "NoAppStateDivergence":
        return init._replace(
            rep_log=(("v1",), ("v1",), ()), rep_op_number=(1, 1, 0),
            rep_commit_number=(1, 1, 0),
            rep_app_state=(("v1",), ("v2",), ()), aux_client_acked=both)
    if name == "AcknowledgedWriteNotLost":
        return init._replace(aux_client_acked=frozenset({("v1", True)}))
    assert name == "CommitNumberNeverHigherThanOpNumber"
    return init._replace(rep_commit_number=(0, 1, 0),
                         rep_app_state=((), ("v1",), ()))


@pytest.mark.parametrize("name", reference.INVARIANTS)
def test_each_cfg_invariant_is_violated_by_its_crafted_state(
        name, spec, model, constants):
    codec, kern = model
    assert name in spec.cfg.invariants and name in kern.INVARIANT_FNS
    state = _violating(name, constants)
    assert not reference.INVARIANT_FNS[name](state, constants)
    dense = codec.encode(to_tlc(state, spec))
    assert not bool(kern.invariant_fn([name])(dense))
    assert bool(kern.invariant_fn([name])(codec.encode(
        to_tlc(reference.init_state(constants), spec))))
    # the door's own host-side check names the first one broken
    first = reference.violated(state, constants, spec.cfg.invariants)
    assert spec.check_invariants(to_tlc(state, spec)) == first


def test_a_second_record_of_one_source_stops_a_run_loudly(
        model, spec, constants):
    """The receive-sets have one slot a source: a second, different
    RecoveryResponse of one source raises the kernel's error flag (what
    makes an engine stop with `slot_error`) instead of dropping it."""
    from tpuvsr.models.vsr import ERR_REC_OVERFLOW
    codec, kern = model
    c = constants
    state, suffix, _nil = _two_replies(c)
    second = suffix._replace(view_number=2)
    state = state._replace(messages=frozenset({(second, 1), (suffix, 0)}))
    (succ,) = [s for a, s in reference.successors(state, c)
               if a == "ReceiveRecoveryResponseMsg"]
    assert reference.commit_stats(succ)["rec_per_source"] == 2
    dense = codec.encode(to_tlc(state, spec))
    succs, en = kern.step_batch({k: np.asarray(v)[None]
                                 for k, v in dense.items()})
    lanes = np.flatnonzero(np.asarray(en)[0])
    errs = np.asarray(succs["err"])[0, lanes]
    assert (errs == ERR_REC_OVERFLOW).sum() == 1 and not (
        errs & ~ERR_REC_OVERFLOW).any()


# ---------------------------------------------------------------------
# (d) the engine paths, level for level
# ---------------------------------------------------------------------
ENGINES = ("device-fused", "paged", "sharded")


def _build(name, spec):
    kw = dict(max_msgs=MAX_MSGS, next_capacity=1 << 13,
              fpset_capacity=1 << 15)
    if name.startswith("device"):
        # the engine at its defaults (max_msgs 24 is the codec's own
        # for this cfg): the program the CLI and the served job below
        # then find built
        from tpuvsr.engine.device_bfs import DeviceBFS
        return DeviceBFS(spec, commit=name[len("device-"):])
    if name == "paged":
        from tpuvsr.engine.paged_bfs import PagedBFS
        return PagedBFS(spec, **kw)
    from tpuvsr.parallel.sharded_bfs import ShardedBFS
    assert len(jax.devices()) >= 2      # tests/conftest.py makes 8
    return ShardedBFS(spec, Mesh(np.array(jax.devices()[:2]), ("d",)),
                      max_msgs=MAX_MSGS, tile=64, next_capacity=1 << 13,
                      fpset_capacity=1 << 15)


@pytest.mark.parametrize("name", ENGINES)
def test_engine_levels_equal_the_references(name, spec, ref_run, model):
    eng = _build(name, spec)
    res = eng.run(max_depth=DEPTH)
    assert res.ok and res.error == f"depth limit {DEPTH} reached"
    assert list(eng.level_sizes) == ref_run["level_sizes"] == LEVELS
    assert res.distinct_states == ref_run["distinct"]
    counters, gauges = res.metrics["counters"], res.metrics["gauges"]
    assert counters.get("grow_message_table", 0) == 0
    # every guard of the kernel is a table a state
    assert gauges["guard_table_lanes"] == model[1].n_lanes == 374
    if name in ("device-fused", "paged"):
        # counted on the device, action by action, and over the
        # committed states (AL05Kernel.commit_stats)
        fired = gauges["action_expansions"]
        assert fired == ref_run["action_expansions"]
        assert sum(fired.values()) + 1 == res.states_generated \
            == ref_run["generated"]
        assert sum(fired[a] for a in CHAIN) * 4 > sum(fired.values())
        committed = ref_run["committed"]
        for stat in STATS:
            got = (gauges if stat.endswith("_peak")
                   else counters).get(stat, 0)
            assert got == committed[stat], stat
        assert committed["recovering_states"] \
            > committed["prefix_survivor_states"] > 0
        # one slot a source holds every receive-set (else the run
        # would have stopped), and the bag its table
        assert (committed["dvc_per_source"],
                committed["rec_per_source"]) == (1, 1)
        assert 0 < committed["bag_peak"] <= MAX_MSGS


def test_cli_runs_the_module_by_name(capsys):
    from tpuvsr.cli.main import main
    # levels of one chunk end whole: past 600 states is depth 4
    rc = main([MODULE, "-config", CFG, "-maxstates", "600", "-json"])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and out["ok"] and out["violated"] is None
    assert out["error"] == "state limit 600 reached"
    assert (out["distinct_states"], out["diameter"]) == (
        sum(LEVELS[:5]), 4)
    assert out["metrics"]["gauges"]["bag_peak"] > 0
    assert out["metrics"]["counters"]["prefix_survivor_states"] > 0


def test_served_job_runs_the_module_by_name(tmp_path, capsys):
    from tpuvsr.service.api import main as api_main
    depth = 3
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
