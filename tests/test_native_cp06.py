"""VR_REPLICA_RECOVERY_CP (CP06) through the native door, from
committed files: `load_spec("VR_REPLICA_RECOVERY_CP", cfg)` with the
committed init state (examples/VR_REPLICA_RECOVERY_CP_init_trace.txt),
the kernel held state by state to the plain reference of its 22 actions
(benchmark/tools/checkpoint_recovery_reference.py: host values, its own
breadth-first loop, nothing of tpuvsr imported), and the engines held to
the reference's level sizes, per-action counts and committed-state
counters at the constants of the benchmark's cell
(benchmark/configs/vr-replica-recovery-cp.cfg: |Values| = 2, timer 2,
CrashLimit 1).

The reference itself is held to the record this repository has of the
real `.tla`: the interpreter's fixpoint 137,524 / 364,538 / diameter 29
and the 29 level sizes at |Values| = 1, timer 1, CrashLimit 1.  No
`.tla`, no interpreter: nothing here is `requires_reference`.
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
import checkpoint_recovery_reference as reference  # noqa: E402
import quorum_counts  # noqa: E402
from checkpoint_recovery_reference import (ANY_DEST, NIL, NOOP,  # noqa: E402
                                           NORMAL, RECOVERING,
                                           STATE_TRANSFER, Msg)

MODULE = "VR_REPLICA_RECOVERY_CP"
CFG = os.path.join(REPO, "benchmark", "configs",
                   "vr-replica-recovery-cp.cfg")
SMALL_CFG = os.path.join(REPO, "examples",
                         "VR_REPLICA_RECOVERY_CP_small.cfg")
MAX_MSGS = 24
# the reference's level sizes at the cell's constants (depth 6)
LEVELS = [1, 7, 35, 140, 510, 1693, 5157]
EVERY_STATE_THROUGH = 5
DEPTH = 6
TRIO = set(reference.STATE_TRANSFER_ACTIONS)
CHAIN = set(reference.CHECKPOINT_RECOVERY_ACTIONS)
BATCH = 128
# ST03's six (the two quorum counters since PR 49), then CP06's four
STATS = ("state_transfer_states", "bag_slots", "bag_tombstones",
         "bag_peak", "quorum_waiting_states", "svc_quorum_waiting_states",
         "recovering_states", "gc_states", "rec_set_peak", "dvc_set_peak")


def quorum_waits(state, constants):
    """`ST03Kernel`'s two quorum counters as this kernel carries them
    (the reference's `commit_stats` is older than they are): a replica
    in ViewChange whose DoViewChange is still to send has processed
    some StartViewChanges of its view and fewer than f (tombstones, as
    ST03 counts them: never at three replicas), or whose StartView is
    still to send holds some DoViewChanges and fewer than f + 1 (the
    receive-set, as this family counts them since AS04)."""
    # the StartViewChange half is ST03's own count (the states share
    # its field names); its tombstone count of DoViewChanges is not
    # what this family's SendSV reads
    svc_waits, _dvc_tombstones = quorum_counts.waiting(state, constants)
    need = constants.replicas // 2 + 1
    dvc_waits = any(
        status == reference.VIEW_CHANGE and not sent
        and 0 < len(received) < need
        for status, sent, received in zip(
            state.rep_status, state.rep_sent_sv, state.rep_recv_dvc))
    return {"quorum_waiting_states": svc_waits or dvc_waits,
            "svc_quorum_waiting_states": svc_waits}


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
def test_init_is_the_codecs_zero_state_in_view_1(spec, model, constants):
    from tpuvsr.models.cp06_kernel import ACTION_NAMES
    from tpuvsr.models.native import INIT_TRACES
    codec, _kern = model
    assert spec.native and spec.module.name == MODULE
    assert os.path.dirname(INIT_TRACES[MODULE]) == os.path.join(
        REPO, "examples")
    (st,) = spec.init_states()
    zero = codec.zero_state()
    zero["view"][:] = 1
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
        == list(reference.ACTIONS) and len(spec.actions) == 22


@pytest.mark.parametrize("action", reference.ACTIONS)
def test_each_action_is_located_in_cp06_or_nowhere(action, spec):
    """CP06's own lines where the kernel cites them (13 of the 22),
    else the generic location: never the lines of a base module
    (ST03's 407-447 for SendGetState, say)."""
    from tpuvsr.models.cp06_kernel import CP06Kernel
    lines = CP06Kernel.ACTION_LINES
    assert len(lines) == 13 and lines["Crash"] == (985, 1009) \
        and lines["ReceiveGetState"] == (644, 680)
    loc = {a.name: a.location for a in spec.actions}[action]
    if action in lines:
        lo, hi = lines[action]
        assert 346 < lo < hi < 1186     # between the helpers and Next
        assert loc == f"lines {lo}-{hi} of module {MODULE}"
    else:
        assert loc == f"native kernel of module {MODULE}"


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


@pytest.mark.parametrize("module", [
    "VR_ASSUME_NEWVIEWCHANGE", "VR_INC_RESEND", "VR_APP_STATE",
    "VR_REPLICA_RECOVERY"])
def test_the_four_other_modules_stay_shut(module):
    with pytest.raises(TLAError, match="no committed init trace"):
        load_spec(module, CFG)


# ---------------------------------------------------------------------
# (a) the reference against the record of the real .tla
# ---------------------------------------------------------------------
def _raw_log_divergence(state):
    """NoLogDivergence as the base modules read it, on the raw logs:
    what flagged a recovered replica before the kernel's OpOf."""
    def entry(r, pos):
        log = state.rep_log[r]
        return log[pos] if pos < len(log) else None
    R = len(state.rep_log)
    return any(entry(a, pos) != entry(b, pos)
               for a in range(R) for b in range(R)
               for pos in range(min(state.rep_commit_number[a],
                                    state.rep_commit_number[b])))


@pytest.fixture(scope="module")
def fixpoint():
    """One reference run over the real module's record, shared."""
    c, invariants = reference.read_cfg(SMALL_CFG)
    assert c == reference.Constants(3, ("v1",), 1, 0, 1)
    return c, reference.bfs(c, invariants, keep_levels=True)


def test_reference_reaches_the_interpreters_fixpoint(fixpoint):
    _c, res = fixpoint
    with open(os.path.join(REPO, "scripts", "fixpoints.json")) as f:
        fix = json.load(f)["06-replica-recovery-cp/" + MODULE]
    with open(os.path.join(REPO, "scripts",
                           "recovery_fixpoints.json")) as f:
        pin = json.load(f)[MODULE]
    assert (fix["distinct"], fix["generated"], fix["diameter"]) == (
        137524, 364538, 29) and fix["fixpoint"]
    assert pin["engines_agree"] and pin["matches_interpreter_137524"]
    assert res["fixpoint"] and res["violation"] is None
    assert res["level_sizes"] == pin["sharded"]["level_sizes"]
    assert (res["distinct"], res["generated"],
            len(res["level_sizes"])) == (137524, 364538, 29)
    assert res["aux_conflicts"] == 0


def test_which_actions_fire_inside_the_real_modules_record(fixpoint):
    """18 of the 22, the whole crash / checkpoint / recovery chain
    among them; the state-transfer trio and NoProgressChange rest on
    transcription (the crafted subtree below)."""
    _c, res = fixpoint
    fired = res["action_expansions"]
    assert sum(fired.values()) + 1 == res["generated"]
    assert {a for a, n in fired.items() if not n} == TRIO | {
        "NoProgressChange"}
    assert {a: fired[a] for a in reference.CHECKPOINT_RECOVERY_ACTIONS} \
        == {"Crash": 4530, "ReceiveGetCheckpointMsg": 10000,
            "ReceiveNewCheckpointMsg": 10000, "ReceiveRecoveryMsg": 2874,
            "ReceiveRecoveryResponseMsg": 27806, "CompleteRecovery": 4221}
    assert (fired["SendSV"], fired["ReceiveSV"]) == (2772, 12438)
    # one slot a source is all either receive-set ever needs there
    committed = res["committed"]
    assert (committed["dvc_per_source"], committed["rec_per_source"],
            committed["bag_peak"]) == (1, 1, 21)


def _first_mended_parent(c, levels):
    """The first state in breadth-first order one of whose
    ReceiveNewCheckpointMsg successors the raw-log reading flags and
    OpOf accepts, with its index."""
    index = 0
    for level in levels:
        for state in level:
            for action, succ in reference.successors(state, c):
                if (action == "ReceiveNewCheckpointMsg"
                        and _raw_log_divergence(succ)):
                    return index, state, succ
            index += 1
    return None


def test_reference_reproduces_the_mended_divergence(fixpoint):
    """scripts/cp06_divergence.py: the device flagged NoLogDivergence
    after ReceiveNewCheckpointMsg at parent gid ~1446 where the
    interpreter accepted; the kernel was mended with OpOf
    (CP06:1219-1222).  The reference's own breadth-first order meets
    the first such parent in the same level (6, numbers 705-1583; 1414
    in two runs: the order inside a level follows the process's string
    hashes), and `_mended_parent` is one of them."""
    c, res = fixpoint
    index, parent, succ = _first_mended_parent(c, res["levels"][:7])
    assert sum(res["level_sizes"][:6]) <= index < sum(
        res["level_sizes"][:7]) and parent in res["levels"][6]
    views = {s[:reference.N_VIEW] for s in res["levels"][6]}
    mine = _mended_parent(c)
    assert mine[:reference.N_VIEW] in views
    (healed,) = [s for a, s in reference.successors(mine, c)
                 if a == "ReceiveNewCheckpointMsg"]
    for state in (succ, healed):
        assert _raw_log_divergence(state)
        assert reference.no_log_divergence(state, c)
        assert reference.violated(state, c, reference.INVARIANTS) is None


def test_reference_levels_at_the_cells_constants(ref_run):
    assert ref_run["level_sizes"] == LEVELS
    assert ref_run["violation"] is None and ref_run["aux_conflicts"] == 0
    with open(os.path.join(REPO, "benchmark", "oracles",
                           "checkpoint_recovery_levels.json")) as f:
        oracle = json.load(f)
    assert oracle["level_sizes"][:DEPTH + 1] == LEVELS
    assert set(oracle["action_expansions"]) == set(reference.ACTIONS)
    assert sum(oracle["action_expansions"].values()) + 1 \
        == oracle["generated"]
    assert sum(oracle["level_sizes"]) == oracle["distinct"]


# ---------------------------------------------------------------------
# (b), (c) the kernel against the reference, state by state
# ---------------------------------------------------------------------
def to_tlc(state, spec):
    """A reference `State` as the TLC-valued dict the codec encodes."""
    c = spec.cfg.constants
    value = {v.name: v for v in c["Values"]}
    value[NOOP] = c["NoOp"]
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
                  "last_normal_vn", "cp_number", "flag", "x"):
            if getattr(m, k) is not None:
                f[k] = getattr(m, k)
        if m.first_op is not None:
            f["first_op"] = c["Nil"] if m.first_op == NIL else m.first_op
        if m.message is not None:
            f["message"] = mk_record(operation=value[m.message])
        if m.checkpoint is not None:
            f["checkpoint"] = log(m.checkpoint)
        if m.log_suffix == NIL:
            f["log_suffix"] = c["Nil"]
        elif m.log_suffix is not None:
            f["log_suffix"] = log(
                m.log_suffix, m.first_op if m.cp_number is None
                else m.cp_number + 1)
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
                host = dict(reference.commit_stats(state),
                            **quorum_waits(state, constants))
                assert list(stats[i]) == [int(host[n]) for n in STATS], \
                    state
        return fired
    return run


def test_kernel_equals_reference_on_levels_0_to_5(compare, ref_run):
    states = [s for level in ref_run["levels"][:EVERY_STATE_THROUGH + 1]
              for s in level]
    assert len(states) == sum(LEVELS[:EVERY_STATE_THROUGH + 1]) == 2386
    fired = compare(states)
    # breadth-first order reaches the view change's second half, the
    # end of a recovery and the state-transfer era too late
    assert fired == {
        "TimerSendSVC", "ReceiveHigherSVC", "ReceiveMatchingSVC",
        "ReceiveClientRequest", "ReceivePrepareMsg", "ReceivePrepareOkMsg",
        "PrimaryExecuteOp"} | CHAIN - {"CompleteRecovery"}


def _mended_parent(c):
    """Parent number 1414 of the real module's record (level 6): 2 has
    crashed with nothing kept and holds a NewCheckpoint of one entry,
    while 1 has committed that entry in its log."""
    return reference.init_state(c)._replace(
        rep_status=(NORMAL, RECOVERING, NORMAL), rep_view_number=(1, 0, 1),
        rep_op_number=(1, 0, 1), rep_commit_number=(1, 0, 0),
        rep_log=(("v1",), (), ("v1",)), rep_app_state=(("v1",), (), ()),
        rep_peer_op_number=((0, 0, 1), (0, 0, 0), (0, 0, 0)),
        rep_rec_number=(0, 1, 0),
        messages=frozenset({
            (Msg("PrepareOkMsg", 1, 3, view_number=1, op_number=1), 0),
            (Msg("NewCheckpointMsg", 2, 1, cp_number=1,
                 checkpoint=("v1",)), 1),
            (Msg("GetCheckpointMsg", ANY_DEST, 2), 0),
            (Msg("PrepareMsg", 2, 1, view_number=1, op_number=1,
                 commit_number=0, message="v1"), 1),
            (Msg("PrepareMsg", 3, 1, view_number=1, op_number=1,
                 commit_number=0, message="v1"), 0)}),
        aux_client_acked=frozenset({("v1", True)}), aux_restart=1)


def _crafted_start(c):
    """View 2 (primary 2) in normal operation.  2 has committed v1 and
    v2 and garbage-collected v1 (a NoOp prefix below an application
    state of two); 1 has committed v1 and has v2's Prepare pending; 3
    is left behind in view 1 with an empty log and both Prepares
    pending, the second an op gap behind a higher view.  One timer and
    the one crash are left."""
    def prepare(dest, op, value, commit, count):
        return (Msg("PrepareMsg", dest, 2, view_number=2, op_number=op,
                    commit_number=commit, message=value), count)
    return reference.init_state(c)._replace(
        rep_view_number=(2, 2, 1), rep_op_number=(1, 2, 0),
        rep_commit_number=(1, 2, 0), rep_last_normal_view=(2, 2, 0),
        rep_log=(("v1",), (NOOP, "v2"), ()),
        rep_app_state=(("v1",), ("v1", "v2"), ()),
        rep_peer_op_number=((0, 0, 0), (2, 0, 0), (0, 0, 0)),
        messages=frozenset({
            prepare(1, 1, "v1", 0, 0), prepare(1, 2, "v2", 1, 1),
            prepare(3, 1, "v1", 0, 1), prepare(3, 2, "v2", 1, 1),
            (Msg("PrepareOkMsg", 2, 1, view_number=2, op_number=1), 0)}),
        aux_svc=1,
        aux_client_acked=frozenset({("v1", True), ("v2", False)}))


def _waiting_in_the_asked_view(c):
    """`_crafted_start` after 3's SendGetState, with 3 in the view it
    asked in: the state a SendGetState that adopted the view would
    leave, and the only kind in which ReceiveNewState fires (the
    reference's choice 7)."""
    asked = next(succ for action, succ in reference.successors(
        _crafted_start(c), c) if action == "SendGetState")
    return asked._replace(rep_view_number=(2, 2, 2))


@pytest.fixture(scope="module")
def subtree(constants):
    """Every state within two steps of the three crafted roots, and
    below them the lines of the state-transfer trio and of the crash /
    checkpoint / recovery chain alone, to a CompleteRecovery."""
    c = constants
    roots = [_crafted_start(c), _waiting_in_the_asked_view(c),
             _mended_parent(c)]
    seen = {s[:reference.N_VIEW] for s in roots}
    frontier, states, by_action = roots, list(roots), {}
    for depth in range(9):
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
    (start, waiting, mended), states, by_action = subtree
    assert TRIO | CHAIN <= set(by_action)
    # a crash that keeps a checkpoint: 2 keeps v1, v2 below a NoOp log
    kept = [succ for s, succ in by_action["Crash"] if s == start
            and succ.rep_status[1] == RECOVERING]
    assert sorted((s.rep_log[1], s.rep_app_state[1]) for s in kept) == [
        ((), ()), ((NOOP,), ("v1",)), ((NOOP, NOOP), ("v1", "v2"))]
    # SendGetState of 3 asks in view 2 from its commit number, once
    asked = next(succ for s, succ in by_action["SendGetState"]
                 if s == start)
    assert asked.rep_status[2] == STATE_TRANSFER
    assert asked.rep_view_number[2] == 1
    (ask,) = [m for m, _n in asked.messages if m.type == "GetStateMsg"]
    assert (ask.dest, ask.source, ask.view_number, ask.op_number) == (
        ANY_DEST, 3, 2, 0)
    # answered by 1 with its log (flag 0) and by 2, whose position 1 is
    # garbage-collected, with a checkpoint (flag 1, one `last_cp`)
    answers = sorted(
        next(m for m, _n in succ.messages if m.type == "NewStateMsg")
        for s, succ in by_action["ReceiveGetState"] if s == asked)
    assert [(m.source, m.flag, m.first_op, m.cp_number, m.commit_number,
             m.checkpoint, m.log_suffix) for m in answers] == [
        (1, 0, 1, None, 1, None, ("v1",)),
        (2, 1, None, 2, 2, ("v1", "v2"), ())]
    # choice 7: below `asked` no ReceiveNewState fires; in the asked
    # view both arms do
    below = {s[:reference.N_VIEW] for s, _ in by_action["ReceiveGetState"]
             if s == asked}
    assert below and not any(s[:reference.N_VIEW] in below for s, _succ
                             in by_action["ReceiveNewState"])
    caught_up = {(succ.rep_status[2], succ.rep_view_number[2],
                  succ.rep_log[2], succ.rep_app_state[2])
                 for _s, succ in by_action["ReceiveNewState"]}
    assert caught_up >= {
        (NORMAL, 2, ("v1",), ("v1",)),
        (NORMAL, 2, (NOOP, NOOP), ("v1", "v2"))}
    # the mended divergence: OpOf accepts what the raw logs would flag
    (healed,) = [succ for s, succ in by_action["ReceiveNewCheckpointMsg"]
                 if s == mended]
    assert healed.rep_log[1] == (NOOP,) and _raw_log_divergence(healed)
    assert reference.violated(healed, c, reference.INVARIANTS) is None
    # a recovery completes from a checkpoint-mode response (flag 1)
    assert any(m.flag == 1 for s, _succ in by_action["CompleteRecovery"]
               for m in s.rep_rec_recv[s.rep_status.index(RECOVERING)])
    assert not any(reference.violated(s, c, reference.INVARIANTS)
                   for s in states)
    assert 300 < len(states) < 6000


def test_kernel_equals_reference_on_the_crafted_subtree(compare, subtree):
    _roots, states, _by_action = subtree
    fired = compare(states)
    assert TRIO | CHAIN <= fired and {
        "TimerSendSVC", "ReceiveHigherSVC", "ReceivePrepareMsg",
        "ReceiveClientRequest"} <= fired


# ---------------------------------------------------------------------
# (e) one crafted violating state per cfg invariant
# ---------------------------------------------------------------------
def _violating(name, constants):
    init = reference.init_state(constants)
    both = frozenset({("v1", False), ("v2", False)})
    if name == "NoLogDivergence":
        # behind a NoOp the application state is what is compared
        return init._replace(
            rep_log=((NOOP,), ("v2",), ()), rep_op_number=(1, 1, 0),
            rep_commit_number=(1, 1, 0),
            rep_app_state=(("v1",), ("v2",), ()), aux_client_acked=both)
    if name == "NoAppStateDivergence":
        return init._replace(
            rep_log=(("v1",), ("v1",), ()), rep_op_number=(1, 1, 0),
            rep_commit_number=(1, 1, 0),
            rep_app_state=(("v1",), ("v2",), ()), aux_client_acked=both)
    if name == "AcknowledgedWriteNotLost":
        return init._replace(aux_client_acked=frozenset({("v1", True)}))
    if name == "CommitNumberNeverHigherThanOpNumber":
        return init._replace(rep_commit_number=(0, 1, 0),
                             rep_app_state=((), ("v1",), ()))
    assert name == "CommitNumberMatchesAppState"
    return init._replace(rep_log=((), ("v1",), ()),
                         rep_op_number=(0, 1, 0),
                         rep_commit_number=(0, 1, 0),
                         aux_client_acked=frozenset({("v1", False)}))


@pytest.mark.parametrize("name", reference.INVARIANTS)
def test_each_cfg_invariant_is_violated_by_its_crafted_state(
        name, spec, model, constants):
    codec, kern = model
    assert name in spec.cfg.invariants and name in kern.INVARIANT_FNS
    state = _violating(name, constants)
    assert not reference.INVARIANT_FNS[name](state, constants)
    if name == "CommitNumberMatchesAppState":
        # the layout has no length column for the application state:
        # the codec refuses to encode the state, loudly
        with pytest.raises(TLAError, match="layout invariant"):
            codec.encode(to_tlc(state, spec))
        return
    dense = codec.encode(to_tlc(state, spec))
    assert not bool(kern.invariant_fn([name])(dense))
    assert bool(kern.invariant_fn([name])(codec.encode(
        to_tlc(reference.init_state(constants), spec))))
    # the door's own host-side check names the first one broken
    first = reference.violated(state, constants, spec.cfg.invariants)
    assert spec.check_invariants(to_tlc(state, spec)) == first


# ---------------------------------------------------------------------
# (d) the engine paths, level for level
# ---------------------------------------------------------------------
# the per-action body builds twenty-two programs, minutes of compile on
# a cold cache whatever the depth, and the engine's own run to the
# 137,524 fixpoint takes four minutes: both outside tier-1 (the first
# ran to the pin for the oracle,
# benchmark/oracles/checkpoint_recovery_levels.json)
ENGINES = ("device-fused",
           pytest.param("device-per-action", marks=pytest.mark.slow),
           "paged", "sharded")


def _build(name, spec):
    kw = dict(max_msgs=MAX_MSGS, next_capacity=1 << 14,
              fpset_capacity=1 << 16)
    if name.startswith("device"):
        from tpuvsr.engine.device_bfs import DeviceBFS
        return DeviceBFS(spec, commit=name[len("device-"):], **kw)
    if name == "paged":
        from tpuvsr.engine.paged_bfs import PagedBFS
        return PagedBFS(spec, **kw)
    from tpuvsr.parallel.sharded_bfs import ShardedBFS
    assert len(jax.devices()) >= 2      # tests/conftest.py makes 8
    return ShardedBFS(spec, Mesh(np.array(jax.devices()[:2]), ("d",)),
                      max_msgs=MAX_MSGS, tile=64, next_capacity=1 << 13,
                      fpset_capacity=1 << 15)


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
        # committed states (CP06Kernel.commit_stats)
        fired = res.metrics["gauges"]["action_expansions"]
        assert fired == ref_run["action_expansions"]
        assert sum(fired.values()) + 1 == res.states_generated \
            == ref_run["generated"]
        assert sum(fired[a] for a in CHAIN) * 4 > sum(fired.values())
        committed = dict(ref_run["committed"])
        for state in (s for level in ref_run["levels"][1:] for s in level):
            for stat, waits in quorum_waits(state, constants).items():
                committed[stat] = committed.get(stat, 0) + int(waits)
        assert committed["svc_quorum_waiting_states"] == 0
        for stat in STATS:
            got = (res.metrics["gauges"] if stat.endswith("_peak")
                   else counters).get(stat, 0)
            assert got == committed[stat], stat
        assert committed["recovering_states"] > 0 < committed["gc_states"]
        # one slot a source holds every receive-set (else the run
        # would have stopped), and the bag its table
        assert (committed["dvc_per_source"],
                committed["rec_per_source"]) == (0, 1)
        assert committed["bag_peak"] == 12 <= MAX_MSGS


@pytest.mark.slow
def test_engine_reaches_the_real_modules_fixpoint():
    from tpuvsr.engine.device_bfs import DeviceBFS
    eng = DeviceBFS(load_spec(MODULE, SMALL_CFG))
    res = eng.run()
    assert res.ok and res.error is None
    assert (res.distinct_states, res.states_generated, res.diameter) == (
        137524, 364538, 29)


def test_a_second_record_of_one_source_stops_a_run_loudly(
        model, spec, constants):
    """The receive-sets have one slot a source: a second, different
    RecoveryResponse of one source raises the kernel's error flag (what
    makes an engine stop with `slot_error`) instead of dropping it."""
    from tpuvsr.models.vsr import ERR_REC_OVERFLOW
    codec, kern = model
    c = constants
    first = Msg("RecoveryResponseMsg", 2, 1, view_number=1, x=1,
                op_number=0, flag=0, log_suffix=NIL, first_op=NIL)
    state = _mended_parent(c)._replace(
        rep_rec_recv=(frozenset(), frozenset({first}), frozenset()),
        messages=frozenset({(first._replace(op_number=1), 1)}))
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
# CLI and the served path, with no new option
# ---------------------------------------------------------------------
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
    assert out["metrics"]["counters"]["recovering_states"] > 0


def test_served_job_runs_the_module_by_name(tmp_path, capsys):
    from tpuvsr.service.api import main as api_main
    depth = 4
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
