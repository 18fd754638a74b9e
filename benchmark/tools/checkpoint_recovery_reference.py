#!/usr/bin/env python3
"""The plain reference of VR_REPLICA_RECOVERY_CP (CP06, the last spec
of the analysis series: VR Revisited with the crash of a replica that
keeps a checkpoint, log garbage collection, dual-mode replies,
checkpointed DoViewChange / StartView, and recovery as GetCheckpoint ->
NewCheckpoint -> Recovery -> RecoveryResponse -> CompleteRecovery): its
22 actions and its five invariants as plain Python on host values, and
its own breadth-first loop over the cfg VIEW.

    python3 benchmark/tools/checkpoint_recovery_reference.py CFG --depth N

A state is a `State` of plain values: a function over the replicas is
a tuple indexed by replica - 1, a log a tuple of value names in which
a garbage-collected position holds `NOOP`, the application state a
tuple of value names as long as the commit number, a message a `Msg`
record whose absent fields are None, the bag a frozenset of (record,
count) pairs that an action opens as a dict, and the two receive-sets
(`rep_recv_dvc`, `rep_rec_recv`) frozensets of the very records
received.  **A record delivered stays in the bag at count 0**; the
quorum of SendDVC counts those entries, the quorum of SendSV counts
the receive-set.  There is no JAX here and nothing of `tpuvsr` is
imported: no plane, no slot, no lane, no mask, no clipped index, no
hash; `send`, `broadcast`, `receivable` and the cfg reader are those
of `state_transfer_reference.py` beside this file (the benchmark's,
and as independent).

**What it is held to.**  `VR_REPLICA_RECOVERY_CP.tla` is not in this
repository.  One record of it is: at |Values| = 1,
StartViewOnTimerLimit = 1, CrashLimit = 1 the interpreter over the real
module reached 137,524 distinct / 364,538 generated / diameter 29
(`scripts/fixpoints.json`), and interpreter, single-chip and sharded
engines agreed on the 29 level sizes of
`scripts/recovery_fixpoints.json`; `bfs` below reproduces all of them
(`tests/test_native_cp06.py`).  Inside that record 18 of the 22
actions fire, the whole crash / checkpoint / recovery chain and the
checkpointed view change among them.  **`SendGetState`,
`ReceiveGetState`, `ReceiveNewState` and `NoProgressChange` never fire
there**: for them the sources are the line ranges the kernel cites
(CP06:644-712, ST03:407-447, 764-776), SURVEY 2.1-2.3 and the record
shapes of `CP06Codec.decode_msg_row`, and the crafted subtree of the
tests is where kernel and reference are held to each other.  Where the
transcription had to choose, it says so at the line, and the choices
are:

1. `WinningDVC`'s CHOOSE among received DoViewChange records that tie
   on (last_normal_vn, op_number) takes the least (checkpoint,
   commit_number, cp_number, log_suffix as (position, entry) pairs,
   source), values ordered by name and NoOp above them (the kernel's
   reading of the interpreter's record order, CP06:885-896).  The new
   commit number is the maximum over ALL received records.
2. `ApplyCheckpoint` (CP06:383-402) sets the commit number to the one
   given, lower or not (SendSV may lower the sender's own); the
   log-suffix arms (flag 0 of ReceiveNewState and CompleteRecovery) and
   ReceivePrepareMsg / PrimaryExecuteOp go through `MaybeExecuteOps`
   and never lower it.
3. `SendDVC` binds `last_cp \\in HighestGCedOp+1..commit` (CP06:799):
   a replica that has committed nothing sends no DoViewChange.  The
   new primary's own record is `SendAsReceived` (count 0) and joins
   its own receive-set.
4. A checkpoint-mode reply (flag 1: ReceiveGetState when the position
   asked from is garbage-collected, ReceiveRecoveryMsg at a primary
   when it is) carries `commit_number = cp_number`, one reply a
   `last_cp`; a log-suffix reply (flag 0) carries the sender's commit
   number.  A backup answers a RecoveryMsg with `log_suffix = Nil`,
   `first_op = Nil`, no commit number.
5. `Crash` is not gated on `no_progress` nor on the status, keeps
   `\\E last_cp \\in 0..commit`, and is `SendOnce` on its
   GetCheckpoint (addressed to AnyDest).  `ReceiveRecoveryMsg`,
   `ReceiveRecoveryResponseMsg` and `CompleteRecovery` are not gated
   on `no_progress` either (the kernel; RR05's are).
6. The RecoveryMsg of `ReceiveNewCheckpointMsg` carries `UniqueNumber`
   evaluated THEN (the highest x of a RecoveryMsg in the bag's domain
   plus one), while the responses are matched against the number set
   at the crash; with `CrashLimit = 1` both are 1.
7. `ReceiveNewState` needs the replica's view EQUAL to the message's
   (the kernel's reading of CP06:682-712; ST03 and AS04 need it
   above), while `SendGetState` (ST03's, inherited unchanged) leaves
   the view below the one it asks in: on the chain SendGetState ->
   ReceiveGetState -> ReceiveNewState of one replica the last does not
   fire.  Only the `.tla` can say which of the two is off (PERF.md 7).
8. `CompleteRecovery` installs, of the responses with a log in the
   highest view of ALL responses, the one from the lowest source (one
   primary a view: they are one).
9. `NoLogDivergence` and `AcknowledgedWriteNotLost` read a NoOp
   position through `OpOf` (CP06:1219-1246: the application state's
   entry there); `NoAppStateDivergence` also rejects a committed NoOp
   in an application state (CP06:1234-1240).  A position past a
   tuple's end reads as None.
10. Init: every replica Normal in view 1 with last normal view 0 (the
    committed `examples/VR_REPLICA_RECOVERY_CP_init_trace.txt`).

The VIEW drops `aux_svc`, `aux_client_acked` and `aux_restart` and
keeps `no_progress` / `no_progress_ctr`; the loop keeps the first full
state of each view it meets, as TLC does, and counts the views it met
twice IN ONE LEVEL under different auxiliaries (`aux_conflicts`).
"""

import argparse
import itertools
import json
import re
import sys
import time
from typing import NamedTuple

import state_transfer_reference
from state_transfer_reference import (ANY_DEST, NORMAL, STATE_TRANSFER,
                                      VIEW_CHANGE, broadcast, receivable,
                                      send)

RECOVERING = "Recovering"
NOOP, NIL = "NoOp", "Nil"
ACTIONS = (
    "TimerSendSVC", "ReceiveHigherSVC", "ReceiveMatchingSVC", "SendDVC",
    "ReceiveHigherDVC", "ReceiveMatchingDVC", "SendSV", "ReceiveSV",
    "ReceiveClientRequest", "ReceivePrepareMsg", "ReceivePrepareOkMsg",
    "PrimaryExecuteOp", "SendGetState", "ReceiveGetState",
    "ReceiveNewState", "Crash", "ReceiveGetCheckpointMsg",
    "ReceiveNewCheckpointMsg", "ReceiveRecoveryMsg",
    "ReceiveRecoveryResponseMsg", "CompleteRecovery", "NoProgressChange")
STATE_TRANSFER_ACTIONS = ("SendGetState", "ReceiveGetState",
                          "ReceiveNewState")
CHECKPOINT_RECOVERY_ACTIONS = (
    "Crash", "ReceiveGetCheckpointMsg", "ReceiveNewCheckpointMsg",
    "ReceiveRecoveryMsg", "ReceiveRecoveryResponseMsg", "CompleteRecovery")


class Msg(NamedTuple):
    """One bag record; a field its type does not carry is None."""
    type: str
    dest: object            # a replica, or ANY_DEST
    source: int
    view_number: object = None
    op_number: object = None
    commit_number: object = None
    last_normal_vn: object = None
    first_op: object = None     # NIL in a backup's RecoveryResponse
    message: object = None      # Prepare: the value of its log entry
    log_suffix: object = None   # a tuple of entries, or NIL
    checkpoint: object = None   # a prefix of an application state
    cp_number: object = None
    flag: object = None         # 0: log suffix, 1: checkpoint + suffix
    x: object = None            # the recovery number


class State(NamedTuple):
    """The 16 variables of the VIEW, then the three auxiliaries."""
    rep_status: tuple
    rep_view_number: tuple
    rep_op_number: tuple
    rep_commit_number: tuple
    rep_last_normal_view: tuple
    rep_log: tuple
    rep_app_state: tuple
    rep_peer_op_number: tuple
    rep_sent_dvc: tuple
    rep_sent_sv: tuple
    rep_recv_dvc: tuple     # of frozensets of DoViewChange records
    rep_rec_number: tuple
    rep_rec_recv: tuple     # of frozensets of RecoveryResponse records
    no_progress: tuple
    no_progress_ctr: int
    messages: frozenset     # of (Msg, count); count 0 entries stay
    aux_svc: int
    aux_client_acked: frozenset   # of (value, acknowledged)
    aux_restart: int


N_VIEW = 16     # State[:N_VIEW] is the VIEW projection


class Constants(NamedTuple):
    replicas: int
    values: tuple           # value names, in order
    timer_limit: int        # StartViewOnTimerLimit
    no_progress_limit: int  # NoProgressChangeLimit
    crash_limit: int        # CrashLimit


def read_cfg(path):
    """(Constants, invariant names) of a TLC cfg: the four constants
    and the INVARIANT section as `state_transfer_reference` reads
    them, and CrashLimit."""
    base, invariants = state_transfer_reference.read_cfg(path)
    with open(path) as f:
        crash = re.search(r"^\s*CrashLimit\s*=\s*(\d+)\s*$",
                          re.sub(r"\\\*.*", "", f.read()), re.M)
    return (Constants(*base, int(crash.group(1)) if crash else 0),
            invariants)


def init_state(c):
    R = c.replicas
    return State(
        rep_status=(NORMAL,) * R, rep_view_number=(1,) * R,
        rep_op_number=(0,) * R, rep_commit_number=(0,) * R,
        rep_last_normal_view=(0,) * R, rep_log=((),) * R,
        rep_app_state=((),) * R, rep_peer_op_number=((0,) * R,) * R,
        rep_sent_dvc=(False,) * R, rep_sent_sv=(False,) * R,
        rep_recv_dvc=(frozenset(),) * R, rep_rec_number=(0,) * R,
        rep_rec_recv=(frozenset(),) * R,
        no_progress=(False,) * R, no_progress_ctr=0,
        messages=frozenset(), aux_svc=0, aux_client_acked=frozenset(),
        aux_restart=0)


# -- checkpoints and log surgery (CP06:346-431) --------------------------
def highest_gced_op(log):
    """HighestGCedOp (CP06:346-354): the highest position that holds
    NoOp, 0 when none does."""
    return max((pos + 1 for pos, e in enumerate(log) if e == NOOP),
               default=0)


def checkpoint(app, last_cp):
    """Checkpoint(r, last_cp): the application state through it."""
    return app[:last_cp]


def log_suffix(log, first):
    """LogSuffix (CP06:364-367): the entries from position `first`."""
    return log[first - 1:]


def apply_checkpoint(cp, cp_number, suffix, op_number, new_commit):
    """ApplyCheckpoint (CP06:383-402) as (log, app state): NoOp through
    the checkpoint and the suffix above it; the checkpoint and the
    suffix's entries through `new_commit`; choice 2."""
    assert len(cp) == cp_number <= new_commit, (cp, cp_number, new_commit)
    assert cp_number + len(suffix) == op_number, (suffix, op_number)
    return ((NOOP,) * cp_number + suffix,
            cp + suffix[:new_commit - cp_number])


def execute_ops(app, commit, log, new_commit):
    """MaybeExecuteOps (AS04:277-282) as (app state, commit number):
    the log's entries old commit + 1 .. new are appended when the new
    commit number is above the old, and nothing is ever lowered."""
    if new_commit <= commit:
        return app, commit
    assert new_commit <= len(log), (log, new_commit)
    return app + log[commit:new_commit], new_commit


def splice(own, first_op, suffix):
    """The log of a log-suffix reply: the own log below `first_op`,
    the message's entries from there."""
    assert len(own) >= first_op - 1, (own, first_op)
    return own[:first_op - 1] + suffix


def unique_number(bag):
    """UniqueNumber (RR05:826-835): the highest x of a RecoveryMsg in
    the bag's domain plus one."""
    return 1 + max((m.x for m in bag if m.type == "RecoveryMsg"),
                   default=0)


def winning_dvc(dvcs, c):
    """(WinningDVC, HighestCommitNumber) of a receive-set; choice 1."""
    rank = {v: i + 1 for i, v in enumerate(c.values)}
    rank[NOOP] = len(c.values) + 1
    top = max((m.last_normal_vn, m.op_number) for m in dvcs)
    best = min(
        (m for m in dvcs if (m.last_normal_vn, m.op_number) == top),
        key=lambda m: (
            tuple(rank[e] for e in m.checkpoint), m.commit_number,
            m.cp_number,
            tuple((m.cp_number + 1 + i, rank[e])
                  for i, e in enumerate(m.log_suffix)),
            m.source))
    return best, max(m.commit_number for m in dvcs)


def successors(state, c):
    """Every (action name, successor State) the 22 actions allow from
    `state`, one entry a binding of the action's existentials (two
    bindings that give one state give two entries)."""
    R = c.replicas
    f = R // 2
    replicas = range(1, R + 1)
    bag0 = dict(state.messages)
    out = []

    def primary(view):
        return 1 + (view - 1) % R

    def at(var, r):
        return getattr(state, var)[r - 1]

    def normal_primary(r):
        return (primary(at("rep_view_number", r)) == r
                and at("rep_status", r) == NORMAL)

    def can_progress(r):
        return not at("no_progress", r)

    def step(action, r=None, bag=None, **changed):
        """`state` with `var=value`: as EXCEPT ![r] for the
        per-replica variables when `r` is given, else the whole."""
        new = {}
        for var, value in changed.items():
            old = getattr(state, var)
            if r is not None and isinstance(old, tuple):
                value = old[:r - 1] + (value,) + old[r:]
            new[var] = value
        if bag is not None:
            new["messages"] = frozenset(bag.items())
        out.append((action, state._replace(**new)))

    def reset_vc():
        """ResetVcVars (AS04:287-291) with an empty receive-set."""
        return dict(rep_sent_dvc=False, rep_sent_sv=False,
                    rep_recv_dvc=frozenset())

    def delivered(m):
        bag = dict(bag0)
        bag[m] -= 1
        return bag

    def checkpoints_of(r):
        """The `last_cp` a checkpointed send of `r` may bind
        (CP06:799): HighestGCedOp + 1 .. commit number."""
        return range(highest_gced_op(at("rep_log", r)) + 1,
                     at("rep_commit_number", r) + 1)

    def installed(cp, cp_number, suffix, op_number, new_commit):
        """ApplyCheckpoint's four variables of one replica."""
        log, app = apply_checkpoint(cp, cp_number, suffix, op_number,
                                    new_commit)
        return dict(rep_log=log, rep_app_state=app,
                    rep_op_number=op_number, rep_commit_number=new_commit)

    def install_reply(r, m):
        """What a dual-mode reply (NewState, RecoveryResponse with a
        log) leaves of log, app state, op and commit number at `r`."""
        if m.flag == 1:
            return installed(cp=m.checkpoint, cp_number=m.cp_number,
                             suffix=m.log_suffix, op_number=m.op_number,
                             new_commit=m.commit_number)
        log = splice(at("rep_log", r), m.first_op, m.log_suffix)
        assert len(log) == m.op_number, (state, m)
        app, commit = execute_ops(at("rep_app_state", r),
                                  at("rep_commit_number", r), log,
                                  m.commit_number)
        return dict(rep_log=log, rep_app_state=app,
                    rep_op_number=m.op_number, rep_commit_number=commit)

    # -- TimerSendSVC (CP06 via RR05:578-600): not while Recovering -----
    if state.aux_svc < c.timer_limit:
        for r in replicas:
            if (can_progress(r) and not normal_primary(r)
                    and at("rep_status", r) != RECOVERING):
                view = at("rep_view_number", r) + 1
                bag = dict(bag0)
                broadcast(bag, Msg("StartViewChangeMsg", None, r,
                                   view_number=view), replicas)
                step("TimerSendSVC", r, bag, rep_view_number=view,
                     rep_status=VIEW_CHANGE, aux_svc=state.aux_svc + 1,
                     **reset_vc())

    # -- the receive actions of a record addressed to one replica ------
    for m, count in bag0.items():
        if count <= 0 or m.dest == ANY_DEST:
            continue
        r = m.dest
        view, status = at("rep_view_number", r), at("rep_status", r)
        recovering = status == RECOVERING

        # the recovery chain is not gated on no_progress; choice 5
        if m.type == "NewCheckpointMsg":
            # ReceiveNewCheckpointMsg (CP06:1051-1079); choice 6
            if can_progress(r) and recovering:
                bag = delivered(m)
                broadcast(bag, Msg("RecoveryMsg", None, r,
                                   x=unique_number(bag0),
                                   op_number=m.cp_number), replicas)
                step("ReceiveNewCheckpointMsg", r, bag,
                     rep_log=(NOOP,) * m.cp_number,
                     rep_app_state=m.checkpoint,
                     rep_op_number=m.cp_number,
                     rep_commit_number=m.cp_number)
            continue
        if m.type == "RecoveryMsg":
            # ReceiveRecoveryMsg (CP06:1081-1105); choice 4
            if status != NORMAL:
                continue
            log, op = at("rep_log", r), at("rep_op_number", r)
            reply = Msg("RecoveryResponseMsg", m.source, r,
                        view_number=view, x=m.x, op_number=op)
            if not normal_primary(r):
                replies = [reply._replace(flag=0, log_suffix=NIL,
                                          first_op=NIL)]
            elif op > m.op_number and log[m.op_number] == NOOP:
                replies = [reply._replace(
                    flag=1, cp_number=cp, commit_number=cp,
                    checkpoint=checkpoint(at("rep_app_state", r), cp),
                    log_suffix=log_suffix(log, cp + 1))
                    for cp in checkpoints_of(r)]
            else:
                replies = [reply._replace(
                    flag=0, first_op=m.op_number + 1,
                    commit_number=at("rep_commit_number", r),
                    log_suffix=log_suffix(log, m.op_number + 1))]
            for answer in replies:
                bag = delivered(m)
                send(bag, answer)
                step("ReceiveRecoveryMsg", bag=bag)
            continue
        if m.type == "RecoveryResponseMsg":
            # ReceiveRecoveryResponseMsg (CP06:1107-1121)
            if recovering and at("rep_rec_number", r) == m.x:
                step("ReceiveRecoveryResponseMsg", r, delivered(m),
                     rep_rec_recv=at("rep_rec_recv", r) | {m})
            continue
        if not can_progress(r):
            continue

        if m.type in ("StartViewChangeMsg", "DoViewChangeMsg"):
            dvc = m.type == "DoViewChangeMsg"
            kind = "DVC" if dvc else "SVC"
            # ReceiveHigherSVC (RR05:602-625), ReceiveHigherDVC
            # (CP06:825-844): not while Recovering; the DVC that
            # carries the view seeds the new receive-set
            if m.view_number > view and not recovering:
                bag = delivered(m)
                broadcast(bag, Msg("StartViewChangeMsg", None, r,
                                   view_number=m.view_number), replicas)
                step("ReceiveHigher" + kind, r, bag,
                     rep_view_number=m.view_number,
                     rep_status=VIEW_CHANGE,
                     **dict(reset_vc(), rep_recv_dvc=frozenset(
                         {m} if dvc else ())))
            # ReceiveMatchingSVC (AS04:589-607: not after the own DVC),
            # ReceiveMatchingDVC (CP06:846-862: into the receive-set)
            if m.view_number == view and status == VIEW_CHANGE:
                if dvc:
                    step("ReceiveMatchingDVC", r, delivered(m),
                         rep_recv_dvc=at("rep_recv_dvc", r) | {m})
                elif not at("rep_sent_dvc", r):
                    step("ReceiveMatchingSVC", bag=delivered(m))

        elif m.type == "StartViewMsg":
            # ReceiveSV (CP06:939-971): not while Recovering
            if (((m.view_number == view and status == VIEW_CHANGE)
                 or m.view_number > view) and not recovering):
                bag = delivered(m)
                if at("rep_commit_number", r) < m.op_number:
                    send(bag, Msg("PrepareOkMsg", primary(m.view_number),
                                  r, view_number=m.view_number,
                                  op_number=m.op_number))
                step("ReceiveSV", r, bag, rep_status=NORMAL,
                     rep_view_number=m.view_number,
                     rep_last_normal_view=m.view_number, **reset_vc(),
                     **installed(cp=m.checkpoint,
                                 cp_number=m.cp_number,
                                 suffix=m.log_suffix,
                                 op_number=m.op_number,
                                 new_commit=m.commit_number))

        elif m.type == "PrepareMsg":
            follower = status == NORMAL and not normal_primary(r)
            op = at("rep_op_number", r)
            # ReceivePrepareMsg (AS04:361-383)
            if (follower and m.view_number == view
                    and m.op_number == op + 1):
                log = at("rep_log", r) + (m.message,)
                app, commit = execute_ops(
                    at("rep_app_state", r), at("rep_commit_number", r),
                    log, m.commit_number)
                bag = delivered(m)
                send(bag, Msg("PrepareOkMsg", m.source, r,
                              view_number=view, op_number=m.op_number))
                step("ReceivePrepareMsg", r, bag, rep_log=log,
                     rep_op_number=m.op_number, rep_app_state=app,
                     rep_commit_number=commit)
            # SendGetState (ST03:407-447, inherited); choice 7
            if (follower and m.view_number > view
                    and m.op_number > op + 1):
                ask = Msg("GetStateMsg", ANY_DEST, r,
                          view_number=m.view_number,
                          op_number=at("rep_commit_number", r))
                if ask not in bag0:             # SendOnce
                    bag = dict(bag0)
                    send(bag, ask)
                    step("SendGetState", r, bag,
                         rep_status=STATE_TRANSFER)

        elif m.type == "PrepareOkMsg":
            # ReceivePrepareOkMsg (ST03:350-374)
            peers = at("rep_peer_op_number", r)
            if (normal_primary(r) and m.view_number == view
                    and m.op_number > peers[m.source - 1]):
                step("ReceivePrepareOkMsg", r, delivered(m),
                     rep_peer_op_number=peers[:m.source - 1]
                     + (m.op_number,) + peers[m.source:])

        elif m.type == "NewStateMsg":
            # ReceiveNewState (CP06:682-712); choice 7
            if status == STATE_TRANSFER and m.view_number == view:
                step("ReceiveNewState", r, delivered(m),
                     rep_status=NORMAL, rep_view_number=m.view_number,
                     rep_last_normal_view=m.view_number,
                     **install_reply(r, m))

    # -- the two requests addressed to AnyDest: every replica but the
    # sender may answer ------------------------------------------------
    for m, count in bag0.items():
        for r in replicas:
            if not can_progress(r):
                continue
            log, app = at("rep_log", r), at("rep_app_state", r)
            op = at("rep_op_number", r)
            # ReceiveGetState (CP06:644-680); choice 4
            if (receivable(m, count, "GetStateMsg", r)
                    and at("rep_status", r) == NORMAL
                    and at("rep_view_number", r) == m.view_number
                    and op > m.op_number):
                reply = Msg("NewStateMsg", m.source, r,
                            view_number=m.view_number, op_number=op)
                if log[m.op_number] == NOOP:
                    replies = [reply._replace(
                        flag=1, cp_number=cp, commit_number=cp,
                        checkpoint=checkpoint(app, cp),
                        log_suffix=log_suffix(log, cp + 1))
                        for cp in checkpoints_of(r)]
                else:
                    replies = [reply._replace(
                        flag=0, first_op=m.op_number + 1,
                        commit_number=at("rep_commit_number", r),
                        log_suffix=log_suffix(log, m.op_number + 1))]
                for answer in replies:
                    bag = delivered(m)
                    send(bag, answer)
                    step("ReceiveGetState", bag=bag)
            # ReceiveGetCheckpointMsg (CP06:1017-1043): any checkpoint
            # 0 .. commit number, from any replica not Recovering
            if (receivable(m, count, "GetCheckpointMsg", r)
                    and at("rep_status", r) != RECOVERING):
                for cp in range(at("rep_commit_number", r) + 1):
                    bag = delivered(m)
                    send(bag, Msg("NewCheckpointMsg", m.source, r,
                                  cp_number=cp,
                                  checkpoint=checkpoint(app, cp)))
                    step("ReceiveGetCheckpointMsg", bag=bag)

    for r in replicas:
        view, status = at("rep_view_number", r), at("rep_status", r)
        log, app = at("rep_log", r), at("rep_app_state", r)
        op, commit = at("rep_op_number", r), at("rep_commit_number", r)
        # -- Crash (CP06:985-1009); choice 5 ----------------------------
        ask = Msg("GetCheckpointMsg", ANY_DEST, r)
        if state.aux_restart < c.crash_limit and ask not in bag0:
            for cp in range(commit + 1):
                bag = dict(bag0)
                send(bag, ask)
                step("Crash", r, bag, rep_status=RECOVERING,
                     rep_log=(NOOP,) * cp,
                     rep_app_state=checkpoint(app, cp),
                     rep_view_number=0, rep_op_number=cp,
                     rep_commit_number=cp, rep_peer_op_number=(0,) * R,
                     rep_last_normal_view=0,
                     rep_rec_number=unique_number(bag0),
                     rep_rec_recv=frozenset(),
                     aux_restart=state.aux_restart + 1, **reset_vc())
        # -- CompleteRecovery (CP06:1138-1170); choice 8 ----------------
        received = at("rep_rec_recv", r)
        if status == RECOVERING and len(received) > f:
            newest = max(m.view_number for m in received)
            with_log = sorted((m for m in received
                               if m.view_number == newest
                               and m.log_suffix != NIL),
                              key=lambda m: m.source)
            if with_log:
                m = with_log[0]
                step("CompleteRecovery", r, rep_status=NORMAL,
                     rep_view_number=m.view_number,
                     rep_last_normal_view=m.view_number,
                     rep_rec_recv=frozenset(), **install_reply(r, m))
        if not can_progress(r):
            continue
        # -- SendDVC (CP06:785-816): f processed SVCs; choice 3 ---------
        processed = sum(
            count == 0 and m.type == "StartViewChangeMsg"
            and m.dest == r and m.view_number == view
            for m, count in bag0.items())
        if (status == VIEW_CHANGE and not at("rep_sent_dvc", r)
                and processed >= f):
            for cp in checkpoints_of(r):
                own = Msg("DoViewChangeMsg", primary(view), r,
                          view_number=view, op_number=op,
                          commit_number=commit,
                          last_normal_vn=at("rep_last_normal_view", r),
                          cp_number=cp, checkpoint=checkpoint(app, cp),
                          log_suffix=log_suffix(log, cp + 1))
                bag = dict(bag0)
                if primary(view) == r:
                    send(bag, own, new_count=0)
                    step("SendDVC", r, bag, rep_sent_dvc=True,
                         rep_recv_dvc=at("rep_recv_dvc", r) | {own})
                else:
                    send(bag, own)
                    step("SendDVC", r, bag, rep_sent_dvc=True)
        # -- SendSV (CP06:898-937): f + 1 received DVCs; choice 1 -------
        dvcs = at("rep_recv_dvc", r)
        if (status == VIEW_CHANGE and not at("rep_sent_sv", r)
                and len(dvcs) >= f + 1):
            best, new_commit = winning_dvc(dvcs, c)
            bag = dict(bag0)
            broadcast(bag, Msg("StartViewMsg", None, r, view_number=view,
                               op_number=best.op_number,
                               commit_number=new_commit,
                               cp_number=best.cp_number,
                               checkpoint=best.checkpoint,
                               log_suffix=best.log_suffix), replicas)
            step("SendSV", r, bag, rep_status=NORMAL,
                 rep_peer_op_number=(0,) * R, rep_sent_sv=True,
                 rep_last_normal_view=view, rep_recv_dvc=frozenset(),
                 **installed(cp=best.checkpoint,
                             cp_number=best.cp_number,
                             suffix=best.log_suffix,
                             op_number=best.op_number,
                             new_commit=new_commit))
        if not normal_primary(r):
            continue
        # -- ReceiveClientRequest (ST03:293-325) -------------------------
        known = {v for v, _acked in state.aux_client_acked}
        for v in c.values:
            if v not in known:
                bag = dict(bag0)
                broadcast(bag, Msg("PrepareMsg", None, r,
                                   view_number=view, op_number=op + 1,
                                   commit_number=commit, message=v),
                          replicas)
                step("ReceiveClientRequest", r, bag, rep_log=log + (v,),
                     rep_op_number=op + 1,
                     aux_client_acked=state.aux_client_acked
                     | {(v, False)})
        # -- PrimaryExecuteOp (AS04:420-437): f peers hold the op -------
        if (commit < op and sum(p >= commit + 1 for p in
                                at("rep_peer_op_number", r)) >= f):
            v = log[commit]
            assert v != NOOP, state
            new_app, new_commit = execute_ops(app, commit, log, commit + 1)
            step("PrimaryExecuteOp", r, rep_app_state=new_app,
                 rep_commit_number=new_commit,
                 aux_client_acked=state.aux_client_acked
                 - {(v, False)} | {(v, True)})

    # -- NoProgressChange (ST03:764-776): any minority subset pauses ---
    if state.no_progress_ctr < c.no_progress_limit:
        for n in range(f + 1):
            for paused in itertools.combinations(replicas, n):
                step("NoProgressChange",
                     no_progress=tuple(r in paused for r in replicas),
                     no_progress_ctr=state.no_progress_ctr + 1)
    return out


# -- invariants (CP06:1219-1281) -----------------------------------------
def op_of(state, r, pos):
    """OpOf (CP06:1219-1222) at 0-based `pos` of replica index `r`: a
    NoOp position reads the application state's entry; choice 9."""
    log, app = state.rep_log[r], state.rep_app_state[r]
    entry = log[pos] if pos < len(log) else None
    if entry == NOOP:
        return app[pos] if pos < len(app) else None
    return entry


def _both_committed(state, c):
    R = c.replicas
    return ((a, b, pos) for a in range(R) for b in range(R)
            for pos in range(min(state.rep_commit_number[a],
                                 state.rep_commit_number[b])))


def no_log_divergence(state, c):
    """CP06:1224-1231: two replicas agree, through OpOf, on every
    position both have committed."""
    return all(op_of(state, a, pos) == op_of(state, b, pos)
               for a, b, pos in _both_committed(state, c))


def no_app_state_divergence(state, c):
    """CP06:1234-1240: two application states agree on every position
    both replicas have committed, and none holds a NoOp there."""
    def entry(r, pos):
        app = state.rep_app_state[r]
        return app[pos] if pos < len(app) else None
    return (all(entry(a, pos) == entry(b, pos)
                for a, b, pos in _both_committed(state, c))
            and not any(entry(r, pos) == NOOP
                        for r in range(c.replicas)
                        for pos in range(state.rep_commit_number[r])))


def acknowledged_write_not_lost(state, c):
    """ReplicaHasOp goes through OpOf (CP06:1244-1246): a value that
    survives only in an application state still counts."""
    def holders(v):
        return sum(any(op_of(state, r, pos) == v
                       for pos in range(len(state.rep_log[r])))
                   for r in range(c.replicas))
    return all(holders(v) >= 1
               for v, acked in state.aux_client_acked if acked)


def commit_number_never_higher_than_op_number(state, c):
    return all(commit <= op for commit, op in
               zip(state.rep_commit_number, state.rep_op_number))


def commit_number_matches_app_state(state, c):
    """CP06:1279-1281."""
    return all(len(app) == commit for app, commit in
               zip(state.rep_app_state, state.rep_commit_number))


INVARIANT_FNS = {
    "NoLogDivergence": no_log_divergence,
    "NoAppStateDivergence": no_app_state_divergence,
    "AcknowledgedWriteNotLost": acknowledged_write_not_lost,
    "CommitNumberNeverHigherThanOpNumber":
        commit_number_never_higher_than_op_number,
    "CommitNumberMatchesAppState": commit_number_matches_app_state,
}
INVARIANTS = tuple(INVARIANT_FNS)


def violated(state, c, invariants):
    """The first of `invariants` that `state` breaks, or None."""
    for name in invariants:
        if not INVARIANT_FNS[name](state, c):
            return name
    return None


# -- what the level program counts over the states it commits ------------
def commit_stats(state):
    """The counters and gauges of `CP06Kernel.commit_stats` for one
    state, on host values: a replica Recovering, a replica whose log
    has a garbage-collected prefix, a replica in StateTransfer, the
    bag's slots and tombstones, the fullest receive-set of each kind,
    and (what the dense layout cannot hold: one slot a source) the most
    records one source has in one receive-set."""
    def per_source(received):
        return max((sum(m.source == s for m in received)
                    for s in {m.source for m in received}), default=0)
    return {
        "recovering_states": RECOVERING in state.rep_status,
        "gc_states": any(highest_gced_op(log) for log in state.rep_log),
        "state_transfer_states": STATE_TRANSFER in state.rep_status,
        "bag_slots": len(state.messages),
        "bag_tombstones": sum(n == 0 for _m, n in state.messages),
        "bag_peak": len(state.messages),
        "dvc_set_peak": max(map(len, state.rep_recv_dvc)),
        "rec_set_peak": max(map(len, state.rep_rec_recv)),
        "dvc_per_source": max(map(per_source, state.rep_recv_dvc)),
        "rec_per_source": max(map(per_source, state.rep_rec_recv)),
    }


PEAKS = ("bag_peak", "dvc_set_peak", "rec_set_peak", "dvc_per_source",
         "rec_per_source")


# -- the breadth-first loop over the VIEW --------------------------------
def bfs(c, invariants=(), max_depth=None, keep_levels=False, log=None):
    """Breadth-first from Init, deduplicating on the VIEW and keeping
    the first full state of each.  Returns a dict: `level_sizes`,
    `distinct`, `generated` (Init and one per successor binding, as
    TLC counts), `action_expansions`, `violation` (invariant, state,
    depth) or None, `aux_conflicts`, `committed` (`commit_stats`
    summed, or its peak, over every state but Init), `fixpoint`, and
    with `keep_levels` the states of every level (`levels`)."""
    init = init_state(c)
    seen = {init[:N_VIEW]}
    frontier, sizes, levels = [init], [1], [[init]]
    fired = dict.fromkeys(ACTIONS, 0)
    committed = dict.fromkeys(commit_stats(init), 0)
    generated, conflicts, violation = 1, 0, None
    bad = violated(init, c, invariants)
    if bad:
        violation = (bad, init, 0)
    while frontier and violation is None and (
            max_depth is None or len(sizes) <= max_depth):
        t0 = time.time()
        fresh = {}      # view -> auxiliaries of the state kept for it
        nxt = []
        for state in frontier:
            for action, succ in successors(state, c):
                generated += 1
                fired[action] += 1
                view = succ[:N_VIEW]
                if view in seen:
                    if fresh.get(view, succ[N_VIEW:]) != succ[N_VIEW:]:
                        conflicts += 1
                    continue
                seen.add(view)
                fresh[view] = succ[N_VIEW:]
                nxt.append(succ)
                for name, n in commit_stats(succ).items():
                    committed[name] = (max(committed[name], n)
                                       if name in PEAKS
                                       else committed[name] + n)
                bad = violated(succ, c, invariants)
                if bad and violation is None:
                    violation = (bad, succ, len(sizes))
        frontier = nxt
        if nxt:
            sizes.append(len(nxt))
            if keep_levels:
                levels.append(nxt)
        if log:
            log(f"level {len(sizes) - 1}: {len(nxt)} states, "
                f"{len(seen)} distinct, {generated} generated, "
                f"{time.time() - t0:.1f}s")
    out = {"level_sizes": sizes, "distinct": len(seen),
           "generated": generated, "action_expansions": fired,
           "violation": violation, "aux_conflicts": conflicts,
           "committed": committed, "fixpoint": not frontier}
    if keep_levels:
        out["levels"] = levels
    return out


# -- TLC-valued states (what a codec decodes to) -> State ----------------
def _name(model_value):
    return getattr(model_value, "name", model_value)


def _entries(log):
    """A log, a suffix or a checkpoint as TLC holds it (a function
    position -> [operation |-> value]) as a tuple of names; Nil stays."""
    if not hasattr(log, "items"):
        return _name(log)
    return tuple(_name(e.apply("operation")) for _pos, e in log.items)


def _msg(rec):
    f = dict(rec.items)
    kw = dict(type=_name(f.pop("type")), dest=_name(f.pop("dest")))
    for k, v in f.items():
        if k in ("log_suffix", "checkpoint"):
            kw[k] = _entries(v)
        elif k == "message":
            kw[k] = _name(v.apply("operation"))
        else:
            kw[k] = _name(v)
    return Msg(**kw)


def from_tlc(tlc, c):
    """A state as TLC prints it (a dict variable -> value whose
    functions and records have `.apply` and `.items`, model values
    `.name`: `CP06Codec.decode`'s, a parsed trace's) as a `State`.
    Duck-typed: nothing is imported for it."""
    reps = range(1, c.replicas + 1)

    def fn(var, conv=lambda x: x):
        return tuple(conv(tlc[var].apply(r)) for r in reps)

    def records(received):
        return frozenset(_msg(m) for m in received)

    return State(
        rep_status=fn("rep_status", _name),
        rep_view_number=fn("rep_view_number"),
        rep_op_number=fn("rep_op_number"),
        rep_commit_number=fn("rep_commit_number"),
        rep_last_normal_view=fn("rep_last_normal_view"),
        rep_log=fn("rep_log", _entries),
        rep_app_state=fn("rep_app_state", _entries),
        rep_peer_op_number=fn(
            "rep_peer_op_number",
            lambda row: tuple(row.apply(p) for p in reps)),
        rep_sent_dvc=fn("rep_sent_dvc", bool),
        rep_sent_sv=fn("rep_sent_sv", bool),
        rep_recv_dvc=fn("rep_recv_dvc", records),
        rep_rec_number=fn("rep_rec_number"),
        rep_rec_recv=fn("rep_rec_recv", records),
        no_progress=fn("no_progress", bool),
        no_progress_ctr=tlc["no_progress_ctr"],
        messages=frozenset((_msg(m), n) for m, n in tlc["messages"].items),
        aux_svc=tlc["aux_svc"],
        aux_client_acked=frozenset(
            (_name(v), bool(a)) for v, a in tlc["aux_client_acked"].items),
        aux_restart=tlc["aux_restart"])


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cfg")
    ap.add_argument("--depth", type=int, default=None)
    args = ap.parse_args(argv)
    c, invariants = read_cfg(args.cfg)
    res = bfs(c, invariants, max_depth=args.depth,
              log=lambda s: print(s, file=sys.stderr, flush=True))
    if res["violation"]:
        res["violation"] = [res["violation"][0], res["violation"][2]]
    print(json.dumps(dict(res, constants=c._asdict())))
    return 0


if __name__ == "__main__":
    sys.exit(main())
