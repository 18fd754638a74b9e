#!/usr/bin/env python3
"""The plain reference of VR_REPLICA_RECOVERY_ASYNC_LOG (AL05: VR
Revisited with an application state, state transfer and the recovery of
a replica whose log is persisted asynchronously, so that a crash keeps
some PREFIX of the log and loses the rest): its 20 actions and the four
invariants of the 05 set as plain Python on host values, and its own
breadth-first loop over the cfg VIEW.

    python3 benchmark/tools/async_log_reference.py CFG --depth N

A state is a `State` of plain values: a function over the replicas is
a tuple indexed by replica - 1, a log and an application state tuples
of value names, a message a `Msg` record whose absent fields are None,
the bag a frozenset of (record, count) pairs that an action opens as a
dict, and the two receive-sets (`rep_recv_dvc`, `rep_rec_recv`)
frozensets of the very records received.  **A record delivered stays
in the bag at count 0**; the quorum of SendDVC counts those entries,
the quorums of SendSV and CompleteRecovery count the receive-set.
There is no JAX here and nothing of `tpuvsr` is imported: no plane, no
slot, no lane, no mask, no clipped index, no re-based suffix, no hash;
`send`, `broadcast`, `receivable` and the cfg reader are those of
`state_transfer_reference.py` beside this file (the benchmark's, and as
independent), as `checkpoint_recovery_reference.py` takes them.

**What it is held to.**  `VR_REPLICA_RECOVERY_ASYNC_LOG.tla` is not in
this repository, and upstream ships no cfg for it.  One record of it
is: at |Values| = 1, StartViewOnTimerLimit = 1, CrashLimit = 1,
NoProgressChangeLimit = 0 the device engine on `AL05Kernel`, with the
real module loaded (Init and the invariants' names from it), reached
its fixpoint at 2,316,959 distinct / 5,123,247 generated / diameter 30
with the 30 level sizes of `scripts/recovery_fixpoints.json`; `bfs`
below reproduces all of them (`tests/test_native_al05.py`: levels 0-12
in tier-1, the whole record under `slow`).  **That anchor is thinner
than the sibling references':** for VR_REPLICA_RECOVERY_CP and
VR_STATE_TRANSFER the interpreter itself, over the real module,
finished; here it was stopped by its state limit at 300,004 distinct
inside level 15 (`scripts/fixpoints.json`; the engine's levels 0-14 sum
to 236,186 and through 15 to 353,611: consistent, no more), so past
level 14 the record is the kernel's word alone, and the kernel is what
this file is compared with.  What is independent is everything the
record's engine shares with today's: layout, codec, lanes, guards,
fingerprints, deduplication.  `SendGetState`, `ReceiveGetState`,
`ReceiveNewState` and `NoProgressChange` never fire inside the record:
for them the sources are the line ranges the kernels cite
(ST03:407-447, 449-477, 764-776, AS04:515-539), SURVEY 2.1-2.3 and the
record shapes of `AL05Codec.decode_msg_row`, and the crafted subtree of
the tests is where kernel and reference are held to each other.  Where
the transcription had to choose, it says so at the line, and the
choices are:

1. `HighestLog`'s CHOOSE among received DoViewChange records that tie
   on (last_normal_vn, op_number) takes the least (commit_number, log,
   source), values ordered by name (AS04:697-727 as the kernel reads
   the interpreter's record order).  The new commit number is the
   maximum over ALL received records.
2. `MaybeExecuteOps` (AS04:277-282) never lowers a commit number and
   never shortens an application state: every path that takes a commit
   number from a message (ReceivePrepareMsg, ReceiveSV, SendSV,
   ReceiveNewState, CompleteRecovery) goes through it.
3. `SendDVC` needs f processed StartViewChanges of the replica's view
   (tombstones in the bag); the new primary's own record is
   `SendAsReceived` (count 0) and joins its own receive-set
   (AS04:644-647).  `ReceiveMatchingSVC` is not taken once the own
   DoViewChange is sent (AS04:601).
4. `Crash` (AL05:851-885) is gated on `no_progress` and on
   `aux_restart < CrashLimit`, not on the status; it binds
   `\\E last_op \\in 0..rep_op_number[r]`, one successor a binding,
   keeps `rep_log[r]` through `last_op` and NOTHING else (application
   state, view, commit number, last normal view, peer table, view-change
   trackers and both receive-sets are gone, the op number is
   `last_op`), takes its recovery number from `UniqueNumber` (the
   highest x of a RecoveryMsg in the bag's domain plus one) and
   broadcasts a RecoveryMsg whose `op` is the floor
   `min(old commit number, last_op)`.
5. `ReceiveRecoveryMsg` (AL05:888-915) is answered by a Normal replica
   only, in two record shapes: the primary of its view sends
   `prefix_ceil` = the message's floor, `log_suffix` = its log above
   the floor, its op and commit number; a backup sends
   `log_suffix = Nil` and no op / commit / ceil fields at all.
6. `ReceiveRecoveryResponseMsg` (AL05:918-932) needs the replica
   Recovering and the record's x equal to its recovery number.
   `CompleteRecovery` (AL05:947-977) needs more than f responses and,
   among them, one with a log in the highest view of ALL responses
   (from the lowest source: one primary a view, they are one); it
   keeps the replica's OWN log through `prefix_ceil`, takes the
   record's suffix above it, and executes through the record's commit
   number from an empty application state.  The recovery number stays.
   All three are gated on `no_progress` (the kernel; RR05's are).
7. `TimerSendSVC`, `ReceiveHigherSVC`, `ReceiveHigherDVC` and
   `ReceiveSV` are not taken by a Recovering replica (RR05:582, 606,
   688, 798); `ReceiveMatchingSVC/DVC` need ViewChange, which a
   Recovering replica is not.
8. `SendGetState`, `ReceiveGetState`, `ReceiveNewState` are ST03's and
   AS04's: a Prepare of a HIGHER view with an op gap at a Normal
   backup, asked once from the commit number to AnyDest; answered by
   any Normal replica but the asker in the asked view whose op number
   is above; taken in StateTransfer from a view ABOVE the own
   (AS04:515-539: the own log below `first_op`, the record's entries
   from there).
9. `NoLogDivergence` and `NoAppStateDivergence` read a position past a
   tuple's end as None; `NoAppStateDivergence` is AS04:852-865's (two
   replicas that have both committed a position disagree in their
   application states there while the first's log agrees with its own
   application state).
10. Init: every replica Normal in view 1 with last normal view 1.  The
    sibling modules' committed Init states read 0 there
    (`examples/VR_STATE_TRANSFER_init_trace.txt`); for this module 0
    contradicts the record (see `init_state`).

The VIEW drops `aux_svc`, `aux_client_acked` and `aux_restart` and
keeps `no_progress` / `no_progress_ctr`; the loop keeps the first full
state of each view it meets, as TLC does, and counts the views it met
twice IN ONE LEVEL under different auxiliaries (`aux_conflicts`).
"""

import argparse
import itertools
import json
import sys
import time
from typing import NamedTuple

import checkpoint_recovery_reference
from state_transfer_reference import (ANY_DEST, NORMAL, STATE_TRANSFER,
                                      VIEW_CHANGE, broadcast, receivable,
                                      send)

RECOVERING = "Recovering"
NIL = "Nil"
ACTIONS = (
    "TimerSendSVC", "ReceiveHigherSVC", "ReceiveMatchingSVC", "SendDVC",
    "ReceiveHigherDVC", "ReceiveMatchingDVC", "SendSV", "ReceiveSV",
    "ReceiveClientRequest", "ReceivePrepareMsg", "ReceivePrepareOkMsg",
    "PrimaryExecuteOp", "SendGetState", "ReceiveGetState",
    "ReceiveNewState", "Crash", "ReceiveRecoveryMsg",
    "ReceiveRecoveryResponseMsg", "CompleteRecovery", "NoProgressChange")
STATE_TRANSFER_ACTIONS = ("SendGetState", "ReceiveGetState",
                          "ReceiveNewState")
RECOVERY_ACTIONS = ("Crash", "ReceiveRecoveryMsg",
                    "ReceiveRecoveryResponseMsg", "CompleteRecovery")


class Msg(NamedTuple):
    """One bag record; a field its type does not carry is None."""
    type: str
    dest: object            # a replica, or ANY_DEST
    source: int
    view_number: object = None
    op_number: object = None
    commit_number: object = None
    last_normal_vn: object = None
    first_op: object = None     # NewState: the suffix's first position
    message: object = None      # Prepare: the value of its log entry
    log: object = None          # DVC, SV: the whole log; NewState: a suffix
    log_suffix: object = None   # RecoveryResponse: entries, or NIL
    prefix_ceil: object = None  # RecoveryResponse of a primary
    x: object = None            # the recovery number
    op: object = None           # RecoveryMsg: min(commit number, last_op)


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

Constants = checkpoint_recovery_reference.Constants
read_cfg = checkpoint_recovery_reference.read_cfg
unique_number = checkpoint_recovery_reference.unique_number
# MaybeExecuteOps (AS04:277-282) as (app state, commit number); choice 2
execute_ops = checkpoint_recovery_reference.execute_ops


def init_state(c):
    """Init; choice 10.  Last normal view 1: a replica that completes a
    recovery in view 1 takes last normal view 1 from the response
    (AL05:947-977), and a DoViewChange of one that never left view 1
    must not rank below it.  With 0 here the record's constants reach,
    in level 16, a StartView that installs the recovered replica's
    empty log over a committed entry (commit number 1 above op number
    0, NoLogDivergence false), where the record has no violation in
    2,316,959 states; with 1 every one of its 30 level sizes comes
    out."""
    R = c.replicas
    return State(
        rep_status=(NORMAL,) * R, rep_view_number=(1,) * R,
        rep_op_number=(0,) * R, rep_commit_number=(0,) * R,
        rep_last_normal_view=(1,) * R, rep_log=((),) * R,
        rep_app_state=((),) * R, rep_peer_op_number=((0,) * R,) * R,
        rep_sent_dvc=(False,) * R, rep_sent_sv=(False,) * R,
        rep_recv_dvc=(frozenset(),) * R, rep_rec_number=(0,) * R,
        rep_rec_recv=(frozenset(),) * R,
        no_progress=(False,) * R, no_progress_ctr=0,
        messages=frozenset(), aux_svc=0, aux_client_acked=frozenset(),
        aux_restart=0)


def highest_log(dvcs, c):
    """(the winning DoViewChange, HighestCommitNumber) of a receive-set;
    choice 1."""
    rank = {v: i + 1 for i, v in enumerate(c.values)}
    top = max((m.last_normal_vn, m.op_number) for m in dvcs)
    best = min((m for m in dvcs
                if (m.last_normal_vn, m.op_number) == top),
               key=lambda m: (m.commit_number,
                              tuple(rank[e] for e in m.log), m.source))
    return best, max(m.commit_number for m in dvcs)


def successors(state, c):
    """Every (action name, successor State) the 20 actions allow from
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

    def installed(r, log, op_number, new_commit, app=None, commit=None):
        """A log taken whole or spliced, and what MaybeExecuteOps makes
        of the application state and the commit number of `r` (or of
        the `app`, `commit` given: a replica that has just lost
        both)."""
        assert len(log) == op_number, (state, log, op_number)
        app, commit = execute_ops(
            at("rep_app_state", r) if app is None else app,
            at("rep_commit_number", r) if commit is None else commit,
            log, new_commit)
        return dict(rep_log=log, rep_op_number=op_number,
                    rep_app_state=app, rep_commit_number=commit)

    # -- TimerSendSVC (AS04:551-566, RR05:578-600); choice 7 ------------
    if state.aux_svc < c.timer_limit:
        for r in replicas:
            if (not at("no_progress", r) and not normal_primary(r)
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
        if at("no_progress", r):
            continue
        view, status = at("rep_view_number", r), at("rep_status", r)
        recovering = status == RECOVERING

        if m.type in ("StartViewChangeMsg", "DoViewChangeMsg"):
            dvc = m.type == "DoViewChangeMsg"
            kind = "DVC" if dvc else "SVC"
            # ReceiveHigherSVC (AS04:575-587), ReceiveHigherDVC
            # (AS04:653-672): the DVC that carries the view seeds the
            # new receive-set; choice 7
            if m.view_number > view and not recovering:
                bag = delivered(m)
                broadcast(bag, Msg("StartViewChangeMsg", None, r,
                                   view_number=m.view_number), replicas)
                step("ReceiveHigher" + kind, r, bag,
                     rep_view_number=m.view_number,
                     rep_status=VIEW_CHANGE,
                     **dict(reset_vc(), rep_recv_dvc=frozenset(
                         {m} if dvc else ())))
            # ReceiveMatchingSVC (AS04:589-607), ReceiveMatchingDVC
            # (AS04:674-690: into the receive-set); choice 3
            if m.view_number == view and status == VIEW_CHANGE:
                if dvc:
                    step("ReceiveMatchingDVC", r, delivered(m),
                         rep_recv_dvc=at("rep_recv_dvc", r) | {m})
                elif not at("rep_sent_dvc", r):
                    step("ReceiveMatchingSVC", bag=delivered(m))

        elif m.type == "StartViewMsg":
            # ReceiveSV (AS04:759-788, RR05:794-822); choice 7
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
                     **installed(r, m.log, m.op_number, m.commit_number))

        elif m.type == "PrepareMsg":
            follower = status == NORMAL and not normal_primary(r)
            op = at("rep_op_number", r)
            # ReceivePrepareMsg (AS04:361-383)
            if (follower and m.view_number == view
                    and m.op_number == op + 1):
                bag = delivered(m)
                send(bag, Msg("PrepareOkMsg", m.source, r,
                              view_number=view, op_number=m.op_number))
                step("ReceivePrepareMsg", r, bag,
                     **installed(r, at("rep_log", r) + (m.message,),
                                 m.op_number, m.commit_number))
            # SendGetState (ST03:407-447); choice 8
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
            # ReceiveNewState (AS04:515-539); choice 8
            if status == STATE_TRANSFER and m.view_number > view:
                own = at("rep_log", r)
                assert len(own) >= m.first_op - 1, (state, m)
                step("ReceiveNewState", r, delivered(m),
                     rep_status=NORMAL, rep_view_number=m.view_number,
                     rep_last_normal_view=m.view_number,
                     **installed(r, own[:m.first_op - 1] + m.log,
                                 m.op_number, m.commit_number))

        elif m.type == "RecoveryMsg":
            # ReceiveRecoveryMsg (AL05:888-915); choice 5
            if status == NORMAL:
                reply = Msg("RecoveryResponseMsg", m.source, r,
                            view_number=view, x=m.x)
                if normal_primary(r):
                    reply = reply._replace(
                        prefix_ceil=m.op,
                        log_suffix=at("rep_log", r)[m.op:],
                        op_number=at("rep_op_number", r),
                        commit_number=at("rep_commit_number", r))
                else:
                    reply = reply._replace(log_suffix=NIL)
                bag = delivered(m)
                send(bag, reply)
                step("ReceiveRecoveryMsg", bag=bag)

        elif m.type == "RecoveryResponseMsg":
            # ReceiveRecoveryResponseMsg (AL05:918-932); choice 6
            if recovering and at("rep_rec_number", r) == m.x:
                step("ReceiveRecoveryResponseMsg", r, delivered(m),
                     rep_rec_recv=at("rep_rec_recv", r) | {m})

    # -- ReceiveGetState (ST03:449-477): AnyDest, every replica but the
    # asker; choice 8 ---------------------------------------------------
    for m, count in bag0.items():
        for r in replicas:
            if (receivable(m, count, "GetStateMsg", r)
                    and not at("no_progress", r)
                    and at("rep_status", r) == NORMAL
                    and at("rep_view_number", r) == m.view_number
                    and at("rep_op_number", r) > m.op_number):
                bag = delivered(m)
                send(bag, Msg(
                    "NewStateMsg", m.source, r, view_number=m.view_number,
                    op_number=at("rep_op_number", r),
                    commit_number=at("rep_commit_number", r),
                    first_op=m.op_number + 1,
                    log=at("rep_log", r)[m.op_number:]))
                step("ReceiveGetState", bag=bag)

    for r in replicas:
        if at("no_progress", r):
            continue
        view, status = at("rep_view_number", r), at("rep_status", r)
        log, app = at("rep_log", r), at("rep_app_state", r)
        op, commit = at("rep_op_number", r), at("rep_commit_number", r)
        # -- Crash (AL05:851-885); choice 4 -----------------------------
        if state.aux_restart < c.crash_limit:
            number = unique_number(bag0)
            for last_op in range(op + 1):
                bag = dict(bag0)
                broadcast(bag, Msg("RecoveryMsg", None, r, x=number,
                                   op=min(commit, last_op)), replicas)
                step("Crash", r, bag, rep_status=RECOVERING,
                     rep_log=log[:last_op], rep_app_state=(),
                     rep_view_number=0, rep_op_number=last_op,
                     rep_commit_number=0, rep_peer_op_number=(0,) * R,
                     rep_last_normal_view=0, rep_rec_number=number,
                     rep_rec_recv=frozenset(),
                     aux_restart=state.aux_restart + 1, **reset_vc())
        # -- CompleteRecovery (AL05:947-977); choice 6 ------------------
        received = at("rep_rec_recv", r)
        if status == RECOVERING and len(received) > f:
            newest = max(m.view_number for m in received)
            with_log = sorted((m for m in received
                               if m.view_number == newest
                               and m.log_suffix != NIL),
                              key=lambda m: m.source)
            if with_log:
                m = with_log[0]
                assert m.prefix_ceil <= len(log), (state, m)
                step("CompleteRecovery", r, rep_status=NORMAL,
                     rep_view_number=m.view_number,
                     rep_last_normal_view=m.view_number,
                     rep_rec_recv=frozenset(),
                     **installed(r, log[:m.prefix_ceil] + m.log_suffix,
                                 m.op_number, m.commit_number,
                                 app=(), commit=0))
        # -- SendDVC (AS04:609-651): f processed SVCs; choice 3 ---------
        processed = sum(
            count == 0 and m.type == "StartViewChangeMsg"
            and m.dest == r and m.view_number == view
            for m, count in bag0.items())
        if (status == VIEW_CHANGE and not at("rep_sent_dvc", r)
                and processed >= f):
            own = Msg("DoViewChangeMsg", primary(view), r,
                      view_number=view, op_number=op, commit_number=commit,
                      last_normal_vn=at("rep_last_normal_view", r),
                      log=log)
            bag = dict(bag0)
            if primary(view) == r:
                send(bag, own, new_count=0)
                step("SendDVC", r, bag, rep_sent_dvc=True,
                     rep_recv_dvc=at("rep_recv_dvc", r) | {own})
            else:
                send(bag, own)
                step("SendDVC", r, bag, rep_sent_dvc=True)
        # -- SendSV (AS04:729-757): f + 1 received DVCs; choice 1 -------
        dvcs = at("rep_recv_dvc", r)
        if (status == VIEW_CHANGE and not at("rep_sent_sv", r)
                and len(dvcs) >= f + 1):
            best, new_commit = highest_log(dvcs, c)
            bag = dict(bag0)
            broadcast(bag, Msg("StartViewMsg", None, r, view_number=view,
                               op_number=best.op_number,
                               commit_number=new_commit, log=best.log),
                      replicas)
            step("SendSV", r, bag, rep_status=NORMAL,
                 rep_peer_op_number=(0,) * R, rep_sent_sv=True,
                 rep_last_normal_view=view, rep_recv_dvc=frozenset(),
                 **installed(r, best.log, best.op_number, new_commit))
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


# -- invariants: the 05 set -----------------------------------------------
def _entry(seq, pos):
    return seq[pos] if pos < len(seq) else None


def _both_committed(state, c):
    R = c.replicas
    return ((a, b, pos) for a in range(R) for b in range(R)
            for pos in range(min(state.rep_commit_number[a],
                                 state.rep_commit_number[b])))


def no_log_divergence(state, c):
    """Two replicas agree on every position both have committed
    (ST03:805-811); choice 9."""
    return all(_entry(state.rep_log[a], pos) == _entry(state.rep_log[b],
                                                       pos)
               for a, b, pos in _both_committed(state, c))


def no_app_state_divergence(state, c):
    """AS04:852-865; choice 9."""
    return not any(
        _entry(state.rep_app_state[a], pos)
        != _entry(state.rep_app_state[b], pos)
        and _entry(state.rep_log[a], pos)
        == _entry(state.rep_app_state[a], pos)
        for a, b, pos in _both_committed(state, c))


def acknowledged_write_not_lost(state, c):
    return all(any(v in log for log in state.rep_log)
               for v, acked in state.aux_client_acked if acked)


def commit_number_never_higher_than_op_number(state, c):
    return all(commit <= op for commit, op in
               zip(state.rep_commit_number, state.rep_op_number))


INVARIANT_FNS = {
    "NoLogDivergence": no_log_divergence,
    "NoAppStateDivergence": no_app_state_divergence,
    "AcknowledgedWriteNotLost": acknowledged_write_not_lost,
    "CommitNumberNeverHigherThanOpNumber":
        commit_number_never_higher_than_op_number,
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
    """The counters and gauges of `AL05Kernel.commit_stats` for one
    state, on host values, and (what the dense layout cannot hold: one
    slot a source) the most records one source has in one receive-set.
    `prefix_survivor_states`: a replica is Recovering with an op number
    above 0, so it kept a non-empty log prefix.  `suffix_reply_states`:
    a RecoveryResponse with `prefix_ceil` above 0 is pending in the bag
    or held in a receive-set, so a splice has a prefix to keep."""
    R = len(state.rep_status)
    f = R // 2

    def per_source(received):
        return max((sum(m.source == s for m in received)
                    for s in {m.source for m in received}), default=0)

    def processed(r):
        return sum(count == 0 and m.type == "StartViewChangeMsg"
                   and m.dest == r + 1
                   and m.view_number == state.rep_view_number[r]
                   for m, count in state.messages)

    in_vc = [state.rep_status[r] == VIEW_CHANGE for r in range(R)]
    svc_waits = any(in_vc[r] and not state.rep_sent_dvc[r]
                    and 0 < processed(r) < f for r in range(R))
    dvc_waits = any(in_vc[r] and not state.rep_sent_sv[r]
                    and 0 < len(state.rep_recv_dvc[r]) < f + 1
                    for r in range(R))
    replies = [m for m, count in state.messages
               if count > 0 and m.type == "RecoveryResponseMsg"]
    replies += [m for received in state.rep_rec_recv for m in received]
    return {
        "state_transfer_states": STATE_TRANSFER in state.rep_status,
        "bag_slots": len(state.messages),
        "bag_tombstones": sum(n == 0 for _m, n in state.messages),
        "bag_peak": len(state.messages),
        "quorum_waiting_states": svc_waits or dvc_waits,
        "svc_quorum_waiting_states": svc_waits,
        "recovering_states": RECOVERING in state.rep_status,
        "prefix_survivor_states": any(
            status == RECOVERING and op > 0 for status, op in
            zip(state.rep_status, state.rep_op_number)),
        "suffix_reply_states": any((m.prefix_ceil or 0) > 0
                                   for m in replies),
        "rec_set_peak": max(map(len, state.rep_rec_recv)),
        "dvc_set_peak": max(map(len, state.rep_recv_dvc)),
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
    summed, or its peak, over every state but Init), `through` (`distinct`, `generated`, `action_expansions` and
    `committed` as they stood when each level was complete: what a run
    stopped at that depth counts), `fixpoint`, and with `keep_levels`
    the states of every level (`levels`)."""
    init = init_state(c)
    seen = {init[:N_VIEW]}
    frontier, sizes, levels = [init], [1], [[init]]
    fired = dict.fromkeys(ACTIONS, 0)
    committed = dict.fromkeys(commit_stats(init), 0)
    generated, conflicts, violation, through = 1, 0, None, []
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
            through.append({
                "distinct": len(seen), "generated": generated,
                "action_expansions": dict(fired),
                "committed": {n: int(v) for n, v in committed.items()}})
            if keep_levels:
                levels.append(nxt)
        if log:
            log(f"level {len(sizes) - 1}: {len(nxt)} states, "
                f"{len(seen)} distinct, {generated} generated, "
                f"{time.time() - t0:.1f}s")
    out = {"level_sizes": sizes, "distinct": len(seen),
           "generated": generated, "action_expansions": fired,
           "violation": violation, "aux_conflicts": conflicts,
           "committed": committed, "through": through,
           "fixpoint": not frontier}
    if keep_levels:
        out["levels"] = levels
    return out


# -- TLC-valued states (what a codec decodes to) -> State ----------------
def _name(model_value):
    return getattr(model_value, "name", model_value)


def _entries(log):
    """A log or a suffix as TLC holds it (a function position ->
    [operation |-> value]) as a tuple of names; Nil stays."""
    if not hasattr(log, "items"):
        return _name(log)
    return tuple(_name(e.apply("operation")) for _pos, e in log.items)


def _msg(rec):
    f = dict(rec.items)
    kw = dict(type=_name(f.pop("type")), dest=_name(f.pop("dest")))
    for k, v in f.items():
        if k in ("log", "log_suffix"):
            kw[k] = _entries(v)
        elif k == "message":
            kw[k] = _name(v.apply("operation"))
        else:
            kw[k] = _name(v)
    return Msg(**kw)


def from_tlc(tlc, c):
    """A state as TLC prints it (a dict variable -> value whose
    functions and records have `.apply` and `.items`, model values
    `.name`: `AL05Codec.decode`'s, a parsed trace's) as a `State`.
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
