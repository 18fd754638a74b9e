#!/usr/bin/env python3
"""The plain reference of VR_STATE_TRANSFER (ST03, the spec in which
the state-transfer data loss of VSR is repaired, and the base of the
analysis family): its 16 actions and its invariants as plain Python on
host values, and its own breadth-first loop over the cfg VIEW.

    python3 benchmark/tools/state_transfer_reference.py CFG --depth N

A state is a `State` of plain values: a function over the replicas is
a tuple indexed by replica - 1, a log a tuple of value names, a
message a `Msg` record whose absent fields are None, the bag a
frozenset of (record, count) pairs that an action opens as a dict
record -> count.  **A record delivered stays in the bag at count 0**,
and the quorums of SendDVC and SendSV count exactly those entries
(SURVEY 2.3, 2.7 item 4).  There is no JAX here and nothing of
`tpuvsr` is imported: no plane, no slot, no lane, no mask, no clipped
index, no hash.  What this file is independent of is the dense layout
(`tpuvsr/models/st03.py`), the kernel (`tpuvsr/models/st03_kernel.py`)
and the engines, which is what a layout, guard or engine PR rewrites.

**What it is held to.**  `VR_STATE_TRANSFER.tla` is not in this
repository.  Two records of it are: at |Values| = 1 and
StartViewOnTimerLimit = 1 the interpreter over the real module reached
42,753 distinct / 106,794 generated / diameter 24
(`scripts/fixpoints.json`) with the 24 level sizes of
`scripts/lower_fixpoint.json`; `bfs` below reproduces all of them
(`tests/test_native_st03.py`).  There the three state-transfer actions
never fire (`BASELINE.md:36`): for them the sources are the line
ranges the kernel cites (ST03:293-776, 164-218 for the bag), SURVEY
2.1-2.3 and 2.7 (items 4, 7, 8), and the record shapes of
`ST03Codec.decode_msg_row`.  Where the transcription had to choose, it
says so at the line, and the choices are:

1. `HighestLog`'s CHOOSE among DoViewChange records that tie on
   (last_normal_vn, op_number) takes the least (commit_number, log,
   source), values ordered by name (the kernel's reading of the
   interpreter's order; SURVEY 2.7 item 5 only says deterministic).
   `HighestOpNumber` is that record's op_number, `HighestCommitNumber`
   the maximum over all valid records (SURVEY 2.2).
2. `SendDVC` of the new primary itself is `SendAsReceived`: inserted
   at count 0, and one more delivery if the record is there already
   (SendFunc's upsert arm; SURVEY 2.3).
3. `ReceiveSV` acknowledges with a PrepareOk, addressed to
   `Primary(m.view_number)`, only when the replica's OLD commit number
   is below the message's op number (the kernel).
4. `ReceivePrepareMsg` takes the message's commit number as it is,
   lower or not; `ReceivePrepareOkMsg` accepts any op number above the
   one recorded for the peer (SURVEY 2.2: matchIndex).
5. `SendGetState` needs a Prepare of a HIGHER view with a gap
   (`m.op_number > rep_op_number[r] + 1`) at a Normal non-primary,
   leaves the Prepare in the bag, asks from `rep_commit_number[r]`,
   addressed to AnyDest, once (`SendOnce`: the record is not in the
   bag's domain) (SURVEY 2.2 "ST03+ (fixed)"; the kernel for the gap).
6. `ReceiveGetState` is answered by any Normal replica but the sender,
   in the message's view, whose op number is above the one asked from;
   the NewState carries the log entries above it, `first_op` their
   first position, addressed to the asker.
7. `ReceiveNewState` needs status StateTransfer and a view above the
   replica's own; it keeps the own log below `first_op`, takes the
   message's entries from there, and sets view, last normal view, op
   and commit number from the message (SURVEY 2.1: "overwrites
   suffix").
8. `TimerSendSVC` has no guard on the status (a replica in
   StateTransfer may time out), and `NoLogDivergence` reads a position
   past a log's end as Nil (the kernel compares a zero there; TLC
   would fault, and `CommitNumberNeverHigherThanOpNumber` says it
   never gets there).
9. Init: every replica Normal in view 1 with last normal view 0 (the
   committed `examples/VR_STATE_TRANSFER_init_trace.txt`).

The VIEW (ST03:97) drops `aux_svc` and `aux_client_acked` and keeps
`no_progress` / `no_progress_ctr`; the loop keeps the first full state
of each view it meets, as TLC does, and counts the views it met twice
IN ONE LEVEL under different auxiliaries (`aux_conflicts`): while that
is 0 the level sizes do not depend on the order within a level.
"""

import argparse
import itertools
import json
import re
import sys
import time
from typing import NamedTuple

NORMAL, VIEW_CHANGE, STATE_TRANSFER = "Normal", "ViewChange", "StateTransfer"
ANY_DEST = "AnyDest"
ACTIONS = (
    "TimerSendSVC", "ReceiveHigherSVC", "ReceiveMatchingSVC", "SendDVC",
    "ReceiveHigherDVC", "ReceiveMatchingDVC", "SendSV", "ReceiveSV",
    "ReceiveClientRequest", "ReceivePrepareMsg", "ReceivePrepareOkMsg",
    "ExecuteOp", "SendGetState", "ReceiveGetState", "ReceiveNewState",
    "NoProgressChange")
STATE_TRANSFER_ACTIONS = ("SendGetState", "ReceiveGetState",
                          "ReceiveNewState")


class Msg(NamedTuple):
    """One bag record; a field its type does not carry is None."""
    type: str
    view_number: int
    dest: object            # a replica, or ANY_DEST
    source: int
    op_number: object = None
    commit_number: object = None
    last_normal_vn: object = None
    first_op: object = None
    message: object = None  # Prepare: the value of its log entry
    log: object = None      # DVC, SV: the whole log; NewState: a suffix


class State(NamedTuple):
    """The 13 variables of the VIEW, then the two auxiliaries."""
    rep_status: tuple
    rep_view_number: tuple
    rep_op_number: tuple
    rep_commit_number: tuple
    rep_last_normal_view: tuple
    rep_log: tuple
    rep_peer_op_number: tuple
    rep_sent_dvc: tuple
    rep_sent_sv: tuple
    no_progress: tuple
    no_progress_ctr: int
    messages: frozenset     # of (Msg, count); count 0 entries stay
    aux_svc: int
    aux_client_acked: frozenset   # of (value, acknowledged)


N_VIEW = 12     # State[:N_VIEW] is the VIEW projection


class Constants(NamedTuple):
    replicas: int
    values: tuple           # value names, in order
    timer_limit: int        # StartViewOnTimerLimit
    no_progress_limit: int  # NoProgressChangeLimit


def read_cfg(path):
    """(Constants, invariant names) of a TLC cfg: the four constants
    this module reads and the INVARIANT section."""
    with open(path) as f:
        text = re.sub(r"\\\*.*", "", f.read())
    found = dict(re.findall(r"^\s*(\w+)\s*=\s*(\{[^}]*\}|\w+)\s*$", text,
                            re.M))
    values = tuple(sorted(v.strip() for v in
                          found["Values"].strip("{}").split(",")))
    body = re.search(r"^INVARIANTS?\b(.*?)(?=^[A-Z_]+\b|\Z)", text,
                     re.M | re.S)
    invariants = tuple(body.group(1).split()) if body else ()
    return (Constants(int(found["ReplicaCount"]), values,
                      int(found["StartViewOnTimerLimit"]),
                      int(found.get("NoProgressChangeLimit", 0))),
            invariants)


def init_state(c):
    R = c.replicas
    return State(
        rep_status=(NORMAL,) * R, rep_view_number=(1,) * R,
        rep_op_number=(0,) * R, rep_commit_number=(0,) * R,
        rep_last_normal_view=(0,) * R, rep_log=((),) * R,
        rep_peer_op_number=((0,) * R,) * R,
        rep_sent_dvc=(False,) * R, rep_sent_sv=(False,) * R,
        no_progress=(False,) * R, no_progress_ctr=0,
        messages=frozenset(), aux_svc=0, aux_client_acked=frozenset())


# -- the bag (ST03:164-218) ----------------------------------------------
def send(bag, m, new_count=1):
    """SendFunc: one more pending delivery of a record that is in the
    domain (a delivered one is revived), else the record at
    `new_count` (0: SendAsReceived)."""
    bag[m] = bag[m] + 1 if m in bag else new_count


def broadcast(bag, m, replicas):
    """BroadcastFunc: `[m EXCEPT !.dest = r]` to all but the source."""
    for r in replicas:
        if r != m.source:
            send(bag, m._replace(dest=r))


def receivable(m, count, mtype, r):
    """ReceivableMsg: a delivery pending, the type, and addressed to
    `r`, or to AnyDest by another replica."""
    return (count > 0 and m.type == mtype
            and (m.dest == r or (m.dest == ANY_DEST and m.source != r)))


def successors(state, c):
    """Every (action name, successor State) the 16 actions allow from
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

    def reset_sent():
        return dict(rep_sent_dvc=False, rep_sent_sv=False)

    # -- TimerSendSVC (ST03:515-535); choice 8 --------------------------
    if state.aux_svc < c.timer_limit:
        for r in replicas:
            if can_progress(r) and not normal_primary(r):
                view = at("rep_view_number", r) + 1
                bag = dict(bag0)
                broadcast(bag, Msg("StartViewChangeMsg", view, None, r),
                          replicas)
                step("TimerSendSVC", r, bag, rep_view_number=view,
                     rep_status=VIEW_CHANGE, aux_svc=state.aux_svc + 1,
                     **reset_sent())

    # -- the receive actions of a record addressed to one replica ------
    for m, count in bag0.items():
        if count <= 0 or m.dest == ANY_DEST:
            continue
        r = m.dest
        if not can_progress(r):
            continue
        view, status = at("rep_view_number", r), at("rep_status", r)

        if m.type in ("StartViewChangeMsg", "DoViewChangeMsg"):
            kind = "SVC" if m.type == "StartViewChangeMsg" else "DVC"
            # ReceiveHigherSVC (537-556), ReceiveHigherDVC (616-635)
            if m.view_number > view:
                bag = dict(bag0)
                bag[m] -= 1
                broadcast(bag, Msg("StartViewChangeMsg", m.view_number,
                                   None, r), replicas)
                step("ReceiveHigher" + kind, r, bag,
                     rep_view_number=m.view_number,
                     rep_status=VIEW_CHANGE, **reset_sent())
            # ReceiveMatchingSVC (558-575), ReceiveMatchingDVC (637-654)
            if m.view_number == view and status == VIEW_CHANGE:
                bag = dict(bag0)
                bag[m] -= 1
                step("ReceiveMatching" + kind, bag=bag)

        elif m.type == "StartViewMsg":
            # ReceiveSV (733-762); SURVEY 2.7 item 7; choice 3
            if ((m.view_number == view and status == VIEW_CHANGE)
                    or m.view_number > view):
                bag = dict(bag0)
                bag[m] -= 1
                if at("rep_commit_number", r) < m.op_number:
                    send(bag, Msg("PrepareOkMsg", m.view_number,
                                  primary(m.view_number), r,
                                  op_number=m.op_number))
                step("ReceiveSV", r, bag, rep_status=NORMAL,
                     rep_view_number=m.view_number, rep_log=m.log,
                     rep_op_number=m.op_number,
                     rep_commit_number=m.commit_number,
                     rep_last_normal_view=m.view_number, **reset_sent())

        elif m.type == "PrepareMsg":
            follower = status == NORMAL and not normal_primary(r)
            op = at("rep_op_number", r)
            # ReceivePrepareMsg (327-348); choice 4
            if (follower and m.view_number == view
                    and m.op_number == op + 1):
                bag = dict(bag0)
                bag[m] -= 1
                send(bag, Msg("PrepareOkMsg", view, m.source, r,
                              op_number=m.op_number))
                step("ReceivePrepareMsg", r, bag,
                     rep_log=at("rep_log", r) + (m.message,),
                     rep_op_number=m.op_number,
                     rep_commit_number=m.commit_number)
            # SendGetState (407-447); choice 5
            if (follower and m.view_number > view
                    and m.op_number > op + 1):
                ask = Msg("GetStateMsg", m.view_number, ANY_DEST, r,
                          op_number=at("rep_commit_number", r))
                if ask not in bag0:             # SendOnce
                    bag = dict(bag0)
                    send(bag, ask)
                    step("SendGetState", r, bag,
                         rep_status=STATE_TRANSFER)

        elif m.type == "PrepareOkMsg":
            # ReceivePrepareOkMsg (350-374); choice 4
            peers = at("rep_peer_op_number", r)
            if (normal_primary(r) and m.view_number == view
                    and m.op_number > peers[m.source - 1]):
                bag = dict(bag0)
                bag[m] -= 1
                step("ReceivePrepareOkMsg", r, bag,
                     rep_peer_op_number=peers[:m.source - 1]
                     + (m.op_number,) + peers[m.source:])

        elif m.type == "NewStateMsg":
            # ReceiveNewState (479-507); choice 7
            if status == STATE_TRANSFER and m.view_number > view:
                own = at("rep_log", r)[:m.first_op - 1]
                log = own + m.log
                assert len(log) == m.op_number, (state, m)
                bag = dict(bag0)
                bag[m] -= 1
                step("ReceiveNewState", r, bag, rep_status=NORMAL,
                     rep_view_number=m.view_number,
                     rep_last_normal_view=m.view_number, rep_log=log,
                     rep_op_number=m.op_number,
                     rep_commit_number=m.commit_number)

    # -- ReceiveGetState (449-477): AnyDest, every replica but the
    # sender; choice 6 --------------------------------------------------
    for m, count in bag0.items():
        for r in replicas:
            if (receivable(m, count, "GetStateMsg", r) and can_progress(r)
                    and at("rep_status", r) == NORMAL
                    and at("rep_view_number", r) == m.view_number
                    and at("rep_op_number", r) > m.op_number):
                bag = dict(bag0)
                bag[m] -= 1
                send(bag, Msg(
                    "NewStateMsg", m.view_number, m.source, r,
                    op_number=at("rep_op_number", r),
                    commit_number=at("rep_commit_number", r),
                    first_op=m.op_number + 1,
                    log=at("rep_log", r)[m.op_number:]))
                step("ReceiveGetState", bag=bag)

    for r in replicas:
        if not can_progress(r):
            continue
        view, status = at("rep_view_number", r), at("rep_status", r)
        processed = [m for m, count in bag0.items()
                     if count == 0 and m.dest == r
                     and m.view_number == view]
        # -- SendDVC (577-614): f processed SVCs; choice 2 --------------
        if (status == VIEW_CHANGE and not at("rep_sent_dvc", r)
                and sum(m.type == "StartViewChangeMsg"
                        for m in processed) >= f):
            bag = dict(bag0)
            send(bag, Msg("DoViewChangeMsg", view, primary(view), r,
                          op_number=at("rep_op_number", r),
                          commit_number=at("rep_commit_number", r),
                          last_normal_vn=at("rep_last_normal_view", r),
                          log=at("rep_log", r)),
                 new_count=0 if primary(view) == r else 1)
            step("SendDVC", r, bag, rep_sent_dvc=True)
        # -- SendSV (669-731): f + 1 processed DVCs; choice 1 -----------
        dvcs = [m for m in processed if m.type == "DoViewChangeMsg"]
        if (status == VIEW_CHANGE and not at("rep_sent_sv", r)
                and len(dvcs) >= f + 1):
            top = max((m.last_normal_vn, m.op_number) for m in dvcs)
            best = min((m for m in dvcs
                        if (m.last_normal_vn, m.op_number) == top),
                       key=lambda m: (m.commit_number, m.log, m.source))
            commit = max(m.commit_number for m in dvcs)
            bag = dict(bag0)
            broadcast(bag, Msg("StartViewMsg", view, None, r,
                               op_number=best.op_number,
                               commit_number=commit, log=best.log),
                      replicas)
            step("SendSV", r, bag, rep_status=NORMAL, rep_log=best.log,
                 rep_op_number=best.op_number,
                 rep_peer_op_number=(0,) * R, rep_commit_number=commit,
                 rep_sent_sv=True, rep_last_normal_view=view)
        if not normal_primary(r):
            continue
        # -- ReceiveClientRequest (293-325) ------------------------------
        known = {v for v, _acked in state.aux_client_acked}
        for v in c.values:
            if v not in known:
                op = at("rep_op_number", r) + 1
                bag = dict(bag0)
                broadcast(bag, Msg(
                    "PrepareMsg", view, None, r, op_number=op,
                    commit_number=at("rep_commit_number", r), message=v),
                    replicas)
                step("ReceiveClientRequest", r, bag,
                     rep_log=at("rep_log", r) + (v,), rep_op_number=op,
                     aux_client_acked=state.aux_client_acked
                     | {(v, False)})
        # -- ExecuteOp (377-405): f peers hold the op; SURVEY 2.7.8 -----
        op = at("rep_commit_number", r) + 1
        if (op <= at("rep_op_number", r)
                and sum(p >= op for p in
                        at("rep_peer_op_number", r)) >= f):
            v = at("rep_log", r)[op - 1]
            step("ExecuteOp", r, rep_commit_number=op,
                 aux_client_acked=state.aux_client_acked
                 - {(v, False)} | {(v, True)})

    # -- NoProgressChange (764-776): any minority subset pauses --------
    if state.no_progress_ctr < c.no_progress_limit:
        for n in range(f + 1):
            for paused in itertools.combinations(replicas, n):
                step("NoProgressChange",
                     no_progress=tuple(r in paused for r in replicas),
                     no_progress_ctr=state.no_progress_ctr + 1)
    return out


# -- invariants (ST03:804-850) -------------------------------------------
def no_log_divergence(state, c):
    """Two replicas agree on every position both have committed
    (ST03:805-811: r1 against r2, commit-gated); choice 8."""
    def entry(r, pos):
        log = state.rep_log[r]
        return log[pos] if pos < len(log) else None
    R = c.replicas
    return all(entry(a, pos) == entry(b, pos)
               for a in range(R) for b in range(R)
               for pos in range(min(state.rep_commit_number[a],
                                    state.rep_commit_number[b])))


def _holders(state, v):
    return sum(v in log for log in state.rep_log)


def acknowledged_write_not_lost(state, c):
    return all(_holders(state, v) >= 1
               for v, acked in state.aux_client_acked if acked)


def acknowledged_writes_exist_on_majority(state, c):
    return all(_holders(state, v) >= c.replicas // 2 + 1
               for v, acked in state.aux_client_acked if acked)


def commit_number_never_higher_than_op_number(state, c):
    """ST03:837-847, the invariant this spec adds."""
    return all(commit <= op for commit, op in
               zip(state.rep_commit_number, state.rep_op_number))


INVARIANT_FNS = {
    "NoLogDivergence": no_log_divergence,
    "AcknowledgedWriteNotLost": acknowledged_write_not_lost,
    "AcknowledgedWritesExistOnMajority":
        acknowledged_writes_exist_on_majority,
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


# -- the breadth-first loop over the VIEW --------------------------------
def bfs(c, invariants=(), max_depth=None, keep_levels=False, log=None):
    """Breadth-first from Init, deduplicating on the VIEW and keeping
    the first full state of each.  Returns a dict: `level_sizes`,
    `distinct`, `generated` (Init and one per successor binding, as
    TLC counts), `action_expansions`, `violation` (invariant, state,
    depth) or None, `aux_conflicts`, `bag_peak`, `fixpoint`, and with
    `keep_levels` the states of every level (`levels`)."""
    init = init_state(c)
    seen = {init[:N_VIEW]}
    frontier, sizes, levels = [init], [1], [[init]]
    fired = dict.fromkeys(ACTIONS, 0)
    generated, conflicts, bag_peak, violation = 1, 0, 0, None
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
                bag_peak = max(bag_peak, len(succ.messages))
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
           "bag_peak": bag_peak, "fixpoint": not frontier}
    if keep_levels:
        out["levels"] = levels
    return out


# -- TLC-valued states (what a codec decodes to) -> State ----------------
def _name(model_value):
    return getattr(model_value, "name", model_value)


_TYPES = ("PrepareMsg", "PrepareOkMsg", "StartViewChangeMsg",
          "DoViewChangeMsg", "StartViewMsg", "GetStateMsg", "NewStateMsg")


def from_tlc(tlc, c):
    """A state as TLC prints it (a dict variable -> value whose
    functions and records have `.apply` and `.items`, model values
    `.name`: `ST03Codec.decode`'s, a parsed trace's) as a `State`.
    Duck-typed: nothing is imported for it."""
    reps = range(1, c.replicas + 1)

    def fn(var, conv=lambda x: x):
        return tuple(conv(tlc[var].apply(r)) for r in reps)

    def log_of(log, first=1):
        return tuple(_name(log.apply(first + i).apply("operation"))
                     for i in range(len(log.items)))

    def msg(rec):
        f = {k: v for k, v in rec.items}
        kw = dict(type=_name(f["type"]), view_number=f["view_number"],
                  dest=_name(f["dest"]), source=f["source"])
        assert kw["type"] in _TYPES, kw
        for k in ("op_number", "commit_number", "last_normal_vn",
                  "first_op"):
            if k in f:
                kw[k] = f[k]
        if "message" in f:
            kw["message"] = _name(f["message"].apply("operation"))
        if "log" in f:
            kw["log"] = log_of(f["log"], f.get("first_op", 1))
        return Msg(**kw)

    return State(
        rep_status=fn("rep_status", _name),
        rep_view_number=fn("rep_view_number"),
        rep_op_number=fn("rep_op_number"),
        rep_commit_number=fn("rep_commit_number"),
        rep_last_normal_view=fn("rep_last_normal_view"),
        rep_log=fn("rep_log", log_of),
        rep_peer_op_number=fn(
            "rep_peer_op_number",
            lambda row: tuple(row.apply(p) for p in reps)),
        rep_sent_dvc=fn("rep_sent_dvc", bool),
        rep_sent_sv=fn("rep_sent_sv", bool),
        no_progress=fn("no_progress", bool),
        no_progress_ctr=tlc["no_progress_ctr"],
        messages=frozenset((msg(m), n) for m, n in tlc["messages"].items),
        aux_svc=tlc["aux_svc"],
        aux_client_acked=frozenset(
            (_name(v), bool(a)) for v, a in tlc["aux_client_acked"].items))


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
