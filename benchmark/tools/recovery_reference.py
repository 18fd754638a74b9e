#!/usr/bin/env python3
"""The plain reference of VSR's view-change receive-sets and of its
disk-less recovery: the successors of one decoded state under the nine
actions that read or write `rep_dvc_recv` / `rep_rec_recv` or belong
to recovery, as plain Python on host values.

    SendDVC  ReceiveHigherDVC  ReceiveMatchingDVC  SendSV  ReceiveSV
    RestartEmpty  ReceivesRecoveryMsg  ReceivesRecoveryResponseMsg
    CompleteRecovery

A state is what `VSRCodec.decode` returns: a dict of TLC-style values
(`core/values.py`: `FnVal` functions, records and sequences, Python
`frozenset`s, ints, bools, model values).  `rep_dvc_recv[r]` is a
`frozenset` of DoViewChange records exactly as TLC prints it
(`benchmark/oracles/found_violation_trace.txt:312`), a quorum is
`len(...)`, a union is `|`, CHOOSE is the `value_key`-least element
(the order the interpreter defines, `core/values.py`), the bag is a
function record -> count.  There is no JAX here, no plane, no slot, no
lane, no mask, no clipped index, no tombstone column, no sort network:
what this file is independent of is the dense layout
(`tpuvsr/models/vsr.py`) and the kernel (`tpuvsr/models/vsr_kernel.py`,
of which nothing is imported), which is what a layout or guard PR
rewrites.

**What it is held to.**  `VSR.tla` is not in this repository.  The
sources are the kernel's cited line ranges (`vsr_kernel.py` names
VSR.tla:648-669, 677-688, 696-703, 716-758, 773-793, 802-837, 842-858,
864-872, 878-894 and 228-275, 299-301 for the bag and the resets),
SURVEY.md 2.2, 2.3, 2.7 and 3.3, and the record shapes of the
committed TLC trace.  Where the transcription had to choose, it says
so at the line, and the choices are:

1. `HighestOpNumber` is `Len` of HighestLog's log (the kernel's
   comment at SendSV; SURVEY 2.2 only names the operator).
2. `ReceiveSV` acknowledges with a PrepareOk only when the replica's
   OLD commit number is below the message's op number, addressed to
   `Primary(m.view_number)`; it leaves the client table and
   `rep_peer_op_number` alone (the kernel; SURVEY 3.3: "installs log
   wholesale, acks tail").
3. `SendSV` leaves `rep_dvc_recv[r]` as it is and zeroes
   `rep_peer_op_number[r]` (the kernel).
4. `RestartEmpty` has no guard on the replica's status, sets its view
   to 1 and its client table to `[request_number |-> 0, op_number |->
   0, executed |-> TRUE]` (the kernel; SURVEY 2.2: "total wipe").
5. `UniqueNumber` is 1 + the highest `x` of the RecoveryMsg records in
   the bag's domain, delivered ones included, and 1 if there is none
   (the kernel; SURVEY 2.7.5 names the CHOOSE only).
6. `CompleteRecovery` keeps `rep_rec_number[r]` and empties
   `rep_rec_recv[r]`; the response it takes is the `value_key`-least
   of those whose log is not Nil, NOT the highest view's (SURVEY 2.2).
7. A non-primary's RecoveryResponse carries Nil for `log`,
   `op_number` and `commit_number` (`VSRCodec.decode_msg_row`).

Two `requires_reference` tests (`tests/test_vsr_kernel.py`
`test_kernel_matches_interpreter_recovery_era`,
`tests/test_device_bfs.py` `test_device_bfs_recovery_fixpoint`) hold
the kernel to the interpreter on `VSR.tla` itself and would settle
each of these; they skip while the corpus is away.

    from recovery_reference import successors, record_of
    for action, succ in successors(state, spec.cfg.constants): ...
"""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))

from tpuvsr.core.values import FnVal, mk_record, value_key  # noqa: E402

ACTIONS = ("SendDVC", "ReceiveHigherDVC", "ReceiveMatchingDVC", "SendSV",
           "ReceiveSV", "RestartEmpty", "ReceivesRecoveryMsg",
           "ReceivesRecoveryResponseMsg", "CompleteRecovery")


def record_of(state):
    """A state dict as one hashable record (variable name -> value)."""
    return FnVal(state.items())


# -- the bag (VSR.tla:228-275) ------------------------------------------
def send(msgs, m):
    """SendFunc: one more pending delivery of `m`; a record delivered
    before (count 0) is revived."""
    return msgs.updated(m, msgs.get(m, 0) + 1)


def broadcast(msgs, msg, source, replicas):
    """BroadcastFunc: `[msg EXCEPT !.dest = r]` to every replica but
    the source."""
    for r in sorted(replicas):
        if r != source:
            msgs = send(msgs, msg.updated("dest", r))
    return msgs


def discard(msgs, m):
    """DiscardFunc: one delivery fewer; the record stays in the domain."""
    return msgs.updated(m, msgs.apply(m) - 1)


def receivable(state, mtype):
    """The records of the bag of one type with a delivery pending."""
    return [m for m, n in state["messages"].items
            if n > 0 and m.apply("type") is mtype]


def choose(candidates):
    """CHOOSE: the least of `candidates` in the interpreter's order."""
    return min(candidates, key=value_key)


def successors(state, constants):
    """The set of (action name, successor record) that the nine actions
    allow from `state` (`record_of` makes a decoded successor
    comparable)."""
    c = constants
    R = c["ReplicaCount"]
    f = R // 2
    replicas = state["replicas"]
    normal, view_change, recovering = (c["Normal"], c["ViewChange"],
                                       c["Recovering"])
    nil = c["Nil"]
    out = set()

    def primary(view):
        return 1 + (view - 1) % R

    def at(var, r):
        return state[var].apply(r)

    def step(action, **changed):
        """`state` with, for each variable named, either a new value
        or a dict replica -> new value (EXCEPT ![r])."""
        succ = dict(state)
        for var, new in changed.items():
            if isinstance(new, dict):
                fn = state[var]
                for r, v in new.items():
                    fn = fn.updated(r, v)
                new = fn
            succ[var] = new
        out.add((action, record_of(succ)))

    svc = mk_record(type=c["StartViewChangeMsg"], view_number=0, dest=0,
                    source=0)

    # -- SendDVC (VSR.tla:648-669) -------------------------------------
    for r in replicas:
        if (at("rep_status", r) is view_change
                and not at("rep_sent_dvc", r)
                and len(at("rep_svc_recv", r)) >= f):
            view = at("rep_view_number", r)
            msg = mk_record(
                type=c["DoViewChangeMsg"], view_number=view,
                log=at("rep_log", r),
                last_normal_vn=at("rep_last_normal_view", r),
                op_number=at("rep_op_number", r),
                commit_number=at("rep_commit_number", r),
                dest=primary(view), source=r)
            if primary(view) == r:      # registers its own, sends nothing
                step("SendDVC", rep_sent_dvc={r: True},
                     rep_dvc_recv={r: at("rep_dvc_recv", r) | {msg}})
            else:
                step("SendDVC", rep_sent_dvc={r: True},
                     messages=send(state["messages"], msg))

    for m in receivable(state, c["DoViewChangeMsg"]):
        r = m.apply("dest")
        # -- ReceiveHigherDVC (VSR.tla:677-688) ------------------------
        if m.apply("view_number") > at("rep_view_number", r):
            view = m.apply("view_number")
            step("ReceiveHigherDVC",
                 rep_view_number={r: view}, rep_status={r: view_change},
                 rep_svc_recv={r: frozenset()},
                 rep_dvc_recv={r: frozenset({m})},
                 rep_sent_dvc={r: False}, rep_sent_sv={r: False},
                 messages=broadcast(
                     discard(state["messages"], m),
                     svc.updated("view_number", view).updated("source", r),
                     r, replicas))
        # -- ReceiveMatchingDVC (VSR.tla:696-703): any status ----------
        if m.apply("view_number") == at("rep_view_number", r):
            step("ReceiveMatchingDVC",
                 rep_dvc_recv={r: at("rep_dvc_recv", r) | {m}},
                 messages=discard(state["messages"], m))

    # -- SendSV (VSR.tla:716-758) --------------------------------------
    for r in replicas:
        dvcs = at("rep_dvc_recv", r)
        if (at("rep_status", r) is view_change
                and not at("rep_sent_sv", r) and len(dvcs) >= f + 1):
            def rank(m):
                return (m.apply("last_normal_vn"), m.apply("op_number"))
            top = max(rank(m) for m in dvcs)
            highest = choose([m for m in dvcs if rank(m) == top])
            log = highest.apply("log")
            op = log.seq_len()                      # choice 1
            commit = max(m.apply("commit_number") for m in dvcs)
            view = at("rep_view_number", r)
            sv = mk_record(type=c["StartViewMsg"], view_number=view,
                           log=log, op_number=op, commit_number=commit,
                           dest=0, source=r)
            step("SendSV", rep_status={r: normal}, rep_log={r: log},
                 rep_op_number={r: op}, rep_commit_number={r: commit},
                 rep_peer_op_number={r: FnVal((p, 0) for p in replicas)},
                 rep_sent_sv={r: True}, rep_last_normal_view={r: view},
                 messages=broadcast(state["messages"], sv, r, replicas))

    # -- ReceiveSV (VSR.tla:773-793): m.view_number >= View(r) ---------
    for m in receivable(state, c["StartViewMsg"]):
        r = m.apply("dest")
        view = m.apply("view_number")
        if view >= at("rep_view_number", r):
            msgs = discard(state["messages"], m)
            if at("rep_commit_number", r) < m.apply("op_number"):  # choice 2
                msgs = send(msgs, mk_record(
                    type=c["PrepareOkMsg"], view_number=view,
                    op_number=m.apply("op_number"), dest=primary(view),
                    source=r))
            step("ReceiveSV", rep_status={r: normal},
                 rep_view_number={r: view}, rep_log={r: m.apply("log")},
                 rep_op_number={r: m.apply("op_number")},
                 rep_commit_number={r: m.apply("commit_number")},
                 rep_last_normal_view={r: view},
                 rep_svc_recv={r: frozenset()},
                 rep_dvc_recv={r: frozenset()},
                 rep_sent_dvc={r: False}, rep_sent_sv={r: False},
                 messages=msgs)

    # -- RestartEmpty (VSR.tla:802-837) --------------------------------
    if state["aux_restart"] < c["RestartEmptyLimit"]:
        nonces = [m.apply("x") for m, _n in state["messages"].items
                  if m.apply("type") is c["RecoveryMsg"]]
        unique = 1 + max(nonces, default=0)                   # choice 5
        for r in replicas:
            rec = mk_record(type=c["RecoveryMsg"], x=unique, dest=0,
                            source=r)
            step("RestartEmpty",
                 rep_log={r: FnVal(())}, rep_view_number={r: 1},
                 rep_op_number={r: 0}, rep_commit_number={r: 0},
                 rep_peer_op_number={r: FnVal((p, 0) for p in replicas)},
                 rep_client_table={r: FnVal(
                     (cl, mk_record(request_number=0, op_number=0,
                                    executed=True))
                     for cl in state["clients"])},
                 rep_svc_recv={r: frozenset()},
                 rep_dvc_recv={r: frozenset()},
                 rep_sent_dvc={r: False}, rep_sent_sv={r: False},
                 rep_last_normal_view={r: 0},
                 rep_rec_recv={r: frozenset()},
                 rep_status={r: recovering}, rep_rec_number={r: unique},
                 aux_restart=state["aux_restart"] + 1,
                 messages=broadcast(state["messages"], rec, r, replicas))

    # -- ReceivesRecoveryMsg (VSR.tla:842-858) -------------------------
    for m in receivable(state, c["RecoveryMsg"]):
        r = m.apply("dest")
        if at("rep_status", r) is normal:
            lead = primary(at("rep_view_number", r)) == r
            reply = mk_record(
                type=c["RecoveryResponseMsg"],
                view_number=at("rep_view_number", r), x=m.apply("x"),
                log=at("rep_log", r) if lead else nil,
                op_number=at("rep_op_number", r) if lead else nil,
                commit_number=at("rep_commit_number", r) if lead else nil,
                dest=m.apply("source"), source=r)
            step("ReceivesRecoveryMsg",
                 messages=send(discard(state["messages"], m), reply))

    # -- ReceivesRecoveryResponseMsg (VSR.tla:864-872) -----------------
    for m in receivable(state, c["RecoveryResponseMsg"]):
        r = m.apply("dest")
        if (at("rep_status", r) is recovering
                and at("rep_rec_number", r) == m.apply("x")):
            step("ReceivesRecoveryResponseMsg",
                 rep_rec_recv={r: at("rep_rec_recv", r) | {m}},
                 messages=discard(state["messages"], m))

    # -- CompleteRecovery (VSR.tla:878-894) ----------------------------
    for r in replicas:
        got = at("rep_rec_recv", r)
        with_log = [m for m in got if m.apply("log") is not nil]
        if (at("rep_status", r) is recovering and len(got) > f
                and with_log):
            m = choose(with_log)                              # choice 6
            step("CompleteRecovery", rep_status={r: normal},
                 rep_view_number={r: m.apply("view_number")},
                 rep_last_normal_view={r: m.apply("view_number")},
                 rep_log={r: m.apply("log")},
                 rep_op_number={r: m.apply("op_number")},
                 rep_commit_number={r: m.apply("commit_number")},
                 rep_rec_recv={r: frozenset()})
    return out
