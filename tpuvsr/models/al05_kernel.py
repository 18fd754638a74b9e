"""jit+vmap transition kernel for VR_REPLICA_RECOVERY_ASYNC_LOG (AL05).

Subclasses the RR05 kernel with the async-log-persistence deltas
(AL05's 20-action Next, AL05:992-1017 — RR05 minus RetryRecovery):

* ``Crash`` keeps a nondeterministic surviving log prefix: one lane
  per (replica, last_op in 0..MAX_OPS); the RecoveryMsg carries the
  floor ``op = min(old commit, last_op)`` (AL05:851-885);
* ``ReceiveRecoveryMsg`` answers in two record shapes (AL05:888-915):
  a backup's Nil log_suffix (no op/commit/ceil fields) or the
  primary's prefix_ceil + suffix-above-the-floor;
* ``CompleteRecovery`` splices the recovering replica's OWN surviving
  prefix (up to prefix_ceil) under the primary's suffix
  (AL05:947-977).
"""

from __future__ import annotations

import jax.numpy as jnp

from .al05 import AL05Codec
from .as04_kernel import AS04Kernel
from .guard_tables import lanes_of
from .rr05 import M_RECOVERY, M_RECOVERYRESP, RECOVERING
from .rr05_kernel import RR05Kernel
from .st03 import M_SVC, NORMAL, VIEWCHANGE
from .st03_kernel import I32, ST03Kernel
from .vsr import H_DEST, H_FIRST, H_OP, H_SRC, H_TYPE, H_VIEW, H_X

ACTION_NAMES = (
    "TimerSendSVC", "ReceiveHigherSVC", "ReceiveMatchingSVC", "SendDVC",
    "ReceiveHigherDVC", "ReceiveMatchingDVC", "SendSV", "ReceiveSV",
    "ReceiveClientRequest", "ReceivePrepareMsg", "ReceivePrepareOkMsg",
    "PrimaryExecuteOp", "SendGetState", "ReceiveGetState",
    "ReceiveNewState", "Crash", "ReceiveRecoveryMsg",
    "ReceiveRecoveryResponseMsg", "CompleteRecovery", "NoProgressChange",
)

REP_KEYS = RR05Kernel.REP_KEYS + ("rec_ceil",)


class AL05Kernel(RR05Kernel):
    action_names = ACTION_NAMES
    # AL05's own line range of each action SURVEY 2.1 cites one for:
    # the location a native spec prints for a counterexample step.  An
    # inherited action has no entry and prints the generic location
    # (models/native.py), never a base module's lines
    ACTION_LINES = {
        "Crash": (851, 885), "ReceiveRecoveryMsg": (888, 915),
        "ReceiveRecoveryResponseMsg": (918, 932),
        "CompleteRecovery": (947, 977),
    }
    REP_KEYS = REP_KEYS

    def __init__(self, codec: AL05Codec, perms=None):
        super().__init__(codec, perms=perms)

    def _rep_shape(self, k):
        if k == "rec_ceil":
            return (self.shape.R, self.shape.R)
        return super()._rep_shape(k)

    # AL05 entries are plain value ids again (AL05:106-108) — undo the
    # RR05 packed-entry borrowings
    _perm_vals = ST03Kernel._perm_vals
    _replica_has_op = ST03Kernel._replica_has_op
    act_receive_client_request = ST03Kernel.act_receive_client_request
    act_execute_op = AS04Kernel.act_execute_op

    def _lane_count(self, name):
        if name == "Crash":
            return self.R * (self.MAX_OPS + 1)
        return super()._lane_count(name)

    def _clear_rec(self, s2, i):
        s2 = super()._clear_rec(s2, i)
        s2["rec_ceil"] = s2["rec_ceil"].at[i].set(0)
        return s2

    #: ST03's six, then what this module adds (the same hook)
    COMMIT_STATS = ST03Kernel.COMMIT_STATS + (
        ("recovering_states", "sum"), ("prefix_survivor_states", "sum"),
        ("suffix_reply_states", "sum"), ("rec_set_peak", "max"),
        ("dvc_set_peak", "max"))

    def commit_stats(self, st):
        """[11] uint32 of one state: ST03's six, whether a replica is
        Recovering, whether one is Recovering with an op number above 0
        (it kept a non-empty log prefix: never in RR05, a checkpoint
        in CP06), whether a RecoveryResponse with ``prefix_ceil`` above
        0 is pending in the bag or held in a receive-set (a splice has
        a prefix to keep), and the fullest RecoveryResponse and
        DoViewChange receive-set, in records of the R slots each has
        (one a source: a second record of one source is
        ``ERR_REC_OVERFLOW`` / ``ERR_DVC_OVERFLOW``, which stops a
        run)."""
        recovering = st["status"] == RECOVERING
        hdr = st["m_hdr"]
        # H_OP = -1 marks a backup's Nil form, whose H_FIRST is 0
        pending = ((st["m_present"] == 1) & (st["m_count"] > 0)
                   & (hdr[:, H_TYPE] == M_RECOVERYRESP)
                   & (hdr[:, H_FIRST] > 0))
        held = (st["rec"] == 1) & (st["rec_ceil"] > 0)
        mine = jnp.stack([
            recovering.any(), (recovering & (st["op"] > 0)).any(),
            pending.any() | held.any(),
            (st["rec"] == 1).sum(-1).max(),
            (st["dvc"] == 1).sum(-1).max()]).astype(jnp.uint32)
        return jnp.concatenate([super().commit_stats(st), mine])

    def act_receive_matching_svc(self, st, lane):  # AS04:589-607
        # AS04's body takes its `en` from `guard_receive_matching_svc`,
        # a table here; the oracle is ST03's body and the module's
        # conjuncts, a lane (as `CP06Kernel` does)
        s2, _en = ST03Kernel.act_receive_matching_svc(self, st, lane)
        i = self._dest_i(st, lane)
        en = (self._recv_guard(st, lane, M_SVC) & self._can_progress(st, i)
              & (st["status"][i] == VIEWCHANGE)
              & (st["m_hdr"][lane, H_VIEW] == st["view"][i])
              & (st["sent_dvc"][i] == 0))
        return s2, en

    # ------------------------------------------------------------------
    # async-log recovery actions
    # ------------------------------------------------------------------
    def act_crash(self, st, lane):                # AL05:851-885
        i = lane // (self.MAX_OPS + 1)
        last_op = lane % (self.MAX_OPS + 1)
        r = i + 1
        en = ((st["aux_restart"] < self.crash_limit)
              & self._can_progress(st, i)
              & (last_op <= st["op"][i]))
        u = self._unique_number(st)
        floor = jnp.minimum(st["commit"][i], last_op)
        pos = jnp.arange(self.MAX_OPS, dtype=I32)
        s2 = dict(st)
        s2["status"] = st["status"].at[i].set(RECOVERING)
        s2["log"] = st["log"].at[i].set(
            jnp.where(pos < last_op, st["log"][i], 0))    # LogPrefix
        s2["app"] = st["app"].at[i].set(0)
        s2["view"] = st["view"].at[i].set(0)
        s2["op"] = st["op"].at[i].set(last_op)
        s2["commit"] = st["commit"].at[i].set(0)
        s2["peer_op"] = st["peer_op"].at[i].set(0)
        s2["lnv"] = st["lnv"].at[i].set(0)
        s2 = self._reset_sent(s2, i)
        s2 = self._clear_dvc(s2, i)
        s2 = self._clear_rec(s2, i)
        s2["rec_number"] = s2["rec_number"].at[i].set(u)
        s2["aux_restart"] = st["aux_restart"] + 1
        s2 = self._broadcast(
            s2, self._row(M_RECOVERY, src=r, x=u, op=floor), r)
        return s2, en

    def act_receive_recovery(self, st, lane):     # AL05:888-915
        k = lane
        hdr = st["m_hdr"][k]
        r = hdr[H_DEST]
        i = jnp.clip(r - 1, 0, self.R - 1)
        en = (self._recv_guard(st, k, M_RECOVERY)
              & self._can_progress(st, i)
              & (st["status"][i] == NORMAL))
        prim = self._is_normal_primary(st, i, r)
        floor = hdr[H_OP]
        pos = jnp.arange(self.MAX_OPS, dtype=I32)
        n_suffix = jnp.maximum(st["op"][i] - floor, 0)
        src_pos = jnp.clip(pos + floor, 0, self.MAX_OPS - 1)
        suffix = jnp.where(pos < n_suffix, st["log"][i][src_pos], 0)
        s2 = self._bag_discard(dict(st), k)
        row = self._row(
            M_RECOVERYRESP, view=st["view"][i], x=hdr[H_X],
            first=jnp.where(prim, floor, 0),
            op=jnp.where(prim, st["op"][i], -1),
            commit=jnp.where(prim, st["commit"][i], -1),
            dest=hdr[H_SRC], src=r,
            log=jnp.where(prim, suffix, jnp.zeros_like(suffix)))
        s2 = self._bag_send(s2, row)
        return s2, en

    def act_receive_recovery_response(self, st, lane):  # AL05:918-932
        s2, en = super().act_receive_recovery_response(st, lane)
        hdr = st["m_hdr"][lane]
        i = jnp.clip(hdr[H_DEST] - 1, 0, self.R - 1)
        j = jnp.clip(hdr[H_SRC] - 1, 0, self.R - 1)
        s2["rec_ceil"] = s2["rec_ceil"].at[i, j].set(
            jnp.where(hdr[H_OP] >= 0, hdr[H_FIRST], 0))
        return s2, en

    def act_complete_recovery(self, st, lane):    # AL05:947-977
        i = lane
        cand, j = self._best_rec(st, i)
        en = (self._can_progress(st, i)
              & (st["status"][i] == RECOVERING)
              & ((st["rec"][i] == 1).sum() > self.R // 2)
              & cand.any())
        ceil = st["rec_ceil"][i, j]
        m_op = st["rec_op"][i, j]
        pos = jnp.arange(self.MAX_OPS, dtype=I32)
        suffix = st["rec_log"][i, j][jnp.clip(pos - ceil, 0,
                                              self.MAX_OPS - 1)]
        new_log = jnp.where(pos < jnp.minimum(ceil, m_op), st["log"][i],
                            jnp.where(pos < m_op, suffix, 0))
        s2 = dict(st)
        s2["status"] = st["status"].at[i].set(NORMAL)
        s2["view"] = st["view"].at[i].set(st["rec_view"][i, j])
        s2["lnv"] = st["lnv"].at[i].set(st["rec_view"][i, j])
        s2["log"] = st["log"].at[i].set(new_log)
        s2["op"] = st["op"].at[i].set(m_op)
        s2 = self._exec_ops(s2, i, new_log, st["rec_commit"][i, j])
        s2 = self._clear_rec(s2, i)
        return s2, en

    # ------------------------------------------------------------------
    # guards: one table a state (stage 1 of the level program), under
    # the rules that stand above `CP06Kernel`'s tables.  ST03's sixteen
    # are inherited as they are where no class between adds a conjunct;
    # the ten guards here carry AS04's (the receive-set quorum of
    # SendSV, the ``rep_sent_dvc = FALSE`` of ReceiveMatchingSVC),
    # RR05's (not Recovering, four times) and the four recovery guards,
    # which RR05Kernel keeps a lane.  They stand in THIS class, below
    # `RR05Kernel`, where `CP06Kernel` (which writes its own 22) cannot
    # pick them up.  The ``act_*`` bodies keep their own ``en`` a lane:
    # tests/test_native_guard_tables.py (shape "al05") and
    # tests/test_native_al05.py hold table == ``en`` on every lane.
    # ------------------------------------------------------------------
    def _not_recovering_at_dest(self, st):                      # [M]
        return self._at_dest(st)("status") != RECOVERING

    def guard_timer_send_svc_table(self, st):                   # [R]
        return (ST03Kernel.guard_timer_send_svc_table(self, st)
                & (st["status"] != RECOVERING))

    def guard_receive_higher_svc_table(self, st):               # [M]
        return (ST03Kernel.guard_receive_higher_svc_table(self, st)
                & self._not_recovering_at_dest(st))

    def guard_receive_matching_svc_table(self, st):             # [M]
        return (ST03Kernel.guard_receive_matching_svc_table(self, st)
                & (self._at_dest(st)("sent_dvc") == 0))

    def guard_receive_higher_dvc_table(self, st):               # [M]
        return (ST03Kernel.guard_receive_higher_dvc_table(self, st)
                & self._not_recovering_at_dest(st))

    def guard_receive_sv_table(self, st):                       # [M]
        return (ST03Kernel.guard_receive_sv_table(self, st)
                & self._not_recovering_at_dest(st))

    def guard_crash_table(self, st):                # [R, MAX_OPS + 1]
        last_op = jnp.arange(self.MAX_OPS + 1, dtype=I32)
        rep = (st["aux_restart"] < self.crash_limit) & (st["no_prog"] == 0)
        return rep[:, None] & (last_op <= st["op"][:, None])

    def guard_receive_recovery_table(self, st):                 # [M]
        at = self._at_dest(st)
        return (self._recv_guard(st, ..., M_RECOVERY)
                & (at("no_prog") == 0) & (at("status") == NORMAL))

    def guard_receive_recovery_response_table(self, st):        # [M]
        at = self._at_dest(st)
        return (self._recv_guard(st, ..., M_RECOVERYRESP)
                & (at("no_prog") == 0)
                & (at("rec_number") == st["m_hdr"][:, H_X])
                & (at("status") == RECOVERING))

    def guard_complete_recovery_table(self, st):                # [R]
        pres = st["rec"] == 1                                   # [R, R]
        vmax = jnp.where(pres, st["rec_view"], -1).max(-1)
        cand = pres & (st["rec_has_log"] == 1) \
            & (st["rec_view"] == vmax[:, None])
        return ((st["no_prog"] == 0) & (st["status"] == RECOVERING)
                & (pres.sum(-1) > self.R // 2) & cand.any(-1))

    guard_timer_send_svc = lanes_of(guard_timer_send_svc_table)
    guard_receive_higher_svc = lanes_of(guard_receive_higher_svc_table)
    guard_receive_matching_svc = lanes_of(
        guard_receive_matching_svc_table)
    guard_receive_higher_dvc = lanes_of(guard_receive_higher_dvc_table)
    # ST03's table reads `_dvc_quorum`, which AS04 turns to the
    # receive-set: the guard a lane of AS04 says the same
    guard_send_sv = ST03Kernel.guard_send_sv
    guard_receive_sv = lanes_of(guard_receive_sv_table)
    guard_crash = lanes_of(guard_crash_table)
    guard_receive_recovery = lanes_of(guard_receive_recovery_table)
    guard_receive_recovery_response = lanes_of(
        guard_receive_recovery_response_table)
    guard_complete_recovery = lanes_of(guard_complete_recovery_table)

    # ------------------------------------------------------------------
    # action table (no RetryRecovery)
    # ------------------------------------------------------------------
    def _without_retry(self, fns):
        """RR05's table of 21 less the slot of the action this module
        does not have."""
        return [fn for name, fn in zip(RR05Kernel.action_names, fns)
                if name != "RetryRecovery"]

    def _guard_fns(self):
        return self._without_retry(super()._guard_fns())

    def _action_fns(self):
        return self._without_retry(super()._action_fns())

    def lane_replica(self, name, st, lane):
        if name == "Crash":
            return lane // (self.MAX_OPS + 1)
        return super().lane_replica(name, st, lane)
