"""jit+vmap transition kernel for VR_APP_STATE (AS04).

Subclasses the ST03 kernel (same bag primitives, AnyDest lanes,
NoProgressChange SUBSET lanes, fingerprint machinery) with the AS04
deltas (AS04:811-831 Next):

* the ``AppendOps``/``MaybeExecuteOps`` recursive executor
  (AS04:270-282) lowered to a masked positional write — every
  commit-advancing action (ReceivePrepareMsg AS04:373,
  PrimaryExecuteOp AS04:431, ReceiveNewState AS04:533, SendSV
  AS04:740, ReceiveSV AS04:777) appends ``log[old+1..new]`` to the
  ``app`` plane and raises commit, and commit is NEVER lowered (unlike
  ST03's wholesale installs);
* DVC quorums from the per-replica ``rep_recv_dvc`` SET (AS04:83)
  as dense [dest, source] slots with implied view/dest, reset on view
  adoption (ResetVcVars AS04:560/582/666/782, seed-with-carrier at
  ReceiveHigherDVC AS04:667) — VSR-style, including the slot-collision
  error channel;
* ``ReceiveMatchingSVC`` gains the ``rep_sent_dvc = FALSE``
  state-space-reduction guard (AS04:601);
* ``ExecuteOp`` becomes ``PrimaryExecuteOp``;
* ``NoAppStateDivergence`` (AS04:852-865).
"""

from __future__ import annotations

import jax.numpy as jnp

from .as04 import ERR_DVC_OVERFLOW, AS04Codec
from .st03 import (M_DVC, M_NEWSTATE, M_PREPARE, M_PREPAREOK, M_SV,
                   M_SVC, NORMAL, STATETRANSFER, VIEWCHANGE)
from .st03_kernel import INF, I32, ST03Kernel
from .vsr import H_COMMIT, H_DEST, H_FIRST, H_LNV, H_OP, H_SRC, H_VIEW

ACTION_NAMES = (
    "TimerSendSVC", "ReceiveHigherSVC", "ReceiveMatchingSVC", "SendDVC",
    "ReceiveHigherDVC", "ReceiveMatchingDVC", "SendSV", "ReceiveSV",
    "ReceiveClientRequest", "ReceivePrepareMsg", "ReceivePrepareOkMsg",
    "PrimaryExecuteOp", "SendGetState", "ReceiveGetState",
    "ReceiveNewState", "NoProgressChange",
)

REP_KEYS = ("status", "view", "op", "commit", "lnv", "log", "app",
            "peer_op", "sent_dvc", "sent_sv", "dvc", "dvc_lnv", "dvc_op",
            "dvc_commit", "dvc_log")


class AS04Kernel(ST03Kernel):
    action_names = ACTION_NAMES
    REP_KEYS = REP_KEYS
    PERM_REP_KEYS = ("log", "app", "dvc_log")

    def __init__(self, codec: AS04Codec, perms=None):
        super().__init__(codec, perms=perms)

    def _rep_shape(self, k):
        s = self.shape
        extra = {
            "app": (s.R, s.MAX_OPS), "dvc": (s.R, s.R),
            "dvc_lnv": (s.R, s.R), "dvc_op": (s.R, s.R),
            "dvc_commit": (s.R, s.R),
            "dvc_log": (s.R, s.R, s.MAX_OPS),
        }
        if k in extra:
            return extra[k]
        return super()._rep_shape(k)

    def _lane_count(self, name):
        if name == "PrimaryExecuteOp":
            return self.R
        return super()._lane_count(name)

    # ------------------------------------------------------------------
    # AS04 helpers
    # ------------------------------------------------------------------
    def _exec_ops(self, s2, i, log_plane, new_commit):
        """MaybeExecuteOps (AS04:277-282): when new_commit exceeds the
        current commit, append log[old+1..new] to the app plane and
        raise commit; otherwise leave both untouched (commit is never
        lowered)."""
        old = s2["commit"][i]
        adv = new_commit > old
        pos = jnp.arange(self.MAX_OPS, dtype=I32)
        write = adv & (pos >= old) & (pos < new_commit)
        s2 = dict(s2)
        s2["app"] = s2["app"].at[i].set(
            jnp.where(write, log_plane, s2["app"][i]))
        s2["commit"] = s2["commit"].at[i].set(
            jnp.where(adv, new_commit, old))
        return s2

    def _clear_dvc(self, s2, i):
        """ResetVcVars' rep_recv_dvc wipe (AS04:287-291)."""
        s2 = dict(s2)
        s2["dvc"] = s2["dvc"].at[i].set(0)
        s2["dvc_lnv"] = s2["dvc_lnv"].at[i].set(0)
        s2["dvc_op"] = s2["dvc_op"].at[i].set(0)
        s2["dvc_commit"] = s2["dvc_commit"].at[i].set(0)
        s2["dvc_log"] = s2["dvc_log"].at[i].set(0)
        return s2

    def _dvc_slot_add(self, s2, i, j, lnv, op, commit, log, pred):
        """Set-union a DVC into slot [i, j]; an identical record is a
        no-op, a different one from the same source needs a multi-slot
        layout (error channel, as in the VSR kernel)."""
        s2 = dict(s2)
        same = ((s2["dvc"][i, j] == 1)
                & (s2["dvc_lnv"][i, j] == lnv)
                & (s2["dvc_op"][i, j] == op)
                & (s2["dvc_commit"][i, j] == commit)
                & (s2["dvc_log"][i, j] == log).all())
        collide = pred & (s2["dvc"][i, j] == 1) & ~same

        def put(key, val):
            s2[key] = jnp.where(pred, s2[key].at[i, j].set(val), s2[key])
        put("dvc", 1)
        put("dvc_lnv", lnv)
        put("dvc_op", op)
        put("dvc_commit", commit)
        put("dvc_log", log)
        s2["err"] = s2["err"] | jnp.where(collide, ERR_DVC_OVERFLOW, 0)
        return s2

    # ------------------------------------------------------------------
    # overridden actions
    # ------------------------------------------------------------------
    def act_receive_higher_svc(self, st, lane):   # AS04:575-587
        s2, en = super().act_receive_higher_svc(st, lane)
        i = jnp.clip(st["m_hdr"][lane, H_DEST] - 1, 0, self.R - 1)
        return self._clear_dvc(s2, i), en

    def act_timer_send_svc(self, st, lane):       # AS04:551-566
        s2, en = super().act_timer_send_svc(st, lane)
        return self._clear_dvc(s2, lane), en

    def act_receive_matching_svc(self, st, lane):  # AS04:589-607
        # ST03 body + the rep_sent_dvc = FALSE state-space-reduction
        # conjunct (already expressed by the guard override)
        s2, _en = super().act_receive_matching_svc(st, lane)
        return s2, self.guard_receive_matching_svc(st, lane)

    def act_send_dvc(self, st, lane):             # AS04:609-651
        # ST03 body (SendAsReceived to self, Send otherwise); the new
        # primary additionally registers its own DVC in its recv_dvc
        # set (AS04:644-647)
        s2, en = super().act_send_dvc(st, lane)
        i = lane
        self_case = self._primary(st["view"][i], self.R) == i + 1
        s2 = self._dvc_slot_add(s2, i, i, st["lnv"][i], st["op"][i],
                                st["commit"][i], st["log"][i],
                                pred=self_case & en)
        return s2, en

    def act_receive_higher_dvc(self, st, lane):   # AS04:653-672
        k = lane
        hdr = st["m_hdr"][k]
        r = hdr[H_DEST]
        i = jnp.clip(r - 1, 0, self.R - 1)
        j = jnp.clip(hdr[H_SRC] - 1, 0, self.R - 1)
        en = (self._recv_guard(st, k, M_DVC) & self._can_progress(st, i)
              & (hdr[H_VIEW] > st["view"][i]))
        s2 = dict(st)
        s2["view"] = st["view"].at[i].set(hdr[H_VIEW])
        s2["status"] = st["status"].at[i].set(VIEWCHANGE)
        s2 = self._reset_sent(s2, i)
        s2 = self._clear_dvc(s2, i)
        # ResetVcVars seeds the set with the carrier DVC (AS04:667)
        s2 = self._dvc_slot_add(s2, i, j, hdr[H_LNV], hdr[H_OP],
                                hdr[H_COMMIT], st["m_log"][k],
                                pred=jnp.asarray(True))
        s2 = self._bag_discard(s2, k)
        s2 = self._broadcast(s2, self._row(M_SVC, view=hdr[H_VIEW], src=r),
                             r)
        return s2, en

    def act_receive_matching_dvc(self, st, lane):  # AS04:674-690
        # ST03 body (discard) + registering into the recv_dvc slots
        s2, en = super().act_receive_matching_dvc(st, lane)
        hdr = st["m_hdr"][lane]
        i = jnp.clip(hdr[H_DEST] - 1, 0, self.R - 1)
        j = jnp.clip(hdr[H_SRC] - 1, 0, self.R - 1)
        s2 = self._dvc_slot_add(s2, i, j, hdr[H_LNV], hdr[H_OP],
                                hdr[H_COMMIT], st["m_log"][lane], pred=en)
        return s2, en

    def _highest_dvc_slot(self, st, i):
        """HighestLog/-OpNumber/-CommitNumber over the recv_dvc slots
        (AS04:697-727): maximal (lnv, op); CHOOSE ties by min value_key
        = lex (commit, log, source)."""
        mask = st["dvc"][i] == 1
        pair = st["dvc_lnv"][i] * I32(self.MAX_OPS + 1) + st["dvc_op"][i]
        best_pair = jnp.max(jnp.where(mask, pair, -1))
        maximal = mask & (pair == best_pair)
        src_ids = jnp.arange(1, self.R + 1, dtype=I32)
        keys = jnp.concatenate(
            [st["dvc_commit"][i][:, None], st["dvc_log"][i],
             src_ids[:, None]], axis=1)
        cand = maximal
        for c in range(keys.shape[1]):
            col = jnp.where(cand, keys[:, c], INF)
            cand = cand & (col == col.min())
        best_j = jnp.argmax(cand)
        return (st["dvc_log"][i, best_j], st["dvc_op"][i, best_j],
                jnp.max(jnp.where(mask, st["dvc_commit"][i], -1)))

    def act_send_sv(self, st, lane):              # AS04:729-757
        i = lane
        r = i + 1
        view = st["view"][i]
        en = (self._can_progress(st, i)
              & (st["status"][i] == VIEWCHANGE) & (st["sent_sv"][i] == 0)
              & ((st["dvc"][i] == 1).sum() >= self.R // 2 + 1))
        new_log, new_on, new_cn = self._highest_dvc_slot(st, i)
        s2 = dict(st)
        s2["status"] = st["status"].at[i].set(NORMAL)
        s2["log"] = st["log"].at[i].set(new_log)
        s2 = self._exec_ops(s2, i, new_log, new_cn)
        s2["op"] = s2["op"].at[i].set(new_on)
        s2["peer_op"] = s2["peer_op"].at[i].set(0)
        s2["sent_sv"] = s2["sent_sv"].at[i].set(1)
        s2["lnv"] = s2["lnv"].at[i].set(view)
        s2 = self._clear_dvc(s2, i)               # AS04:745
        # the SV carries HighestCommitNumber (AS04:736,750), which can
        # be BELOW the sender's own (possibly just-executed) commit
        row = self._row(M_SV, view=view, op=new_on,
                        commit=new_cn, src=r, log=new_log)
        s2 = self._broadcast(s2, row, r)
        return s2, en

    def act_receive_sv(self, st, lane):           # AS04:759-788
        k = lane
        hdr = st["m_hdr"][k]
        r = hdr[H_DEST]
        i = jnp.clip(r - 1, 0, self.R - 1)
        en = (self._recv_guard(st, k, M_SV) & self._can_progress(st, i)
              & (((hdr[H_VIEW] == st["view"][i])
                  & (st["status"][i] == VIEWCHANGE))
                 | (hdr[H_VIEW] > st["view"][i])))
        old_commit = st["commit"][i]
        s2 = dict(st)
        s2["status"] = st["status"].at[i].set(NORMAL)
        s2["view"] = st["view"].at[i].set(hdr[H_VIEW])
        s2["log"] = st["log"].at[i].set(st["m_log"][k])
        s2 = self._exec_ops(s2, i, st["m_log"][k], hdr[H_COMMIT])
        s2["op"] = s2["op"].at[i].set(hdr[H_OP])
        s2["lnv"] = s2["lnv"].at[i].set(hdr[H_VIEW])
        s2 = self._reset_sent(s2, i)
        s2 = self._clear_dvc(s2, i)
        s2 = self._bag_discard(s2, k)
        ok_row = self._row(M_PREPAREOK, view=hdr[H_VIEW], op=hdr[H_OP],
                           dest=self._primary(hdr[H_VIEW], self.R), src=r)
        s2 = self._bag_send(s2, ok_row, pred=old_commit < hdr[H_OP])
        return s2, en

    def act_receive_prepare(self, st, lane):      # AS04:361-383
        k = lane
        hdr = st["m_hdr"][k]
        r = hdr[H_DEST]
        i = jnp.clip(r - 1, 0, self.R - 1)
        en = (self._recv_guard(st, k, M_PREPARE)
              & self._can_progress(st, i)
              & ~self._is_normal_primary(st, i, r)
              & (st["status"][i] == NORMAL)
              & (hdr[H_VIEW] == st["view"][i])
              & (hdr[H_OP] == st["op"][i] + 1))
        s2 = dict(st)
        new_log = st["log"][i].at[
            jnp.clip(hdr[H_OP] - 1, 0, self.MAX_OPS - 1)] \
            .set(st["m_entry"][k])
        s2["log"] = st["log"].at[i].set(new_log)
        s2["op"] = st["op"].at[i].set(hdr[H_OP])
        s2 = self._exec_ops(s2, i, new_log, hdr[H_COMMIT])
        s2 = self._bag_discard(s2, k)
        ok_row = self._row(M_PREPAREOK, view=st["view"][i],
                           op=hdr[H_OP], dest=hdr[H_SRC], src=r)
        s2 = self._bag_send(s2, ok_row)
        return s2, en

    def act_execute_op(self, st, lane):           # PrimaryExecuteOp
        i = lane                                  # AS04:420-437
        r = i + 1
        opn = st["commit"][i] + 1
        committed = (st["peer_op"][i] >= opn).sum() >= self.R // 2
        en = (self._can_progress(st, i)
              & self._is_normal_primary(st, i, r)
              & (st["commit"][i] < st["op"][i]) & committed)
        vid = st["log"][i, jnp.clip(opn - 1, 0, self.MAX_OPS - 1)]
        s2 = self._exec_ops(dict(st), i, st["log"][i], opn)
        s2["aux_acked"] = s2["aux_acked"].at[
            jnp.clip(vid - 1, 0, self.V - 1)].set(2)
        return s2, en

    def act_receive_new_state(self, st, lane):    # AS04:515-539
        k = lane
        hdr = st["m_hdr"][k]
        r = hdr[H_DEST]
        i = jnp.clip(r - 1, 0, self.R - 1)
        en = (self._recv_guard(st, k, M_NEWSTATE)
              & self._can_progress(st, i)
              & (st["status"][i] == STATETRANSFER)
              & (hdr[H_VIEW] > st["view"][i]))
        first = hdr[H_FIRST]
        pos = jnp.arange(self.MAX_OPS, dtype=I32)
        suffix = st["m_log"][k][jnp.clip(pos - (first - 1), 0,
                                         self.MAX_OPS - 1)]
        new_log = jnp.where(pos < first - 1, st["log"][i],
                            jnp.where(pos < hdr[H_OP], suffix, 0))
        s2 = dict(st)
        s2["status"] = st["status"].at[i].set(NORMAL)
        s2["view"] = st["view"].at[i].set(hdr[H_VIEW])
        s2["lnv"] = st["lnv"].at[i].set(hdr[H_VIEW])
        s2["log"] = st["log"].at[i].set(new_log)
        s2 = self._exec_ops(s2, i, new_log, hdr[H_COMMIT])
        s2["op"] = s2["op"].at[i].set(hdr[H_OP])
        s2 = self._bag_discard(s2, k)
        return s2, en

    # overridden guards --------------------------------------------------
    def guard_receive_matching_svc(self, st, k):
        i = self._dest_i(st, k)
        return (super().guard_receive_matching_svc(st, k)
                & (st["sent_dvc"][i] == 0))

    def _dvc_quorum(self, st):
        """([R], need): from here on the family keeps the DoViewChanges
        a replica has received in its ``dvc`` receive-set, one slot a
        source; what SendSV counts and ``commit_stats`` reads."""
        return (st["dvc"] == 1).sum(-1), self.R // 2 + 1

    def guard_send_sv(self, st, lane):
        i = lane
        return (self._can_progress(st, i)
                & (st["status"][i] == VIEWCHANGE)
                & (st["sent_sv"][i] == 0)
                & ((st["dvc"][i] == 1).sum() >= self.R // 2 + 1))

    def lane_replica(self, name, st, lane):
        if name == "PrimaryExecuteOp":
            return lane
        return super().lane_replica(name, st, lane)

    # invariants ---------------------------------------------------------
    def inv_no_app_state_divergence(self, st):
        # AS04:852-865: no pair both-committed at op with differing app
        # entries while r1's log agrees with r1's app at that op
        pos = jnp.arange(self.MAX_OPS, dtype=I32)
        comm = pos[None, :] < st["commit"][:, None]          # [R, P]
        app_diff = st["app"][:, None, :] != st["app"][None, :, :]
        log_eq_app = st["log"] == st["app"]                  # [R, P]
        viol = (comm[:, None, :] & comm[None, :, :] & app_diff
                & log_eq_app[:, None, :])
        return ~viol.any()

    INVARIANT_FNS = dict(
        ST03Kernel.INVARIANT_FNS,
        NoAppStateDivergence="inv_no_app_state_divergence")

