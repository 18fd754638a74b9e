"""jit+vmap transition kernel for VR_REPLICA_RECOVERY_CP (CP06).

The checkpointing spec — last and largest of the corpus (22-action
Next, CP06:1186-1213).  Subclasses the RR05 kernel with:

* NoOp log entries marking the GC'd prefix (id V+1, fixed under value
  permutations); ``HighestGCedOp`` as a vectorized max over NoOp
  positions (CP06:346-354);
* implicit checkpoints: replies and DVCs choose
  ``\\E last_cp \\in HighestGCedOp+1..commit`` — an extra lane
  dimension on SendDVC / ReceiveGetState / ReceiveGetCheckpointMsg /
  ReceiveRecoveryMsg (and Crash's ``0..commit``);
* dual-mode payloads (flag 0/1): log-suffix vs checkpoint+suffix
  (CP06:404-431), with ``ApplyCheckpoint`` (CP06:383-402) lowered to
  masked positional writes over the log/app planes;
* checkpointed DVC/SV (CP06:785-823, 898-927): WinningDVC carries
  (checkpoint, cp_number, log_suffix), the tie-break following the
  interpreter's value_key record order — (checkpoint, commit,
  cp_number, domain-keyed log_suffix, source);
* the GetCheckpoint -> NewCheckpoint -> Recovery chain (CP06:985-1135)
  and the dual-mode CompleteRecovery (CP06:1138-1170).
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from .as04_kernel import AS04Kernel
from .guard_tables import lanes_of
from .cp06 import M_GETCP, M_NEWCP, M_RECOVERY, M_RECOVERYRESP, CP06Codec
from .rr05 import RECOVERING
from .rr05_kernel import RR05Kernel
from .st03 import (ANYDEST, M_DVC, M_GETSTATE, M_NEWSTATE, M_PREPARE,
                   M_PREPAREOK, M_SV, M_SVC, NORMAL, STATETRANSFER,
                   VIEWCHANGE)
from .st03_kernel import INF, I32, ST03Kernel
from .vsr import (ERR_REC_OVERFLOW, H_COMMIT, H_CP, H_DEST, H_FIRST,
                  H_FLAG, H_LNV, H_OP, H_SRC, H_TYPE, H_VIEW, H_X)

ACTION_NAMES = (
    "TimerSendSVC", "ReceiveHigherSVC", "ReceiveMatchingSVC", "SendDVC",
    "ReceiveHigherDVC", "ReceiveMatchingDVC", "SendSV", "ReceiveSV",
    "ReceiveClientRequest", "ReceivePrepareMsg", "ReceivePrepareOkMsg",
    "PrimaryExecuteOp", "SendGetState", "ReceiveGetState",
    "ReceiveNewState", "Crash", "ReceiveGetCheckpointMsg",
    "ReceiveNewCheckpointMsg", "ReceiveRecoveryMsg",
    "ReceiveRecoveryResponseMsg", "CompleteRecovery", "NoProgressChange",
)

REP_KEYS = RR05Kernel.REP_KEYS + (
    "dvc_cpn", "dvc_cp", "rec_flag", "rec_first", "rec_cp", "rec_cpn")


class CP06Kernel(RR05Kernel):
    action_names = ACTION_NAMES
    # CP06's own line range of each action this file or SURVEY 2.1-2.2
    # cites one for: the location a native spec prints for a
    # counterexample step.  An inherited action whose CP06 lines no
    # record gives has no entry and prints the generic location
    # (models/native.py), never a base module's lines
    ACTION_LINES = {
        "SendDVC": (785, 816), "ReceiveHigherDVC": (825, 844),
        "ReceiveMatchingDVC": (846, 862), "SendSV": (898, 937),
        "ReceiveSV": (939, 971), "ReceiveGetState": (644, 680),
        "ReceiveNewState": (682, 712), "Crash": (985, 1009),
        "ReceiveGetCheckpointMsg": (1017, 1043),
        "ReceiveNewCheckpointMsg": (1051, 1079),
        "ReceiveRecoveryMsg": (1081, 1105),
        "ReceiveRecoveryResponseMsg": (1107, 1121),
        "CompleteRecovery": (1138, 1170),
    }
    REP_KEYS = REP_KEYS
    MSG_KEYS = RR05Kernel.MSG_KEYS + ("m_cp",)
    PERM_REP_KEYS = ("log", "app", "dvc_log", "dvc_cp", "rec_log",
                     "rec_cp")
    PERM_MSG_KEYS = ("m_entry", "m_log", "m_cp")
    ROW_PLANES = (("entry", "m_entry"), ("log", "m_log"), ("cp", "m_cp"))

    def __init__(self, codec: CP06Codec, perms=None):
        self.NOOP = codec.noop_id
        super().__init__(codec, perms=perms)

    # plain 1-field entries + NoOp (fixed under permutations)
    def _perm_vals(self, arr, perm):
        return jnp.where(arr > self.V, arr,
                         perm[jnp.clip(arr, 0, self.V)])

    act_receive_client_request = ST03Kernel.act_receive_client_request
    act_execute_op = AS04Kernel.act_execute_op

    def _rep_shape(self, k):
        s = self.shape
        extra = {
            "dvc_cpn": (s.R, s.R), "dvc_cp": (s.R, s.R, s.MAX_OPS),
            "rec_flag": (s.R, s.R), "rec_first": (s.R, s.R),
            "rec_cp": (s.R, s.R, s.MAX_OPS), "rec_cpn": (s.R, s.R),
        }
        if k in extra:
            return extra[k]
        return super()._rep_shape(k)

    def _nmsg(self):
        return super()._nmsg() + self.MAX_OPS     # + m_cp plane

    def _lane_count(self, name):
        C = self.MAX_OPS + 1
        if name in ("SendDVC", "Crash"):
            return self.R * C
        if name in ("ReceiveGetState", "ReceiveGetCheckpointMsg"):
            return self.M * self.R * C
        if name == "ReceiveRecoveryMsg":
            return self.M * C
        if name in ("ReceiveNewCheckpointMsg",):
            return self.M
        return super()._lane_count(name)

    def _row(self, *args, cp=None, **kw):
        row = super()._row(*args, **kw)
        row["cp"] = cp if cp is not None \
            else jnp.zeros((self.MAX_OPS,), I32)
        return row

    #: ST03's six, then what this module adds (the same hook)
    COMMIT_STATS = ST03Kernel.COMMIT_STATS + (
        ("recovering_states", "sum"), ("gc_states", "sum"),
        ("rec_set_peak", "max"), ("dvc_set_peak", "max"))

    def commit_stats(self, st):
        """[10] uint32 of one state: ST03's six, whether a replica is
        Recovering, whether a replica's log has a garbage-collected
        (NoOp) prefix (HighestGCedOp > 0), and the fullest
        RecoveryResponse and DoViewChange receive-set, in records of
        the R slots each has (one a source: a second record of one
        source is ``ERR_REC_OVERFLOW`` / ``ERR_DVC_OVERFLOW``, which
        stops a run)."""
        mine = jnp.stack([
            (st["status"] == RECOVERING).any(),
            (st["log"] == self.NOOP).any(),
            (st["rec"] == 1).sum(-1).max(),
            (st["dvc"] == 1).sum(-1).max()]).astype(jnp.uint32)
        return jnp.concatenate([super().commit_stats(st), mine])

    # ------------------------------------------------------------------
    # checkpoint helpers
    # ------------------------------------------------------------------
    def _hgc(self, log_row):
        """HighestGCedOp (CP06:346-354): highest 1-based position
        holding NoLogEntry, 0 when none."""
        pos = jnp.arange(self.MAX_OPS, dtype=I32)
        return jnp.max(jnp.where(log_row == self.NOOP, pos + 1, 0))

    def _clear_dvc(self, s2, i):
        s2 = super()._clear_dvc(s2, i)
        s2["dvc_cpn"] = s2["dvc_cpn"].at[i].set(0)
        s2["dvc_cp"] = s2["dvc_cp"].at[i].set(0)
        return s2

    def _clear_rec(self, s2, i):
        s2 = super()._clear_rec(s2, i)
        for key in ("rec_flag", "rec_first", "rec_cpn"):
            s2[key] = s2[key].at[i].set(0)
        s2["rec_cp"] = s2["rec_cp"].at[i].set(0)
        return s2

    def _apply_checkpoint(self, s2, i, suffix, cp_plane, cpn, opn,
                          new_commit):
        """ApplyCheckpoint (CP06:383-402): NoOp the prefix covered by
        the checkpoint, install the suffix above it, set the app state
        to checkpoint + executed suffix, raise commit to new_commit."""
        pos = jnp.arange(self.MAX_OPS, dtype=I32)
        sfx = suffix[jnp.clip(pos - cpn, 0, self.MAX_OPS - 1)]
        new_log = jnp.where(pos < cpn, self.NOOP,
                            jnp.where(pos < opn, sfx, 0))
        new_app = jnp.where(pos < cpn, cp_plane,
                            jnp.where(pos < new_commit, sfx, 0))
        s2 = dict(s2)
        s2["log"] = s2["log"].at[i].set(new_log)
        s2["app"] = s2["app"].at[i].set(new_app)
        s2["op"] = s2["op"].at[i].set(opn)
        s2["commit"] = s2["commit"].at[i].set(new_commit)
        return s2

    def _log_suffix(self, log_row, first):
        """LogSuffix re-based at 0 (source positions first-1.., zero
        beyond the log end — Len(log) == op for every CP06 log)."""
        pos = jnp.arange(self.MAX_OPS, dtype=I32)
        src = jnp.clip(pos + first - 1, 0, self.MAX_OPS - 1)
        return jnp.where(pos + first - 1 < self.MAX_OPS, log_row[src], 0)

    # ------------------------------------------------------------------
    # view change: checkpointed DVC / SV
    # ------------------------------------------------------------------
    def act_receive_matching_svc(self, st, lane):  # AS04:589-607
        # AS04's body takes its `en` from `guard_receive_matching_svc`,
        # a table here and half a table there; the oracle is ST03's
        # body and the module's conjuncts, a lane
        s2, _en = ST03Kernel.act_receive_matching_svc(self, st, lane)
        i = self._dest_i(st, lane)
        en = (self._recv_guard(st, lane, M_SVC) & self._can_progress(st, i)
              & (st["status"][i] == VIEWCHANGE)
              & (st["m_hdr"][lane, H_VIEW] == st["view"][i])
              & (st["sent_dvc"][i] == 0))
        return s2, en

    def act_send_dvc(self, st, lane):             # CP06:785-816
        C = self.MAX_OPS + 1
        i = lane // C
        cp = lane % C
        r = i + 1
        view = st["view"][i]
        prim = self._primary(view, self.R)
        hgc = self._hgc(st["log"][i])
        en = (self._can_progress(st, i)
              & (st["status"][i] == VIEWCHANGE) & (st["sent_dvc"][i] == 0)
              & (self._svc_tombstones(st, i) >= self.R // 2)
              & (cp >= hgc + 1) & (cp <= st["commit"][i]))
        cp_plane = jnp.where(jnp.arange(self.MAX_OPS, dtype=I32) < cp,
                             st["app"][i], 0)
        suffix = self._log_suffix(st["log"][i], cp + 1)
        s2 = dict(st)
        s2["sent_dvc"] = st["sent_dvc"].at[i].set(1)
        row = self._row(M_DVC, view=view, op=st["op"][i],
                        commit=st["commit"][i], dest=prim, src=r,
                        lnv=st["lnv"][i], log=suffix, cp=cp_plane)
        row["hdr"] = row["hdr"].at[H_CP].set(cp)
        self_case = prim == r
        s2 = self._bag_send(s2, row, new_count=jnp.where(self_case, 0, 1))
        s2 = self._dvc_slot_add_cp(s2, i, i, st["lnv"][i], st["op"][i],
                                   st["commit"][i], suffix, cp_plane, cp,
                                   pred=self_case & en)
        return s2, en

    def _dvc_slot_add_cp(self, s2, i, j, lnv, op, commit, suffix,
                         cp_plane, cpn, pred):
        s2 = self._dvc_slot_add(s2, i, j, lnv, op, commit, suffix,
                                pred=pred)

        def put(key, val):
            s2[key] = jnp.where(pred, s2[key].at[i, j].set(val), s2[key])
        put("dvc_cpn", cpn)
        put("dvc_cp", cp_plane)
        return s2

    def act_receive_higher_dvc(self, st, lane):   # CP06:825-844
        k = lane
        hdr = st["m_hdr"][k]
        r = hdr[H_DEST]
        i = jnp.clip(r - 1, 0, self.R - 1)
        j = jnp.clip(hdr[H_SRC] - 1, 0, self.R - 1)
        en = (self._recv_guard(st, k, M_DVC) & self._can_progress(st, i)
              & self._not_recovering(st, i)
              & (hdr[H_VIEW] > st["view"][i]))
        s2 = dict(st)
        s2["view"] = st["view"].at[i].set(hdr[H_VIEW])
        s2["status"] = st["status"].at[i].set(VIEWCHANGE)
        s2 = self._reset_sent(s2, i)
        s2 = self._clear_dvc(s2, i)
        s2 = self._dvc_slot_add_cp(
            s2, i, j, hdr[H_LNV], hdr[H_OP], hdr[H_COMMIT],
            st["m_log"][k], st["m_cp"][k], hdr[H_CP],
            pred=jnp.asarray(True))
        s2 = self._bag_discard(s2, k)
        s2 = self._broadcast(s2, self._row(M_SVC, view=hdr[H_VIEW], src=r),
                             r)
        return s2, en

    def act_receive_matching_dvc(self, st, lane):  # CP06:846-862
        k = lane
        hdr = st["m_hdr"][k]
        i = jnp.clip(hdr[H_DEST] - 1, 0, self.R - 1)
        j = jnp.clip(hdr[H_SRC] - 1, 0, self.R - 1)
        en = (self._recv_guard(st, k, M_DVC) & self._can_progress(st, i)
              & (st["status"][i] == VIEWCHANGE)
              & (hdr[H_VIEW] == st["view"][i]))
        s2 = self._bag_discard(dict(st), k)
        s2 = self._dvc_slot_add_cp(
            s2, i, j, hdr[H_LNV], hdr[H_OP], hdr[H_COMMIT],
            st["m_log"][k], st["m_cp"][k], hdr[H_CP], pred=en)
        return s2, en

    def _winning_dvc(self, st, i):
        """WinningDVC (CP06:885-896) + HighestCommitNumber: maximal
        (lnv, op); CHOOSE ties by min value_key = lex (checkpoint,
        commit, cp_number, domain-keyed log_suffix, source)."""
        mask = st["dvc"][i] == 1
        pair = st["dvc_lnv"][i] * I32(self.MAX_OPS + 1) + st["dvc_op"][i]
        best_pair = jnp.max(jnp.where(mask, pair, -1))
        maximal = mask & (pair == best_pair)
        src_ids = jnp.arange(1, self.R + 1, dtype=I32)
        pos = jnp.arange(self.MAX_OPS, dtype=I32)[None, :]
        # suffix keys carry their (domain, entry) pairs packed: the
        # FnVal item order compares domain key first
        n_sfx = st["dvc_op"][i] - st["dvc_cpn"][i]          # [R]
        sfx_key = jnp.where(
            pos < n_sfx[:, None],
            (st["dvc_cpn"][i][:, None] + 1 + pos) * I32(64)
            + st["dvc_log"][i], 0)
        keys = jnp.concatenate(
            [st["dvc_cp"][i], st["dvc_commit"][i][:, None],
             st["dvc_cpn"][i][:, None], sfx_key, src_ids[:, None]],
            axis=1)
        cand = maximal
        for c in range(keys.shape[1]):
            col = jnp.where(cand, keys[:, c], INF)
            cand = cand & (col == col.min())
        best_j = jnp.argmax(cand)
        new_cn = jnp.max(jnp.where(mask, st["dvc_commit"][i], -1))
        return best_j, new_cn

    def act_send_sv(self, st, lane):              # CP06:898-937
        i = lane
        r = i + 1
        view = st["view"][i]
        en = (self._can_progress(st, i)
              & (st["status"][i] == VIEWCHANGE) & (st["sent_sv"][i] == 0)
              & ((st["dvc"][i] == 1).sum() >= self.R // 2 + 1))
        j, new_cn = self._winning_dvc(st, i)
        w_sfx = st["dvc_log"][i, j]
        w_cp = st["dvc_cp"][i, j]
        w_cpn = st["dvc_cpn"][i, j]
        w_op = st["dvc_op"][i, j]
        s2 = dict(st)
        s2["status"] = st["status"].at[i].set(NORMAL)
        s2 = self._apply_checkpoint(s2, i, w_sfx, w_cp, w_cpn, w_op,
                                    new_cn)
        s2["peer_op"] = s2["peer_op"].at[i].set(0)
        s2["sent_sv"] = s2["sent_sv"].at[i].set(1)
        s2["lnv"] = s2["lnv"].at[i].set(view)
        s2 = self._clear_dvc(s2, i)
        row = self._row(M_SV, view=view, op=w_op, commit=new_cn, src=r,
                        log=w_sfx, cp=w_cp)
        row["hdr"] = row["hdr"].at[H_CP].set(w_cpn)
        s2 = self._broadcast(s2, row, r)
        return s2, en

    def act_receive_sv(self, st, lane):           # CP06:939-971
        k = lane
        hdr = st["m_hdr"][k]
        r = hdr[H_DEST]
        i = jnp.clip(r - 1, 0, self.R - 1)
        en = (self._recv_guard(st, k, M_SV) & self._can_progress(st, i)
              & self._not_recovering(st, i)
              & (((hdr[H_VIEW] == st["view"][i])
                  & (st["status"][i] == VIEWCHANGE))
                 | (hdr[H_VIEW] > st["view"][i])))
        old_commit = st["commit"][i]
        s2 = dict(st)
        s2["status"] = st["status"].at[i].set(NORMAL)
        s2["view"] = st["view"].at[i].set(hdr[H_VIEW])
        s2 = self._apply_checkpoint(s2, i, st["m_log"][k], st["m_cp"][k],
                                    hdr[H_CP], hdr[H_OP], hdr[H_COMMIT])
        s2["lnv"] = s2["lnv"].at[i].set(hdr[H_VIEW])
        s2 = self._reset_sent(s2, i)
        s2 = self._clear_dvc(s2, i)
        s2 = self._bag_discard(s2, k)
        ok_row = self._row(M_PREPAREOK, view=hdr[H_VIEW], op=hdr[H_OP],
                           dest=self._primary(hdr[H_VIEW], self.R), src=r)
        s2 = self._bag_send(s2, ok_row, pred=old_commit < hdr[H_OP])
        return s2, en

    # ------------------------------------------------------------------
    # state transfer: dual-mode replies
    # ------------------------------------------------------------------
    def _get_state_en(self, st, lane):
        C = self.MAX_OPS + 1
        k = lane // (self.R * C)
        rest = lane % (self.R * C)
        i = rest // C
        cp = rest % C
        r = i + 1
        hdr = st["m_hdr"][k]
        base = ((st["m_present"][k] == 1) & (st["m_count"][k] > 0)
                & (hdr[H_TYPE] == M_GETSTATE)
                & ((hdr[H_DEST] == r)
                   | ((hdr[H_DEST] == ANYDEST) & (hdr[H_SRC] != r)))
                & self._can_progress(st, i)
                & (st["status"][i] == NORMAL)
                & (st["view"][i] == hdr[H_VIEW])
                & (st["op"][i] > hdr[H_OP]))
        # branch select: GC'd at m.op+1 -> checkpoint reply (cp lanes),
        # else log-suffix reply (the cp == 0 lane)
        gced = st["log"][i][jnp.clip(hdr[H_OP], 0, self.MAX_OPS - 1)] \
            == self.NOOP
        hgc = self._hgc(st["log"][i])
        en_cp = base & gced & (cp >= hgc + 1) & (cp <= st["commit"][i])
        en_ls = base & ~gced & (cp == 0)
        return (en_cp | en_ls), k, i, cp, gced

    def act_receive_get_state(self, st, lane):    # CP06:644-680
        en, k, i, cp, gced = self._get_state_en(st, lane)
        hdr = st["m_hdr"][k]
        r = i + 1
        s2 = self._bag_discard(dict(st), k)
        pos = jnp.arange(self.MAX_OPS, dtype=I32)
        cp_plane = jnp.where(pos < cp, st["app"][i], 0)
        first_ls = hdr[H_OP] + 1
        row_log = jnp.where(gced,
                            self._log_suffix(st["log"][i], cp + 1),
                            self._log_suffix(st["log"][i], first_ls))
        row = self._row(M_NEWSTATE, view=st["view"][i], op=st["op"][i],
                        dest=hdr[H_SRC], src=r, log=row_log,
                        cp=jnp.where(gced, cp_plane, 0))
        h = row["hdr"]
        h = h.at[H_FLAG].set(jnp.where(gced, 1, 0))
        h = h.at[H_CP].set(jnp.where(gced, cp, 0))
        h = h.at[H_FIRST].set(jnp.where(gced, 0, first_ls))
        h = h.at[H_COMMIT].set(jnp.where(gced, cp, st["commit"][i]))
        row["hdr"] = h
        s2 = self._bag_send(s2, row)
        return s2, en

    def act_receive_new_state(self, st, lane):    # CP06:682-712
        k = lane
        hdr = st["m_hdr"][k]
        r = hdr[H_DEST]
        i = jnp.clip(r - 1, 0, self.R - 1)
        en = (self._recv_guard(st, k, M_NEWSTATE)
              & self._can_progress(st, i)
              & (st["status"][i] == STATETRANSFER)
              & (st["view"][i] == hdr[H_VIEW]))
        is_cp = hdr[H_FLAG] == 1
        pos = jnp.arange(self.MAX_OPS, dtype=I32)
        # flag=1 path: ApplyCheckpoint wholesale
        s2_cp = self._apply_checkpoint(
            dict(st), i, st["m_log"][k], st["m_cp"][k], hdr[H_CP],
            hdr[H_OP], hdr[H_COMMIT])
        # flag=0 path: splice own prefix below first_op with msg suffix
        first = hdr[H_FIRST]
        sfx0 = st["m_log"][k][jnp.clip(pos - (first - 1), 0,
                                       self.MAX_OPS - 1)]
        log0 = jnp.where(pos < first - 1, st["log"][i],
                         jnp.where(pos < hdr[H_OP], sfx0, 0))
        s2_ls = dict(st)
        s2_ls["log"] = st["log"].at[i].set(log0)
        s2_ls = self._exec_ops(s2_ls, i, log0, hdr[H_COMMIT])
        s2_ls["op"] = s2_ls["op"].at[i].set(hdr[H_OP])
        s2 = {key: jnp.where(jnp.broadcast_to(is_cp,
                                              jnp.shape(s2_cp[key])),
                             s2_cp[key], s2_ls[key])
              for key in s2_cp}
        s2["status"] = s2["status"].at[i].set(NORMAL)
        s2["view"] = s2["view"].at[i].set(hdr[H_VIEW])
        s2["lnv"] = s2["lnv"].at[i].set(hdr[H_VIEW])
        s2 = self._bag_discard(s2, k)
        return s2, en

    # ------------------------------------------------------------------
    # recovery: GetCheckpoint -> NewCheckpoint -> Recovery -> responses
    # ------------------------------------------------------------------
    def act_crash(self, st, lane):                # CP06:985-1009
        C = self.MAX_OPS + 1
        i = lane // C
        cp = lane % C
        r = i + 1
        row = self._row(M_GETCP, dest=ANYDEST, src=r)
        en = ((st["aux_restart"] < self.crash_limit)
              & (cp <= st["commit"][i])
              & ~self._row_eq(st, row).any())     # SendOnce
        pos = jnp.arange(self.MAX_OPS, dtype=I32)
        s2 = dict(st)
        s2["status"] = st["status"].at[i].set(RECOVERING)
        s2["log"] = st["log"].at[i].set(
            jnp.where(pos < cp, self.NOOP, 0))    # EmptyLog(cp)
        s2["app"] = st["app"].at[i].set(
            jnp.where(pos < cp, st["app"][i], 0))  # Checkpoint(r, cp)
        s2["view"] = st["view"].at[i].set(0)
        s2["op"] = st["op"].at[i].set(cp)
        s2["commit"] = st["commit"].at[i].set(cp)
        s2["peer_op"] = st["peer_op"].at[i].set(0)
        s2["lnv"] = st["lnv"].at[i].set(0)
        s2 = self._reset_sent(s2, i)
        s2 = self._clear_dvc(s2, i)
        s2 = self._clear_rec(s2, i)
        s2["rec_number"] = s2["rec_number"].at[i].set(
            self._unique_number(st))
        s2["aux_restart"] = st["aux_restart"] + 1
        s2 = self._bag_send(s2, row)
        return s2, en

    def act_receive_get_checkpoint(self, st, lane):  # CP06:1017-1043
        C = self.MAX_OPS + 1
        k = lane // (self.R * C)
        rest = lane % (self.R * C)
        i = rest // C
        cp = rest % C
        r = i + 1
        hdr = st["m_hdr"][k]
        en = ((st["m_present"][k] == 1) & (st["m_count"][k] > 0)
              & (hdr[H_TYPE] == M_GETCP)
              & ((hdr[H_DEST] == r)
                 | ((hdr[H_DEST] == ANYDEST) & (hdr[H_SRC] != r)))
              & self._can_progress(st, i)
              & self._not_recovering(st, i)
              & (cp <= st["commit"][i]))
        pos = jnp.arange(self.MAX_OPS, dtype=I32)
        cp_plane = jnp.where(pos < cp, st["app"][i], 0)
        s2 = self._bag_discard(dict(st), k)
        row = self._row(M_NEWCP, dest=hdr[H_SRC], src=r, cp=cp_plane)
        row["hdr"] = row["hdr"].at[H_CP].set(cp)
        s2 = self._bag_send(s2, row)
        return s2, en

    def act_receive_new_checkpoint(self, st, lane):  # CP06:1051-1079
        k = lane
        hdr = st["m_hdr"][k]
        r = hdr[H_DEST]
        i = jnp.clip(r - 1, 0, self.R - 1)
        en = (self._recv_guard(st, k, M_NEWCP)
              & self._can_progress(st, i)
              & (st["status"][i] == RECOVERING))
        cpn = hdr[H_CP]
        u = self._unique_number(st)
        pos = jnp.arange(self.MAX_OPS, dtype=I32)
        s2 = dict(st)
        s2["log"] = st["log"].at[i].set(
            jnp.where(pos < cpn, self.NOOP, 0))
        s2["app"] = st["app"].at[i].set(st["m_cp"][k])
        s2["op"] = st["op"].at[i].set(cpn)
        s2["commit"] = st["commit"].at[i].set(cpn)
        s2 = self._bag_discard(s2, k)
        s2 = self._broadcast(
            s2, self._row(M_RECOVERY, src=r, x=u, op=cpn), r)
        return s2, en

    def act_receive_recovery(self, st, lane):     # CP06:1081-1105
        C = self.MAX_OPS + 1
        k = lane // C
        cp = lane % C
        hdr = st["m_hdr"][k]
        r = hdr[H_DEST]
        i = jnp.clip(r - 1, 0, self.R - 1)
        base = (self._recv_guard(st, k, M_RECOVERY)
                & (st["status"][i] == NORMAL))
        prim = self._is_normal_primary(st, i, r)
        m_op = hdr[H_OP]
        gced = (st["op"][i] > m_op) \
            & (st["log"][i][jnp.clip(m_op, 0, self.MAX_OPS - 1)]
               == self.NOOP)
        hgc = self._hgc(st["log"][i])
        en_cp = base & prim & gced & (cp >= hgc + 1) \
            & (cp <= st["commit"][i])
        en_other = base & (~prim | ~gced) & (cp == 0)
        en = en_cp | en_other
        pos = jnp.arange(self.MAX_OPS, dtype=I32)
        cp_plane = jnp.where(pos < cp, st["app"][i], 0)
        s2 = self._bag_discard(dict(st), k)
        first_ls = m_op + 1
        row_log = jnp.where(prim & gced,
                            self._log_suffix(st["log"][i], cp + 1),
                            jnp.where(prim,
                                      self._log_suffix(st["log"][i],
                                                       first_ls),
                                      jnp.zeros((self.MAX_OPS,), I32)))
        row = self._row(M_RECOVERYRESP, view=st["view"][i], x=hdr[H_X],
                        op=st["op"][i], dest=hdr[H_SRC], src=r,
                        log=row_log,
                        cp=jnp.where(prim & gced, cp_plane, 0))
        h = row["hdr"]
        h = h.at[H_FLAG].set(jnp.where(prim & gced, 1, 0))
        h = h.at[H_CP].set(jnp.where(prim & gced, cp, 0))
        h = h.at[H_FIRST].set(
            jnp.where(~prim, -1, jnp.where(gced, 0, first_ls)))
        h = h.at[H_COMMIT].set(
            jnp.where(~prim, -1,
                      jnp.where(gced, cp, st["commit"][i])))
        row["hdr"] = h
        s2 = self._bag_send(s2, row)
        return s2, en

    def act_receive_recovery_response(self, st, lane):  # CP06:1107-1121
        k = lane
        hdr = st["m_hdr"][k]
        r = hdr[H_DEST]
        i = jnp.clip(r - 1, 0, self.R - 1)
        j = jnp.clip(hdr[H_SRC] - 1, 0, self.R - 1)
        en = (self._recv_guard(st, k, M_RECOVERYRESP)
              & (st["rec_number"][i] == hdr[H_X])
              & (st["status"][i] == RECOVERING))
        has_log = ~((hdr[H_FIRST] == -1) & (hdr[H_COMMIT] == -1))
        s2 = dict(st)
        collide = en & (s2["rec"][i, j] == 1) \
            & ((s2["rec_view"][i, j] != hdr[H_VIEW])
               | (s2["rec_op"][i, j] != hdr[H_OP]))
        s2["rec"] = s2["rec"].at[i, j].set(1)
        s2["rec_view"] = s2["rec_view"].at[i, j].set(hdr[H_VIEW])
        s2["rec_op"] = s2["rec_op"].at[i, j].set(hdr[H_OP])
        s2["rec_has_log"] = s2["rec_has_log"].at[i, j].set(
            has_log.astype(I32))
        s2["rec_flag"] = s2["rec_flag"].at[i, j].set(hdr[H_FLAG])
        s2["rec_first"] = s2["rec_first"].at[i, j].set(
            jnp.where(hdr[H_FLAG] == 1, hdr[H_CP] + 1, hdr[H_FIRST]))
        s2["rec_cpn"] = s2["rec_cpn"].at[i, j].set(hdr[H_CP])
        s2["rec_commit"] = s2["rec_commit"].at[i, j].set(hdr[H_COMMIT])
        s2["rec_log"] = s2["rec_log"].at[i, j].set(st["m_log"][k])
        s2["rec_cp"] = s2["rec_cp"].at[i, j].set(st["m_cp"][k])
        s2["err"] = s2["err"] | jnp.where(collide, ERR_REC_OVERFLOW, 0)
        s2 = self._bag_discard(s2, k)
        return s2, en

    def act_complete_recovery(self, st, lane):    # CP06:1138-1170
        i = lane
        cand, j = self._best_rec(st, i)
        en = ((st["status"][i] == RECOVERING)
              & ((st["rec"][i] == 1).sum() > self.R // 2)
              & cand.any())
        is_cp = st["rec_flag"][i, j] == 1
        m_op = st["rec_op"][i, j]
        m_commit = st["rec_commit"][i, j]
        pos = jnp.arange(self.MAX_OPS, dtype=I32)
        # flag=1 path
        s2_cp = self._apply_checkpoint(
            dict(st), i, st["rec_log"][i, j], st["rec_cp"][i, j],
            st["rec_cpn"][i, j], m_op, m_commit)
        # flag=0 path
        first = st["rec_first"][i, j]
        sfx0 = st["rec_log"][i, j][jnp.clip(pos - (first - 1), 0,
                                            self.MAX_OPS - 1)]
        log0 = jnp.where(pos < first - 1, st["log"][i],
                         jnp.where(pos < m_op, sfx0, 0))
        s2_ls = dict(st)
        s2_ls["log"] = st["log"].at[i].set(log0)
        s2_ls = self._exec_ops(s2_ls, i, log0, m_commit)
        s2_ls["op"] = s2_ls["op"].at[i].set(m_op)
        s2 = dict(st)
        for key in set(s2_cp) | set(s2_ls):
            a, b = s2_cp[key], s2_ls[key]
            s2[key] = jnp.where(jnp.broadcast_to(is_cp, jnp.shape(a)),
                                a, b)
        s2["status"] = s2["status"].at[i].set(NORMAL)
        s2["view"] = s2["view"].at[i].set(st["rec_view"][i, j])
        s2["lnv"] = s2["lnv"].at[i].set(st["rec_view"][i, j])
        s2 = self._clear_rec(s2, i)
        return s2, en

    # ------------------------------------------------------------------
    # guards: one table a state (stage 1 of the level program)
    # ------------------------------------------------------------------
    # Stage 1 evaluates every guard on every lane of every row of a
    # chunk, in every dispatch.  Written a lane at a time (decode k, i,
    # cp; gather the message and the replica; scan the log for NoOps,
    # the bag for tombstones or for the record a SendOnce would send)
    # the 812 lanes cost 12.8 ns a lane and row on the v5e, 57 % of a
    # traced slice (PERF.md, PR 46).  So every guard here is
    # ``guard_x_table(st)``: the action's enabling for ALL its lanes,
    # shaped like its lane decode ([M], [R, C], [M, C], [M, R, C], ...;
    # row-major = the lane number ``_lane_count`` and the ``act_*``
    # use), built from factors that each read the state once: of the
    # message [M], of the replica [R], of replica and checkpoint
    # [R, C], of the pair [M, R], combined by broadcast.  The per-lane
    # function the engines call is ``table(st).reshape(-1)[lane]``:
    # under their vmap over lanes the table depends on no lane, stays
    # unbatched and is computed once a state.
    #
    # What a table may read: the state's planes whole, never a plane
    # at a lane's index.  A replica's column at a message's dest is a
    # one-hot select over R (`_at_dest`), a log position a one-hot over
    # MAX_OPS: R and MAX_OPS are small, and the gather a lane is what
    # the tables exist to avoid.  Factors two guards share (the dest
    # one-hot, `_hgc_all`, `_addressed`) are written once and traced
    # by each; XLA's CSE makes them one in the level program.
    #
    # The action bodies are the oracle and are not written in terms of
    # these: each ``act_*`` computes its own ``en`` a lane, from the
    # cited lines of the module, and tests/test_native_cp06_guard_tables
    # .py holds table == ``en`` on every lane.  A lane a guard loses is
    # a state the checker loses; a lane it adds is only wasted work
    # (the expand's own ``en`` masks it).
    #
    # `_at_dest`, `_ids`, `_addressed`, `_normal_primary` and
    # `_sent_once` are `ST03Kernel`'s, whose own guards are tables of
    # the same kind (ISSUE 48).  The 22 tables here stay this class's:
    # they carry AS04's and RR05's conjuncts, which a `super()` on
    # ST03's table would skip.
    def _hgc_all(self, st):
        """[R]: HighestGCedOp of every replica (`_hgc` a row)."""
        pos = jnp.arange(self.MAX_OPS, dtype=I32)
        return jnp.where(st["log"] == self.NOOP, pos + 1, 0).max(-1)

    def _noop_at(self, log, op):
        """Whether ``log[..., clip(op)]`` is a NoOp, the position taken
        by a one-hot over MAX_OPS; `log` and `op` broadcast."""
        pos = jnp.arange(self.MAX_OPS, dtype=I32)
        at_op = jnp.clip(op, 0, self.MAX_OPS - 1)[..., None] == pos
        return (at_op & (log == self.NOOP)).any(-1)

    def _cp_upto_commit(self, st):
        """[R, C]: ``last_cp`` in 0..commit (Crash, GetCheckpoint)."""
        cps = jnp.arange(self.MAX_OPS + 1, dtype=I32)
        return cps <= st["commit"][:, None]

    def _cp_above_gc(self, st):
        """[R, C]: ``last_cp`` in HighestGCedOp+1..commit, wherever a
        checkpoint is sent (CP06:799)."""
        cps = jnp.arange(self.MAX_OPS + 1, dtype=I32)
        return (cps >= self._hgc_all(st)[:, None] + 1) \
            & self._cp_upto_commit(st)

    # -- R-lane guards ----------------------------------------------------
    def guard_timer_send_svc_table(self, st):                   # [R]
        return ((st["aux_svc"] < self.shape.timer_limit)
                & (st["no_prog"] == 0)
                & ~self._normal_primary(st["view"], st["status"],
                                        self._ids)
                & (st["status"] != RECOVERING))

    def guard_send_dvc_table(self, st):                         # [R, C]
        hdr = st["m_hdr"]
        tomb = ((st["m_present"] == 1) & (st["m_count"] == 0)
                & (hdr[:, H_TYPE] == M_SVC))[:, None] \
            & (hdr[:, H_DEST, None] == self._ids) \
            & (hdr[:, H_VIEW, None] == st["view"])              # [M, R]
        rep = ((st["no_prog"] == 0) & (st["status"] == VIEWCHANGE)
               & (st["sent_dvc"] == 0) & (tomb.sum(0) >= self.R // 2))
        return rep[:, None] & self._cp_above_gc(st)

    def guard_send_sv_table(self, st):                          # [R]
        return ((st["no_prog"] == 0) & (st["status"] == VIEWCHANGE)
                & (st["sent_sv"] == 0)
                & ((st["dvc"] == 1).sum(-1) >= self.R // 2 + 1))

    def guard_receive_client_request_table(self, st):           # [R, V]
        rep = (st["no_prog"] == 0) \
            & self._normal_primary(st["view"], st["status"], self._ids)
        return rep[:, None] & (st["aux_acked"] == 0)

    def guard_execute_op_table(self, st):                       # [R]
        opn = st["commit"] + 1
        committed = (st["peer_op"] >= opn[:, None]).sum(-1) >= self.R // 2
        return ((st["no_prog"] == 0)
                & self._normal_primary(st["view"], st["status"], self._ids)
                & (st["commit"] < st["op"]) & committed)

    def guard_crash_table(self, st):                            # [R, C]
        # SendOnce: the record lane (i, cp) would send is
        # _row(M_GETCP, dest=ANYDEST, src=i + 1) whatever cp, so only
        # the source varies
        same = self._sent_once(st, self._row(M_GETCP, dest=ANYDEST),
                               (H_SRC,))
        sent = (same[:, None]
                & (st["m_hdr"][:, H_SRC, None] == self._ids)).any(0)
        return ((st["aux_restart"] < self.crash_limit)
                & ~sent[:, None] & self._cp_upto_commit(st))

    def guard_complete_recovery_table(self, st):                # [R]
        pres = st["rec"] == 1                                   # [R, R]
        vmax = jnp.where(pres, st["rec_view"], -1).max(-1)
        cand = pres & (st["rec_has_log"] == 1) \
            & (st["rec_view"] == vmax[:, None])
        return ((st["status"] == RECOVERING)
                & (pres.sum(-1) > self.R // 2) & cand.any(-1))

    def guard_no_progress_change_table(self, st):               # [1 << R]
        bits = (np.arange(1 << self.R)[:, None] >> np.arange(self.R)) & 1
        return ((st["np_ctr"] < self.shape.np_limit)
                & jnp.asarray(bits.sum(-1) <= self.R // 2))

    # -- M-lane guards: message k at its dest ------------------------------
    def guard_receive_higher_svc_table(self, st):
        at = self._at_dest(st)
        return (self._recv_guard(st, ..., M_SVC) & (at("no_prog") == 0)
                & (st["m_hdr"][:, H_VIEW] > at("view"))
                & (at("status") != RECOVERING))

    def guard_receive_matching_svc_table(self, st):
        at = self._at_dest(st)
        return (self._recv_guard(st, ..., M_SVC) & (at("no_prog") == 0)
                & (at("status") == VIEWCHANGE)
                & (st["m_hdr"][:, H_VIEW] == at("view"))
                & (at("sent_dvc") == 0))

    def guard_receive_higher_dvc_table(self, st):
        at = self._at_dest(st)
        return (self._recv_guard(st, ..., M_DVC) & (at("no_prog") == 0)
                & (st["m_hdr"][:, H_VIEW] > at("view"))
                & (at("status") != RECOVERING))

    def guard_receive_matching_dvc_table(self, st):
        at = self._at_dest(st)
        return (self._recv_guard(st, ..., M_DVC) & (at("no_prog") == 0)
                & (at("status") == VIEWCHANGE)
                & (st["m_hdr"][:, H_VIEW] == at("view")))

    def guard_receive_sv_table(self, st):
        at = self._at_dest(st)
        hv = st["m_hdr"][:, H_VIEW]
        return (self._recv_guard(st, ..., M_SV) & (at("no_prog") == 0)
                & (((hv == at("view")) & (at("status") == VIEWCHANGE))
                   | (hv > at("view")))
                & (at("status") != RECOVERING))

    def guard_receive_prepare_table(self, st):
        at = self._at_dest(st)
        hdr = st["m_hdr"]
        return (self._recv_guard(st, ..., M_PREPARE)
                & (at("no_prog") == 0)
                & ~self._normal_primary(at("view"), at("status"),
                                        hdr[:, H_DEST])
                & (at("status") == NORMAL)
                & (hdr[:, H_VIEW] == at("view"))
                & (hdr[:, H_OP] == at("op") + 1))

    def guard_receive_prepare_ok_table(self, st):
        at = self._at_dest(st)
        hdr = st["m_hdr"]
        src_i = jnp.clip(hdr[:, H_SRC] - 1, 0, self.R - 1)
        from_src = src_i[:, None] == jnp.arange(self.R, dtype=I32)
        peer_op = jnp.where(from_src, at("peer_op"), 0).sum(-1)
        return (self._recv_guard(st, ..., M_PREPAREOK)
                & (at("no_prog") == 0)
                & self._normal_primary(at("view"), at("status"),
                                       hdr[:, H_DEST])
                & (hdr[:, H_VIEW] == at("view"))
                & (hdr[:, H_OP] > peer_op))

    def guard_send_get_state_table(self, st):
        # SendOnce: lane k would send _get_state_row(st, k, i) =
        # _row(M_GETSTATE, view=the Prepare's, op=commit[i],
        # dest=ANYDEST, src=i + 1), so view, op and source vary
        at = self._at_dest(st)
        hdr = st["m_hdr"]
        en = (self._recv_guard(st, ..., M_PREPARE) & (at("no_prog") == 0)
              & ~self._normal_primary(at("view"), at("status"),
                                      hdr[:, H_DEST])
              & (at("status") == NORMAL)
              & (hdr[:, H_VIEW] > at("view"))
              & (hdr[:, H_OP] > at("op") + 1))
        same = self._sent_once(st, self._row(M_GETSTATE, dest=ANYDEST),
                               (H_VIEW, H_OP, H_SRC))           # [k']
        src = jnp.clip(hdr[:, H_DEST] - 1, 0, self.R - 1) + 1
        hit = (same & (hdr[:, H_VIEW] == hdr[:, H_VIEW, None])
               & (hdr[:, H_OP] == at("commit")[:, None])
               & (hdr[:, H_SRC] == src[:, None]))               # [k, k']
        return en & ~hit.any(-1)

    def guard_receive_new_state_table(self, st):
        at = self._at_dest(st)
        return (self._recv_guard(st, ..., M_NEWSTATE)
                & (at("no_prog") == 0) & (at("status") == STATETRANSFER)
                & (at("view") == st["m_hdr"][:, H_VIEW]))

    def guard_receive_new_checkpoint_table(self, st):
        at = self._at_dest(st)
        return (self._recv_guard(st, ..., M_NEWCP) & (at("no_prog") == 0)
                & (at("status") == RECOVERING))

    def guard_receive_recovery_response_table(self, st):
        at = self._at_dest(st)
        return (self._recv_guard(st, ..., M_RECOVERYRESP)
                & (at("rec_number") == st["m_hdr"][:, H_X])
                & (at("status") == RECOVERING))

    def guard_receive_recovery_table(self, st):                 # [M, C]
        at = self._at_dest(st)
        hdr = st["m_hdr"]
        base = self._recv_guard(st, ..., M_RECOVERY) \
            & (at("status") == NORMAL)
        prim = self._normal_primary(at("view"), at("status"),
                                    hdr[:, H_DEST])
        gced = (at("op") > hdr[:, H_OP]) \
            & self._noop_at(at("log"), hdr[:, H_OP])
        # the primary behind a GC'd position replies with a checkpoint
        # (the cp lanes of its window); anyone else on lane cp == 0
        cps = jnp.arange(self.MAX_OPS + 1, dtype=I32)
        return base[:, None] & jnp.where((prim & gced)[:, None],
                                         at(self._cp_above_gc(st)),
                                         cps == 0)

    # -- [M, R, last_cp] guards: message k, receiving replica i ------------
    def guard_receive_get_state_table(self, st):
        hdr = st["m_hdr"]
        rep = (st["no_prog"] == 0) & (st["status"] == NORMAL)   # [R]
        base = (self._recv_guard(st, ..., M_GETSTATE)[:, None]
                & self._addressed(st) & rep
                & (st["view"] == hdr[:, H_VIEW, None])
                & (st["op"] > hdr[:, H_OP, None]))              # [M, R]
        # branch select: GC'd at m.op + 1 -> checkpoint reply (the cp
        # lanes of the window), else log-suffix reply (lane cp == 0)
        gced = self._noop_at(st["log"], hdr[:, H_OP, None])     # [M, R]
        cps = jnp.arange(self.MAX_OPS + 1, dtype=I32)
        return base[:, :, None] & jnp.where(gced[:, :, None],
                                            self._cp_above_gc(st), cps == 0)

    def guard_receive_get_checkpoint_table(self, st):
        rep = (st["no_prog"] == 0) & (st["status"] != RECOVERING)
        pair = (self._recv_guard(st, ..., M_GETCP)[:, None]
                & self._addressed(st) & rep)                    # [M, R]
        return pair[:, :, None] & self._cp_upto_commit(st)

    guard_timer_send_svc = lanes_of(guard_timer_send_svc_table)
    guard_receive_higher_svc = lanes_of(guard_receive_higher_svc_table)
    guard_receive_matching_svc = lanes_of(
        guard_receive_matching_svc_table)
    guard_send_dvc = lanes_of(guard_send_dvc_table)
    guard_receive_higher_dvc = lanes_of(guard_receive_higher_dvc_table)
    guard_receive_matching_dvc = lanes_of(
        guard_receive_matching_dvc_table)
    guard_send_sv = lanes_of(guard_send_sv_table)
    guard_receive_sv = lanes_of(guard_receive_sv_table)
    guard_receive_client_request = lanes_of(
        guard_receive_client_request_table)
    guard_receive_prepare = lanes_of(guard_receive_prepare_table)
    guard_receive_prepare_ok = lanes_of(guard_receive_prepare_ok_table)
    guard_execute_op = lanes_of(guard_execute_op_table)
    guard_send_get_state = lanes_of(guard_send_get_state_table)
    guard_receive_get_state = lanes_of(guard_receive_get_state_table)
    guard_receive_new_state = lanes_of(guard_receive_new_state_table)
    guard_crash = lanes_of(guard_crash_table)
    guard_receive_get_checkpoint = lanes_of(
        guard_receive_get_checkpoint_table)
    guard_receive_new_checkpoint = lanes_of(
        guard_receive_new_checkpoint_table)
    guard_receive_recovery = lanes_of(guard_receive_recovery_table)
    guard_receive_recovery_response = lanes_of(
        guard_receive_recovery_response_table)
    guard_complete_recovery = lanes_of(guard_complete_recovery_table)
    guard_no_progress_change = lanes_of(guard_no_progress_change_table)

    # ------------------------------------------------------------------
    # action table
    # ------------------------------------------------------------------
    def _guard_fns(self):
        return [
            self.guard_timer_send_svc, self.guard_receive_higher_svc,
            self.guard_receive_matching_svc, self.guard_send_dvc,
            self.guard_receive_higher_dvc, self.guard_receive_matching_dvc,
            self.guard_send_sv, self.guard_receive_sv,
            self.guard_receive_client_request, self.guard_receive_prepare,
            self.guard_receive_prepare_ok, self.guard_execute_op,
            self.guard_send_get_state, self.guard_receive_get_state,
            self.guard_receive_new_state, self.guard_crash,
            self.guard_receive_get_checkpoint,
            self.guard_receive_new_checkpoint,
            self.guard_receive_recovery,
            self.guard_receive_recovery_response,
            self.guard_complete_recovery, self.guard_no_progress_change,
        ]

    def _action_fns(self):
        return [
            self.act_timer_send_svc, self.act_receive_higher_svc,
            self.act_receive_matching_svc, self.act_send_dvc,
            self.act_receive_higher_dvc, self.act_receive_matching_dvc,
            self.act_send_sv, self.act_receive_sv,
            self.act_receive_client_request, self.act_receive_prepare,
            self.act_receive_prepare_ok, self.act_execute_op,
            self.act_send_get_state, self.act_receive_get_state,
            self.act_receive_new_state, self.act_crash,
            self.act_receive_get_checkpoint,
            self.act_receive_new_checkpoint, self.act_receive_recovery,
            self.act_receive_recovery_response,
            self.act_complete_recovery, self.act_no_progress_change,
        ]

    # ------------------------------------------------------------------
    # invariants
    # ------------------------------------------------------------------
    def inv_commit_matches_app_state(self, st):
        # CP06:1279-1281 — trivially preserved by the layout invariant
        # Len(app) == commit, but check the planes honestly: app is
        # nonzero exactly below commit
        pos = jnp.arange(self.MAX_OPS, dtype=I32)
        filled = st["app"] != 0                               # [R, P]
        want = pos[None, :] < st["commit"][:, None]
        return (filled == want).all()

    def _op_of(self, st):
        """OpOf (CP06:1219-1222): a NoOp (GC'd) log slot defers to the
        app-state entry.  The inherited raw-log invariants are WRONG
        for CP06 — a recovered/checkpointed replica's log prefix is
        NoOps while its app state carries the real operations (device
        falsely flagged NoLogDivergence on such states; the engine's
        loud-fail divergence check caught it at gid 1446 of the small
        fixpoint config)."""
        return jnp.where(st["log"] == self.NOOP, st["app"], st["log"])

    def _replica_has_op(self, st):
        # ReplicaHasOp (CP06:1244-1246) goes through OpOf, so a value
        # surviving only in app state after log GC still counts
        v_ids = jnp.arange(1, self.V + 1, dtype=I32)
        op_of = self._op_of(st)
        return (op_of[:, :, None] == v_ids[None, None, :]).any(axis=1)

    def inv_no_log_divergence(self, st):
        # CP06:1224-1231: both-committed ops compared through OpOf
        pos = jnp.arange(self.MAX_OPS, dtype=I32)
        comm = pos[None, :] < st["commit"][:, None]          # [R, P]
        op_of = self._op_of(st)
        diff = op_of[:, None, :] != op_of[None, :, :]
        both = comm[:, None, :] & comm[None, :, :]
        return ~(both & diff).any()

    def inv_no_app_state_divergence(self, st):
        # CP06:1234-1240: pairwise app divergence on both-committed
        # ops, OR any committed app entry equal to NoLogEntry ("would
        # indicate a bug in the spec" — r1=r2 makes the \E catch it)
        pos = jnp.arange(self.MAX_OPS, dtype=I32)
        comm = pos[None, :] < st["commit"][:, None]          # [R, P]
        app_diff = st["app"][:, None, :] != st["app"][None, :, :]
        both = comm[:, None, :] & comm[None, :, :]
        pair_viol = (both & app_diff).any()
        noop_viol = ((st["app"] == self.NOOP) & comm).any()
        return ~(pair_viol | noop_viol)

    INVARIANT_FNS = dict(
        RR05Kernel.INVARIANT_FNS,
        CommitNumberMatchesAppState="inv_commit_matches_app_state")

    def lane_replica(self, name, st, lane):
        C = self.MAX_OPS + 1
        if name in ("SendDVC", "Crash"):
            return lane // C
        if name == "CompleteRecovery":
            return lane
        if name in ("ReceiveGetState", "ReceiveGetCheckpointMsg"):
            return (lane % (self.R * C)) // C
        if name == "ReceiveRecoveryMsg":
            return jnp.clip(st["m_hdr"][lane // C, H_DEST] - 1, 0,
                            self.R - 1)
        if name in ("ReceiveNewCheckpointMsg",
                    "ReceiveRecoveryResponseMsg"):
            return jnp.clip(st["m_hdr"][lane, H_DEST] - 1, 0, self.R - 1)
        return super().lane_replica(name, st, lane)
