"""jit+vmap transition kernel for VR_STATE_TRANSFER (ST03).

One XLA program per action x lane enumerating the existentials of
ST03's 16-action Next (ST03:779-797); same engine interface as
vsr_kernel.VSRKernel (guards/actions/step_all/fingerprint*/invariants).

ST03-specific kernel mechanics:

* Quorums count count-0 bag tombstones directly (SendDVC ST03:595-600,
  SendSV ValidDvc ST03:669-674) — vectorized sums over the slot table.
* ``SendAsReceived`` (ST03:186-187): bag insert with delivery count 0
  (the new primary's own DVC); SendFunc's upsert arm still +1s an
  existing record (ST03:164-168).
* ``HighestLog``'s CHOOSE (ST03:676-686) picks the maximal
  (last_normal_vn, op_number) DVC; ties are broken the way the
  interpreter's deterministic CHOOSE does — minimum ``value_key`` of
  the message record, which for equal-view/dest/lnv/op candidates
  reduces to lexicographic (commit_number, log, source).
* ``AnyDest`` receive (ST03:213-218): ReceiveGetState lanes are
  (slot x receiving replica) pairs since the destination is
  nondeterministic.
* ``NoProgressChange`` (ST03:764-776) enumerates ``SUBSET replicas``
  masked to minority subsets: one lane per bitmask.  It mutates the
  whole no_progress plane, so no_progress/no_progress_ctr live in a
  separate "global" hash row that the incremental fingerprint always
  recomputes (they are INSIDE the VIEW projection, ST03:97).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from .guard_tables import at_dest, lanes_of, replica_ids
from .st03 import (ANYDEST, ERR_BAG_OVERFLOW, M_DVC, M_GETSTATE,
                   M_NEWSTATE, M_PREPARE, M_PREPAREOK, M_SV, M_SVC,
                   NORMAL, STATETRANSFER, VIEWCHANGE, ST03Codec)
from .vsr import (H_COMMIT, H_DEST, H_FIRST, H_LNV, H_OP, H_SRC, H_TYPE,
                  H_VIEW, H_X)

I32 = jnp.int32
INF = np.int32(0x7FFFFFFF)

ACTION_NAMES = (
    "TimerSendSVC", "ReceiveHigherSVC", "ReceiveMatchingSVC", "SendDVC",
    "ReceiveHigherDVC", "ReceiveMatchingDVC", "SendSV", "ReceiveSV",
    "ReceiveClientRequest", "ReceivePrepareMsg", "ReceivePrepareOkMsg",
    "ExecuteOp", "SendGetState", "ReceiveGetState", "ReceiveNewState",
    "NoProgressChange",
)

# Replica-state planes, fixed order for hashing
REP_KEYS = ("status", "view", "op", "commit", "lnv", "log", "peer_op",
            "sent_dvc", "sent_sv")
# Hashed global planes (inside VIEW but not per-replica-row shaped)
GLOBAL_KEYS = ("no_prog", "np_ctr")
MSG_KEYS = ("m_present", "m_count", "m_hdr", "m_entry", "m_log")
AUX_KEYS = ("aux_svc", "aux_acked", "err")


def _lex_less(a, b):
    """Lexicographic a < b over trailing axis (small fixed width)."""
    less = jnp.asarray(False)
    eq = jnp.asarray(True)
    for c in range(a.shape[0]):
        less = less | (eq & (a[c] < b[c]))
        eq = eq & (a[c] == b[c])
    return less


class ST03Kernel:
    action_names = ACTION_NAMES
    # ST03's own line range of each action, as the action functions
    # cite them: the location a native spec prints for a counterexample
    # step (read off this class alone, never off a subclass)
    ACTION_LINES = {
        "TimerSendSVC": (515, 535), "ReceiveHigherSVC": (537, 556),
        "ReceiveMatchingSVC": (558, 575), "SendDVC": (577, 614),
        "ReceiveHigherDVC": (616, 635), "ReceiveMatchingDVC": (637, 654),
        "SendSV": (699, 731), "ReceiveSV": (733, 762),
        "ReceiveClientRequest": (293, 325),
        "ReceivePrepareMsg": (327, 348),
        "ReceivePrepareOkMsg": (350, 374), "ExecuteOp": (377, 405),
        "SendGetState": (407, 447), "ReceiveGetState": (449, 477),
        "ReceiveNewState": (479, 507), "NoProgressChange": (764, 776),
    }
    REP_KEYS = REP_KEYS          # per-replica hashed planes (class attr
                                 # so subclasses can extend the layout)
    MSG_KEYS = MSG_KEYS
    AUX_KEYS = AUX_KEYS
    GLOBAL_KEYS = GLOBAL_KEYS
    # value-id planes a symmetry permutation must remap.  These ARE
    # the family's plane -> orbit table (ISSUE 11): engine/canon.py's
    # orbit_planes derives the device canonicalization table from
    # them (subclasses extend the tuples as their layouts grow), and
    # the packed-entry subclasses keep the ACTION correct by
    # overriding _perm_vals — canon prefers the kernel's _permuted,
    # so the table only names what is touched, never how
    PERM_REP_KEYS = ("log",)
    PERM_MSG_KEYS = ("m_entry", "m_log")
    # bag-row payload pieces -> their slot planes (CP06 adds a second
    # log plane for checkpoints)
    ROW_PLANES = (("entry", "m_entry"), ("log", "m_log"))

    def __init__(self, codec: ST03Codec, perms: np.ndarray = None):
        self.codec = codec
        self.shape = s = codec.shape
        self.R, self.V, self.M = s.R, s.V, s.MAX_MSGS
        self.MAX_OPS = s.MAX_OPS
        self.NHDR = codec.NHDR
        if perms is None:
            perms = np.arange(s.V + 1, dtype=np.int32)[None, :]
        self.perms = np.asarray(perms, dtype=np.int32)

        acts, params = [], []
        for aid, name in enumerate(self.action_names):
            n = self._lane_count(name)
            acts.append(np.full(n, aid, np.int32))
            params.append(np.arange(n, dtype=np.int32))
        self.lane_action = np.concatenate(acts)
        self.lane_param = np.concatenate(params)
        self.n_lanes = int(self.lane_action.size)

        rng = np.random.default_rng(0x57A7E03)
        nrep = 1 + sum(int(np.prod(self._rep_shape(k))) // s.R
                       for k in self.REP_KEYS)
        nmsg = self._nmsg()
        nglob = s.R + 1                          # no_prog plane + ctr

        def keys(n):
            return jnp.asarray(rng.integers(1, 2**32, size=(4, n),
                                            dtype=np.uint64)
                               .astype(np.uint32) | 1)
        self._k_rep = keys(nrep)
        self._k_msg = keys(nmsg)
        self._k_glob = keys(nglob)
        self._seeds = jnp.asarray(
            rng.integers(1, 2**32, size=(4,), dtype=np.uint64)
            .astype(np.uint32))

        self.step_batch = jax.jit(jax.vmap(self.step_all))
        self.fingerprint_batch = jax.jit(jax.vmap(self.fingerprint))

    #: name and reduction of each entry of ``commit_stats(st)``: what
    #: the level program counts over the states it commits (the hook
    #: ``DeviceBFS`` / ``PagedBFS`` read, fused commit)
    COMMIT_STATS = (("state_transfer_states", "sum"), ("bag_slots", "sum"),
                    ("bag_tombstones", "sum"), ("bag_peak", "max"),
                    ("quorum_waiting_states", "sum"),
                    ("svc_quorum_waiting_states", "sum"))

    def commit_stats(self, st):
        """[6] uint32 of one state: whether a replica is in
        StateTransfer, the bag's present slots (twice: summed, and the
        run's peak, what ``max_msgs`` is sized by), those at count
        0, the tombstones the quorum guards scan (ST03:595-600, 703),
        whether a replica waits on a partly filled StartViewChange or
        DoViewChange quorum, and on a StartViewChange quorum alone
        (``_quorum_waiting``: never at R = 3, where one record is the
        quorum)."""
        present = st["m_present"] == 1
        slots = present.sum()
        svc, dvc = self._quorum_waiting(st)
        return jnp.stack([(st["status"] == STATETRANSFER).any(), slots,
                          (present & (st["m_count"] == 0)).sum(),
                          slots, (svc | dvc).any(), svc.any()
                          ]).astype(jnp.uint32)

    def _nmsg(self):
        # hdr + entry + log + count
        return self.NHDR + 1 + self.MAX_OPS + 1

    def _rep_shape(self, k):
        s = self.shape
        return {
            "status": (s.R,), "view": (s.R,), "op": (s.R,),
            "commit": (s.R,), "lnv": (s.R,), "log": (s.R, s.MAX_OPS),
            "peer_op": (s.R, s.R), "sent_dvc": (s.R,), "sent_sv": (s.R,),
        }[k]

    def _lane_count(self, name):
        R, V, M = self.R, self.V, self.M
        return {"TimerSendSVC": R, "SendDVC": R, "SendSV": R,
                "ExecuteOp": R, "ReceiveClientRequest": R * V,
                "ReceiveGetState": M * R,
                "NoProgressChange": 1 << R}.get(name, M)

    # ==================================================================
    # message-bag primitives (ST03:164-218)
    # ==================================================================
    def _row(self, type_, view=0, op=0, commit=0, dest=0, src=0,
             first=0, lnv=0, entry=0, log=None, x=0):
        hdr = jnp.zeros((self.NHDR,), I32)
        for col, v in ((H_TYPE, type_), (H_VIEW, view), (H_OP, op),
                       (H_COMMIT, commit), (H_DEST, dest), (H_SRC, src),
                       (H_FIRST, first), (H_LNV, lnv), (H_X, x)):
            hdr = hdr.at[col].set(jnp.asarray(v, I32))
        return {
            "hdr": hdr,
            "entry": jnp.asarray(entry, I32),
            "log": log if log is not None
            else jnp.zeros((self.MAX_OPS,), I32),
        }

    def _row_eq(self, st, row):
        eq = (st["m_present"] == 1) & (st["m_hdr"] == row["hdr"]).all(-1)
        for rk, plane in self.ROW_PLANES:
            cmp = st[plane] == row[rk]
            eq = eq & (cmp if cmp.ndim == 1 else cmp.all(-1))
        return eq

    def _touch(self, st, idx, pred):
        if "_ts" not in st:
            return st
        st = dict(st)
        n = jnp.clip(st["_tn"], 0, st["_ts"].shape[0] - 1)
        st["_ts"] = jnp.where(pred, st["_ts"].at[n].set(idx), st["_ts"])
        st["_tn"] = st["_tn"] + jnp.where(pred, 1, 0)
        return st

    def _bag_send(self, st, row, pred=None, new_count=1):
        """SendFunc(m, msgs, new_count) (ST03:164-168): +1 if the record
        is already in the domain (tombstones revive), else insert with
        `new_count` pending deliveries (0 = SendAsReceived)."""
        if pred is None:
            pred = jnp.asarray(True)
        eq = self._row_eq(st, row)
        found = eq.any()
        free = st["m_present"] == 0
        idx = jnp.where(found, jnp.argmax(eq), jnp.argmax(free))
        overflow = pred & ~found & ~free.any()
        st = self._touch(st, idx, pred)
        st = dict(st)
        st["m_count"] = st["m_count"].at[idx].add(
            jnp.where(pred & found, 1, 0))
        wr = pred & ~found

        def put(cur, val):
            return jnp.where(wr, cur.at[idx].set(val), cur)
        st["m_present"] = jnp.where(pred, st["m_present"].at[idx].set(1),
                                    st["m_present"])
        st["m_count"] = jnp.where(
            wr, st["m_count"].at[idx].set(new_count), st["m_count"])
        st["m_hdr"] = put(st["m_hdr"], row["hdr"])
        for rk, plane in self.ROW_PLANES:
            st[plane] = put(st[plane], row[rk])
        st["err"] = st["err"] | jnp.where(overflow, ERR_BAG_OVERFLOW, 0)
        return st

    def _bag_discard(self, st, k):
        st = self._touch(st, k, jnp.asarray(True))
        st = dict(st)
        st["m_count"] = st["m_count"].at[k].add(-1)
        return st

    def _broadcast(self, st, row, src):
        for d in range(1, self.R + 1):
            rd = dict(row)
            rd["hdr"] = row["hdr"].at[H_DEST].set(d)
            st = self._bag_send(st, rd, pred=(src != d))
        return st

    # ==================================================================
    # state helpers
    # ==================================================================
    @staticmethod
    def _primary(view, R):
        return 1 + ((view - 1) % R)

    def _is_normal_primary(self, st, i, r):
        return ((self._primary(st["view"][i], self.R) == r)
                & (st["status"][i] == NORMAL))

    def _can_progress(self, st, i):
        return st["no_prog"][i] == 0

    def _reset_sent(self, st, i):
        st["sent_dvc"] = st["sent_dvc"].at[i].set(0)
        st["sent_sv"] = st["sent_sv"].at[i].set(0)
        return st

    def _svc_tombstones(self, st, i):
        """# of processed SVCs for View(r) addressed to r (ST03:595-600)."""
        h = st["m_hdr"]
        return ((st["m_present"] == 1) & (st["m_count"] == 0)
                & (h[:, H_TYPE] == M_SVC) & (h[:, H_DEST] == i + 1)
                & (h[:, H_VIEW] == st["view"][i])).sum()

    def _valid_dvc(self, st, i):
        """[M] ValidDvc(r, m) mask (ST03:669-674)."""
        h = st["m_hdr"]
        return ((st["m_present"] == 1) & (st["m_count"] == 0)
                & (h[:, H_TYPE] == M_DVC) & (h[:, H_DEST] == i + 1)
                & (h[:, H_VIEW] == st["view"][i]))

    # ==================================================================
    # the 16 actions
    # ==================================================================
    def act_timer_send_svc(self, st, lane):       # ST03:515-535
        i = lane
        r = i + 1
        en = ((st["aux_svc"] < self.shape.timer_limit)
              & self._can_progress(st, i)
              & ~self._is_normal_primary(st, i, r))
        new_view = st["view"][i] + 1
        s2 = dict(st)
        s2["view"] = st["view"].at[i].set(new_view)
        s2["status"] = st["status"].at[i].set(VIEWCHANGE)
        s2 = self._reset_sent(s2, i)
        s2["aux_svc"] = st["aux_svc"] + 1
        s2 = self._broadcast(s2, self._row(M_SVC, view=new_view, src=r), r)
        return s2, en

    def act_receive_higher_svc(self, st, lane):   # ST03:537-556
        k = lane
        hdr = st["m_hdr"][k]
        r = hdr[H_DEST]
        i = jnp.clip(r - 1, 0, self.R - 1)
        en = (self._recv_guard(st, k, M_SVC) & self._can_progress(st, i)
              & (hdr[H_VIEW] > st["view"][i]))
        s2 = dict(st)
        s2["view"] = st["view"].at[i].set(hdr[H_VIEW])
        s2["status"] = st["status"].at[i].set(VIEWCHANGE)
        s2 = self._reset_sent(s2, i)
        s2 = self._bag_discard(s2, k)
        s2 = self._broadcast(s2, self._row(M_SVC, view=hdr[H_VIEW], src=r), r)
        return s2, en

    def act_receive_matching_svc(self, st, lane):  # ST03:558-575
        k = lane
        hdr = st["m_hdr"][k]
        i = jnp.clip(hdr[H_DEST] - 1, 0, self.R - 1)
        en = (self._recv_guard(st, k, M_SVC) & self._can_progress(st, i)
              & (st["status"][i] == VIEWCHANGE)
              & (hdr[H_VIEW] == st["view"][i]))
        s2 = self._bag_discard(dict(st), k)
        return s2, en

    def act_send_dvc(self, st, lane):             # ST03:577-614
        i = lane
        r = i + 1
        view = st["view"][i]
        prim = self._primary(view, self.R)
        en = (self._can_progress(st, i)
              & (st["status"][i] == VIEWCHANGE) & (st["sent_dvc"][i] == 0)
              & (self._svc_tombstones(st, i) >= self.R // 2))
        s2 = dict(st)
        s2["sent_dvc"] = st["sent_dvc"].at[i].set(1)
        row = self._row(M_DVC, view=view, op=st["op"][i],
                        commit=st["commit"][i], dest=prim, src=r,
                        lnv=st["lnv"][i], log=st["log"][i])
        # the new primary's own DVC is born processed (SendAsReceived,
        # ST03:610-613); everyone else Sends it for delivery
        s2 = self._bag_send(s2, row,
                            new_count=jnp.where(prim == r, 0, 1))
        return s2, en

    def act_receive_higher_dvc(self, st, lane):   # ST03:616-635
        k = lane
        hdr = st["m_hdr"][k]
        r = hdr[H_DEST]
        i = jnp.clip(r - 1, 0, self.R - 1)
        en = (self._recv_guard(st, k, M_DVC) & self._can_progress(st, i)
              & (hdr[H_VIEW] > st["view"][i]))
        s2 = dict(st)
        s2["view"] = st["view"].at[i].set(hdr[H_VIEW])
        s2["status"] = st["status"].at[i].set(VIEWCHANGE)
        s2 = self._reset_sent(s2, i)
        s2 = self._bag_discard(s2, k)
        s2 = self._broadcast(s2, self._row(M_SVC, view=hdr[H_VIEW], src=r), r)
        return s2, en

    def act_receive_matching_dvc(self, st, lane):  # ST03:637-654
        k = lane
        hdr = st["m_hdr"][k]
        i = jnp.clip(hdr[H_DEST] - 1, 0, self.R - 1)
        en = (self._recv_guard(st, k, M_DVC) & self._can_progress(st, i)
              & (st["status"][i] == VIEWCHANGE)
              & (hdr[H_VIEW] == st["view"][i]))
        s2 = self._bag_discard(dict(st), k)
        return s2, en

    def _highest_log(self, st, i):
        """HighestLog/-OpNumber/-CommitNumber (ST03:676-697): maximal
        (lnv, op) ValidDvc, CHOOSE ties by min value_key = lex
        (commit, log, source); commit maximized independently."""
        valid = self._valid_dvc(st, i)
        h = st["m_hdr"]
        pair = h[:, H_LNV] * I32(self.MAX_OPS + 1) + h[:, H_OP]
        best_pair = jnp.max(jnp.where(valid, pair, -1))
        maximal = valid & (pair == best_pair)
        keys = jnp.concatenate(
            [h[:, H_COMMIT][:, None], st["m_log"],
             h[:, H_SRC][:, None]], axis=1)          # [M, 2+MAX_OPS]
        cand = maximal
        for c in range(keys.shape[1]):
            col = jnp.where(cand, keys[:, c], INF)
            cand = cand & (col == col.min())
        best_k = jnp.argmax(cand)
        new_log = st["m_log"][best_k]
        new_on = h[best_k, H_OP]
        new_cn = jnp.max(jnp.where(valid, h[:, H_COMMIT], -1))
        return new_log, new_on, new_cn

    def act_send_sv(self, st, lane):              # ST03:699-731
        i = lane
        r = i + 1
        view = st["view"][i]
        en = (self._can_progress(st, i)
              & (st["status"][i] == VIEWCHANGE) & (st["sent_sv"][i] == 0)
              & (self._valid_dvc(st, i).sum() >= self.R // 2 + 1))
        new_log, new_on, new_cn = self._highest_log(st, i)
        s2 = dict(st)
        s2["status"] = st["status"].at[i].set(NORMAL)
        s2["log"] = st["log"].at[i].set(new_log)
        s2["op"] = st["op"].at[i].set(new_on)
        s2["peer_op"] = st["peer_op"].at[i].set(0)
        s2["commit"] = st["commit"].at[i].set(new_cn)
        s2["sent_sv"] = st["sent_sv"].at[i].set(1)
        s2["lnv"] = st["lnv"].at[i].set(view)
        row = self._row(M_SV, view=view, op=new_on, commit=new_cn, src=r,
                        log=new_log)
        s2 = self._broadcast(s2, row, r)
        return s2, en

    def act_receive_sv(self, st, lane):           # ST03:733-762
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
        s2["op"] = st["op"].at[i].set(hdr[H_OP])
        s2["commit"] = st["commit"].at[i].set(hdr[H_COMMIT])
        s2["lnv"] = st["lnv"].at[i].set(hdr[H_VIEW])
        s2 = self._reset_sent(s2, i)
        s2 = self._bag_discard(s2, k)
        ok_row = self._row(M_PREPAREOK, view=hdr[H_VIEW], op=hdr[H_OP],
                           dest=self._primary(hdr[H_VIEW], self.R), src=r)
        s2 = self._bag_send(s2, ok_row, pred=old_commit < hdr[H_OP])
        return s2, en

    def act_receive_client_request(self, st, lane):  # ST03:293-325
        i = lane // self.V
        r = i + 1
        vid = lane % self.V + 1
        en = (self._can_progress(st, i)
              & self._is_normal_primary(st, i, r)
              & (st["aux_acked"][vid - 1] == 0))
        opn = st["op"][i] + 1
        s2 = dict(st)
        s2["log"] = st["log"].at[i, jnp.clip(opn - 1, 0, self.MAX_OPS - 1)] \
            .set(vid)
        s2["op"] = st["op"].at[i].set(opn)
        s2["aux_acked"] = st["aux_acked"].at[vid - 1].set(1)
        row = self._row(M_PREPARE, view=st["view"][i], op=opn,
                        commit=st["commit"][i], src=r, entry=vid)
        s2 = self._broadcast(s2, row, r)
        return s2, en

    def act_receive_prepare(self, st, lane):      # ST03:327-348
        k = lane
        hdr = st["m_hdr"][k]
        r = hdr[H_DEST]
        i = jnp.clip(r - 1, 0, self.R - 1)
        en = (self._recv_guard(st, k, M_PREPARE) & self._can_progress(st, i)
              & ~self._is_normal_primary(st, i, r)
              & (st["status"][i] == NORMAL)
              & (hdr[H_VIEW] == st["view"][i])
              & (hdr[H_OP] == st["op"][i] + 1))
        s2 = dict(st)
        s2["log"] = st["log"].at[
            i, jnp.clip(hdr[H_OP] - 1, 0, self.MAX_OPS - 1)] \
            .set(st["m_entry"][k])
        s2["op"] = st["op"].at[i].set(hdr[H_OP])
        s2["commit"] = st["commit"].at[i].set(hdr[H_COMMIT])
        s2 = self._bag_discard(s2, k)
        ok_row = self._row(M_PREPAREOK, view=st["view"][i], op=hdr[H_OP],
                           dest=hdr[H_SRC], src=r)
        s2 = self._bag_send(s2, ok_row)
        return s2, en

    def act_receive_prepare_ok(self, st, lane):   # ST03:350-374
        k = lane
        hdr = st["m_hdr"][k]
        r = hdr[H_DEST]
        i = jnp.clip(r - 1, 0, self.R - 1)
        j = jnp.clip(hdr[H_SRC] - 1, 0, self.R - 1)
        en = (self._recv_guard(st, k, M_PREPAREOK)
              & self._can_progress(st, i)
              & self._is_normal_primary(st, i, r)
              & (hdr[H_VIEW] == st["view"][i])
              & (hdr[H_OP] > st["peer_op"][i, j]))
        s2 = dict(st)
        s2["peer_op"] = st["peer_op"].at[i, j].set(hdr[H_OP])
        s2 = self._bag_discard(s2, k)
        return s2, en

    def act_execute_op(self, st, lane):           # ST03:377-405
        i = lane
        r = i + 1
        opn = st["commit"][i] + 1
        committed = (st["peer_op"][i] >= opn).sum() >= self.R // 2
        en = (self._can_progress(st, i)
              & self._is_normal_primary(st, i, r)
              & (st["commit"][i] < st["op"][i]) & committed)
        vid = st["log"][i, jnp.clip(opn - 1, 0, self.MAX_OPS - 1)]
        s2 = dict(st)
        s2["commit"] = st["commit"].at[i].set(opn)
        s2["aux_acked"] = st["aux_acked"].at[
            jnp.clip(vid - 1, 0, self.V - 1)].set(2)
        return s2, en

    def _get_state_row(self, st, k, i):
        """The GetState record SendGetState would emit (SendOnce
        membership is checked against the parent bag, ST03:440-445)."""
        return self._row(M_GETSTATE, view=st["m_hdr"][k, H_VIEW],
                         op=st["commit"][i], dest=ANYDEST, src=i + 1)

    def act_send_get_state(self, st, lane):       # ST03:407-447
        k = lane
        hdr = st["m_hdr"][k]
        r = hdr[H_DEST]
        i = jnp.clip(r - 1, 0, self.R - 1)
        row = self._get_state_row(st, k, i)
        en = (self._recv_guard(st, k, M_PREPARE) & self._can_progress(st, i)
              & ~self._is_normal_primary(st, i, r)
              & (st["status"][i] == NORMAL)
              & (hdr[H_VIEW] > st["view"][i])
              & (hdr[H_OP] > st["op"][i] + 1)
              & ~self._row_eq(st, row).any())        # SendOnce
        s2 = dict(st)
        s2["status"] = st["status"].at[i].set(STATETRANSFER)
        s2 = self._bag_send(s2, row)
        return s2, en

    def act_receive_get_state(self, st, lane):    # ST03:449-477
        k = lane // self.R
        i = lane % self.R
        r = i + 1
        hdr = st["m_hdr"][k]
        en = ((st["m_present"][k] == 1) & (st["m_count"][k] > 0)
              & (hdr[H_TYPE] == M_GETSTATE)
              & ((hdr[H_DEST] == r)
                 | ((hdr[H_DEST] == ANYDEST) & (hdr[H_SRC] != r)))
              & self._can_progress(st, i)
              & (st["status"][i] == NORMAL)
              & (st["view"][i] == hdr[H_VIEW])
              & (st["op"][i] > hdr[H_OP]))
        # log slice m.op_number+1 .. rep_op_number[r], re-based to 0
        first = hdr[H_OP] + 1
        pos = jnp.arange(self.MAX_OPS, dtype=I32)
        src_pos = jnp.clip(pos + first - 1, 0, self.MAX_OPS - 1)
        n = st["op"][i] - hdr[H_OP]
        slice_log = jnp.where(pos < n, st["log"][i][src_pos], 0)
        s2 = self._bag_discard(dict(st), k)
        row = self._row(M_NEWSTATE, view=st["view"][i], op=st["op"][i],
                        commit=st["commit"][i], first=first,
                        dest=hdr[H_SRC], src=r, log=slice_log)
        s2 = self._bag_send(s2, row)
        return s2, en

    def act_receive_new_state(self, st, lane):    # ST03:479-507
        k = lane
        hdr = st["m_hdr"][k]
        r = hdr[H_DEST]
        i = jnp.clip(r - 1, 0, self.R - 1)
        en = (self._recv_guard(st, k, M_NEWSTATE)
              & self._can_progress(st, i)
              & (st["status"][i] == STATETRANSFER)
              & (hdr[H_VIEW] > st["view"][i]))
        # new log over 1..m.op_number: own prefix below first_op, the
        # message's suffix (stored re-based at 0) from there
        first = hdr[H_FIRST]
        pos = jnp.arange(self.MAX_OPS, dtype=I32)       # 0-based
        suffix = st["m_log"][k][jnp.clip(pos - (first - 1), 0,
                                         self.MAX_OPS - 1)]
        new_log = jnp.where(pos < first - 1, st["log"][i],
                            jnp.where(pos < hdr[H_OP], suffix, 0))
        s2 = dict(st)
        s2["status"] = st["status"].at[i].set(NORMAL)
        s2["view"] = st["view"].at[i].set(hdr[H_VIEW])
        s2["lnv"] = st["lnv"].at[i].set(hdr[H_VIEW])
        s2["log"] = st["log"].at[i].set(new_log)
        s2["op"] = st["op"].at[i].set(hdr[H_OP])
        s2["commit"] = st["commit"].at[i].set(hdr[H_COMMIT])
        s2 = self._bag_discard(s2, k)
        return s2, en

    def act_no_progress_change(self, st, lane):   # ST03:764-776
        bits = (lane >> jnp.arange(self.R, dtype=I32)) & 1
        en = ((st["np_ctr"] < self.shape.np_limit)
              & (bits.sum() <= self.R // 2))
        s2 = dict(st)
        s2["no_prog"] = bits.astype(I32)
        s2["np_ctr"] = st["np_ctr"] + 1
        return s2, en

    # ==================================================================
    # guards: one table a state (stage 1 of the level program), under
    # the rules that stand above `CP06Kernel`'s tables.  A subclass
    # that overrides a guard a lane (`super().guard_x(st, k) & ...`)
    # reads the inherited half off the table and keeps its own
    # conjunct a lane.
    # ==================================================================
    def _recv_guard(self, st, k, mtype):
        return ((st["m_present"][k] == 1) & (st["m_count"][k] > 0)
                & (st["m_hdr"][k, H_TYPE] == mtype))

    def _dest_i(self, st, k):
        return jnp.clip(st["m_hdr"][k, H_DEST] - 1, 0, self.R - 1)

    _at_dest = at_dest

    _ids = property(replica_ids)

    def _addressed(self, st):
        """[M, R]: message k may be received by replica i + 1 — named
        dest, or AnyDest and not its source (ST03:213-218)."""
        hdr = st["m_hdr"]
        dest, src = hdr[:, H_DEST, None], hdr[:, H_SRC, None]
        ids = self._ids
        return (dest == ids) | ((dest == ANYDEST) & (src != ids))

    def _normal_primary(self, view, status, r):
        return (self._primary(view, self.R) == r) & (status == NORMAL)

    def _sent_once(self, st, tmpl, vary):
        """[M]: the bag's slots (tombstones too) equal to record `tmpl`
        in every plane of `ROW_PLANES` and every header column but
        `vary`: SendOnce's membership test, all of it that does not
        depend on the sender."""
        free = np.zeros((self.NHDR,), bool)
        free[list(vary)] = True
        same = (st["m_present"] == 1) \
            & ((st["m_hdr"] == tmpl["hdr"]) | free).all(-1)
        for rk, plane in self.ROW_PLANES:
            cmp = st[plane] == tmpl[rk]
            same = same & (cmp if cmp.ndim == 1 else cmp.all(-1))
        return same

    def _processed(self, st, mtype):
        """[R]: the count-0 records of `mtype` addressed to each
        replica in its own view (`_svc_tombstones`, `_valid_dvc`)."""
        hdr = st["m_hdr"]
        mine = ((st["m_present"] == 1) & (st["m_count"] == 0)
                & (hdr[:, H_TYPE] == mtype))[:, None] \
            & (hdr[:, H_DEST, None] == self._ids) \
            & (hdr[:, H_VIEW, None] == st["view"])              # [M, R]
        return mine.sum(0)

    def _svc_quorum(self, st):
        """([R], need): the StartViewChanges each replica has processed
        in its view and the f of them SendDVC needs (ST03:595-600; the
        sender is implicit).  What the guard and the counters read."""
        return self._processed(st, M_SVC), self.R // 2

    def _dvc_quorum(self, st):
        """([R], need): the DoViewChanges each replica has processed in
        its view, its own among them, and the f + 1 SendSV needs
        (ST03:669-674, 703)."""
        return self._processed(st, M_DVC), self.R // 2 + 1

    def _quorum_waiting(self, st):
        """([R], [R]): replica i waits on its StartViewChange /
        DoViewChange quorum — in ViewChange with the quorum's send
        still to make, at least one record counted toward it and fewer
        than it needs.  At R = 3 SendDVC needs one record, so the first
        half is False by construction."""
        def waits(sent, quorum):
            counted, need = quorum
            return ((st["status"] == VIEWCHANGE) & (st[sent] == 0)
                    & (counted > 0) & (counted < need))
        return (waits("sent_dvc", self._svc_quorum(st)),
                waits("sent_sv", self._dvc_quorum(st)))

    # -- R-lane guards ----------------------------------------------------
    def guard_timer_send_svc_table(self, st):                   # [R]
        return ((st["aux_svc"] < self.shape.timer_limit)
                & (st["no_prog"] == 0)
                & ~self._normal_primary(st["view"], st["status"],
                                        self._ids))

    def guard_send_dvc_table(self, st):                         # [R]
        counted, need = self._svc_quorum(st)
        return ((st["no_prog"] == 0) & (st["status"] == VIEWCHANGE)
                & (st["sent_dvc"] == 0) & (counted >= need))

    def guard_send_sv_table(self, st):                          # [R]
        counted, need = self._dvc_quorum(st)
        return ((st["no_prog"] == 0) & (st["status"] == VIEWCHANGE)
                & (st["sent_sv"] == 0) & (counted >= need))

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

    def guard_no_progress_change_table(self, st):               # [1 << R]
        bits = (np.arange(1 << self.R)[:, None] >> np.arange(self.R)) & 1
        return ((st["np_ctr"] < self.shape.np_limit)
                & jnp.asarray(bits.sum(-1) <= self.R // 2))

    # -- M-lane guards: message k at its dest ------------------------------
    def guard_receive_higher_svc_table(self, st):
        at = self._at_dest(st)
        return (self._recv_guard(st, ..., M_SVC) & (at("no_prog") == 0)
                & (st["m_hdr"][:, H_VIEW] > at("view")))

    def guard_receive_matching_svc_table(self, st):
        at = self._at_dest(st)
        return (self._recv_guard(st, ..., M_SVC) & (at("no_prog") == 0)
                & (at("status") == VIEWCHANGE)
                & (st["m_hdr"][:, H_VIEW] == at("view")))

    def guard_receive_higher_dvc_table(self, st):
        at = self._at_dest(st)
        return (self._recv_guard(st, ..., M_DVC) & (at("no_prog") == 0)
                & (st["m_hdr"][:, H_VIEW] > at("view")))

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
                   | (hv > at("view"))))

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
                & (st["m_hdr"][:, H_VIEW] > at("view")))

    # -- [M, R]: message k, receiving replica i (AnyDest) ------------------
    def guard_receive_get_state_table(self, st):
        hdr = st["m_hdr"]
        rep = (st["no_prog"] == 0) & (st["status"] == NORMAL)   # [R]
        return (self._recv_guard(st, ..., M_GETSTATE)[:, None]
                & self._addressed(st) & rep
                & (st["view"] == hdr[:, H_VIEW, None])
                & (st["op"] > hdr[:, H_OP, None]))

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
    guard_no_progress_change = lanes_of(guard_no_progress_change_table)

    def _guard_fns(self):
        return [
            self.guard_timer_send_svc, self.guard_receive_higher_svc,
            self.guard_receive_matching_svc, self.guard_send_dvc,
            self.guard_receive_higher_dvc, self.guard_receive_matching_dvc,
            self.guard_send_sv, self.guard_receive_sv,
            self.guard_receive_client_request, self.guard_receive_prepare,
            self.guard_receive_prepare_ok, self.guard_execute_op,
            self.guard_send_get_state, self.guard_receive_get_state,
            self.guard_receive_new_state, self.guard_no_progress_change,
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
            self.act_receive_new_state, self.act_no_progress_change,
        ]

    def lane_replica(self, name, st, lane):
        """The one replica whose row a lane's action can mutate.
        NoProgressChange touches no per-replica hashed plane (no_prog is
        in the global row), so any fixed index is correct."""
        if name in ("TimerSendSVC", "SendDVC", "SendSV", "ExecuteOp"):
            return lane
        if name == "NoProgressChange":
            return jnp.zeros((), I32)
        if name == "ReceiveClientRequest":
            return lane // self.V
        if name == "ReceiveGetState":
            return lane % self.R
        if name == "SendGetState":
            k = lane
        else:
            k = lane
        return jnp.clip(st["m_hdr"][k, H_DEST] - 1, 0, self.R - 1)

    def seed_touch(self, st):
        st = dict(st)
        st["_ts"] = jnp.full((self.R + 1,), -1, I32)
        st["_tn"] = jnp.asarray(0, I32)
        return st

    def step_all(self, st):
        st = {k: jnp.asarray(v, I32) for k, v in st.items()}
        parts, ens = [], []
        for name, fn in zip(self.action_names, self._action_fns()):
            lanes = jnp.arange(self._lane_count(name), dtype=I32)
            succ, en = jax.vmap(fn, in_axes=(None, 0))(st, lanes)
            parts.append(succ)
            ens.append(en)
        succs = {k: jnp.concatenate([p[k] for p in parts], axis=0)
                 for k in st if not k.startswith("_")}
        return succs, jnp.concatenate(ens)

    # ==================================================================
    # fingerprinting: VIEW projection (ST03:97 — includes no_prog_vars,
    # excludes aux_vars) -> symmetry-least 128-bit hash
    # ==================================================================
    @staticmethod
    def _mix32(x):
        x = jnp.asarray(x, jnp.uint32)
        x = x ^ (x >> 16)
        x = x * jnp.uint32(0x85EBCA6B)
        x = x ^ (x >> 13)
        x = x * jnp.uint32(0xC2B2AE35)
        x = x ^ (x >> 16)
        return x

    def _perm_vals(self, arr, perm):
        """Apply a value-id permutation to a packed-entry array (ST03
        entries ARE value ids; subclasses with packed multi-field
        entries override)."""
        return perm[arr]

    def _permuted(self, st, perm):
        st = dict(st)
        for k in self.PERM_REP_KEYS:
            st[k] = self._perm_vals(st[k], perm)
        for k in self.PERM_MSG_KEYS:
            st[k] = self._perm_vals(st[k], perm)
        return st

    def _rep_rows(self, st):
        R = self.R
        cols = [jnp.arange(R, dtype=jnp.uint32)[:, None]]
        for k in self.REP_KEYS:
            cols.append(jnp.asarray(st[k], jnp.uint32).reshape(R, -1))
        return jnp.concatenate(cols, axis=1)

    def _rep_hashes(self, st):
        rows = self._rep_rows(st)
        return self._mix32((rows[:, None, :] * self._k_rep[None]).sum(axis=2)
                           + self._seeds[None, :])

    def _slot_rows(self, st):
        # AnyDest (-1) casts to 0xFFFFFFFF — distinct from every id
        cols = [jnp.asarray(st["m_hdr"], jnp.uint32)]
        for _rk, plane in self.ROW_PLANES:
            v = jnp.asarray(st[plane], jnp.uint32)
            cols.append(v[:, None] if v.ndim == 1 else v)
        cols.append(jnp.asarray(st["m_count"], jnp.uint32)[:, None])
        return jnp.concatenate(cols, axis=1)

    def _slot_hashes(self, st):
        rows = self._slot_rows(st)
        return self._mix32((rows[:, None, :] * self._k_msg[None]).sum(axis=2)
                           + self._seeds[None, :])

    def _glob_hash(self, st):
        row = jnp.concatenate(
            [jnp.asarray(st["no_prog"], jnp.uint32),
             jnp.asarray(st["np_ctr"], jnp.uint32)[None]])
        return self._mix32((row[None, :] * self._k_glob).sum(axis=1)
                           + self._seeds)

    def _fp_one(self, st, perm):
        st = self._permuted(st, perm)
        h_rep = self._rep_hashes(st).sum(axis=0)
        pres = jnp.asarray(st["m_present"], jnp.uint32)[:, None]
        h_msg = (self._slot_hashes(st) * pres).sum(axis=0)
        return self._mix32(self._mix32(h_rep + h_msg + self._glob_hash(st))
                           + self._seeds)

    @staticmethod
    def _lex_min4(fps):
        best = fps[0]
        for p in range(1, fps.shape[0]):
            a, b = fps[p], best
            less = ((a[0] < b[0])
                    | ((a[0] == b[0]) & (a[1] < b[1]))
                    | ((a[0] == b[0]) & (a[1] == b[1]) & (a[2] < b[2]))
                    | ((a[0] == b[0]) & (a[1] == b[1]) & (a[2] == b[2])
                       & (a[3] < b[3])))
            best = jnp.where(less, a, best)
        return best

    def fingerprint(self, st):
        st = {k: jnp.asarray(v) for k, v in st.items()}
        fps = jax.vmap(lambda p: self._fp_one(st, p))(jnp.asarray(self.perms))
        return self._lex_min4(fps)

    # -- incremental fingerprinting ------------------------------------
    def parent_parts(self, st):
        """Per-permutation (rep [P,R,4], slot [P,M,4], total [P,4]);
        total EXCLUDES the global row (recomputed per successor)."""
        def parts_one(perm):
            stp = self._permuted(st, perm)
            rep = self._rep_hashes(stp)
            slot = self._slot_hashes(stp)
            pres = jnp.asarray(stp["m_present"], jnp.uint32)[:, None]
            total = rep.sum(axis=0) + (slot * pres).sum(axis=0)
            return rep, slot, total
        return jax.vmap(parts_one)(jnp.asarray(self.perms))

    def _rep_row_one(self, st, i, perm):
        cols = [jnp.asarray(i, jnp.uint32)[None]]
        for k in self.REP_KEYS:
            v = st[k][i]
            if k in self.PERM_REP_KEYS:
                v = self._perm_vals(v, perm)
            cols.append(jnp.asarray(v, jnp.uint32).reshape(-1))
        return jnp.concatenate(cols)

    def _slot_row_one(self, st, m, perm):
        cols = [jnp.asarray(st["m_hdr"][m], jnp.uint32)]
        for _rk, plane in self.ROW_PLANES:
            v = st[plane][m]
            if plane in self.PERM_MSG_KEYS:
                v = self._perm_vals(v, perm)
            v = jnp.asarray(v, jnp.uint32)
            cols.append(v[None] if v.ndim == 0 else v)
        cols.append(jnp.asarray(st["m_count"][m], jnp.uint32)[None])
        return jnp.concatenate(cols)

    def fingerprint_incremental(self, succ, ri, parts, parent):
        rep_h, slot_h, total = parts
        i = ri
        ts = succ["_ts"]
        perms = jnp.asarray(self.perms)
        p_pres = jnp.asarray(parent["m_present"], jnp.uint32)
        s_pres = jnp.asarray(succ["m_present"], jnp.uint32)
        glob = self._glob_hash(succ)        # perm-independent

        def fp_p(p):
            perm = perms[p]
            d = total[p] - rep_h[p, i]
            row = self._rep_row_one(succ, i, perm)
            d = d + self._mix32((row[None, :] * self._k_rep).sum(axis=1)
                                + self._seeds)
            for t in range(ts.shape[0]):
                s = ts[t]
                ok = s >= 0
                sc = jnp.clip(s, 0, self.M - 1)
                d = d - jnp.where(ok, slot_h[p, sc] * p_pres[sc], 0)
                new_row = self._slot_row_one(succ, sc, perm)
                new_h = self._mix32(
                    (new_row[None, :] * self._k_msg).sum(axis=1)
                    + self._seeds)
                d = d + jnp.where(ok, new_h * s_pres[sc], 0)
            return self._mix32(self._mix32(d + glob) + self._seeds)

        fps = jax.vmap(fp_p)(jnp.arange(self.perms.shape[0]))
        return self._lex_min4(fps)

    # ==================================================================
    # invariants (ST03:804-850), vectorized
    # ==================================================================
    def _replica_has_op(self, st):
        v_ids = jnp.arange(1, self.V + 1, dtype=I32)
        return (st["log"][:, :, None] == v_ids[None, None, :]).any(axis=1)

    def inv_no_log_divergence(self, st):
        # the REAL r1-vs-r2, commit-gated divergence check (ST03:805-811)
        pos = jnp.arange(self.MAX_OPS, dtype=I32)
        comm = pos[None, :] < st["commit"][:, None]          # [R, P]
        diff = st["log"][:, None, :] != st["log"][None, :, :]
        both = comm[:, None, :] & comm[None, :, :]
        return ~(both & diff).any()

    def inv_acknowledged_write_not_lost(self, st):
        acked = st["aux_acked"] == 2
        has = self._replica_has_op(st).any(axis=0)
        return (~acked | has).all()

    def inv_acknowledged_writes_exist_on_majority(self, st):
        acked = st["aux_acked"] == 2
        n_has = self._replica_has_op(st).sum(axis=0)
        return (~acked | (n_has >= self.R // 2 + 1)).all()

    def inv_commit_never_higher_than_op(self, st):
        return (st["commit"] <= st["op"]).all()

    def inv_test(self, st):
        return jnp.asarray(True)

    def pred_all_replicas_same_view(self, st):
        # AllReplicasMoveToSameView (ST03:884-898) incl. the
        # BlockedOnLastViewChange shield (ST03:877-881)
        r_ids = jnp.arange(1, self.R + 1, dtype=I32)
        prim_of = self._primary(st["view"], self.R)          # [R]
        prim_count = (prim_of[None, :] == r_ids[:, None]).sum(axis=1)
        blocked = ((st["aux_svc"] == self.shape.timer_limit)
                   & ((st["no_prog"] == 1)
                      & (prim_count > self.R // 2)).any())
        prog = st["no_prog"] == 0
        vmax = jnp.max(jnp.where(prog, st["view"], -1))
        ok = ((~prog | (st["view"] == vmax)).all()
              & (~prog | (st["status"] == NORMAL)).all())
        return blocked | ok

    def hunt_score(self, st):
        """Defect-proximity score for guided simulation (same shape as
        VSRKernel.hunt_score; ST03 is the *fixed* protocol, so this
        mostly demonstrates absence under guidance)."""
        acked = st["aux_acked"] == 2
        has = self._replica_has_op(st)
        missing = (~has).sum(axis=0)
        worst = jnp.max(jnp.where(acked, missing, -1))
        return jnp.where(acked.any(), 1 + worst, 0).astype(I32)

    INVARIANT_FNS = {
        "NoLogDivergence": "inv_no_log_divergence",
        "AcknowledgedWriteNotLost": "inv_acknowledged_write_not_lost",
        "AcknowledgedWritesExistOnMajority":
            "inv_acknowledged_writes_exist_on_majority",
        "CommitNumberNeverHigherThanOpNumber":
            "inv_commit_never_higher_than_op",
        "TestInv": "inv_test",
        "AllReplicasMoveToSameView": "pred_all_replicas_same_view",
    }

    def invariant_fn(self, names):
        fns = [getattr(self, self.INVARIANT_FNS[n]) for n in names]

        def check(st):
            ok = jnp.asarray(True)
            for f in fns:
                ok = ok & f(st)
            return ok
        return check
