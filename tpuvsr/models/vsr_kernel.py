"""jit+vmap transition kernel for VSR (reference: VSR.tla:366-918).

This is the TPU replacement for TLC's ``Tool.getNextStates`` (SURVEY.md
§2.5, §3.1): one XLA program that, given a dense state (vsr.py layout),
evaluates *every* action x bound-variable combination as one SIMD lane
and returns the stacked successor states plus an enabled mask.  The BFS
and simulation engines vmap it over a frontier batch.

Lane plan (one lane = one ``\\E`` binding of one action; VSR.tla Next
disjunct order at VSR.tla:896-918):

  action                          lanes     bound vars
  TimerSendSVC                    R         r            (VSR.tla:578)
  ReceiveHigherSVC                M         m (r=m.dest) (VSR.tla:602)
  ReceiveMatchingSVC              M         m            (VSR.tla:625)
  SendDVC                         R         r            (VSR.tla:648)
  ReceiveHigherDVC                M         m            (VSR.tla:677)
  ReceiveMatchingDVC              M         m            (VSR.tla:696)
  SendSV                          R         r            (VSR.tla:735)
  ReceiveSV                       M         m            (VSR.tla:773)
  ReceiveClientRequest            R*V       r, v (C=1)   (VSR.tla:366)
  ReceivePrepareMsg               M         m            (VSR.tla:405)
  ReceivePrepareOkMsg             M         m            (VSR.tla:437)
  ExecuteOp                       R         r            (VSR.tla:462)
  SendGetState                    M*R       m, rDest     (VSR.tla:496)
  ReceiveGetState                 M         m            (VSR.tla:526)
  ReceiveNewState                 M         m            (VSR.tla:551)
  RestartEmpty                    R         r            (VSR.tla:813)
  ReceivesRecoveryMsg             M         m            (VSR.tla:842)
  ReceivesRecoveryResponseMsg     M         m            (VSR.tla:864)
  CompleteRecovery                R         r            (VSR.tla:878)

Semantic fine print honored here (SURVEY.md §2.7):

* Bag upsert/discard/tombstones: SendFunc/DiscardFunc (VSR.tla:228-245)
  keep delivered messages in the domain at count 0; ``SendOnce`` fails on
  a tombstone (VSR.tla:250-252) — ``m_present`` vs ``m_count`` columns.
* Deterministic CHOOSE: the interpreter picks the value_key-least element
  satisfying the predicate (core/values.py).  The kernel reproduces the
  induced order for the record sets it choses over: records compare by
  field name alphabetically, so DVC records order by (commit_number,
  dest, last_normal_vn, log, op_number, source, ...) and recovery
  responses by (commit_number, dest, log, op_number, source, ...), with
  logs comparing entry-wise by (client_id, operation, request_number,
  view_number) and shorter-prefix-first — see _entry_sort_key/_lex_less.
* The dead ``m.commit`` arm of ReceivePrepareMsg (VSR.tla:421) is
  unreachable for C = 1 (enforced by the layout), so the kernel only
  implements the client's own arm.
* Unused array slots are kept all-zero (canonical-zero invariant) so
  whole-array equality and flat hashing are content-exact.

Also here: the fingerprint kernel (VIEW projection -> symmetry-least
128-bit hash; VSR.tla:149-151) and device invariant kernels for the VSR
property set (VSR.tla:926-952).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from .guard_tables import at_dest, lanes_of, replica_ids
from .vsr import (E_OPER, E_REQ, ERR_BAG_OVERFLOW,
                  ERR_DVC_OVERFLOW, ERR_REC_OVERFLOW, H_COMMIT, H_DEST,
                  H_FIRST, H_LNV, H_OP, H_SRC, H_TYPE, H_VIEW, H_X,
                  M_DVC, M_GETSTATE, M_NEWSTATE, M_PREPARE, M_PREPAREOK,
                  M_RECOVERY, M_RECOVERYRESP, M_SV, M_SVC, NENT,
                  NORMAL, RECOVERING, T_EXEC, T_OP, T_REQ, VIEWCHANGE,
                  VSRCodec, entry_sort_key)

I32 = jnp.int32
INF = np.int32(0x7FFFFFFF)

ACTION_NAMES = (
    "TimerSendSVC", "ReceiveHigherSVC", "ReceiveMatchingSVC", "SendDVC",
    "ReceiveHigherDVC", "ReceiveMatchingDVC", "SendSV", "ReceiveSV",
    "ReceiveClientRequest", "ReceivePrepareMsg", "ReceivePrepareOkMsg",
    "ExecuteOp", "SendGetState", "ReceiveGetState", "ReceiveNewState",
    "RestartEmpty", "ReceivesRecoveryMsg", "ReceivesRecoveryResponseMsg",
    "CompleteRecovery",
)

# Replica-state array keys, in a fixed order used for hashing/stacking.
REP_KEYS = ("status", "view", "op", "commit", "lnv", "log", "log_len",
            "peer_op", "ct", "svc", "dvc", "dvc_lnv", "dvc_op",
            "dvc_commit", "dvc_log", "dvc_log_len", "sent_dvc", "sent_sv",
            "rec_number", "rec", "rec_view", "rec_has_log", "rec_log",
            "rec_log_len", "rec_op", "rec_commit")
MSG_KEYS = ("m_present", "m_count", "m_hdr", "m_entry", "m_log",
            "m_log_len", "m_has_log")
AUX_KEYS = ("aux_svc", "aux_restart", "aux_acked", "err")
ALL_KEYS = REP_KEYS + MSG_KEYS + AUX_KEYS
# the planes of one DoViewChange record of rep_dvc_recv (vsr.py layout)
DVC_KEYS = ("dvc", "dvc_lnv", "dvc_op", "dvc_commit", "dvc_log",
            "dvc_log_len")


def _lex_less(a, b):
    """Lexicographic < on two equal-length int vectors."""
    ne = a != b
    first = jnp.argmax(ne)
    return ne.any() & (a[first] < b[first])


class VSRKernel:
    action_names = ACTION_NAMES
    # layout key tables as class attributes: the speclint drift pass
    # (analysis/passes/drift.py) checks them against codec.zero_state
    REP_KEYS = REP_KEYS
    MSG_KEYS = MSG_KEYS
    AUX_KEYS = AUX_KEYS
    # plane -> orbit table (ISSUE 11): which planes a symmetry value
    # permutation touches, and how — value ids live in the operation
    # column of every log-entry row (_permuted applies exactly this;
    # engine/canon.py and the speclint symmetry pass both consume the
    # table via canon.orbit_planes, so lint and kernel cannot drift)
    SYM_PLANES = {"log": ("col", E_OPER), "dvc_log": ("col", E_OPER),
                  "rec_log": ("col", E_OPER), "m_log": ("col", E_OPER),
                  "m_entry": ("col", E_OPER)}

    def __init__(self, codec: VSRCodec, perms: np.ndarray = None):
        self.codec = codec
        self.shape = s = codec.shape
        self.R, self.V, self.M = s.R, s.V, s.MAX_MSGS
        self.MAX_OPS = s.MAX_OPS
        self.NHDR = codec.NHDR
        # value-id permutation table for symmetry canonicalization
        # ([P, V+1], row 0 of each perm maps padding 0 -> 0)
        if perms is None:
            perms = np.arange(s.V + 1, dtype=np.int32)[None, :]
        self.perms = np.asarray(perms, dtype=np.int32)

        # lane -> (action_id, param) tables (host-side metadata)
        acts, params = [], []
        for aid, name in enumerate(ACTION_NAMES):
            n = self._lane_count(name)
            acts.append(np.full(n, aid, np.int32))
            params.append(np.arange(n, dtype=np.int32))
        self.lane_action = np.concatenate(acts)
        self.lane_param = np.concatenate(params)
        self.n_lanes = int(self.lane_action.size)

        # deterministic hash coefficients (4 x 32-bit lanes = 128-bit fp).
        # The fingerprint is decomposable (SURVEY.md §7.3.8 incremental
        # hashing): fp = mix(mix(sum_r rep_row_hash(r)
        #                        + sum_m present_m * slot_hash(m)) + seed),
        # so a transition that touches one replica row and a few message
        # slots updates the sums in O(touched) — the expand pass exploits
        # this; the full recompute path must produce identical values.
        rng = np.random.default_rng(0xC0FFEE)
        nrep = 1 + sum(int(np.prod(self._rep_shape(k))) // s.R
                       for k in REP_KEYS)      # replica id + per-r slices
        nmsg = self.NHDR + NENT + self.MAX_OPS * NENT + 3
        self._k_rep = jnp.asarray(
            rng.integers(1, 2**32, size=(4, nrep), dtype=np.uint64)
            .astype(np.uint32) | 1)
        self._k_msg = jnp.asarray(
            rng.integers(1, 2**32, size=(4, nmsg), dtype=np.uint64)
            .astype(np.uint32) | 1)
        self._seeds = jnp.asarray(
            rng.integers(1, 2**32, size=(4,), dtype=np.uint64)
            .astype(np.uint32))

        self.step_batch = jax.jit(jax.vmap(self.step_all))
        self.fingerprint_batch = jax.jit(jax.vmap(self.fingerprint))

    @property
    def K(self):
        """Records of the DVC receive-set a (dest, source); the planes
        carry a K axis only where K > 1 (vsr.py layout)."""
        return self.shape.DVC_SLOTS

    @property
    def commit_stats(self):
        """What the level program counts over the states it commits
        (``COMMIT_STATS``), or None: only a shape that can restart
        carries the counters, so every other program is the one it
        was."""
        return self._commit_stats if self.shape.restart_limit else None

    #: name and reduction of each entry of ``commit_stats(st)``
    COMMIT_STATS = (("recovering_states", "sum"), ("dvc_set_peak", "max"))

    def _commit_stats(self, st):
        """[2] uint32 of one state: whether a replica is Recovering,
        and the most DVC records one (dest, source) pair holds."""
        held = (st["dvc"] == 1).reshape(self.R, self.R, -1).sum(-1)
        return jnp.stack([(st["status"] == RECOVERING).any(),
                          held.max()]).astype(jnp.uint32)

    def _rep_shape(self, k):
        s = self.shape
        if k in DVC_KEYS and self.K > 1:
            tail = (s.MAX_OPS, NENT) if k == "dvc_log" else ()
            return (s.R, s.R, self.K) + tail
        return {
            "status": (s.R,), "view": (s.R,), "op": (s.R,), "commit": (s.R,),
            "lnv": (s.R,), "log": (s.R, s.MAX_OPS, NENT), "log_len": (s.R,),
            "peer_op": (s.R, s.R), "ct": (s.R, s.C, 3), "svc": (s.R, s.R),
            "dvc": (s.R, s.R), "dvc_lnv": (s.R, s.R), "dvc_op": (s.R, s.R),
            "dvc_commit": (s.R, s.R),
            "dvc_log": (s.R, s.R, s.MAX_OPS, NENT),
            "dvc_log_len": (s.R, s.R), "sent_dvc": (s.R,), "sent_sv": (s.R,),
            "rec_number": (s.R,), "rec": (s.R, s.R), "rec_view": (s.R, s.R),
            "rec_has_log": (s.R, s.R), "rec_log": (s.R, s.R, s.MAX_OPS, NENT),
            "rec_log_len": (s.R, s.R), "rec_op": (s.R, s.R),
            "rec_commit": (s.R, s.R),
        }[k]

    def _lane_count(self, name):
        R, V, M = self.R, self.V, self.M
        return {"TimerSendSVC": R, "SendDVC": R, "SendSV": R, "ExecuteOp": R,
                "RestartEmpty": R, "CompleteRecovery": R,
                "ReceiveClientRequest": R * V, "SendGetState": M * R,
                }.get(name, M)

    # ==================================================================
    # message-bag primitives (VSR.tla:228-275)
    # ==================================================================
    def _row(self, type_, view=0, op=0, commit=0, dest=0, src=0, x=0,
             first=0, lnv=0, entry=None, log=None, log_len=0, has_log=0):
        z = jnp.zeros
        hdr = z((self.NHDR,), I32).at[:9].set(
            jnp.stack([jnp.asarray(v, I32) for v in
                       (type_, view, op, commit, dest, src, x, first,
                        lnv)]))
        return {
            "hdr": hdr,
            "entry": entry if entry is not None else z((NENT,), I32),
            "log": log if log is not None else z((self.MAX_OPS, NENT), I32),
            "log_len": jnp.asarray(log_len, I32),
            "has_log": jnp.asarray(has_log, I32),
        }

    def _row_eq(self, st, row):
        """[M] mask: domain entry equal to row (full record equality)."""
        return ((st["m_present"] == 1)
                & (st["m_hdr"] == row["hdr"]).all(-1)
                & (st["m_entry"] == row["entry"]).all(-1)
                & (st["m_log"] == row["log"]).all((-1, -2))
                & (st["m_log_len"] == row["log_len"])
                & (st["m_has_log"] == row["has_log"]))

    def _touch(self, st, idx, pred):
        """Record a touched message slot for incremental fingerprinting
        (no-op unless the caller seeded the "_ts" scratch keys)."""
        if "_ts" not in st:
            return st
        st = dict(st)
        n = jnp.clip(st["_tn"], 0, st["_ts"].shape[0] - 1)
        st["_ts"] = jnp.where(pred, st["_ts"].at[n].set(idx), st["_ts"])
        st["_tn"] = st["_tn"] + jnp.where(pred, 1, 0)
        return st

    def _bag_send(self, st, row, pred=None):
        """SendFunc upsert (VSR.tla:228-231): +1 if present (tombstones
        revive), else insert at the first free slot with count 1."""
        if pred is None:
            pred = jnp.asarray(True)
        eq = self._row_eq(st, row)
        found = eq.any()
        free = st["m_present"] == 0
        idx = jnp.where(found, jnp.argmax(eq), jnp.argmax(free))
        overflow = pred & ~found & ~free.any()
        st = self._touch(st, idx, pred)
        st = dict(st)
        st["m_count"] = st["m_count"].at[idx].add(jnp.where(pred, 1, 0))
        wr = pred & ~found

        def put(cur, val):
            return jnp.where(wr, cur.at[idx].set(val), cur)
        st["m_present"] = jnp.where(pred, st["m_present"].at[idx].set(1),
                                    st["m_present"])
        st["m_hdr"] = put(st["m_hdr"], row["hdr"])
        st["m_entry"] = put(st["m_entry"], row["entry"])
        st["m_log"] = put(st["m_log"], row["log"])
        st["m_log_len"] = put(st["m_log_len"], row["log_len"])
        st["m_has_log"] = put(st["m_has_log"], row["has_log"])
        st["err"] = st["err"] | jnp.where(overflow, ERR_BAG_OVERFLOW, 0)
        return st

    def _bag_send_once(self, st, row):
        """SendOnce (VSR.tla:250-252): guard fails if the record is in the
        domain at all — a count-0 tombstone blocks the resend."""
        ok = ~self._row_eq(st, row).any()
        return self._bag_send(st, row), ok

    def _bag_discard(self, st, k):
        st = self._touch(st, k, jnp.asarray(True))
        st = dict(st)
        st["m_count"] = st["m_count"].at[k].add(-1)
        return st

    def _broadcast(self, st, row, src):
        """BroadcastFunc (VSR.tla:233-240): upsert [msg EXCEPT !.dest = d]
        for every d != src.  Sequential upserts are equivalent because the
        per-destination records are distinct."""
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

    def _is_primary(self, st, i, r):
        return self._primary(st["view"][i], self.R) == r

    def _clear_vc(self, st, i, svc=True, dvc=True):
        """ResetRecvMsgs (VSR.tla:299-301) with canonical-zero payloads."""
        if svc:
            st["svc"] = st["svc"].at[i].set(0)
        if dvc:
            st["dvc"] = st["dvc"].at[i].set(0)
            st["dvc_lnv"] = st["dvc_lnv"].at[i].set(0)
            st["dvc_op"] = st["dvc_op"].at[i].set(0)
            st["dvc_commit"] = st["dvc_commit"].at[i].set(0)
            st["dvc_log"] = st["dvc_log"].at[i].set(0)
            st["dvc_log_len"] = st["dvc_log_len"].at[i].set(0)
        return st

    def _clear_rec(self, st, i):
        st["rec"] = st["rec"].at[i].set(0)
        st["rec_view"] = st["rec_view"].at[i].set(0)
        st["rec_has_log"] = st["rec_has_log"].at[i].set(0)
        st["rec_log"] = st["rec_log"].at[i].set(0)
        st["rec_log_len"] = st["rec_log_len"].at[i].set(0)
        st["rec_op"] = st["rec_op"].at[i].set(0)
        st["rec_commit"] = st["rec_commit"].at[i].set(0)
        return st

    def _reset_sent(self, st, i):
        st["sent_dvc"] = st["sent_dvc"].at[i].set(0)
        st["sent_sv"] = st["sent_sv"].at[i].set(0)
        return st

    @staticmethod
    def _entry_sort_key(rows):
        """value_key order of a log entry record: fields compare
        alphabetically (client_id, operation, request_number, view_number).
        Packed big-endian into one int32; all-zero padding rows -> 0."""
        return entry_sort_key(rows)

    def _log_sort_key(self, log_rows):
        """[..., MAX_OPS] per-position keys; prefix-padding with 0 makes a
        shorter log order before any extension, matching FnVal item-tuple
        comparison (core/values.py value_key)."""
        return self._entry_sort_key(log_rows)

    # -- the DVC receive-set where K > 1 (vsr.py layout) ----------------
    def _dvc_sorted(self, g):
        """DVC planes (a dict over DVC_KEYS, ``[..., K]`` with any
        leading pair axes) with every pair's K slots in canonical
        order: the records first, by ascending (commit_number,
        last_normal_vn, log, op_number) — the value_key order of DVC
        records that share view, dest and source — then the free
        slots, which are all-zero and equal.  Two records of one set
        differ in that key (a log's length is the number of its
        non-zero entry keys), so the order is a function of the set
        alone.  Elementwise throughout (no gather, no scatter): the
        canon stage runs it for every image of every generated
        state."""
        K = self.K
        keys = jnp.concatenate(
            [g["dvc_commit"][..., None], g["dvc_lnv"][..., None],
             self._log_sort_key(g["dvc_log"]), g["dvc_op"][..., None]],
            axis=-1)                                     # [..., K, L]
        keys = jnp.where((g["dvc"] == 1)[..., None], keys, INF)
        # slot a against slot b > a, lexicographically
        less, same = {}, {}
        for a in range(K):
            for b in range(a + 1, K):
                lt = jnp.zeros(keys.shape[:-2], bool)
                eq = jnp.ones(keys.shape[:-2], bool)
                for col in range(keys.shape[-1]):
                    x, y = keys[..., a, col], keys[..., b, col]
                    lt = lt | (eq & (x < y))
                    eq = eq & (x == y)
                less[a, b], same[a, b] = lt, eq
        # rank of a slot: how many order before it (free slots tie,
        # and keep their order)
        rank = []
        for a in range(K):
            n = jnp.zeros(keys.shape[:-2], I32)
            for b in range(K):
                if b < a:
                    n = n + (less[b, a] | same[b, a])
                elif b > a:
                    n = n + (~less[a, b] & ~same[a, b])
            rank.append(n)
        rank = jnp.stack(rank, axis=-1)                  # [..., K]
        to = rank[..., None, :] == jnp.arange(K, dtype=I32)[:, None]

        def place(v):
            tail = v.ndim - rank.ndim
            sel = to.reshape(to.shape + (1,) * tail)     # [..., t, a, 1..]
            return jnp.where(sel, jnp.expand_dims(v, rank.ndim - 1),
                             0).sum(rank.ndim)
        return {k: place(v) for k, v in g.items()}

    def _dvc_insert(self, st, i, j, rec, pred):
        """``rep_dvc_recv[i] \\union {rec}`` for a record of source j
        where `pred`: a record that is there already changes nothing,
        another takes a free slot and the pair is put in order again.
        Returns (st, overflow): a new record that finds no free slot
        is not stored, and the caller raises ERR_DVC_OVERFLOW."""
        g = {k: st[k][i, j] for k in DVC_KEYS}
        there = ((g["dvc"] == 1)
                 & (g["dvc_lnv"] == rec["dvc_lnv"])
                 & (g["dvc_op"] == rec["dvc_op"])
                 & (g["dvc_commit"] == rec["dvc_commit"])
                 & (g["dvc_log_len"] == rec["dvc_log_len"])
                 & (g["dvc_log"] == rec["dvc_log"]).all((-1, -2))).any()
        free = g["dvc"] == 0
        new = pred & ~there
        add = new & free.any()
        slot = jnp.argmax(free)
        g = self._dvc_sorted(
            {k: jnp.where(add, v.at[slot].set(rec[k]), v)
             for k, v in g.items()})
        st = dict(st)
        for k in DVC_KEYS:
            st[k] = st[k].at[i, j].set(g[k])
        return st, new & ~free.any()

    # ==================================================================
    # the 19 actions.  Each takes (st, lane) and returns (succ, enabled);
    # successors are computed totally and masked by the engine.
    # ==================================================================
    def act_timer_send_svc(self, st, lane):       # VSR.tla:578-590
        i = lane
        r = i + 1
        en = ((st["aux_svc"] < self.shape.timer_limit)
              & ~self._is_primary(st, i, r))
        new_view = st["view"][i] + 1
        s2 = dict(st)
        s2["view"] = st["view"].at[i].set(new_view)
        s2["status"] = st["status"].at[i].set(VIEWCHANGE)
        s2 = self._clear_vc(s2, i)
        s2 = self._reset_sent(s2, i)
        s2["aux_svc"] = st["aux_svc"] + 1
        s2 = self._broadcast(s2, self._row(M_SVC, view=new_view, src=r), r)
        return s2, en

    def act_receive_higher_svc(self, st, lane):   # VSR.tla:602-613
        k = lane
        hdr = st["m_hdr"][k]
        r = hdr[H_DEST]
        i = jnp.clip(r - 1, 0, self.R - 1)
        en = ((st["m_present"][k] == 1) & (st["m_count"][k] > 0)
              & (hdr[H_TYPE] == M_SVC) & (hdr[H_VIEW] > st["view"][i]))
        s2 = dict(st)
        s2["view"] = st["view"].at[i].set(hdr[H_VIEW])
        s2["status"] = st["status"].at[i].set(VIEWCHANGE)
        s2 = self._clear_vc(s2, i)
        s2["svc"] = s2["svc"].at[i, jnp.clip(hdr[H_SRC] - 1, 0, self.R - 1)].set(1)
        s2 = self._reset_sent(s2, i)
        s2 = self._bag_discard(s2, k)
        s2 = self._broadcast(s2, self._row(M_SVC, view=hdr[H_VIEW], src=r), r)
        return s2, en

    def act_receive_matching_svc(self, st, lane):  # VSR.tla:625-634
        k = lane
        hdr = st["m_hdr"][k]
        r = hdr[H_DEST]
        i = jnp.clip(r - 1, 0, self.R - 1)
        en = ((st["m_present"][k] == 1) & (st["m_count"][k] > 0)
              & (hdr[H_TYPE] == M_SVC) & (hdr[H_VIEW] == st["view"][i])
              & (st["status"][i] == VIEWCHANGE))
        s2 = dict(st)
        s2["svc"] = st["svc"].at[i, jnp.clip(hdr[H_SRC] - 1, 0, self.R - 1)].set(1)
        s2 = self._bag_discard(s2, k)
        return s2, en

    def _send_dvc_self_one_slot(self, st, s2, i, self_case):
        """SendDVC's self-delivery where a pair holds one record:
        set-union of an identical record is a no-op, a different one
        does not fit (ERR_DVC_OVERFLOW)."""
        same = ((st["dvc_lnv"][i, i] == st["lnv"][i])
                & (st["dvc_op"][i, i] == st["op"][i])
                & (st["dvc_commit"][i, i] == st["commit"][i])
                & (st["dvc_log_len"][i, i] == st["log_len"][i])
                & (st["dvc_log"][i, i] == st["log"][i]).all())
        collide = self_case & (st["dvc"][i, i] == 1) & ~same
        s2["dvc"] = jnp.where(self_case, s2["dvc"].at[i, i].set(1), s2["dvc"])
        s2["dvc_lnv"] = jnp.where(
            self_case, s2["dvc_lnv"].at[i, i].set(st["lnv"][i]), s2["dvc_lnv"])
        s2["dvc_op"] = jnp.where(
            self_case, s2["dvc_op"].at[i, i].set(st["op"][i]), s2["dvc_op"])
        s2["dvc_commit"] = jnp.where(
            self_case, s2["dvc_commit"].at[i, i].set(st["commit"][i]),
            s2["dvc_commit"])
        s2["dvc_log"] = jnp.where(
            self_case, s2["dvc_log"].at[i, i].set(st["log"][i]), s2["dvc_log"])
        s2["dvc_log_len"] = jnp.where(
            self_case, s2["dvc_log_len"].at[i, i].set(st["log_len"][i]),
            s2["dvc_log_len"])
        s2["err"] = s2["err"] | jnp.where(collide, ERR_DVC_OVERFLOW, 0)
        return s2

    def act_send_dvc(self, st, lane):             # VSR.tla:648-669
        i = lane
        r = i + 1
        view = st["view"][i]
        prim = self._primary(view, self.R)
        en = ((st["status"][i] == VIEWCHANGE) & (st["sent_dvc"][i] == 0)
              & (st["svc"][i].sum() >= self.R // 2))
        s2 = dict(st)
        s2["sent_dvc"] = st["sent_dvc"].at[i].set(1)
        # self-delivery: the new primary registers its own DVC directly
        self_case = prim == r
        if self.K > 1:
            s2, over = self._dvc_insert(s2, i, i, {
                "dvc": jnp.asarray(1, I32), "dvc_lnv": st["lnv"][i],
                "dvc_op": st["op"][i], "dvc_commit": st["commit"][i],
                "dvc_log": st["log"][i], "dvc_log_len": st["log_len"][i]},
                self_case)
            s2["err"] = s2["err"] | jnp.where(over, ERR_DVC_OVERFLOW, 0)
        else:
            s2 = self._send_dvc_self_one_slot(st, s2, i, self_case)
        row = self._row(M_DVC, view=view, op=st["op"][i],
                        commit=st["commit"][i], dest=prim, src=r,
                        lnv=st["lnv"][i], log=st["log"][i],
                        log_len=st["log_len"][i], has_log=1)
        s2 = self._bag_send(s2, row, pred=~self_case)
        return s2, en

    def act_receive_higher_dvc(self, st, lane):   # VSR.tla:677-688
        k = lane
        hdr = st["m_hdr"][k]
        r = hdr[H_DEST]
        i = jnp.clip(r - 1, 0, self.R - 1)
        j = jnp.clip(hdr[H_SRC] - 1, 0, self.R - 1)
        en = ((st["m_present"][k] == 1) & (st["m_count"][k] > 0)
              & (hdr[H_TYPE] == M_DVC) & (hdr[H_VIEW] > st["view"][i]))
        s2 = dict(st)
        s2["view"] = st["view"].at[i].set(hdr[H_VIEW])
        s2["status"] = st["status"].at[i].set(VIEWCHANGE)
        s2 = self._clear_vc(s2, i)
        # the first record of an emptied set: slot 0 of its pair
        at = (i, j, 0) if self.K > 1 else (i, j)
        s2["dvc"] = s2["dvc"].at[at].set(1)
        s2["dvc_lnv"] = s2["dvc_lnv"].at[at].set(hdr[H_LNV])
        s2["dvc_op"] = s2["dvc_op"].at[at].set(hdr[H_OP])
        s2["dvc_commit"] = s2["dvc_commit"].at[at].set(hdr[H_COMMIT])
        s2["dvc_log"] = s2["dvc_log"].at[at].set(st["m_log"][k])
        s2["dvc_log_len"] = s2["dvc_log_len"].at[at].set(st["m_log_len"][k])
        s2 = self._reset_sent(s2, i)
        s2 = self._bag_discard(s2, k)
        s2 = self._broadcast(s2, self._row(M_SVC, view=hdr[H_VIEW], src=r), r)
        return s2, en

    def act_receive_matching_dvc(self, st, lane):  # VSR.tla:696-703
        k = lane
        hdr = st["m_hdr"][k]
        r = hdr[H_DEST]
        i = jnp.clip(r - 1, 0, self.R - 1)
        j = jnp.clip(hdr[H_SRC] - 1, 0, self.R - 1)
        en = ((st["m_present"][k] == 1) & (st["m_count"][k] > 0)
              & (hdr[H_TYPE] == M_DVC) & (hdr[H_VIEW] == st["view"][i]))
        if self.K > 1:
            s2, over = self._dvc_insert(st, i, j, {
                "dvc": jnp.asarray(1, I32), "dvc_lnv": hdr[H_LNV],
                "dvc_op": hdr[H_OP], "dvc_commit": hdr[H_COMMIT],
                "dvc_log": st["m_log"][k],
                "dvc_log_len": st["m_log_len"][k]}, jnp.asarray(True))
            s2["err"] = st["err"] | jnp.where(over & en,
                                              ERR_DVC_OVERFLOW, 0)
            return self._bag_discard(s2, k), en
        # set-union: an identical record already present is a no-op; a
        # *different* DVC from the same source does not fit one slot
        same = ((st["dvc"][i, j] == 1)
                & (st["dvc_lnv"][i, j] == hdr[H_LNV])
                & (st["dvc_op"][i, j] == hdr[H_OP])
                & (st["dvc_commit"][i, j] == hdr[H_COMMIT])
                & (st["dvc_log_len"][i, j] == st["m_log_len"][k])
                & (st["dvc_log"][i, j] == st["m_log"][k]).all())
        collide = (st["dvc"][i, j] == 1) & ~same
        s2 = dict(st)
        s2["dvc"] = st["dvc"].at[i, j].set(1)
        s2["dvc_lnv"] = st["dvc_lnv"].at[i, j].set(hdr[H_LNV])
        s2["dvc_op"] = st["dvc_op"].at[i, j].set(hdr[H_OP])
        s2["dvc_commit"] = st["dvc_commit"].at[i, j].set(hdr[H_COMMIT])
        s2["dvc_log"] = st["dvc_log"].at[i, j].set(st["m_log"][k])
        s2["dvc_log_len"] = st["dvc_log_len"].at[i, j].set(st["m_log_len"][k])
        s2["err"] = st["err"] | jnp.where(collide & en, ERR_DVC_OVERFLOW, 0)
        s2 = self._bag_discard(s2, k)
        return s2, en

    def _highest_log_of_set(self, st, i):
        """SendSV's reading of rep_dvc_recv[r] where a pair holds K
        records: (HighestLog's log, its length, the highest
        commit_number) over every record of the set, as the quorum
        counts every record (the spec's Cardinality)."""
        n = self.R * self.K
        d = {k: st[k][i].reshape((n,) + st[k].shape[3:])
             for k in DVC_KEYS}
        mask = d["dvc"] == 1
        pair = d["dvc_lnv"] * (self.MAX_OPS + 1) + d["dvc_op"]
        maximal = mask & (pair == jnp.max(jnp.where(mask, pair, -1)))
        # CHOOSE among the maximal: value_key order (commit_number,
        # log, source); two records of one source that tie there are
        # one record
        src_ids = jnp.repeat(jnp.arange(1, self.R + 1, dtype=I32), self.K)
        keys = jnp.concatenate(
            [d["dvc_commit"][:, None], self._log_sort_key(d["dvc_log"]),
             src_ids[:, None]], axis=1)
        keys = jnp.where(maximal[:, None], keys, INF)
        best_j = jnp.asarray(0, I32)
        best_key = keys[0]
        for j in range(1, n):
            less = _lex_less(keys[j], best_key)
            best_key = jnp.where(less, keys[j], best_key)
            best_j = jnp.where(less, j, best_j)
        return (d["dvc_log"][best_j], d["dvc_log_len"][best_j],
                jnp.max(jnp.where(mask, d["dvc_commit"], -1)))

    def act_send_sv(self, st, lane):              # VSR.tla:716-758
        i = lane
        r = i + 1
        view = st["view"][i]
        mask = st["dvc"][i] == 1
        en = ((st["status"][i] == VIEWCHANGE) & (st["sent_sv"][i] == 0)
              & (mask.sum() >= self.R // 2 + 1))
        if self.K > 1:
            new_log, new_on, new_cn = self._highest_log_of_set(st, i)
        else:
            # HighestLog (VSR.tla:716-722): maximal by (last_normal_vn,
            # op_number); CHOOSE ties broken by value_key record order
            # (commit, dest=, lnv=, log, op=, source).
            pair = st["dvc_lnv"][i] * (self.MAX_OPS + 1) + st["dvc_op"][i]
            best_pair = jnp.max(jnp.where(mask, pair, -1))
            maximal = mask & (pair == best_pair)
            logk = self._log_sort_key(st["dvc_log"][i])      # [R, MAX_OPS]
            src_ids = jnp.arange(1, self.R + 1, dtype=I32)
            keys = jnp.concatenate(
                [st["dvc_commit"][i][:, None], logk, src_ids[:, None]],
                axis=1)
            keys = jnp.where(maximal[:, None], keys, INF)
            best_j = jnp.asarray(0, I32)
            best_key = keys[0]
            for j in range(1, self.R):
                less = _lex_less(keys[j], best_key)
                best_key = jnp.where(less, keys[j], best_key)
                best_j = jnp.where(less, j, best_j)
            new_log = st["dvc_log"][i, best_j]
            new_on = st["dvc_log_len"][i, best_j]  # HighestOpNumber = Len(log)
            new_cn = jnp.max(jnp.where(mask, st["dvc_commit"][i], -1))
        s2 = dict(st)
        s2["status"] = st["status"].at[i].set(NORMAL)
        s2["log"] = st["log"].at[i].set(new_log)
        s2["log_len"] = st["log_len"].at[i].set(new_on)
        s2["op"] = st["op"].at[i].set(new_on)
        s2["peer_op"] = st["peer_op"].at[i].set(0)
        s2["commit"] = st["commit"].at[i].set(new_cn)
        s2["sent_sv"] = st["sent_sv"].at[i].set(1)
        s2["lnv"] = st["lnv"].at[i].set(view)
        row = self._row(M_SV, view=view, op=new_on, commit=new_cn, src=r,
                        log=new_log, log_len=new_on, has_log=1)
        s2 = self._broadcast(s2, row, r)
        return s2, en

    def act_receive_sv(self, st, lane):           # VSR.tla:773-793
        k = lane
        hdr = st["m_hdr"][k]
        r = hdr[H_DEST]
        i = jnp.clip(r - 1, 0, self.R - 1)
        en = ((st["m_present"][k] == 1) & (st["m_count"][k] > 0)
              & (hdr[H_TYPE] == M_SV) & (hdr[H_VIEW] >= st["view"][i]))
        old_commit = st["commit"][i]
        s2 = dict(st)
        s2["status"] = st["status"].at[i].set(NORMAL)
        s2["view"] = st["view"].at[i].set(hdr[H_VIEW])
        s2["log"] = st["log"].at[i].set(st["m_log"][k])
        s2["log_len"] = st["log_len"].at[i].set(st["m_log_len"][k])
        s2["op"] = st["op"].at[i].set(hdr[H_OP])
        s2["commit"] = st["commit"].at[i].set(hdr[H_COMMIT])
        s2["lnv"] = st["lnv"].at[i].set(hdr[H_VIEW])
        s2 = self._clear_vc(s2, i)
        s2 = self._reset_sent(s2, i)
        s2 = self._bag_discard(s2, k)
        ack = self._row(M_PREPAREOK, view=hdr[H_VIEW], op=hdr[H_OP],
                        dest=self._primary(hdr[H_VIEW], self.R), src=r)
        s2 = self._bag_send(s2, ack, pred=(old_commit < hdr[H_OP]))
        return s2, en

    def act_receive_client_request(self, st, lane):  # VSR.tla:366-394
        i = lane // self.V
        v = lane % self.V + 1          # value id
        r = i + 1
        en = (self._is_primary(st, i, r) & (st["status"][i] == NORMAL)
              & (st["aux_acked"][v - 1] == 0) & (st["ct"][i, 0, T_EXEC] == 1))
        req = st["ct"][i, 0, T_REQ] + 1
        opn = st["log_len"][i] + 1
        entry = jnp.stack([st["view"][i], jnp.asarray(v, I32),
                           jnp.asarray(1, I32), req])
        pos = jnp.clip(st["log_len"][i], 0, self.MAX_OPS - 1)
        s2 = dict(st)
        s2["log"] = st["log"].at[i, pos].set(entry)
        s2["log_len"] = st["log_len"].at[i].set(opn)
        s2["op"] = st["op"].at[i].set(opn)
        s2["ct"] = st["ct"].at[i, 0].set(jnp.stack([req, opn, jnp.asarray(0, I32)]))
        row = self._row(M_PREPARE, view=st["view"][i], op=opn,
                        commit=st["commit"][i], src=r, entry=entry)
        s2 = self._broadcast(s2, row, r)
        s2["aux_acked"] = st["aux_acked"].at[v - 1].set(1)   # v :> FALSE
        return s2, en

    def act_receive_prepare(self, st, lane):      # VSR.tla:405-428
        k = lane
        hdr = st["m_hdr"][k]
        r = hdr[H_DEST]
        i = jnp.clip(r - 1, 0, self.R - 1)
        en = ((st["m_present"][k] == 1) & (st["m_count"][k] > 0)
              & (hdr[H_TYPE] == M_PREPARE) & (st["status"][i] == NORMAL)
              & (hdr[H_VIEW] == st["view"][i])
              & (hdr[H_OP] == st["op"][i] + 1))
        entry = st["m_entry"][k]
        pos = jnp.clip(st["log_len"][i], 0, self.MAX_OPS - 1)
        s2 = dict(st)
        s2["log"] = st["log"].at[i, pos].set(entry)
        s2["log_len"] = st["log_len"].at[i].set(hdr[H_OP])
        s2["op"] = st["op"].at[i].set(hdr[H_OP])
        s2["commit"] = st["commit"].at[i].set(hdr[H_COMMIT])
        # client table: C = 1, message's client arm only (VSR.tla:414-419;
        # the other-client arm is the dead m.commit branch)
        exec_ = (hdr[H_OP] <= hdr[H_COMMIT]).astype(I32)
        s2["ct"] = st["ct"].at[i, 0].set(
            jnp.stack([entry[E_REQ], hdr[H_OP], exec_]))
        s2 = self._bag_discard(s2, k)
        ack = self._row(M_PREPAREOK, view=st["view"][i], op=hdr[H_OP],
                        dest=hdr[H_SRC], src=r)
        s2 = self._bag_send(s2, ack)
        return s2, en

    def act_receive_prepare_ok(self, st, lane):   # VSR.tla:437-447
        k = lane
        hdr = st["m_hdr"][k]
        r = hdr[H_DEST]
        i = jnp.clip(r - 1, 0, self.R - 1)
        j = jnp.clip(hdr[H_SRC] - 1, 0, self.R - 1)
        en = ((st["m_present"][k] == 1) & (st["m_count"][k] > 0)
              & (hdr[H_TYPE] == M_PREPAREOK)
              & self._is_primary(st, i, r) & (st["status"][i] == NORMAL)
              & (hdr[H_VIEW] == st["view"][i])
              & (hdr[H_OP] > st["peer_op"][i, j]))
        s2 = dict(st)
        s2["peer_op"] = st["peer_op"].at[i, j].set(hdr[H_OP])
        s2 = self._bag_discard(s2, k)
        return s2, en

    def act_execute_op(self, st, lane):           # VSR.tla:457-476
        i = lane
        r = i + 1
        opn = st["commit"][i] + 1
        committed = ((st["peer_op"][i] >= opn).sum() >= self.R // 2)
        en = (self._is_primary(st, i, r) & (st["status"][i] == NORMAL)
              & (st["commit"][i] < st["op"][i]) & committed)
        entry = st["log"][i, jnp.clip(opn - 1, 0, self.MAX_OPS - 1)]
        s2 = dict(st)
        s2["commit"] = st["commit"].at[i].set(opn)
        s2["ct"] = st["ct"].at[i, 0, T_EXEC].set(1)
        s2["aux_acked"] = st["aux_acked"].at[
            jnp.clip(entry[E_OPER] - 1, 0, self.V - 1)].set(2)  # v :> TRUE
        return s2, en

    def act_send_get_state(self, st, lane):       # VSR.tla:491-516
        k = lane // self.R
        rdest = lane % self.R + 1
        hdr = st["m_hdr"][k]
        r = hdr[H_DEST]
        i = jnp.clip(r - 1, 0, self.R - 1)
        en = ((st["m_present"][k] == 1) & (st["m_count"][k] > 0)
              & (hdr[H_TYPE] == M_PREPARE)
              & ~self._is_primary(st, i, r) & (r != rdest)
              & (st["status"][i] == NORMAL)
              & (hdr[H_VIEW] > st["view"][i])
              & (hdr[H_OP] > st["op"][i] + 1))
        trunc = jnp.minimum(st["commit"][i], st["log_len"][i])
        keep = jnp.arange(self.MAX_OPS, dtype=I32) < trunc
        s2 = dict(st)
        s2["log"] = st["log"].at[i].set(
            jnp.where(keep[:, None], st["log"][i], 0))
        s2["log_len"] = st["log_len"].at[i].set(trunc)
        s2["op"] = st["op"].at[i].set(trunc)
        s2["view"] = st["view"].at[i].set(hdr[H_VIEW])
        s2["lnv"] = st["lnv"].at[i].set(hdr[H_VIEW])
        row = self._row(M_GETSTATE, view=hdr[H_VIEW], op=trunc,
                        dest=rdest, src=r)
        s2, ok = self._bag_send_once(s2, row)
        return s2, en & ok

    def act_receive_get_state(self, st, lane):    # VSR.tla:526-543
        k = lane
        hdr = st["m_hdr"][k]
        r = hdr[H_DEST]
        i = jnp.clip(r - 1, 0, self.R - 1)
        en = ((st["m_present"][k] == 1) & (st["m_count"][k] > 0)
              & (hdr[H_TYPE] == M_GETSTATE)
              & (st["view"][i] == hdr[H_VIEW]) & (st["status"][i] == NORMAL)
              & (st["op"][i] > hdr[H_OP]))
        # log slice m.op_number+1 .. rep_op_number[r], re-based to row 0
        n = st["op"][i] - hdr[H_OP]
        idx = jnp.arange(self.MAX_OPS, dtype=I32)
        src_pos = jnp.clip(hdr[H_OP] + idx, 0, self.MAX_OPS - 1)
        rows = jnp.where((idx < n)[:, None], st["log"][i][src_pos], 0)
        reply = self._row(M_NEWSTATE, view=st["view"][i], op=st["op"][i],
                          commit=st["commit"][i], first=hdr[H_OP] + 1,
                          dest=hdr[H_SRC], src=r, log=rows,
                          log_len=jnp.clip(n, 0, self.MAX_OPS), has_log=1)
        s2 = self._bag_discard(dict(st), k)
        s2 = self._bag_send(s2, reply)
        return s2, en

    def act_receive_new_state(self, st, lane):    # VSR.tla:551-567
        k = lane
        hdr = st["m_hdr"][k]
        r = hdr[H_DEST]
        i = jnp.clip(r - 1, 0, self.R - 1)
        en = ((st["m_present"][k] == 1) & (st["m_count"][k] > 0)
              & (hdr[H_TYPE] == M_NEWSTATE)
              & (st["view"][i] == hdr[H_VIEW]) & (st["status"][i] == NORMAL)
              & (st["op"][i] == hdr[H_FIRST] - 1))
        own_n = st["op"][i]
        idx = jnp.arange(self.MAX_OPS, dtype=I32)
        from_msg = st["m_log"][k][jnp.clip(idx - own_n, 0, self.MAX_OPS - 1)]
        rows = jnp.where((idx < own_n)[:, None], st["log"][i],
                         jnp.where((idx < hdr[H_OP])[:, None], from_msg, 0))
        s2 = dict(st)
        s2["log"] = st["log"].at[i].set(rows)
        s2["log_len"] = st["log_len"].at[i].set(hdr[H_OP])
        s2["op"] = st["op"].at[i].set(hdr[H_OP])
        s2 = self._bag_discard(s2, k)
        return s2, en

    def act_restart_empty(self, st, lane):        # VSR.tla:802-837
        i = lane
        r = i + 1
        en = st["aux_restart"] < self.shape.restart_limit
        # UniqueNumber: 1 + highest x over RecoveryMsg domain entries
        is_rec = (st["m_present"] == 1) & (st["m_hdr"][:, H_TYPE] == M_RECOVERY)
        unique = jnp.max(jnp.where(is_rec, st["m_hdr"][:, H_X], 0)) + 1
        s2 = dict(st)
        s2["log"] = st["log"].at[i].set(0)
        s2["log_len"] = st["log_len"].at[i].set(0)
        s2["view"] = st["view"].at[i].set(1)
        s2["op"] = st["op"].at[i].set(0)
        s2["commit"] = st["commit"].at[i].set(0)
        s2["peer_op"] = st["peer_op"].at[i].set(0)
        empty_row = jnp.zeros((self.shape.C, 3), I32).at[:, T_EXEC].set(1)
        s2["ct"] = st["ct"].at[i].set(empty_row)
        s2 = self._clear_vc(s2, i)
        s2 = self._reset_sent(s2, i)
        s2["lnv"] = st["lnv"].at[i].set(0)
        s2 = self._clear_rec(s2, i)
        s2["status"] = st["status"].at[i].set(RECOVERING)
        s2["rec_number"] = st["rec_number"].at[i].set(unique)
        s2["aux_restart"] = st["aux_restart"] + 1
        s2 = self._broadcast(s2, self._row(M_RECOVERY, x=unique, src=r), r)
        return s2, en

    def act_receive_recovery(self, st, lane):     # VSR.tla:842-858
        k = lane
        hdr = st["m_hdr"][k]
        r = hdr[H_DEST]
        i = jnp.clip(r - 1, 0, self.R - 1)
        en = ((st["m_present"][k] == 1) & (st["m_count"][k] > 0)
              & (hdr[H_TYPE] == M_RECOVERY) & (st["status"][i] == NORMAL))
        isp = self._is_primary(st, i, r)
        reply = self._row(
            M_RECOVERYRESP, view=st["view"][i], x=hdr[H_X], dest=hdr[H_SRC],
            src=r,
            op=jnp.where(isp, st["op"][i], -1),
            commit=jnp.where(isp, st["commit"][i], -1),
            log=jnp.where(isp, st["log"][i], 0),
            log_len=jnp.where(isp, st["log_len"][i], 0),
            has_log=jnp.where(isp, 1, 0))
        s2 = self._bag_discard(dict(st), k)
        s2 = self._bag_send(s2, reply)
        return s2, en

    def act_receive_recovery_response(self, st, lane):  # VSR.tla:864-872
        k = lane
        hdr = st["m_hdr"][k]
        r = hdr[H_DEST]
        i = jnp.clip(r - 1, 0, self.R - 1)
        j = jnp.clip(hdr[H_SRC] - 1, 0, self.R - 1)
        en = ((st["m_present"][k] == 1) & (st["m_count"][k] > 0)
              & (hdr[H_TYPE] == M_RECOVERYRESP)
              & (st["rec_number"][i] == hdr[H_X])
              & (st["status"][i] == RECOVERING))
        same = ((st["rec"][i, j] == 1)
                & (st["rec_view"][i, j] == hdr[H_VIEW])
                & (st["rec_has_log"][i, j] == st["m_has_log"][k])
                & (st["rec_op"][i, j] == hdr[H_OP])
                & (st["rec_commit"][i, j] == hdr[H_COMMIT])
                & (st["rec_log_len"][i, j] == st["m_log_len"][k])
                & (st["rec_log"][i, j] == st["m_log"][k]).all())
        collide = (st["rec"][i, j] == 1) & ~same
        s2 = dict(st)
        s2["rec"] = st["rec"].at[i, j].set(1)
        s2["rec_view"] = st["rec_view"].at[i, j].set(hdr[H_VIEW])
        s2["rec_has_log"] = st["rec_has_log"].at[i, j].set(st["m_has_log"][k])
        s2["rec_log"] = st["rec_log"].at[i, j].set(st["m_log"][k])
        s2["rec_log_len"] = st["rec_log_len"].at[i, j].set(st["m_log_len"][k])
        s2["rec_op"] = st["rec_op"].at[i, j].set(hdr[H_OP])
        s2["rec_commit"] = st["rec_commit"].at[i, j].set(hdr[H_COMMIT])
        s2["err"] = st["err"] | jnp.where(collide & en, ERR_REC_OVERFLOW, 0)
        s2 = self._bag_discard(s2, k)
        return s2, en

    def act_complete_recovery(self, st, lane):    # VSR.tla:878-894
        i = lane
        cand = (st["rec"][i] == 1) & (st["rec_has_log"][i] == 1)
        en = ((st["status"][i] == RECOVERING)
              & ((st["rec"][i] == 1).sum() > self.R // 2)
              & cand.any())
        # CHOOSE m : m.log # Nil — value_key-least response record:
        # (commit_number, dest=, log, op_number, source, type=, view, x=)
        logk = self._log_sort_key(st["rec_log"][i])
        src_ids = jnp.arange(1, self.R + 1, dtype=I32)
        keys = jnp.concatenate(
            [st["rec_commit"][i][:, None], logk, st["rec_op"][i][:, None],
             src_ids[:, None], st["rec_view"][i][:, None]], axis=1)
        keys = jnp.where(cand[:, None], keys, INF)
        best_j = jnp.asarray(0, I32)
        best_key = keys[0]
        for j in range(1, self.R):
            less = _lex_less(keys[j], best_key)
            best_key = jnp.where(less, keys[j], best_key)
            best_j = jnp.where(less, j, best_j)
        s2 = dict(st)
        s2["status"] = st["status"].at[i].set(NORMAL)
        s2["view"] = st["view"].at[i].set(st["rec_view"][i, best_j])
        s2["lnv"] = st["lnv"].at[i].set(st["rec_view"][i, best_j])
        s2["log"] = st["log"].at[i].set(st["rec_log"][i, best_j])
        s2["log_len"] = st["log_len"].at[i].set(st["rec_log_len"][i, best_j])
        s2["op"] = st["op"].at[i].set(st["rec_op"][i, best_j])
        s2["commit"] = st["commit"].at[i].set(st["rec_commit"][i, best_j])
        s2 = self._clear_rec(s2, i)
        return s2, en

    # ==================================================================
    # guard-only evaluation (stage 1 of the level program): one table a
    # state
    #
    # Each ``guard_x_table(st)`` is the `en` conjunction of its action
    # for ALL the action's lanes, shaped like the lane decode ([R],
    # [R, V], [M], [M, R]) and built from the state's planes whole, so
    # the engine evaluates enabledness over the full [T, n_lanes] lane
    # space at a few percent of the cost of building successors and
    # expands only the enabled lanes.  The engines call
    # ``guard_x(st, lane)``, the table read at the lane (`lanes_of`);
    # the rules are the ones above `CP06Kernel`'s tables.  Kept in
    # lockstep with the action bodies, which are the oracle:
    # tests/test_native_guard_tables.py holds every table to its
    # action's own `en` on every lane (test_native_guard_table.py
    # SendGetState's), and `test_guard_fns_match` differentially where
    # the reference is mounted.
    # ==================================================================
    def _recv_guard(self, st, k, mtype):
        return ((st["m_present"][k] == 1) & (st["m_count"][k] > 0)
                & (st["m_hdr"][k, H_TYPE] == mtype))

    def _dest_i(self, st, k):
        return jnp.clip(st["m_hdr"][k, H_DEST] - 1, 0, self.R - 1)

    _at_dest = at_dest

    _ids = property(replica_ids)

    # -- R-lane guards ----------------------------------------------------
    def guard_timer_send_svc_table(self, st):                   # [R]
        return ((st["aux_svc"] < self.shape.timer_limit)
                & (self._primary(st["view"], self.R) != self._ids))

    def guard_send_dvc_table(self, st):                         # [R]
        return ((st["status"] == VIEWCHANGE) & (st["sent_dvc"] == 0)
                & (st["svc"].sum(-1) >= self.R // 2))

    def guard_send_sv_table(self, st):                          # [R]
        # every record of the set counts, one slot a source or K
        held = (st["dvc"] == 1).reshape(self.R, -1).sum(-1)
        return ((st["status"] == VIEWCHANGE) & (st["sent_sv"] == 0)
                & (held >= self.R // 2 + 1))

    def guard_receive_client_request_table(self, st):           # [R, V]
        rep = ((self._primary(st["view"], self.R) == self._ids)
               & (st["status"] == NORMAL) & (st["ct"][:, 0, T_EXEC] == 1))
        return rep[:, None] & (st["aux_acked"] == 0)

    def guard_execute_op_table(self, st):                       # [R]
        opn = st["commit"] + 1
        committed = (st["peer_op"] >= opn[:, None]).sum(-1) >= self.R // 2
        return ((self._primary(st["view"], self.R) == self._ids)
                & (st["status"] == NORMAL)
                & (st["commit"] < st["op"]) & committed)

    def guard_restart_empty_table(self, st):                    # [R]
        return jnp.broadcast_to(
            st["aux_restart"] < self.shape.restart_limit, (self.R,))

    def guard_complete_recovery_table(self, st):                # [R]
        pres = st["rec"] == 1                                   # [R, R]
        return ((st["status"] == RECOVERING)
                & (pres.sum(-1) > self.R // 2)
                & (pres & (st["rec_has_log"] == 1)).any(-1))

    # -- M-lane guards: message k at its dest ------------------------------
    def guard_receive_higher_svc_table(self, st):
        at = self._at_dest(st)
        return (self._recv_guard(st, ..., M_SVC)
                & (st["m_hdr"][:, H_VIEW] > at("view")))

    def guard_receive_matching_svc_table(self, st):
        at = self._at_dest(st)
        return (self._recv_guard(st, ..., M_SVC)
                & (st["m_hdr"][:, H_VIEW] == at("view"))
                & (at("status") == VIEWCHANGE))

    def guard_receive_higher_dvc_table(self, st):
        at = self._at_dest(st)
        return (self._recv_guard(st, ..., M_DVC)
                & (st["m_hdr"][:, H_VIEW] > at("view")))

    def guard_receive_matching_dvc_table(self, st):
        at = self._at_dest(st)
        return (self._recv_guard(st, ..., M_DVC)
                & (st["m_hdr"][:, H_VIEW] == at("view")))

    def guard_receive_sv_table(self, st):
        at = self._at_dest(st)
        return (self._recv_guard(st, ..., M_SV)
                & (st["m_hdr"][:, H_VIEW] >= at("view")))

    def guard_receive_prepare_table(self, st):
        at = self._at_dest(st)
        hdr = st["m_hdr"]
        return (self._recv_guard(st, ..., M_PREPARE)
                & (at("status") == NORMAL)
                & (hdr[:, H_VIEW] == at("view"))
                & (hdr[:, H_OP] == at("op") + 1))

    def guard_receive_prepare_ok_table(self, st):
        at = self._at_dest(st)
        hdr = st["m_hdr"]
        # peer_op at (dest, source): the dest's row, then a one-hot
        # over the source
        src_i = jnp.clip(hdr[:, H_SRC] - 1, 0, self.R - 1)
        from_src = src_i[:, None] == jnp.arange(self.R, dtype=I32)
        peer_op = jnp.where(from_src, at("peer_op"), 0).sum(-1)
        return (self._recv_guard(st, ..., M_PREPAREOK)
                & (self._primary(at("view"), self.R) == hdr[:, H_DEST])
                & (at("status") == NORMAL)
                & (hdr[:, H_VIEW] == at("view"))
                & (hdr[:, H_OP] > peer_op))

    def guard_send_get_state_table(self, st):
        """[M, R] bool: SendGetState's guard for every (message k,
        rDest d + 1) at once; lane ``k * R + d`` of the action.

        SendOnce (VSR.tla:250-252) asks whether the GetState record
        the lane would send is in the bag's domain already, tombstones
        included.  The action's truncation rewrites one replica's log,
        op and view and leaves the bag alone, so the membership test
        may read the parent state.  Of that record only four columns
        vary — view (the Prepare's), op (the truncated log's length),
        dest (rDest) and source (the Prepare's dest) — and every other
        column, entry and log word is ``_row(M_GETSTATE)``'s.  So the
        bag is read once for the slots that are GetState records in
        all but those four ([M]), each message is held against each
        such slot on three of them ([M, M]) and the fourth, dest,
        spreads a hit over rDest.  On the v5e the read of 96 lanes
        from 8,192 tables costs nothing beside the table (0.26 ms a
        chunk with it, 0.28 ms for the rows taken whole; CHANGES.md,
        PR 32)."""
        hdr = st["m_hdr"]                                       # [M, NHDR]
        tmpl = self._row(M_GETSTATE)
        vary = np.zeros((self.NHDR,), bool)
        vary[[H_VIEW, H_OP, H_DEST, H_SRC]] = True
        gs = ((st["m_present"] == 1)
              & ((hdr == tmpl["hdr"]) | vary).all(-1)
              & (st["m_entry"] == tmpl["entry"]).all(-1)
              & (st["m_log"] == tmpl["log"]).all((-1, -2))
              & (st["m_log_len"] == tmpl["log_len"])
              & (st["m_has_log"] == tmpl["has_log"]))           # [M]
        at = self._at_dest(st)
        r = hdr[:, H_DEST]                                      # [M]
        view, op = at("view"), at("op")
        cheap = (self._recv_guard(st, ..., M_PREPARE)            # all k
                 & (self._primary(view, self.R) != r)
                 & (at("status") == NORMAL)
                 & (hdr[:, H_VIEW] > view) & (hdr[:, H_OP] > op + 1))
        trunc = jnp.minimum(at("commit"), at("log_len"))
        hit = (gs & (hdr[:, H_VIEW] == hdr[:, H_VIEW, None])
               & (hdr[:, H_OP] == trunc[:, None])
               & (hdr[:, H_SRC] == r[:, None]))                 # [k, k']
        to_d = hdr[:, H_DEST, None] == self._ids                # [k', R]
        blocked = (hit[:, :, None] & to_d).any(1)               # [M, R]
        return cheap[:, None] & (r[:, None] != self._ids) & ~blocked

    def guard_receive_get_state_table(self, st):
        at = self._at_dest(st)
        hdr = st["m_hdr"]
        return (self._recv_guard(st, ..., M_GETSTATE)
                & (at("view") == hdr[:, H_VIEW])
                & (at("status") == NORMAL)
                & (at("op") > hdr[:, H_OP]))

    def guard_receive_new_state_table(self, st):
        at = self._at_dest(st)
        hdr = st["m_hdr"]
        return (self._recv_guard(st, ..., M_NEWSTATE)
                & (at("view") == hdr[:, H_VIEW])
                & (at("status") == NORMAL)
                & (at("op") == hdr[:, H_FIRST] - 1))

    def guard_receive_recovery_table(self, st):
        return (self._recv_guard(st, ..., M_RECOVERY)
                & (self._at_dest(st)("status") == NORMAL))

    def guard_receive_recovery_response_table(self, st):
        at = self._at_dest(st)
        return (self._recv_guard(st, ..., M_RECOVERYRESP)
                & (at("rec_number") == st["m_hdr"][:, H_X])
                & (at("status") == RECOVERING))

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
    guard_restart_empty = lanes_of(guard_restart_empty_table)
    guard_receive_recovery = lanes_of(guard_receive_recovery_table)
    guard_receive_recovery_response = lanes_of(
        guard_receive_recovery_response_table)
    guard_complete_recovery = lanes_of(guard_complete_recovery_table)

    def _guard_fns(self):
        return [
            self.guard_timer_send_svc, self.guard_receive_higher_svc,
            self.guard_receive_matching_svc, self.guard_send_dvc,
            self.guard_receive_higher_dvc, self.guard_receive_matching_dvc,
            self.guard_send_sv, self.guard_receive_sv,
            self.guard_receive_client_request, self.guard_receive_prepare,
            self.guard_receive_prepare_ok, self.guard_execute_op,
            self.guard_send_get_state, self.guard_receive_get_state,
            self.guard_receive_new_state, self.guard_restart_empty,
            self.guard_receive_recovery, self.guard_receive_recovery_response,
            self.guard_complete_recovery,
        ]

    # ==================================================================
    # full Next: all lanes of all actions, stacked
    # ==================================================================
    def _action_fns(self):
        return [
            self.act_timer_send_svc, self.act_receive_higher_svc,
            self.act_receive_matching_svc, self.act_send_dvc,
            self.act_receive_higher_dvc, self.act_receive_matching_dvc,
            self.act_send_sv, self.act_receive_sv,
            self.act_receive_client_request, self.act_receive_prepare,
            self.act_receive_prepare_ok, self.act_execute_op,
            self.act_send_get_state, self.act_receive_get_state,
            self.act_receive_new_state, self.act_restart_empty,
            self.act_receive_recovery, self.act_receive_recovery_response,
            self.act_complete_recovery,
        ]

    def lane_replica(self, name, st, lane):
        """The one replica a lane's action mutates (every VSR action
        updates through EXCEPT ![r] on a single replica)."""
        if name in ("TimerSendSVC", "SendDVC", "SendSV", "ExecuteOp",
                    "RestartEmpty", "CompleteRecovery"):
            return lane
        if name == "ReceiveClientRequest":
            return lane // self.V
        if name == "SendGetState":
            k = lane // self.R
        else:
            k = lane
        return jnp.clip(st["m_hdr"][k, H_DEST] - 1, 0, self.R - 1)

    def seed_touch(self, st):
        """Add the incremental-fingerprint scratch keys."""
        st = dict(st)
        st["_ts"] = jnp.full((self.R + 1,), -1, I32)
        st["_tn"] = jnp.asarray(0, I32)
        return st

    def step_all(self, st):
        """One state -> all lane successors.

        Returns (succs, enabled): succs is the state pytree with a leading
        lane axis [n_lanes, ...]; enabled is [n_lanes] bool.  Disabled
        lanes contain garbage and must be masked by the caller.
        """
        st = {k: jnp.asarray(v, I32) for k, v in st.items()}
        parts, ens = [], []
        for name, fn in zip(ACTION_NAMES, self._action_fns()):
            lanes = jnp.arange(self._lane_count(name), dtype=I32)
            succ, en = jax.vmap(fn, in_axes=(None, 0))(st, lanes)
            parts.append(succ)
            ens.append(en)
        succs = {k: jnp.concatenate([p[k] for p in parts], axis=0)
                 for k in st if not k.startswith("_")}
        return succs, jnp.concatenate(ens)

    # ==================================================================
    # fingerprinting: VIEW projection (excludes aux_vars, VSR.tla:149-150)
    # -> symmetry-least 4x32-bit hash (VSR.tla:151)
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

    def _permuted(self, st, perm, resort=True):
        """Remap value ids through one symmetry permutation ([V+1] table,
        0 -> 0).  Value ids live in the operation column of every log-
        entry row (rep/dvc/rec logs, message entry and payload logs).
        Where a pair of the DVC receive-set holds K > 1 records, their
        order reads the logs: the pairs are put in canonical order
        again, so the image of a state is the state TLC's permuted
        value would encode to (`resort` False: the caller knows `perm`
        is the identity)."""
        st = dict(st)
        for k in ("log", "dvc_log", "rec_log", "m_log"):
            st[k] = st[k].at[..., E_OPER].set(perm[st[k][..., E_OPER]])
        st["m_entry"] = st["m_entry"].at[..., E_OPER].set(
            perm[st["m_entry"][..., E_OPER]])
        if resort and self.K > 1:
            st.update(self._dvc_sorted({k: st[k] for k in DVC_KEYS}))
        return st

    @property
    def _folds(self):
        """Whether the fingerprint folds a group itself (more than the
        identity in `perms`); the engines' kernels do not."""
        return self.perms.shape[0] > 1

    def _rep_rows(self, st):
        """[R, n_rep] uint32 content rows, one per replica: the replica
        id followed by every per-replica state slice."""
        R = self.R
        cols = [jnp.arange(R, dtype=jnp.uint32)[:, None]]
        for k in REP_KEYS:
            v = jnp.asarray(st[k], jnp.uint32)
            cols.append(v.reshape(R, -1))
        return jnp.concatenate(cols, axis=1)

    def _rep_hashes(self, st):
        """[R, 4] per-replica row hashes (position-keyed by replica id)."""
        rows = self._rep_rows(st)
        return self._mix32((rows[:, None, :] * self._k_rep[None]).sum(axis=2)
                           + self._seeds[None, :])

    def _slot_rows(self, st):
        """[M, n_msg] uint32 content rows, one per message slot (slot
        index NOT injected: the bag hash is slot-order-invariant)."""
        return jnp.concatenate(
            [jnp.asarray(st["m_hdr"], jnp.uint32),
             jnp.asarray(st["m_entry"], jnp.uint32),
             jnp.asarray(st["m_log"], jnp.uint32).reshape(self.M, -1),
             jnp.asarray(st["m_log_len"], jnp.uint32)[:, None],
             jnp.asarray(st["m_has_log"], jnp.uint32)[:, None],
             jnp.asarray(st["m_count"], jnp.uint32)[:, None]], axis=1)

    def _slot_hashes(self, st):
        rows = self._slot_rows(st)
        return self._mix32((rows[:, None, :] * self._k_msg[None]).sum(axis=2)
                           + self._seeds[None, :])       # [M, 4]

    def _fp_one(self, st, perm):
        st = self._permuted(st, perm, resort=self._folds)
        h_rep = self._rep_hashes(st).sum(axis=0)
        pres = jnp.asarray(st["m_present"], jnp.uint32)[:, None]
        h_msg = (self._slot_hashes(st) * pres).sum(axis=0)
        return self._mix32(self._mix32(h_rep + h_msg) + self._seeds)

    @staticmethod
    def _lex_min4(fps):
        """[P, 4] -> [4]: lexicographic least row."""
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
        """[4] uint32 canonical fingerprint: least over symmetry perms."""
        st = {k: jnp.asarray(v) for k, v in st.items()}
        fps = jax.vmap(lambda p: self._fp_one(st, p))(jnp.asarray(self.perms))
        return self._lex_min4(fps)

    # -- incremental fingerprinting ------------------------------------
    # Every action mutates exactly ONE replica row (VSR.tla actions all
    # update through EXCEPT ![r]) plus at most R+1 message slots (a
    # discard + an R-1-destination broadcast).  The kernel records the
    # touched replica in succ["_ri"] and touched slots in succ["_ts"]
    # (engine strips them), and the expand pass reconstitutes the
    # successor fingerprint from the parent's per-row hash sums.

    def parent_parts(self, st):
        """Per-permutation hash parts of a parent state:
        rep [P, R, 4], slot [P, M, 4], total [P, 4] (pre-mix sums)."""
        def parts_one(perm):
            stp = self._permuted(st, perm, resort=self._folds)
            rep = self._rep_hashes(stp)
            slot = self._slot_hashes(stp)
            pres = jnp.asarray(stp["m_present"], jnp.uint32)[:, None]
            total = rep.sum(axis=0) + (slot * pres).sum(axis=0)
            return rep, slot, total
        return jax.vmap(parts_one)(jnp.asarray(self.perms))

    def _perm_entry_cols(self, rows, perm):
        """Apply a value permutation to the oper column of [..., NENT]
        log-entry rows."""
        return rows.at[..., E_OPER].set(perm[rows[..., E_OPER]])

    def _rep_row_one(self, st, i, perm):
        """[n_rep] content row of replica i with `perm` applied."""
        cols = [jnp.asarray(i, jnp.uint32)[None]]
        resorted = {}
        if self.K > 1 and self._folds:      # as _permuted does
            resorted = self._dvc_sorted(
                {k: self._perm_entry_cols(st[k][i], perm)
                 if k == "dvc_log" else st[k][i] for k in DVC_KEYS})
        for k in REP_KEYS:
            v = st[k][i]
            if k in resorted:
                v = resorted[k]
            elif k in ("log", "dvc_log", "rec_log"):
                v = self._perm_entry_cols(v, perm)
            cols.append(jnp.asarray(v, jnp.uint32).reshape(-1))
        return jnp.concatenate(cols)

    def _slot_row_one(self, st, m, perm):
        """[n_msg] content row of message slot m with `perm` applied."""
        return jnp.concatenate([
            jnp.asarray(st["m_hdr"][m], jnp.uint32),
            jnp.asarray(self._perm_entry_cols(st["m_entry"][m], perm),
                        jnp.uint32),
            jnp.asarray(self._perm_entry_cols(st["m_log"][m], perm),
                        jnp.uint32).reshape(-1),
            jnp.asarray(st["m_log_len"][m], jnp.uint32)[None],
            jnp.asarray(st["m_has_log"][m], jnp.uint32)[None],
            jnp.asarray(st["m_count"][m], jnp.uint32)[None]])

    def fingerprint_incremental(self, succ, ri, parts, parent):
        """Successor fingerprint in O(touched rows) from parent parts.

        `ri` is the one replica the lane's action mutated
        (lane_replica); succ carries "_ts" ([R+1] touched slot indices,
        -1 padded, recorded by the bag primitives).  Produces values
        identical to `fingerprint(succ)`."""
        rep_h, slot_h, total = parts
        i = ri
        ts = succ["_ts"]
        perms = jnp.asarray(self.perms)
        p_pres = jnp.asarray(parent["m_present"], jnp.uint32)
        s_pres = jnp.asarray(succ["m_present"], jnp.uint32)

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
            return self._mix32(self._mix32(d) + self._seeds)

        fps = jax.vmap(fp_p)(jnp.arange(self.perms.shape[0]))
        return self._lex_min4(fps)

    # ==================================================================
    # invariants (VSR.tla:926-952), vectorized
    # ==================================================================
    def _replica_has_op(self, st):
        """[R, V] bool: ReplicaHasOp(r, v) (VSR.tla:933-935)."""
        opers = st["log"][..., E_OPER]                   # [R, MAX_OPS]
        v_ids = jnp.arange(1, self.V + 1, dtype=I32)
        return (opers[:, :, None] == v_ids[None, None, :]).any(axis=1)

    def inv_acknowledged_write_not_lost(self, st):
        acked = st["aux_acked"] == 2                     # v |-> TRUE
        has = self._replica_has_op(st).any(axis=0)       # [V]
        return (~acked | has).all()

    def inv_acknowledged_writes_exist_on_majority(self, st):
        acked = st["aux_acked"] == 2
        n_has = self._replica_has_op(st).sum(axis=0)
        return (~acked | (n_has >= self.R // 2 + 1)).all()

    def inv_no_log_divergence(self, st):
        # Faithful to VSR.tla:926-931: the body compares rep_log[r1] with
        # itself, so the invariant is vacuously true (SURVEY.md §2.7.2).
        return jnp.asarray(True)

    def inv_test(self, st):
        return jnp.asarray(True)

    def pred_all_replicas_same_view(self, st):
        # AllReplicasMoveToSameView (VSR.tla:958-962): a state predicate
        # used by the liveness property []<>P; not an invariant in the
        # shipped cfg, but checkable as one
        return ((st["view"] == st["view"][0]).all()
                & (st["status"] == NORMAL).all())

    def hunt_score(self, st):
        """Defect-proximity score for guided simulation (importance
        splitting): how close is this state to losing an acknowledged
        write (AcknowledgedWriteNotLost, VSR.tla:945-950)?

        0 while nothing is acked; afterwards a shaped sum of milestones
        along the truncation path (VSR.tla:64-86):
          +2 per replica missing the worst acked value (reaches +2R at
             the violation),
          +1 if some Normal replica lags the max view while holding an
             acked value (the SendGetState truncation candidate),
          +1 if a GetState record is in the bag (truncation fired —
             VSR.tla:496-516 truncates on SEND).
        The intermediate milestones give the splitter gradient through
        the view-change phase, where the missing-count alone is flat."""
        acked = st["aux_acked"] == 2                      # [V]
        has = self._replica_has_op(st)                    # [R, V]
        missing = (~has).sum(axis=0)                      # [V]
        worst = jnp.max(jnp.where(acked, missing, -1))
        vmax = st["view"].max()
        has_acked_val = (has & acked[None, :]).any(axis=1)   # [R]
        lag = ((st["status"] == NORMAL) & (st["view"] < vmax)
               & has_acked_val).any()
        gs = ((st["m_present"] == 1)
              & (st["m_hdr"][:, H_TYPE] == M_GETSTATE)).any()
        score = 1 + 2 * worst + lag.astype(I32) + gs.astype(I32)
        return jnp.where(acked.any(), score, 0).astype(I32)

    INVARIANT_FNS = {
        "AcknowledgedWriteNotLost": "inv_acknowledged_write_not_lost",
        "AcknowledgedWritesExistOnMajority":
            "inv_acknowledged_writes_exist_on_majority",
        "NoLogDivergence": "inv_no_log_divergence",
        "TestInv": "inv_test",
        "AllReplicasMoveToSameView": "pred_all_replicas_same_view",
    }

    def invariant_fn(self, names):
        """Build st -> ok_bool over the named invariants (cfg INVARIANT
        block).  Raises KeyError for invariants with no device kernel."""
        fns = [getattr(self, self.INVARIANT_FNS[n]) for n in names]

        def check(st):
            ok = jnp.asarray(True)
            for f in fns:
                ok = ok & f(st)
            return ok
        return check
