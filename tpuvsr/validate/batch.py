"""Batched trace validation on the device mesh (ISSUE 8 tentpole).

``BatchValidator`` is the production CI engine: thousands of recorded
implementation traces are checked against the compiled spec kernel
concurrently — per step the kernel expands every candidate state's
full successor set (``kern.step_all``), filters to successors
consistent with the recorded event (action id and/or encoded-leaf
observations), dedups by fingerprint, and keeps the surviving
candidates.  Traces are vmapped over the batch axis and shard_mapped
across a 1-D device mesh (walkers become traces — the ``sim/fleet``
idiom), steps run in fused chunks behind the ``engine/pipeline``
dispatch window, and a SIGTERM under a ``PreemptionGuard`` writes a
CRC'd rescue snapshot of the committed candidate frontier and raises
``Preempted`` (the exit-75 contract) which ``resume_from`` continues
bit-identically.

**Determinism contract.**  Every per-step op is elementwise over the
trace axis and reductions are integer psums, rounds cover contiguous
trace ranges in order, and the candidate dedup/truncation is a pure
first-occurrence scan in (candidate, lane) order — so the divergence
report of every trace (event index, candidate count, spec-side
enabled set) is bit-identical across mesh sizes, batch sizes, and
rescue/resume seams.

**Exactness.**  The candidate set is bounded by ``cand_cap`` slots
per trace.  A step producing more consistent successors than fit is
NOT silently truncated: the chunk reports overflow, the host doubles
the cap, recompiles, and redraws the round from step 0 (no RNG — the
redraw is exact), journaled as ``grow {what: "cand_cap"}``.  Message
-table overflow inside a successor redraws the same way.  Every
device-reported divergence is confirmed by the interpreter validator
(``host.validate_trace``) before it reaches the report — a
device/interpreter disagreement is a loud ``TLAError``, never a
wrong verdict (the fleet replay cross-check idiom).
"""

from __future__ import annotations

import hashlib
import json
import time

import numpy as np

import jax
import jax.numpy as jnp

from ..core.values import TLAError
from ..engine.checkpoint import spec_digest
from ..engine.pipeline import DispatchPipeline
from ..engine.spec import SpecModel
from ..exitcodes import (EX_OK, EX_RESUMABLE, EX_SOFTWARE,
                         EX_VIOLATION, job_state)
from ..models import registry
from ..obs import RunObserver, closes_observer
from ..resilience.faults import fault_point
from ..resilience.supervisor import (Outcome, Preempted,
                                     PreemptionGuard, is_oom,
                                     preempt_signal)
from ..sim.fleet import load_fleet_snapshot, save_fleet_snapshot
from .host import (ValidateResult, divergence_record, validate_trace)
from .traces import Trace  # noqa: F401 — the input type

I32 = jnp.int32


class ObservationUnsupported(TLAError):
    """The codec cannot express a trace observation as encoded-leaf
    comparisons — the caller should fall back to the interpreter
    validator (``host.host_validate_batch``)."""


def encode_obs(codec, tmpl, var, value):
    """Encode one pinned spec variable as ``{leaf_key: (mask, values)}``
    against the codec's state layout.  Codecs may provide their own
    ``encode_obs(var, value)`` hook; the default covers the common
    case of a scalar int/bool variable stored under its own leaf key
    (the stub codec, and any codec whose leaves are named after the
    variables they hold).  Anything else raises
    :class:`ObservationUnsupported`."""
    hook = getattr(codec, "encode_obs", None)
    if hook is not None:
        return hook(var, value)
    if var not in tmpl:
        raise ObservationUnsupported(
            f"codec {type(codec).__name__} has no leaf for variable "
            f"{var!r} and no encode_obs hook")
    leaf = tmpl[var]
    if not isinstance(value, (bool, int, np.integer)):
        raise ObservationUnsupported(
            f"variable {var!r}: only scalar int/bool observations are "
            f"encodable without a codec encode_obs hook "
            f"(got {type(value).__name__})")
    vals = np.full(leaf.shape, int(value), leaf.dtype)
    # an observation that does not round-trip through the leaf dtype
    # (2**40 wraps to 0 in int32, 2 to True in bool) would compare
    # equal to the WRONG encoded state — a silent false accept, the
    # one verdict the interpreter cross-check never sees
    if int(vals.flat[0]) != int(value):
        raise ObservationUnsupported(
            f"variable {var!r}: observation {value!r} does not fit "
            f"the encoded leaf dtype {leaf.dtype}")
    return {var: (np.ones(leaf.shape, bool), vals)}


def traces_digest(traces):
    """Identity of a trace batch — stamped into rescue snapshots so a
    resume against a different TRACE.jsonl is a policy error."""
    h = hashlib.sha1()
    for t in traces:
        h.update(json.dumps(t.to_record(), sort_keys=True).encode())
    return h.hexdigest()[:16]


VALIDATE_FORMAT = 1


class BatchValidator:
    """The sharded trace-validation engine (module docstring).

    ``batch`` traces run per round (padded to a multiple of the mesh
    size; pad slots never act); ``cand_cap`` is the per-trace
    candidate-set bound (grown on overflow); ``chunk_steps`` the fused
    step count per dispatch; ``pipeline`` the dispatch-window depth;
    ``confirm=False`` skips the per-divergence interpreter
    cross-check (benchmarks only — the default always confirms)."""

    def __init__(self, spec: SpecModel, batch=1024, n_devices=None,
                 mesh=None, chunk_steps=8, cand_cap=4, max_msgs=None,
                 pipeline=2, min_batch=8, max_retries=4,
                 model_factory=None, confirm=True, log=None):
        # trace validation tracks CONCRETE states: an observation may
        # pin any variable to a specific (model) value, so two
        # orbit-equivalent candidates are NOT interchangeable and
        # symmetry reduction never applies here (ISSUE 11: the default
        # kernel is built with fold_symmetry=False so orbit-folded
        # fingerprints can't merge distinct candidates; the CLI
        # rejects -symmetry on with -validate)
        self._model_factory = model_factory or (
            lambda spec, max_msgs=None: registry.make_model(
                spec, max_msgs=max_msgs, fold_symmetry=False))
        self.spec = spec
        self.inv_names = list(spec.cfg.invariants)
        self.chunk = int(chunk_steps)
        self.confirm = bool(confirm)
        self.min_batch = int(min_batch)
        self.max_retries = int(max_retries)
        self.pipeline = max(1, int(pipeline))
        self._log = log
        if cand_cap < 1:
            raise ValueError(f"cand_cap must be >= 1 (got {cand_cap})")
        self.K = int(cand_cap)
        if mesh is not None:
            self.mesh = mesh
            self.axis = mesh.axis_names[0]
            self._n_req = mesh.shape[self.axis]
        else:
            self.mesh = None
            self.axis = "d"
            self._n_req = n_devices     # None = every visible device
        self._max_msgs = max_msgs
        self._restore_batch = None   # requested batch, during a resume
        # pre-flight memo: (the checked batch, its digest) — by
        # reference, so run() on the same list skips both the encode
        # pass and the digest recompute
        self._obs_checked = (None, None)
        self._set_batch(int(batch))

    def log(self, msg):
        if self._log:
            self._log(f"validate: {msg}")

    # -- construction --------------------------------------------------
    def _set_batch(self, batch):
        """(Re)shape the engine for a round size: mesh, padding,
        recompile.  The OOM-degrade knob (batch halving)."""
        if batch < 1:
            raise ValueError(f"batch must be >= 1 (got {batch})")
        self.batch = int(batch)
        n = self._n_req or len(jax.devices())
        n = max(1, min(int(n), self.batch, len(jax.devices())))
        if self.mesh is None or self.mesh.shape[self.axis] != n:
            from jax.sharding import Mesh
            self.mesh = Mesh(np.array(jax.devices()[:n]), (self.axis,))
        self.D = self.mesh.shape[self.axis]
        self.T_pad = -(-self.batch // self.D) * self.D
        self._build(self._max_msgs)

    def _build(self, max_msgs):
        """Compile the fused validation-chunk kernel for the current
        (batch, mesh, cand_cap, message-table) shape."""
        from ..parallel.sharded_bfs import _shard_map
        self._max_msgs = max_msgs
        self.codec, self.kern = self._model_factory(self.spec,
                                                    max_msgs=max_msgs)
        kern = self.kern
        # leaf template: shapes/dtypes of one encoded state (also the
        # default encode_obs schema)
        st0 = next(iter(self.spec.init_states()))
        self._tmpl = {k: np.asarray(v)
                      for k, v in self.codec.encode(st0).items()}
        self._init_enc = None        # lazy cache of encoded init states
        lane_aid = jnp.asarray(kern.lane_action)
        L = int(lane_aid.shape[0])
        self.L = L
        K = self.K
        keys = sorted(self._tmpl)
        axis = self.axis
        n_steps = self.chunk

        def step_all_clean(st):
            succs, en = kern.step_all(st)
            return ({k: v for k, v in succs.items()
                     if not k.startswith("_")}, en)

        def one_trace(cs, al, da, de, dc, tl, aid1, m1, v1, s):
            """Advance one trace's candidate set through one event.
            cs: {k: [K, ...]}, al: [K], da/dc/tl: scalars, de: [L],
            aid1: scalar action obs, m1/v1: {k: leaf-shaped obs}."""
            active = (s < tl) & (da < 0)
            succs, en = jax.vmap(step_all_clean)(cs)   # [K, L, ...]
            en = en & al[:, None]
            ok = en & ((aid1 < 0) | (lane_aid == aid1))[None, :]
            for k in keys:
                eq = (succs[k] == v1[k]) | ~m1[k]
                ok = ok & eq.reshape(K, L, -1).all(-1)
            okf = ok.reshape(K * L)
            flat = {k: v.reshape((K * L,) + v.shape[2:])
                    for k, v in succs.items()}
            err1 = jnp.asarray(False)
            if "err" in flat:
                errf = flat["err"].reshape(K * L, -1).any(-1) \
                    if flat["err"].ndim > 1 else flat["err"] != 0
                err1 = active & (okf & errf).any()
                okf = okf & ~errf
            fp = jax.vmap(kern.fingerprint)(flat)      # [K*L, W]
            fp = fp.reshape(K * L, -1)
            same = (fp[:, None, :] == fp[None, :, :]).all(-1)
            dup = (jnp.tril(same, k=-1) & okf[None, :]).any(1)
            uniq = okf & ~dup
            n_new = uniq.sum(dtype=I32)
            rank = jnp.cumsum(uniq.astype(I32)) - 1
            keep = uniq & (rank < K)
            dest = jnp.where(keep, rank, K).astype(I32)
            new_c = {k: jnp.zeros((K,) + v.shape[1:], v.dtype)
                     .at[dest].set(v, mode="drop")
                     for k, v in flat.items()}
            new_al = jnp.zeros((K,), bool).at[dest].set(
                jnp.ones((K * L,), bool), mode="drop")
            ovf1 = active & (n_new > K)
            div_now = active & (n_new == 0)
            da = jnp.where(div_now, s, da)
            de = jnp.where(div_now, en.any(0), de)
            dc = jnp.where(div_now, al.sum(dtype=I32), dc)
            upd = active & (n_new > 0)
            cs = {k: jnp.where(upd, new_c[k], cs[k]) for k in cs}
            al = jnp.where(upd, new_al, al)
            return cs, al, da, de, dc, ovf1, err1

        def chunk_fn(cands, alive, div_at, div_en, div_cand, tlen,
                     aid_obs, ob_m, ob_v, step0):
            def step(carry, t):
                cands, alive, div_at, div_en, div_cand, ovf, err = carry
                s = (step0 + t).astype(I32)
                out = jax.vmap(one_trace,
                               in_axes=(0, 0, 0, 0, 0, 0, 0, 0, 0,
                                        None))(
                    cands, alive, div_at, div_en, div_cand, tlen,
                    aid_obs[:, t],
                    {k: v[:, t] for k, v in ob_m.items()},
                    {k: v[:, t] for k, v in ob_v.items()}, s)
                (cands, alive, div_at, div_en, div_cand,
                 ovf_t, err_t) = out
                return (cands, alive, div_at, div_en, div_cand,
                        ovf | ovf_t.any(), err | err_t.any()), None

            init = (cands, alive, div_at, div_en, div_cand,
                    jnp.asarray(False), jnp.asarray(False))
            (cands, alive, div_at, div_en, div_cand, ovf,
             err), _ = jax.lax.scan(step, init,
                                    jnp.arange(n_steps, dtype=I32))
            rem = jax.lax.psum(
                ((div_at < 0) & (tlen > step0 + n_steps))
                .sum(dtype=I32), axis)
            n_div = jax.lax.psum((div_at >= 0).sum(dtype=I32), axis)
            ovf_g = jax.lax.psum(ovf.astype(I32), axis) > 0
            err_g = jax.lax.psum(err.astype(I32), axis) > 0
            return (cands, alive, div_at, div_en, div_cand,
                    rem, n_div, ovf_g, err_g)

        from jax.sharding import PartitionSpec as P
        sp = P(self.axis)
        self._chunk = jax.jit(_shard_map(
            chunk_fn, self.mesh,
            in_specs=(sp, sp, sp, sp, sp, sp, sp, sp, sp, P()),
            out_specs=(sp, sp, sp, sp, sp, P(), P(), P(), P())))
        self._fresh_jit = True

    # -- host-side encoding --------------------------------------------
    def _init_states_enc(self):
        """Interpreter init states + their encodings, computed once per
        build (the fleet ``_init_batch`` caching idiom)."""
        if self._init_enc is None:
            states = list(self.spec.init_states())
            self._init_enc = (states,
                              [{k: np.asarray(v) for k, v in
                                self.codec.encode(st).items()}
                               for st in states])
        return self._init_enc

    def check_observations(self, traces):
        """Fail fast (ObservationUnsupported) if any observation in
        `traces` cannot be encoded against this codec — so the caller
        can fall back to the host validator before any device time.
        A passed batch is memoized (with its digest) so :meth:`run`
        on the same list pays neither the O(traces x events) encode
        pass nor the digest serialization a second time."""
        for t in traces:
            for k, v in t.init.items():
                encode_obs(self.codec, self._tmpl, k, v)
            for ev in t.events:
                if ev.action is not None and \
                        ev.action not in self.kern.action_names:
                    raise TLAError(
                        f"trace {t.tid}: action {ev.action!r} has no "
                        f"kernel lane (spec actions: "
                        f"{self.kern.action_names})")
                for k, v in ev.vars.items():
                    encode_obs(self.codec, self._tmpl, k, v)
        self._obs_checked = (traces, traces_digest(traces))

    def _encode_round(self, rtraces):
        """Host arrays for one round: initial candidate sets, event
        observation planes, lengths.  Returns ``(arrays, pre_div, S)``
        where ``pre_div[i]`` is a host-side verdict for traces whose
        init observation matches NO init state (they never reach the
        device), and S the padded step count.  May grow ``cand_cap``
        first when an init candidate set alone exceeds it."""
        from .host import _obs_matches
        states, encs = self._init_states_enc()
        T, K = self.T_pad, self.K
        init_sets = []
        for t in rtraces:
            idxs = [j for j, st in enumerate(states)
                    if _obs_matches(st, t.init)]
            init_sets.append(idxs)
        need = max([len(x) for x in init_sets] or [1])
        if need > K:
            while self.K < need:
                self.K *= 2
            self.log(f"init candidate sets need {need} slots; growing "
                     f"cand_cap to {self.K}")
            self._build(self._max_msgs)
            states, encs = self._init_states_enc()
            K = self.K
        S = max([len(t.events) for t in rtraces] or [0])
        S = max(S, 1)
        cands = {k: np.zeros((T, K) + v.shape, v.dtype)
                 for k, v in self._tmpl.items()}
        alive = np.zeros((T, K), bool)
        tlen = np.zeros((T,), np.int32)
        aid_obs = np.full((T, S), -1, np.int32)
        ob_m = {k: np.zeros((T, S) + v.shape, bool)
                for k, v in self._tmpl.items()}
        ob_v = {k: np.zeros((T, S) + v.shape, v.dtype)
                for k, v in self._tmpl.items()}
        pre_div = {}
        for i, t in enumerate(rtraces):
            if not init_sets[i]:
                pre_div[i] = True     # host-reported: no init state
                continue
            tlen[i] = len(t.events)
            for c, j in enumerate(init_sets[i]):
                for k in cands:
                    cands[k][i, c] = encs[j][k]
                alive[i, c] = True
            for s, ev in enumerate(t.events):
                if ev.action is not None:
                    aid_obs[i, s] = self.kern.action_names.index(
                        ev.action)
                for var, val in ev.vars.items():
                    for k, (m, v) in encode_obs(
                            self.codec, self._tmpl, var, val).items():
                        ob_m[k][i, s] |= np.asarray(m, bool)
                        ob_v[k][i, s] = np.where(
                            np.asarray(m, bool), v, ob_v[k][i, s])
        arrays = {"cands": cands, "alive": alive, "tlen": tlen,
                  "aid_obs": aid_obs, "ob_m": ob_m, "ob_v": ob_v}
        return arrays, pre_div, S

    # -- rescue/resume -------------------------------------------------
    def _rescue(self, path, *, base, active, step, committed, res,
                digest, chunks, obs, extra=None):
        sig = preempt_signal() or "SIGTERM"
        manifest = {
            "spec_digest": spec_digest(self.spec),
            "traces_digest": digest,
            "base": int(base), "active": int(active),
            "step": int(step), "chunks": int(chunks),
            "batch": int(self.batch), "cand_cap": int(self.K),
            "max_msgs": (int(self.codec.shape.MAX_MSGS)
                         if getattr(self.codec, "shape", None)
                         is not None else None),
            "traces": int(res.traces_checked),
            "accepted": int(res.accepted),
            # snapshot_info-compat keys (the service rescue handoff)
            "depth": int(step), "fp_count": int(base),
            "elapsed": float(obs.elapsed()),
            "extra": dict(extra or {},
                          divergences=res.divergences),
        }
        arrays = None
        if path:
            cands, alive, div_at, div_en, div_cand = committed
            ca = {f"c_{k}": np.asarray(jax.device_get(v))
                  for k, v in cands.items()}
            ca["alive"] = np.asarray(jax.device_get(alive))
            ca["div_at"] = np.asarray(jax.device_get(div_at))
            ca["div_en"] = np.asarray(jax.device_get(div_en))
            ca["div_cand"] = np.asarray(jax.device_get(div_cand))
            arrays = {"walkers.npz": ca}
            save_fleet_snapshot(path, manifest=manifest,
                                arrays=arrays, kind="validate")
        obs.rescue(path or "", step, base, sig)
        self.log(f"preempted by {sig}: candidate frontier rescued at "
                 f"step {step} of the round at base {base}")
        return Preempted(path, step, base, sig)

    def _load_resume(self, path, digest):
        manifest, arrays = load_fleet_snapshot(
            path, expect_digest=spec_digest(self.spec),
            kind="validate")
        if manifest.get("traces_digest") != digest:
            raise ValueError(
                f"{path}: snapshot was written for a different trace "
                f"batch (digest {manifest.get('traces_digest')}, this "
                f"run {digest}); refusing to resume")
        if int(manifest["cand_cap"]) != self.K \
                or int(manifest["batch"]) != self.batch \
                or manifest.get("max_msgs") != (
                    int(self.codec.shape.MAX_MSGS)
                    if getattr(self.codec, "shape", None) is not None
                    else None):
            if int(manifest["batch"]) != self.batch:
                # the rescued round must finish at the snapshot's
                # batch; rounds after it rescale back to the requested
                # one (the elastic --batch-per-device contract)
                self._restore_batch = self.batch
            self.K = int(manifest["cand_cap"])
            self._max_msgs = manifest.get("max_msgs")
            self._set_batch(int(manifest["batch"]))

        def fit(a, fill):
            # re-pad the rescued rows to this mesh's T_pad: live traces
            # occupy rows [0, active) and active <= batch <= every
            # T_pad, so added/dropped rows are always dead pad slots
            a = np.asarray(a)
            if a.shape[0] > self.T_pad:
                return a[:self.T_pad]
            if a.shape[0] < self.T_pad:
                pad = np.full((self.T_pad - a.shape[0],) + a.shape[1:],
                              fill, a.dtype)
                return np.concatenate([a, pad], axis=0)
            return a

        ca = arrays.get("walkers.npz", {})
        resume = None
        if int(manifest["step"]) > 0 and ca:
            resume = {
                "step": int(manifest["step"]),
                "cands": {k[2:]: fit(ca[k], 0) for k in ca
                          if k.startswith("c_")},
                "alive": fit(ca["alive"], False),
                "div_at": fit(ca["div_at"], -1),
                "div_en": fit(ca["div_en"], False),
                "div_cand": fit(ca["div_cand"], 0)}
        return manifest, resume

    # -- one round -----------------------------------------------------
    def _run_round(self, rtraces, *, base, obs, checkpoint_path,
                   on_chunk, chunks_before, res, digest, deadline,
                   resume=None, rescue_extra=None):
        """Validate one round of traces to completion, redrawing from
        step 0 on a growth event (candidate cap / message table — no
        RNG, so the redraw is exact).  Returns
        ``(div_at, div_en, div_cand, pre_div, chunks, stopped)``."""
        while True:                       # growth-redraw loop
            arrays, pre_div, S = self._encode_round(rtraces)
            if resume is not None:
                step = int(resume["step"])
                committed = (
                    {k: jnp.asarray(v)
                     for k, v in resume["cands"].items()},
                    jnp.asarray(resume["alive"]),
                    jnp.asarray(resume["div_at"]),
                    jnp.asarray(resume["div_en"]),
                    jnp.asarray(resume["div_cand"]))
                resume = None
            else:
                step = 0
                committed = (
                    {k: jnp.asarray(v)
                     for k, v in arrays["cands"].items()},
                    jnp.asarray(arrays["alive"]),
                    jnp.full((self.T_pad,), -1, np.int32),
                    jnp.zeros((self.T_pad, self.L), bool),
                    jnp.zeros((self.T_pad,), np.int32))
            status, committed, chunks_before, stopped = \
                self._round_chunks(
                    arrays, committed, step, S, base=base, obs=obs,
                    checkpoint_path=checkpoint_path,
                    on_chunk=on_chunk, chunks_before=chunks_before,
                    res=res, digest=digest, deadline=deadline,
                    active=len(rtraces), rescue_extra=rescue_extra)
            if status == "done":
                break
        div_at = np.asarray(jax.device_get(committed[2]))
        div_en = np.asarray(jax.device_get(committed[3]))
        div_cand = np.asarray(jax.device_get(committed[4]))
        return div_at, div_en, div_cand, pre_div, chunks_before, stopped

    def _round_chunks(self, arrays, committed, step, S, *, base, obs,
                      checkpoint_path, on_chunk, chunks_before, res,
                      digest, deadline, active, rescue_extra):
        """The chunked dispatch loop of one round.  Returns
        ``(status, committed, chunks, stopped)`` where status is
        ``"done"`` (round finished / deadline-stopped) or ``"grown"``
        (a growth happened — the caller re-encodes and redraws)."""
        tlen = jnp.asarray(arrays["tlen"])
        pipe = DispatchPipeline(self.pipeline, obs,
                                ready=lambda out: out[5])
        launched = step
        cur = committed
        chunk_idx = chunks_before
        stopped = False

        def pull(out):
            return jax.device_get((out[5], out[6], out[7], out[8]))

        try:
            while step < S:
                while pipe.has_room() and launched < S:
                    aid = ev_slice_d(arrays, "aid_obs", launched,
                                     self.chunk, self.T_pad, -1)
                    m_sl = {k: ev_slice_d(arrays["ob_m"], k, launched,
                                          self.chunk, self.T_pad,
                                          False)
                            for k in arrays["ob_m"]}
                    v_sl = {k: ev_slice_d(arrays["ob_v"], k, launched,
                                          self.chunk, self.T_pad, 0)
                            for k in arrays["ob_v"]}
                    out = pipe.launch(
                        self._chunk, cur[0], cur[1], cur[2], cur[3],
                        cur[4], tlen, aid, m_sl, v_sl,
                        jnp.asarray(launched, I32),
                        fresh=self._fresh_jit,
                        step=launched)
                    self._fresh_jit = False
                    cur = (out[0], out[1], out[2], out[3], out[4])
                    launched += self.chunk
                out, sc = pipe.collect(pull)
                rem, n_div, ovf, err = sc
                if bool(err):
                    pipe.drain()
                    old = self.codec.shape.MAX_MSGS
                    self._build(old * 2)
                    obs.grow("message_table", self.codec.shape.MAX_MSGS)
                    self.log(f"message table grown to "
                             f"{self.codec.shape.MAX_MSGS} slots; "
                             f"redrawing the round")
                    return "grown", committed, chunk_idx, False
                if bool(ovf):
                    pipe.drain()
                    self.K *= 2
                    self._build(self._max_msgs)
                    obs.grow("cand_cap", self.K)
                    self.log(f"candidate set overflowed; cand_cap "
                             f"grown to {self.K}; redrawing the round")
                    return "grown", committed, chunk_idx, False
                committed = (out[0], out[1], out[2], out[3], out[4])
                step = min(step + self.chunk, S)
                chunk_idx += 1
                fault_point("level", depth=chunk_idx, obs=obs)
                # rem/n_div are exact in-round counts, so both
                # counters stay cumulative-across-the-run mid-round
                # (SCHEMA.md contract; the host validator's rows agree)
                obs.validate_chunk(step,
                                   traces=(res.traces_checked
                                           + active - int(rem)),
                                   divergences=(len(res.divergences)
                                                + int(n_div)),
                                   active=int(rem), base=int(base))
                if on_chunk is not None:
                    on_chunk(step)
                if preempt_signal() is not None:
                    pipe.drain()
                    raise self._rescue(
                        checkpoint_path, base=base, active=active,
                        step=step, committed=committed, res=res,
                        digest=digest, chunks=chunk_idx, obs=obs,
                        extra=rescue_extra)
                if int(rem) == 0:
                    pipe.drain()
                    break
                if deadline is not None and time.time() > deadline:
                    pipe.drain()
                    stopped = True
                    break
        finally:
            pipe.drain()
        return "done", committed, chunk_idx, stopped

    # -- divergence reporting ------------------------------------------
    def _enabled_from_lanes(self, mask):
        """Device lane mask -> the sorted spec-side enabled set with
        action/location metadata — aggregated to the ACTION level so
        the record is byte-identical to the interpreter validator's
        ``divergence_record`` shape (one stable report shape across
        both engines; lane params are a device-layout detail)."""
        names = self.kern.action_names
        la = np.asarray(self.kern.lane_action)
        locs = {a.name: a.location for a in self.spec.actions}
        seen = sorted({names[int(la[ln])]
                       for ln in np.nonzero(np.asarray(mask))[0]})
        return [{"action": a, "location": locs.get(a)} for a in seen]

    def _commit_round(self, res, rtraces, div_at, div_en, div_cand,
                      pre_div, obs):
        for i, t in enumerate(rtraces):
            res.traces_checked += 1
            if i in pre_div:
                verdict = validate_trace(self.spec, t)
                rec = divergence_record(t, verdict)
                res.divergences.append(rec)
                obs.divergence(t.tid, rec["step"],
                               candidates=rec["candidates"])
                continue
            if div_at[i] < 0:
                res.accepted += 1
                continue
            step = int(div_at[i])
            ev = (t.events[step].to_record()
                  if step < len(t.events) else {})
            rec = {"trace": t.tid, "step": step, "event": ev,
                   "enabled": self._enabled_from_lanes(div_en[i]),
                   "candidates": int(div_cand[i])}
            if self.confirm:
                verdict = validate_trace(self.spec, t)
                if verdict.ok or verdict.diverged_at != step:
                    raise TLAError(
                        f"device/interpreter divergence: the batch "
                        f"validator reports trace {t.tid} diverging "
                        f"at event {step}, but the interpreter says "
                        f"{'accepted' if verdict.ok else f'event {verdict.diverged_at}'}")
                if verdict.violated_invariant:
                    rec["invariant"] = verdict.violated_invariant
                    rec["invariant_step"] = verdict.violated_at
            res.divergences.append(rec)
            obs.divergence(t.tid, step,
                           enabled=[e["action"] for e in rec["enabled"]],
                           candidates=rec["candidates"])

    # -- the entry -----------------------------------------------------
    @closes_observer
    def run(self, traces, *, checkpoint_path=None, resume_from=None,
            obs=None, log=None, max_seconds=None,
            on_chunk=None) -> ValidateResult:
        """Validate `traces` (a list of :class:`Trace`) in rounds of
        ``batch``; returns a :class:`ValidateResult` whose
        ``divergences`` records are bit-identical across mesh sizes,
        batch sizes and rescue/resume seams (module docstring)."""
        if log is not None:
            self._log = self._log or log
        obs = RunObserver.ensure(obs, "validate", self.spec, log=log)
        self._obs_active = obs
        if self._obs_checked[0] is not traces:
            self.check_observations(traces)
        digest = self._obs_checked[1]
        res = ValidateResult(batch=self.batch)
        t0 = time.time()
        base = 0
        round_active = None
        chunks = 0
        resume = None
        if resume_from:
            manifest, resume = self._load_resume(resume_from, digest)
            base = int(manifest["base"])
            round_active = int(manifest["active"])
            chunks = int(manifest.get("chunks", 0))
            res.traces_checked = int(manifest.get("traces", 0))
            res.accepted = int(manifest.get("accepted", 0))
            res.divergences = list(
                (manifest.get("extra") or {}).get("divergences") or [])
            res.batch = self.batch
            t0 -= float(manifest["elapsed"])
        obs.start(t0, backend=jax.default_backend(),
                  resumed=resume_from is not None)
        obs.gauge("mesh_devices", self.D)
        obs.gauge("pipeline_depth", self.pipeline)
        obs.gauge("cand_cap", self.K)
        obs.gauge("validate_batch", self.batch)
        deadline = (t0 + max_seconds) if max_seconds else None
        retries = 0
        while base < len(traces):
            active = (round_active if round_active is not None
                      else min(self.batch, len(traces) - base))
            round_active = None
            rtraces = traces[base:base + active]
            try:
                (div_at, div_en, div_cand, pre_div, chunks,
                 stopped) = self._run_round(
                    rtraces, base=base, obs=obs,
                    checkpoint_path=checkpoint_path,
                    on_chunk=on_chunk, chunks_before=chunks, res=res,
                    digest=digest, deadline=deadline, resume=resume)
            except Preempted:
                raise
            except Exception as e:  # noqa: BLE001 — OOM ladder below
                resume = None
                self._restore_batch = None   # the degrade wins
                if not self._try_degrade_oom(e, retries, obs):
                    raise
                retries += 1
                continue
            resume = None
            if stopped:
                # deadline-cut round: its traces did NOT finish — do
                # not report them (a half-scanned trace is neither
                # accepted nor diverged)
                res.error = "deadline"
                break
            self._commit_round(res, rtraces, div_at, div_en, div_cand,
                               pre_div, obs)
            base += active
            if self._restore_batch is not None:
                if self._restore_batch != self.batch:
                    self._set_batch(self._restore_batch)
                    res.batch = self.batch
                    obs.gauge("validate_batch", self.batch)
                    self.log(f"rescued round committed; batch rescaled "
                             f"to the requested {self.batch}")
                self._restore_batch = None
            obs.progress(traces=res.traces_checked,
                         extra=f"{len(res.divergences)} divergence(s)")
        # a deadline stop is an incomplete run, not a divergence —
        # res.error says so; ok mirrors the BFS time-budget contract
        res.ok = not res.divergences
        obs.gauge("divergences", len(res.divergences))
        obs.gauge("cand_cap", self.K)
        return obs.finish(res)

    def _try_degrade_oom(self, e, retries, obs):
        """The validator's OOM ladder: halve the round batch (fewer
        traces resident per dispatch) and redraw — per-trace results
        are independent of round boundaries, so the degraded run's
        report is unchanged."""
        from ..resilience.faults import InjectedFault
        if not is_oom(e) or retries >= self.max_retries \
                or self.batch // 2 < self.min_batch:
            return False
        if not isinstance(e, InjectedFault):
            obs.fault("oom", "level")
        old = self.batch
        self._set_batch(self.batch // 2)
        obs.degrade("validate_batch", old, self.batch)
        obs.retry(retries + 1, 0.0)
        obs.gauge("validate_batch", self.batch)
        self.log(f"OOM ({e}): halving the round batch {old} -> "
                 f"{self.batch} traces and redrawing")
        return True


def ev_slice_d(src, key, s0, chunk, t_pad, fill):
    """Slice one observation plane ``src[key][:, s0:s0+chunk]``,
    padded to the chunk width (steps beyond the round's last event are
    unobserved and inactive anyway — ``tlen`` gates them)."""
    sl = src[key][:, s0:s0 + chunk]
    if sl.shape[1] < chunk:
        pad_shape = (t_pad, chunk - sl.shape[1]) + sl.shape[2:]
        sl = np.concatenate([sl, np.full(pad_shape, fill, sl.dtype)],
                            axis=1)
    return sl


def batch_validate(spec, traces, *, batch=1024, n_devices=None,
                   chunk_steps=8, cand_cap=4, max_msgs=None,
                   pipeline=2, confirm=True, model_factory=None,
                   checkpoint_path=None, resume_from=None, obs=None,
                   log=None, max_seconds=None) -> ValidateResult:
    """One-call batched validation (the CLI ``-validate`` engine)."""
    bv = BatchValidator(spec, batch=batch, n_devices=n_devices,
                        chunk_steps=chunk_steps, cand_cap=cand_cap,
                        max_msgs=max_msgs, pipeline=pipeline,
                        confirm=confirm, model_factory=model_factory,
                        log=log)
    return bv.run(traces, checkpoint_path=checkpoint_path,
                  resume_from=resume_from, obs=obs, log=log,
                  max_seconds=max_seconds)


def validate_result_summary(res):
    """ValidateResult -> the JSON-able summary stored on a service
    job."""
    return {"ok": bool(res.ok), "traces": int(res.traces_checked),
            "accepted": int(res.accepted),
            "divergences": list(res.divergences or []),
            "first_divergence": res.first_divergence,
            "error": res.error,
            "elapsed_s": round(float(res.elapsed or 0.0), 3)}


def run_validate_job(spec, traces, *, checkpoint_path=None,
                     journal_path=None, metrics_path=None, log=None,
                     observer_factory=None, **kwargs) -> Outcome:
    """The worker-process entry for ``kind="validate"`` jobs — the
    validation twin of ``sim.hunt.run_hunt_job``: run a batch
    validation under a PreemptionGuard and reify every ending as an
    :class:`Outcome` through the one exit-code table:

    * every trace accepted            -> ``done`` (EX_OK)
    * divergences found               -> ``violated`` (EX_VIOLATION)
    * SIGTERM/cancel/scheduler tick   -> ``preempted-requeued``
      (EX_RESUMABLE) with the candidate-frontier rescue attached
    * anything else                   -> ``failed`` (EX_SOFTWARE)

    Unencodable observations fall back to the interpreter validator
    (the CLI idiom): pre-flighted BEFORE the journal-backed observer
    is handed over, so the fallback run still writes the job's
    journal/metrics through the same observer.
    """
    from .host import host_validate_batch
    factory = observer_factory or RunObserver
    obs = factory(journal_path=journal_path,
                  metrics_path=metrics_path, log=log)
    summary = {"engine": "validate", "traces": len(traces)}
    run_kw = {k: kwargs.pop(k) for k in ("resume_from", "max_seconds")
              if k in kwargs}
    try:
        with PreemptionGuard(log=log):
            bv = None
            try:
                bv = BatchValidator(spec, log=log, **kwargs)
                bv.check_observations(traces)
            except ObservationUnsupported as e:
                if log:
                    log(f"{e}; falling back to the interpreter "
                        f"validator")
                res = host_validate_batch(
                    spec, traces, obs=obs, log=log,
                    max_seconds=run_kw.get("max_seconds"))
                bv = None
            if bv is not None:
                res = bv.run(traces, checkpoint_path=checkpoint_path,
                             obs=obs, log=log, **run_kw)
    except Preempted as p:
        return Outcome(
            state=job_state(EX_RESUMABLE), exit_code=EX_RESUMABLE,
            rescue={"path": p.path, "depth": p.depth,
                    "distinct": p.distinct, "signal": p.signal},
            summary=summary)
    except Exception as e:  # noqa: BLE001 — reified, not swallowed
        return Outcome(state=job_state(EX_SOFTWARE),
                       exit_code=EX_SOFTWARE,
                       error=f"{type(e).__name__}: {e}",
                       summary=summary)
    summary["traces"] = res.traces_checked
    summary["divergences"] = len(res.divergences or [])
    code = EX_OK if res.ok else EX_VIOLATION
    return Outcome(state=job_state(code), exit_code=code, result=res,
                   summary=summary)
