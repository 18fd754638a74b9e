"""Sharded walker-fleet simulation (ISSUE 7 tentpole).

``FleetSimulator`` supersedes the scan-loop in ``engine/device_sim.py``
as the simulation backend: 10^5+ concurrent walkers advance in fused
multi-step chunks inside one jit, vmapped over the per-walker step and
shard_mapped across a 1-D device mesh (the ``engine/paged_bfs``/
``parallel/sharded_bfs`` idiom), with the ``engine/pipeline.py``
dispatch window keeping chunks in flight so host work (journal,
metrics, scheduler ticks) never stalls the fleet.

**Seed-reproducibility contract.**  Walk ``i`` is a pure function of
``(seed, i)``: every per-step draw comes from
``fold_in(fold_in(PRNGKey(seed), i), step)``, so a walk's action
sequence does not depend on the walker count, the mesh shape, or where
a rescue/resume seam fell.  Rounds cover contiguous walk-id ranges in
increasing order (round ``r`` starts at the id where round ``r-1``
ended), and a violating round always runs to its full depth before
reporting, with the reported violation chosen as the one on the
**minimum walk id** (at that walk's first violating step).  Together
these make the replayed TRACE-format counterexample bit-identical for
a fixed seed across walker counts (the first violating id encountered
while scanning ids in order is the globally minimal one), across mesh
sizes (every on-device op in the walk path is per-walker elementwise,
reductions are integer psums), and across a rescue/resume (snapshots
restore the committed chunk boundary bit-exactly; keys are stateless).
Importance splitting (``splitting.py``) trades the walker-count leg of
this contract for hit rate — guided runs stay bit-identical across
mesh sizes and rescue/resume seams for a fixed (seed, walkers).

**Resilience.**  ``oom@level=N`` / ``kill@level=N`` faults fire at
chunk boundaries (``level`` = completed-chunk index).  On OOM — real
RESOURCE_EXHAUSTED or injected — the fleet degrades by halving its
walker count (journaled ``degrade {what: "walkers"}``) and redraws the
round; SIGTERM under a ``PreemptionGuard`` writes a rescue snapshot of
the walker frontier at the committed chunk boundary and raises
``Preempted`` (the exit-75 contract), which ``resume_from`` continues
bit-identically.
"""

from __future__ import annotations

import json
import os
import shutil
import time

import numpy as np

import jax
import jax.numpy as jnp

from ..engine.checkpoint import _crc32_file, _fsync_path, spec_digest
from ..engine.device_bfs import _align8
from ..engine.device_sim import materialize_walk
from ..engine.pipeline import DispatchPipeline
from ..engine.simulate import SimResult
from ..engine.spec import SpecModel
from ..models import registry
from ..obs import RunObserver, closes_observer
from ..resilience.faults import InjectedFault, fault_point
from ..resilience.supervisor import Preempted, is_oom, preempt_signal

I32 = jnp.int32
U32 = jnp.uint32

FLEET_FORMAT = 1
#: payload files of a fleet snapshot (walkers.npz is absent on a
#: round-boundary snapshot — the next round restarts from init states)
FLEET_PAYLOADS = ("walkers.npz", "hist.npz", "seen.npz")


# ---------------------------------------------------------------------
# fleet snapshots: the walker-frontier rescue format (manifest + CRC'd
# npz payloads, atomic rename — the engine checkpoint idiom, minus the
# BFS-specific payload set)
# ---------------------------------------------------------------------
def save_fleet_snapshot(path, *, manifest, arrays=None,
                        kind="fleet-sim"):
    """Write a fleet snapshot to `path` (atomic + durable).

    ``manifest`` is the JSON-able driver state; ``arrays`` maps payload
    file name -> {array name -> np array} (omit a payload to skip it —
    a round-boundary snapshot carries no walker arrays).  The manifest
    mirrors the engine checkpoint's ``depth``/``fp_count``/``elapsed``
    keys so ``checkpoint.snapshot_info`` (the dispatch service's cheap
    rescue-handoff reader) works on fleet snapshots unchanged.
    ``kind`` distinguishes snapshot families sharing this format (the
    batched trace validator writes ``kind="validate"``, ISSUE 8)."""
    tmp = path + ".ckpt-tmp"
    if os.path.isdir(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)
    arrays = arrays or {}
    written = []
    for name in FLEET_PAYLOADS:
        if name not in arrays:
            continue
        np.savez_compressed(os.path.join(tmp, name),
                            **{k: np.asarray(v)
                               for k, v in arrays[name].items()})
        written.append(name)
    manifest = dict(manifest)
    manifest["format"] = FLEET_FORMAT
    manifest["kind"] = kind
    manifest["payload_crc32"] = {
        name: _crc32_file(os.path.join(tmp, name)) for name in written}
    with open(os.path.join(tmp, "manifest.json"), "w") as f:
        json.dump(manifest, f)
        f.flush()
        os.fsync(f.fileno())
    for name in written:
        _fsync_path(os.path.join(tmp, name))
    _fsync_path(tmp)
    old = path + ".old"
    if os.path.isdir(old):
        shutil.rmtree(old)
    if os.path.isdir(path):
        os.rename(path, old)
    os.rename(tmp, path)
    parent = os.path.dirname(os.path.abspath(path)) or "."
    _fsync_path(parent)
    if os.path.isdir(old):
        shutil.rmtree(old)


def load_fleet_snapshot(path, expect_digest=None, kind="fleet-sim"):
    """Read + CRC-verify a fleet snapshot; returns (manifest, arrays).
    Raises ValueError on a wrong-kind snapshot, CRC mismatch, or a
    spec-digest mismatch (resuming a different model is a policy
    error, never masked)."""
    with open(os.path.join(path, "manifest.json")) as f:
        manifest = json.load(f)
    if manifest.get("kind") != kind \
            or manifest.get("format") != FLEET_FORMAT:
        raise ValueError(
            f"{path}: not a {kind}/{FLEET_FORMAT} snapshot "
            f"(kind={manifest.get('kind')!r})")
    if expect_digest is not None and manifest.get("spec_digest") and \
            manifest["spec_digest"] != expect_digest:
        raise ValueError(
            f"fleet snapshot was written by a different spec/.cfg "
            f"(digest {manifest['spec_digest']}, this run "
            f"{expect_digest}); refusing to resume")
    arrays = {}
    for name, want in (manifest.get("payload_crc32") or {}).items():
        p = os.path.join(path, name)
        if _crc32_file(p) != int(want):
            raise ValueError(f"{p}: CRC32 mismatch (snapshot payload "
                             f"corrupted after write)")
        with np.load(p) as z:
            arrays[name] = {k: z[k] for k in z.files}
    return manifest, arrays


def fleet_snapshot_info(path):
    """Cheap manifest-only summary (walks/steps/step), or None."""
    try:
        with open(os.path.join(path, "manifest.json")) as f:
            mf = json.load(f)
        if mf.get("kind") != "fleet-sim":
            return None
        return {"path": path, "walks": int(mf["walks"]),
                "steps": int(mf["steps"]), "step": int(mf["step"]),
                "base": int(mf["base"]),
                "elapsed": float(mf["elapsed"])}
    except (OSError, ValueError, KeyError, TypeError):
        return None


class FleetSimulator:
    """The sharded walker fleet (module docstring has the contract).

    ``walkers`` is the fleet size (padded up to a multiple of the mesh
    size; pad slots never act); ``n_devices``/``mesh`` pick the 1-D
    mesh (default: every visible device, capped at the walker count).
    ``action_weights``/``swarm_sigma`` are the scheduler-bias knobs
    carried over from ``DeviceSimulator`` — swarm noise is drawn from
    each walk's own key, so it respects the per-walk determinism
    contract.  ``split=NoveltySplitter(...)`` (or True for defaults)
    enables importance splitting at chunk boundaries; splitting
    serializes the dispatch window (the resample is a population-wide
    host step, so speculative chunks past a split boundary would be
    wrong).  ``pipeline`` is the ``engine/pipeline.py`` dispatch-window
    depth for unguided runs."""

    def __init__(self, spec: SpecModel, walkers=4096, n_devices=None,
                 mesh=None, chunk_steps=16, max_msgs=None,
                 action_weights=None, swarm_sigma=0.0, split=None,
                 pipeline=2, dispatch="grouped", group_caps=None,
                 min_walkers=64, max_retries=4, model_factory=None,
                 seen_capacity=1 << 14, log=None, symmetry="auto"):
        # symmetry canonicalization (ISSUE 11): fleet fingerprints
        # only feed the novelty seen-set (splitting.py), so the canon
        # seam makes novelty count ORBITS — a walker exploring a
        # permuted replay of seen territory scores as revisiting.
        # "auto" = on iff the cfg declares SYMMETRY; verdicts and the
        # (seed, walk-id) determinism contract are untouched (canon is
        # a pure function applied pre-insert)
        self._symmetry_req = symmetry
        self._model_factory = model_factory or (
            lambda spec, max_msgs=None: registry.make_model(
                spec, max_msgs=max_msgs, fold_symmetry=False))
        self.spec = spec
        self.inv_names = list(spec.cfg.invariants)
        self.chunk = int(chunk_steps)
        self.dispatch = dispatch
        self.group_caps = list(group_caps) if group_caps else None
        self.min_walkers = int(min_walkers)
        self.max_retries = int(max_retries)
        self.swarm_sigma = float(swarm_sigma)
        self._log = log
        self._resolve_weights(action_weights)
        if split is True:
            from .splitting import NoveltySplitter
            split = NoveltySplitter(capacity=seen_capacity)
        self.splitter = split or None
        self.pipeline = 1 if self.splitter is not None \
            else max(1, int(pipeline))
        if mesh is not None:
            self.mesh = mesh
            self.axis = mesh.axis_names[0]
            self._n_req = mesh.shape[self.axis]
        else:
            self.mesh = None
            self.axis = "d"
            self._n_req = n_devices      # None = every visible device
        self._max_msgs = max_msgs
        # keep_caps: the constructor's calibrated caps (e.g. a prior
        # sim_scale round's steady state) survive the first build;
        # later reshapes re-derive defaults for the new local size
        self._set_walkers(int(walkers), keep_caps=True)

    # -- construction --------------------------------------------------
    def log(self, msg):
        if self._log:
            self._log(f"fleet: {msg}")

    def _resolve_weights(self, aw):
        self._action_weights = aw
        self.log_w = None if aw is None else "deferred"

    def _set_walkers(self, walkers, keep_caps=False):
        """(Re)build the fleet at a walker count: recompute the mesh
        and padding, recompile the chunk kernel.  The elastic and
        OOM-degrade knob."""
        if walkers < 1:
            raise ValueError(f"walkers must be >= 1 (got {walkers})")
        self.walkers = int(walkers)
        n = self._n_req or len(jax.devices())
        n = max(1, min(int(n), self.walkers, len(jax.devices())))
        if self.mesh is None or self.mesh.shape[self.axis] != n:
            # != not >: a fleet whose mesh was capped small (walkers <
            # requested devices) regains devices on a later grow
            from jax.sharding import Mesh
            self.mesh = Mesh(np.array(jax.devices()[:n]), (self.axis,))
        self.D = self.mesh.shape[self.axis]
        self.W_pad = -(-self.walkers // self.D) * self.D
        if not keep_caps:
            self.group_caps = None   # re-derived for the new local size
        self._build(self._max_msgs)

    def _symmetry_on(self):
        """True when novelty fingerprints are orbit-reduced — via the
        canon seam or a factory-supplied folded kernel (NOT merely
        because the cfg declares SYMMETRY: symmetry=False really
        turns the fold off)."""
        return self._canon is not None or (
            bool(self.spec.symmetry_perms) and self._sym_fold > 1)

    def _build(self, max_msgs):
        """Compile the fused multi-step chunk kernel for the current
        (walkers, mesh, message-table, dispatch-cap) shape."""
        from ..parallel.sharded_bfs import _shard_map
        self._max_msgs = max_msgs
        self.codec, self.kern = self._model_factory(self.spec,
                                                    max_msgs=max_msgs)
        from ..engine.canon import build_canon_spec, kernel_fold_order
        self._sym_fold = kernel_fold_order(self.kern)
        if self._sym_fold > 1:
            # a factory-supplied folded kernel already orbit-folds its
            # fingerprints — the novelty seen-set needs no extra canon
            self._canon = None
        else:
            self._canon = build_canon_spec(self.spec, self.codec,
                                           self.kern,
                                           self._symmetry_req)
        kern = self.kern
        names = kern.action_names
        n_act = len(names)
        if self._action_weights is not None:
            aw = self._action_weights
            if isinstance(aw, dict):
                w = np.ones(len(names))
                for name, x in aw.items():
                    w[names.index(name)] = x
            else:
                w = np.asarray(aw, float)
            if w.shape != (len(names),) or (w <= 0).any():
                raise ValueError("action_weights must be positive, "
                                 "one per action")
            self.log_w = np.log(w)
        inv = kern.invariant_fn(self.inv_names)
        lane_aid = jnp.asarray(kern.lane_action)
        lane_prm = jnp.asarray(kern.lane_param)
        guards = kern._guard_fns()
        fns = kern._action_fns()
        L = int(lane_aid.shape[0])
        W_loc = self.W_pad // self.D

        def guard_all(st):
            outs = []
            for name, g in zip(names, guards):
                lanes = jnp.arange(kern._lane_count(name), dtype=I32)
                outs.append(jax.vmap(lambda ln, g=g: g(st, ln))(lanes))
            return jnp.concatenate(outs)

        if self.group_caps is None:
            self.group_caps = [min(W_loc, max(32, W_loc // 4))] * n_act
        caps = [min(int(c), W_loc) for c in self.group_caps]

        def apply_dense(states, aid, prm, act):
            # compute-all-actions + mask-select (see DeviceSimulator:
            # the vmapped lax.switch lowering miscompiled on TPU)
            out = None
            for a, f in enumerate(fns):
                s_a, _en = jax.vmap(f, in_axes=(0, 0))(states, prm)
                m = aid == a
                if out is None:
                    out = {k: jnp.where(
                        m.reshape((-1,) + (1,) * (v.ndim - 1)), v,
                        states[k])
                        for k, v in s_a.items() if not k.startswith("_")}
                else:
                    out = {k: jnp.where(
                        m.reshape((-1,) + (1,) * (s_a[k].ndim - 1)),
                        s_a[k], v) for k, v in out.items()}
            return out, jnp.zeros((n_act,), I32)

        def apply_grouped(states, aid, prm, act):
            # guard-gathered grouped dispatch (the DeviceSimulator
            # round-3 win): each action body runs on just the walkers
            # that chose it.  The EXACT per-action chooser counts ride
            # out of the chunk (ISSUE 10: the same exact-count packing
            # the BFS level kernel adopted) so a cap overflow grows
            # straight to the true high-water mark instead of doubling
            # blind; the redraw stays exact (same keys -> same draws)
            out = {k: v for k, v in states.items()}
            cnt = []
            for a, f in enumerate(fns):
                C = caps[a]
                m = (aid == a) & act
                cnt.append(m.sum(dtype=I32))
                (sel,) = jnp.nonzero(m, size=C, fill_value=W_loc)
                ok = sel < W_loc
                idx = jnp.clip(sel, 0, W_loc - 1)
                st_a = {k: v[idx] for k, v in states.items()}
                s_a, _en = jax.vmap(f, in_axes=(0, 0))(st_a, prm[idx])
                dest = jnp.where(ok, sel, W_loc).astype(I32)
                for k in out:
                    out[k] = out[k].at[dest].set(s_a[k], mode="drop")
            return out, jnp.stack(cnt)

        apply_chosen = (apply_grouped if self.dispatch == "grouped"
                        else apply_dense)
        weighted = self.log_w is not None
        logw = (jnp.asarray(self.log_w, jnp.float32)
                if weighted else None)
        sigma = self.swarm_sigma
        axis = self.axis
        n_steps = self.chunk

        def chunk_fn(key, states, alive, violated_at, dead_at,
                     walk_ids, step0, depth_limit):
            wkeys = jax.vmap(jax.random.fold_in,
                             in_axes=(None, 0))(key, walk_ids)
            if weighted:
                wlogw = jnp.broadcast_to(logw[None, :],
                                         (walk_ids.shape[0], n_act))
                if sigma > 0.0:
                    nk = jax.vmap(jax.random.fold_in,
                                  in_axes=(0, None))(
                        wkeys, jnp.uint32(0xA5A5))
                    noise = jax.vmap(
                        lambda k: jax.random.normal(k, (n_act,)))(nk)
                    wlogw = wlogw + noise * sigma

            def step(carry, t):
                (states, alive, violated_at, dead_at, steps, err_any,
                 need) = carry
                d = step0 + t
                on = d < depth_limit
                keys = jax.vmap(jax.random.fold_in,
                                in_axes=(0, None))(
                    wkeys, d.astype(jnp.uint32))
                en = jax.vmap(guard_all)(states)
                if weighted:
                    k1 = jax.vmap(jax.random.fold_in,
                                  in_axes=(0, None))(keys, jnp.uint32(1))
                    k2 = jax.vmap(jax.random.fold_in,
                                  in_axes=(0, None))(keys, jnp.uint32(2))
                    act_en = jnp.zeros((en.shape[0], n_act), bool) \
                        .at[:, lane_aid].max(en)
                    g = jax.vmap(
                        lambda k: jax.random.gumbel(k, (n_act,)))(k1) \
                        + wlogw
                    a_star = jnp.argmax(
                        jnp.where(act_en, g, -jnp.inf), axis=1)
                    v = jax.vmap(
                        lambda k: jax.random.uniform(k, (L,)))(k2)
                    in_act = en & (lane_aid[None, :] == a_star[:, None])
                    lane = jnp.argmax(jnp.where(in_act, v, -1.0),
                                      axis=1)
                else:
                    u = jax.vmap(
                        lambda k: jax.random.uniform(k, (L,)))(keys)
                    lane = jnp.argmax(jnp.where(en, u, -1.0), axis=1)
                can = en.any(axis=1)
                act = alive & can & on
                newly_dead = alive & ~can & on
                dead_at = jnp.where(newly_dead & (dead_at < 0),
                                    d, dead_at)
                aid = lane_aid[lane]
                prm = lane_prm[lane]
                succ, cnt_a = apply_chosen(states, aid, prm, act)
                selm = {k: act.reshape((-1,) + (1,) * (v.ndim - 1))
                        for k, v in states.items()}
                states = {k: jnp.where(selm[k], succ[k], v)
                          for k, v in states.items()}
                err = act & (states["err"] != 0)
                iok = jax.vmap(inv)(states)
                badw = act & ~iok & ~err
                violated_at = jnp.where(badw & (violated_at < 0),
                                        d + 1, violated_at)
                alive = jnp.where(on, alive & can & ~badw, alive)
                steps = steps + act.sum(dtype=I32)
                err_any = err_any | err.any()
                hist = (jnp.where(act, aid, -1).astype(I32),
                        jnp.where(act, prm, 0).astype(I32))
                return (states, alive, violated_at, dead_at, steps,
                        err_any, jnp.maximum(need, cnt_a)), hist

            init = (states, alive, violated_at, dead_at,
                    jnp.asarray(0, I32), jnp.asarray(False),
                    jnp.zeros((n_act,), I32))
            (states, alive, violated_at, dead_at, steps, err_any,
             need), hist = jax.lax.scan(
                step, init, jnp.arange(n_steps, dtype=I32))
            steps_g = jax.lax.psum(steps, axis)
            n_alive = jax.lax.psum(alive.sum(dtype=I32), axis)
            n_events = jax.lax.psum(
                ((violated_at >= 0) | (dead_at >= 0)).sum(dtype=I32),
                axis)
            err_g = jax.lax.psum(err_any.astype(I32), axis) > 0
            # exact per-action chooser maxima, fleet-maxed: the host
            # compares against the live caps and grows to the true
            # need (ISSUE 10 exact-count packing)
            need_g = jax.lax.pmax(need, axis)
            return (states, alive, violated_at, dead_at, hist,
                    steps_g, n_alive, n_events, err_g, need_g)

        from jax.sharding import PartitionSpec as P
        sp = P(self.axis)
        # donate the walker-state carry (ISSUE 10 satellite /
        # ROADMAP item 2 residual): each chunk writes its successor
        # states INTO the previous generation's HBM buffers instead of
        # holding two walker generations.  The small per-walker event
        # arrays (alive/violated/dead) stay un-donated — deadline
        # stops and round ends read them off the committed ticket.
        # Guided (splitter) runs keep the un-donated kernel: the
        # resample and its redraw paths read the committed states
        # directly, and a splitter round is not replayable (the seen
        # set mutates per chunk), so the replay-rebuild the donated
        # growth/rescue paths use is unavailable there.
        self._donate = self.splitter is None
        self._chunk = jax.jit(_shard_map(
            chunk_fn, self.mesh,
            in_specs=(P(), sp, sp, sp, sp, sp, P(), P()),
            out_specs=(sp, sp, sp, sp, (P(None, self.axis),
                                        P(None, self.axis)),
                       P(), P(), P(), P(), P())),
            donate_argnums=(1,) if self._donate else ())
        self._fresh_jit = True
        if self.splitter is not None:
            self.splitter.bind(kern, canon=self._canon)
        self._mat = {}
        # the encoded init batch is a pure function of the codec (and
        # the codec only changes on a rebuild) — cache it per build
        # instead of re-enumerating spec.init_states() every round
        # (ROADMAP item 2 residual)
        self._init_cache = None

    # -- growth --------------------------------------------------------
    def _grow_msgs(self, batches):
        old = self.codec.shape.MAX_MSGS
        self._build(old * 2)
        return [self.codec.pad_msgs(b, old) for b in batches]

    def _replay_states(self, key, walk_ids, depth_j, upto_step, base):
        """Rebuild the committed walker STATES at ``upto_step`` by
        re-executing the round's chunks from the host-side ``base``
        (the round's entry carry — start or resume point).  Only the
        donated-carry growth/rescue paths need this: later launches
        wrote into the committed generation's HBM buffers, and the
        per-(seed, walk-id) determinism contract makes the replay
        exact (same keys -> same draws; cap/table growth never changes
        a draw).  The event arrays (alive/violated/dead) are never
        donated, so only the states come from the replay."""
        step0, h_states, h_alive, h_violated, h_dead = base
        states = {k: jnp.asarray(v) for k, v in h_states.items()}
        alive = jnp.asarray(h_alive)
        violated = jnp.asarray(h_violated)
        dead = jnp.asarray(h_dead)
        s = step0
        while s < upto_step:
            out = self._chunk(key, states, alive, violated, dead,
                              walk_ids, jnp.asarray(s, I32), depth_j)
            states, alive, violated, dead = out[0], out[1], out[2], out[3]
            s += self.chunk
        return states

    # -- replay --------------------------------------------------------
    def replay(self, init_row, hists, slot, n_steps):
        """Re-execute walker `slot`'s first `n_steps` recorded choices
        into a TRACE-format counterexample (``TraceEntry`` list) —
        the one shared materialize-replay (engine/device_sim.py)."""
        aids = np.concatenate(
            [np.asarray(ha)[:, slot] for ha, _hp in hists]) \
            if hists else np.zeros((0,), np.int32)
        prms = np.concatenate(
            [np.asarray(hp)[:, slot] for _ha, hp in hists]) \
            if hists else np.zeros((0,), np.int32)
        st = {k: np.asarray(v) for k, v in init_row.items()}
        return materialize_walk(self.kern, self.codec, self.spec, st,
                                aids, prms, n_steps, cache=self._mat)

    # -- round driver --------------------------------------------------
    def _init_batch(self, base, active):
        """Dense walker batch at the round start: walker slot s begins
        at init state ``(base + s) % n_init`` (the per-walk
        deterministic analog of TLC's random init choice).  The
        encoded init states are cached per build — enumeration and
        encoding happen once, not once per round."""
        if self._init_cache is None:
            init_dense = [self.codec.encode(st)
                          for st in self.spec.init_states()]
            self._init_cache = (
                {k: np.stack([np.asarray(d[k]) for d in init_dense])
                 for k in init_dense[0]}, len(init_dense))
        batch, n_init = self._init_cache
        idx = (base + np.arange(self.W_pad)) % n_init
        states = {k: v[idx] for k, v in batch.items()}
        alive = np.arange(self.W_pad) < active
        return states, alive

    def run_round(self, *, base, active, depth, key, obs,
                  deadline=None, on_chunk=None, checkpoint_path=None,
                  rescue_extra=None, resume=None, steps_before=0,
                  chunks_before=0, deadlocks_before=0):
        """Run one round: walkers at slots [0, active) walk walk-ids
        [base, base+active) to `depth` (or until every walker froze).
        Returns ``(violated_at, dead_at, hists, init_states, steps,
        completed, chunks)`` — event arrays over the padded slot axis,
        the recorded histories, the round's init batch, the steps
        taken this call, whether the round ran to its natural end, and
        the cumulative committed-chunk index.

        ``on_chunk(committed_depth)`` is the service tick, invoked at
        every committed chunk boundary (where cancel/rebalance
        decisions land).  A pending preemption writes a rescue
        snapshot of the committed walker frontier to
        ``checkpoint_path`` and raises ``Preempted``.  Deterministic
        faults (``oom@level=N`` / ``kill@level=N``) fire as the N-th
        chunk of the round commits."""
        splitter = self.splitter
        if resume is not None:
            step = int(resume["step"])
            states = {k: jnp.asarray(v)
                      for k, v in resume["states"].items()}
            alive = jnp.asarray(resume["alive"])
            violated = jnp.asarray(resume["violated_at"])
            dead = jnp.asarray(resume["dead_at"])
            hists = [(jnp.asarray(ha), jnp.asarray(hp))
                     for ha, hp in resume["hists"]]
            init_states = resume["init_states"]
            if splitter is not None:
                if resume.get("split") is not None:
                    splitter.load_state(resume["split"])
                else:
                    splitter.reset(self.W_pad)
        else:
            step = 0
            h_states, h_alive = self._init_batch(base, active)
            init_states = h_states
            states = {k: jnp.asarray(v) for k, v in h_states.items()}
            alive = jnp.asarray(h_alive)
            violated = jnp.full((self.W_pad,), -1, np.int32)
            dead = jnp.full((self.W_pad,), -1, np.int32)
            hists = []
            if splitter is not None:
                splitter.reset(self.W_pad)
        steps_total = 0
        walk_ids = jnp.asarray(
            (base + np.arange(self.W_pad)) % (1 << 31), U32)
        depth_j = jnp.asarray(int(depth), I32)
        # host-side replay base (donated carry, ISSUE 10 satellite):
        # the round's entry carry, kept on host RAM so the
        # growth/rescue paths can rebuild the committed STATES by
        # deterministic replay after later launches consumed their
        # HBM buffers
        replay_base = (
            step,
            {k: np.asarray(jax.device_get(v))
             for k, v in states.items()},
            np.asarray(jax.device_get(alive)),
            np.asarray(jax.device_get(violated)),
            np.asarray(jax.device_get(dead)))

        pipe = DispatchPipeline(self.pipeline, obs,
                                ready=lambda out: out[5])
        launched = step
        committed = (states, alive, violated, dead)
        cur = committed               # newest launched chunk's outputs
        # the fault-site id is the CUMULATIVE committed-chunk index
        # across the whole run (like the BFS engines' absolute level):
        # a resumed run continues past an already-fired kill@level=N
        # instead of re-tripping it every attempt
        chunk_idx = chunks_before
        stop = False

        def pull(out):
            return jax.device_get((out[5], out[6], out[7], out[8],
                                   out[9]))

        try:
            while step < depth:
                while pipe.has_room() and launched < depth:
                    out = pipe.launch(
                        self._chunk, key, cur[0], cur[1], cur[2],
                        cur[3], walk_ids, jnp.asarray(launched, I32),
                        depth_j, fresh=self._fresh_jit,
                        step=launched)
                    self._fresh_jit = False
                    cur = (out[0], out[1], out[2], out[3])
                    launched += self.chunk
                out, sc = pipe.collect(pull)
                steps_k, n_alive, n_events, err_any, need = sc
                if bool(err_any):
                    # bag overflow inside the chunk: drop the window,
                    # grow the message table, pad the committed entry
                    # states AND the round's init batch, redraw
                    pipe.drain()
                    if self._donate:
                        # the committed state buffers were consumed by
                        # later launches: pad the HOST copies (init +
                        # replay base), then rebuild by exact replay
                        ini_pad, base_pad = self._grow_msgs(
                            [{k: jnp.asarray(v)
                              for k, v in init_states.items()},
                             {k: jnp.asarray(v)
                              for k, v in replay_base[1].items()}])
                        init_states = {k: np.asarray(v)
                                       for k, v in ini_pad.items()}
                        replay_base = (replay_base[0],
                                       {k: np.asarray(v)
                                        for k, v in base_pad.items()}
                                       ) + replay_base[2:]
                        committed = (self._replay_states(
                            key, walk_ids, depth_j, step, replay_base),
                            ) + committed[1:]
                    else:
                        st_pad, ini_pad = self._grow_msgs(
                            [committed[0],
                             {k: jnp.asarray(v)
                              for k, v in init_states.items()}])
                        committed = (st_pad,) + committed[1:]
                        init_states = {k: np.asarray(v)
                                       for k, v in ini_pad.items()}
                    obs.grow("message_table",
                             self.codec.shape.MAX_MSGS)
                    self.log(f"message table grown to "
                             f"{self.codec.shape.MAX_MSGS} slots")
                    launched = step
                    cur = committed
                    continue
                need = np.asarray(need)
                W_loc = self.W_pad // self.D
                caps_now = np.minimum(
                    np.asarray(self.group_caps, np.int64), W_loc)
                over = need > caps_now
                if over.any():
                    # dispatch-group cap overflow: grow the flagged
                    # caps straight to the EXACT fleet-maxed chooser
                    # count (ISSUE 10 — no doubling guesses),
                    # recompile, redraw (same keys, same draws)
                    pipe.drain()
                    for a in np.nonzero(over)[0]:
                        self.group_caps[a] = int(min(
                            W_loc, _align8(need[a])))
                        obs.grow("dispatch_group", self.group_caps[a])
                    self._build(self.codec.shape.MAX_MSGS)
                    if self._donate:
                        committed = (self._replay_states(
                            key, walk_ids, depth_j, step, replay_base),
                            ) + committed[1:]
                    launched = step
                    cur = committed
                    continue
                # commit the chunk
                committed = (out[0], out[1], out[2], out[3])
                hists.append(out[4])
                step = min(step + self.chunk, depth)
                steps_total += int(steps_k)
                chunk_idx += 1
                fault_point("level", depth=chunk_idx, obs=obs)
                obs.sim_chunk(depth=step, walks=int(base),
                              steps=steps_before + steps_total,
                              alive=int(n_alive),
                              events=int(n_events), base=int(base))
                if on_chunk is not None:
                    on_chunk(step)
                # the split runs BEFORE any rescue at this boundary:
                # the snapshot then holds the post-split population —
                # exactly the state an uninterrupted run carries into
                # the next chunk — so a guided resume replays
                # bit-identically (resuming pre-split would skip this
                # boundary's resample entirely)
                if splitter is not None and step < depth \
                        and int(n_alive) > 1 \
                        and splitter.due(chunk_idx):
                    (states_s, alive_s, hists, init_states) = \
                        splitter.resample(
                            committed[0], committed[1], committed[2],
                            committed[3], hists, init_states, obs=obs)
                    committed = (states_s, alive_s, committed[2],
                                 committed[3])
                    cur = committed
                if preempt_signal() is not None:
                    pipe.drain()
                    if self._donate and launched > step:
                        # speculative launches consumed the committed
                        # state buffers — rebuild them by exact replay
                        # before the snapshot reads them
                        committed = (self._replay_states(
                            key, walk_ids, depth_j, step, replay_base),
                            ) + committed[1:]
                    raise self._rescue(
                        checkpoint_path, base=base, active=active,
                        step=step, depth=depth, committed=committed,
                        hists=hists, init_states=init_states,
                        steps=steps_before + steps_total,
                        chunks=chunk_idx, obs=obs,
                        deadlocks=deadlocks_before,
                        extra=rescue_extra)
                if int(n_alive) == 0:
                    pipe.drain()
                    break
                if deadline is not None and time.time() > deadline:
                    pipe.drain()
                    stop = True
                    break
        finally:
            pipe.drain()
        violated_h = np.asarray(jax.device_get(committed[2]))
        dead_h = np.asarray(jax.device_get(committed[3]))
        return (violated_h, dead_h, hists, init_states, steps_total,
                not stop, chunk_idx)

    def _rescue(self, path, *, base, active, step, depth, committed,
                hists, init_states, steps, chunks, obs, deadlocks=0,
                extra=None):
        """Write the committed walker frontier as a rescue snapshot
        and return the Preempted to raise."""
        sig = preempt_signal() or "SIGTERM"
        manifest = {
            "spec_digest": spec_digest(self.spec),
            "walkers": self.walkers, "w_pad": self.W_pad,
            "base": int(base), "active": int(active),
            "step": int(step), "round_depth": int(depth),
            "steps": int(steps), "chunks": int(chunks),
            "deadlocks": int(deadlocks),
            "max_msgs": int(self.codec.shape.MAX_MSGS),
            "group_caps": list(self.group_caps),
            # snapshot_info-compat keys (the service's cheap rescue
            # handoff): depth = committed walk step, fp_count = walks
            "depth": int(step), "fp_count": int(base),
            "walks": int(base), "elapsed": float(obs.elapsed()),
            "extra": extra,
        }
        arrays = None
        if path:
            states, alive, violated, dead = committed
            wa = {f"st_{k}": np.asarray(jax.device_get(v))
                  for k, v in states.items()}
            wa["alive"] = np.asarray(jax.device_get(alive))
            wa["violated_at"] = np.asarray(jax.device_get(violated))
            wa["dead_at"] = np.asarray(jax.device_get(dead))
            for k, v in init_states.items():
                wa[f"init_{k}"] = np.asarray(v)
            ha = (np.concatenate([np.asarray(a) for a, _p in hists])
                  if hists else np.zeros((0, self.W_pad), np.int32))
            hp = (np.concatenate([np.asarray(p) for _a, p in hists])
                  if hists else np.zeros((0, self.W_pad), np.int32))
            arrays = {"walkers.npz": wa,
                      "hist.npz": {"ha": ha, "hp": hp}}
            if self.splitter is not None:
                arrays["seen.npz"] = self.splitter.state_arrays()
                manifest["split"] = self.splitter.state_manifest()
            save_fleet_snapshot(path, manifest=manifest, arrays=arrays)
        obs.rescue(path or "", step, base, sig)
        self.log(f"preempted by {sig}: walker frontier rescued at "
                 f"step {step} of the round at base {base}")
        return Preempted(path, step, base, sig)

    def _load_resume(self, path):
        """Read a rescue snapshot into ``run_round(resume=...)`` form.
        Adopts the snapshot's walker count/message table for the
        in-flight round (the caller may reshape at the next round
        boundary); slot arrays are re-padded for this fleet's mesh
        (pad slots are inactive in both layouts, so padding is
        content-free)."""
        manifest, arrays = load_fleet_snapshot(
            path, expect_digest=spec_digest(self.spec))
        # adopt the snapshot's message table and calibrated caps
        # BEFORE the (single) rebuild — an elastic resume must not pay
        # two chunk-kernel compiles
        caps = [int(c) for c in manifest["group_caps"]]
        if int(manifest["walkers"]) != self.walkers:
            self.log(f"snapshot holds {manifest['walkers']} walkers "
                     f"(this fleet wants {self.walkers}); finishing "
                     f"the in-flight round at the snapshot's count")
            self._max_msgs = int(manifest["max_msgs"])
            self.group_caps = caps
            self._set_walkers(int(manifest["walkers"]),
                              keep_caps=True)
        elif int(manifest["max_msgs"]) != self.codec.shape.MAX_MSGS \
                or caps != self.group_caps:
            self._max_msgs = int(manifest["max_msgs"])
            self.group_caps = caps
            self._build(self._max_msgs)
        wa = arrays.get("walkers.npz", {})
        hist = arrays.get("hist.npz", {})

        def repad(v, fill):
            # saved arrays carry the writing mesh's padding; slots
            # >= walkers are inactive either way — pad or truncate
            # the slot axis (axis 0) to this mesh's W_pad
            v = np.asarray(v)
            if v.shape[0] == self.W_pad:
                return v
            if v.shape[0] > self.W_pad:
                return v[:self.W_pad]
            pad = np.broadcast_to(
                fill, (self.W_pad - v.shape[0],) + v.shape[1:])
            return np.concatenate([v, np.ascontiguousarray(pad)])

        states = {k[3:]: None for k in wa if k.startswith("st_")}
        states = {k: repad(wa[f"st_{k}"], wa[f"st_{k}"][:1])
                  for k in states}
        init_states = {k[5:]: repad(wa[k], wa[k][:1])
                       for k in wa if k.startswith("init_")}
        hists = []
        ha, hp = hist.get("ha"), hist.get("hp")
        if ha is not None and ha.shape[0]:
            ha = repad(ha.T, np.int32(-1)).T
            hp = repad(hp.T, np.int32(0)).T
            for off in range(0, ha.shape[0], self.chunk):
                hists.append((ha[off:off + self.chunk],
                              hp[off:off + self.chunk]))
        resume = None
        if int(manifest["step"]) > 0 and states:
            resume = {"step": int(manifest["step"]),
                      "states": states,
                      "alive": repad(wa["alive"], False),
                      "violated_at": repad(wa["violated_at"],
                                           np.int32(-1)),
                      "dead_at": repad(wa["dead_at"], np.int32(-1)),
                      "hists": hists, "init_states": init_states}
            if "split" in manifest and self.splitter is not None:
                sd = dict(manifest["split"])
                for k, v in arrays.get("seen.npz", {}).items():
                    sd[k] = v
                if "novelty" in sd:
                    # the novelty accumulator is slot-indexed too —
                    # re-pad it alongside the walker arrays
                    sd["novelty"] = repad(sd["novelty"],
                                          np.float64(0.0))
                resume["split"] = sd
        return manifest, resume

    def try_degrade_oom(self, e, retries, obs):
        """The fleet's OOM ladder (shared by ``run`` and the hunt
        driver): on a retryable allocation failure, halve the walker
        count — journaled ``degrade {what: "walkers"}`` + ``retry`` —
        and return True so the caller redraws the round.  Returns
        False (caller re-raises) for non-OOM errors, an exhausted
        retry budget, or a fleet already at ``min_walkers``."""
        if not is_oom(e) or retries >= self.max_retries \
                or self.walkers // 2 < self.min_walkers:
            return False
        if not isinstance(e, InjectedFault):
            obs.fault("oom", "level")
        old = self.walkers
        self._set_walkers(self.walkers // 2)
        obs.degrade("walkers", old, self.walkers)
        obs.retry(retries + 1, 0.0)
        obs.gauge("walkers", self.walkers)
        self.log(f"OOM ({e}): halving the fleet {old} -> "
                 f"{self.walkers} walkers and redrawing the round")
        return True

    # -- the TLC-simulator entry ---------------------------------------
    @closes_observer
    def run(self, num=1000, depth=100, seed=0, check_deadlock=False,
            log=None, max_seconds=None, obs=None, checkpoint_path=None,
            resume_from=None, on_chunk=None) -> SimResult:
        """Run walks until `num` of them completed (rounds of
        ``walkers`` at a time), reporting the minimum-walk-id violation
        of the first violating round (module docstring: the
        determinism contract).  The round loop is the shared
        :func:`drive_rounds` driver; only the per-round event handling
        (stop at the first violation) lives here."""
        if log is not None:
            self._log = self._log or log
        obs = RunObserver.ensure(obs, "fleet-sim", self.spec, log=log)
        obs.symmetry = self._symmetry_on()
        res = SimResult()

        def on_round(rr):
            ev = self._pick_event(rr.violated, rr.dead, rr.active,
                                  check_deadlock)
            if ev is None:
                return False
            slot, ev_depth, kind = ev
            res.ok = False
            res.trace = self.replay(
                {k: v[slot] for k, v in rr.init_states.items()},
                rr.hists, slot, ev_depth)
            if kind == "deadlock":
                res.violated_invariant = None
                return True
            confirmed = self.spec.check_invariants(
                res.trace[-1].state)
            if confirmed is None:
                from ..core.values import TLAError
                err = TLAError(
                    "device/interpreter divergence: the fleet "
                    "invariant kernel reported a violation at "
                    f"walk {rr.base + slot} step {ev_depth}, but the "
                    "interpreter accepts the replayed state")
                err.trace = res.trace
                raise err
            res.violated_invariant = confirmed
            return True

        return drive_rounds(
            self, self.spec, res, depth=depth, seed=seed, num=num,
            obs=obs, max_seconds=max_seconds,
            checkpoint_path=checkpoint_path, resume_from=resume_from,
            on_chunk=on_chunk, on_round=on_round, log=log)

    def _pick_event(self, violated, dead, active, check_deadlock):
        """The deterministic violation choice: the minimum walk id
        carrying an event (invariant violation, or — under
        ``check_deadlock`` — a deadlock), at that walk's first event
        step.  Returns (slot, event_depth, kind) or None."""
        v_slots = np.nonzero(violated[:active] >= 0)[0]
        d_slots = (np.nonzero(dead[:active] >= 0)[0]
                   if check_deadlock else np.zeros((0,), int))
        if not len(v_slots) and not len(d_slots):
            return None
        best = None
        for slot in sorted(set(v_slots.tolist())
                           | set(d_slots.tolist())):
            vd = violated[slot] if violated[slot] >= 0 else None
            dd = dead[slot] if (check_deadlock
                               and dead[slot] >= 0) else None
            # within one step the deadlock check comes first
            # (per-walker the two are exclusive; the guard is for
            # belt-and-braces ordering)
            if dd is not None and (vd is None or dd <= vd):
                best = (int(slot), int(dd), "deadlock")
            else:
                best = (int(slot), int(vd), "invariant")
            break
        return best


class RoundData:
    """What one committed round hands to the caller's ``on_round``
    hook: the event arrays over the padded slot axis, the recorded
    histories, the round's init batch, and the round bookkeeping."""

    __slots__ = ("violated", "dead", "hists", "init_states", "base",
                 "active", "completed")

    def __init__(self, violated, dead, hists, init_states, base,
                 active, completed):
        self.violated = violated
        self.dead = dead
        self.hists = hists
        self.init_states = init_states
        self.base = base
        self.active = active
        self.completed = completed


def drive_rounds(sim, spec, res, *, depth, seed, obs, num=None,
                 max_seconds=None, checkpoint_path=None,
                 resume_from=None, on_chunk=None, rescue_extra=None,
                 on_resume=None, on_round=None, should_stop=None,
                 finalize=None, elastic=None, reshape_rounds=False,
                 progress_extra=None, log=None) -> SimResult:
    """THE round driver shared by ``FleetSimulator.run`` and
    ``sim.hunt.run_hunt`` (ISSUE 8 satellite — the rescue/resume and
    OOM-ladder bookkeeping used to be duplicated in both, and the
    missed-deadlocks seam bug had to be fixed twice).

    The driver owns everything mode-independent: resume-manifest
    unpacking, observer start/gauges, the init-state invariant
    pre-check, round sizing, the per-round rescue-extra envelope
    (``seed``/``depth``/``num``/``round_idx`` + the caller's
    ``rescue_extra()`` dict), the fleet OOM degrade ladder, walks/
    steps/deadlocks accounting, and — under ``reshape_rounds`` — the
    walker-count elasticity applied at round boundaries (journaled
    ``hunt_elastic``).  Callers plug in:

    * ``on_round(RoundData) -> bool`` — mode-specific event handling
      (stop-at-first-violation vs collect-and-dedup); truthy = stop;
    * ``should_stop()`` — extra loop-top stop condition;
    * ``on_resume(manifest, extra)`` — restore mode state from a
      rescue snapshot's extra envelope;
    * ``rescue_extra()`` — mode state to carry in the next rescue;
    * ``finalize(res)`` — result fields computed at a NORMAL end (not
      on the init-state-violation fast path);
    * ``elastic(round_idx) -> walkers|None`` — the reshape schedule.
    """
    if depth < 1:
        raise ValueError(f"depth must be >= 1 (got {depth})")
    sim._obs_active = obs
    res.walkers = sim.walkers
    target_walkers = sim.walkers
    t0 = time.time()
    resume = None
    base = 0
    round_active = None
    chunks = 0
    round_idx = 0
    if resume_from:
        manifest, resume = sim._load_resume(resume_from)
        base = int(manifest["base"])
        res.walks = int(manifest["walks"])
        res.steps = int(manifest["steps"])
        res.deadlocks = int(manifest.get("deadlocks", 0))
        round_active = int(manifest["active"])
        chunks = int(manifest.get("chunks", 0))
        t0 -= float(manifest["elapsed"])
        extra = manifest.get("extra") or {}
        # round numbering survives a rescue/resume so elastic
        # schedules don't restart from 0 after a preemption
        round_idx = int(extra.get("round_idx") or 0)
        if on_resume is not None:
            on_resume(manifest, extra)
        res.walkers = sim.walkers
    obs.start(t0, backend=jax.default_backend(),
              resumed=resume_from is not None)
    obs.gauge("walkers", sim.walkers)
    obs.gauge("mesh_devices", sim.D)
    obs.gauge("pipeline_depth", sim.pipeline)
    bad0 = spec.check_invariants(next(iter(spec.init_states())))
    if bad0:
        res.ok = False
        res.violated_invariant = bad0
        return obs.finish(res)
    key = jax.random.PRNGKey(seed)
    deadline = (t0 + max_seconds) if max_seconds else None
    retries = 0
    try:
        while True:
            if num is not None and res.walks >= num:
                break
            if should_stop is not None and should_stop():
                break
            if deadline is not None and time.time() > deadline:
                break
            active = (round_active if round_active is not None else
                      (min(sim.walkers, num - res.walks)
                       if num is not None else sim.walkers))
            round_active = None
            extra_env = {"seed": seed, "depth": depth, "num": num,
                         "round_idx": round_idx}
            if rescue_extra is not None:
                extra_env.update(rescue_extra())
            try:
                (violated, dead, hists, init_states, steps,
                 completed, chunks) = sim.run_round(
                    base=base, active=active, depth=depth, key=key,
                    obs=obs, deadline=deadline, on_chunk=on_chunk,
                    checkpoint_path=checkpoint_path,
                    rescue_extra=extra_env,
                    resume=resume, steps_before=res.steps,
                    chunks_before=chunks,
                    deadlocks_before=res.deadlocks)
            except Exception as e:  # noqa: BLE001 — fleet OOM ladder
                resume = None
                if not sim.try_degrade_oom(e, retries, obs):
                    raise
                retries += 1
                res.walkers = sim.walkers
                # the degraded count IS the new target — regrowing at
                # the next round boundary would just re-trip the OOM
                target_walkers = sim.walkers
                continue
            resume = None
            res.steps += steps
            res.deadlocks += int((dead >= 0).sum())
            stop = bool(on_round(RoundData(
                violated, dead, hists, init_states, base, active,
                completed))) if on_round is not None else False
            if completed:
                res.walks += active
                base += active
                round_idx += 1
            if stop or not completed:
                # an event stopped the run, or a deadline cut the
                # round short (its walks did NOT complete — do not
                # count them; steps, which really ran, are counted)
                break
            obs.progress(walks=res.walks, steps=res.steps,
                         extra=(progress_extra()
                                if progress_extra is not None
                                else None))
            if reshape_rounds:
                # walker-count elasticity, applied at the round
                # boundary (rounds restart from init states, so
                # reshaping is free)
                target = (elastic(round_idx) if elastic is not None
                          else target_walkers)
                if target and int(target) != sim.walkers:
                    old = sim.walkers
                    sim._set_walkers(int(target))
                    target_walkers = sim.walkers
                    obs.hunt_elastic(old, sim.walkers)
                    obs.gauge("walkers", sim.walkers)
                    obs.gauge("mesh_devices", sim.D)
                    if log:
                        log(f"hunt: fleet reshaped {old} -> "
                            f"{sim.walkers} walkers")
    except BaseException:
        # the crash contract: finalize instrumentation (valid journal
        # prefix, no run_end) on ANY escaping exception — Preempted
        # included, whose rescue_checkpoint event is already journaled
        sim._obs_active = None
        obs.close()
        raise
    if finalize is not None:
        finalize(res)
    return obs.finish(res)


def fleet_simulate(spec, num=1000, depth=100, seed=0, walkers=4096,
                   n_devices=None, max_msgs=None, chunk_steps=16,
                   action_weights=None, swarm_sigma=0.0, split=None,
                   pipeline=2, check_deadlock=False, log=None,
                   max_seconds=None, obs=None, checkpoint_path=None,
                   resume_from=None, model_factory=None,
                   symmetry="auto") -> SimResult:
    """One-call fleet simulation (the ``device_simulate`` successor)."""
    sim = FleetSimulator(spec, walkers=walkers, n_devices=n_devices,
                         max_msgs=max_msgs, chunk_steps=chunk_steps,
                         action_weights=action_weights,
                         swarm_sigma=swarm_sigma, split=split,
                         pipeline=pipeline, symmetry=symmetry,
                         model_factory=model_factory, log=log)
    return sim.run(num=num, depth=depth, seed=seed,
                   check_deadlock=check_deadlock, log=log,
                   max_seconds=max_seconds, obs=obs,
                   checkpoint_path=checkpoint_path,
                   resume_from=resume_from)
