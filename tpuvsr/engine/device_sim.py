"""Device simulation mode: vectorized random walks (TLC's simulator,
README:22, rebuilt as a scan-based XLA program; BASELINE.json
configs[2]).

SUPERSEDED as the simulation backend by the sharded walker fleet
(``tpuvsr/sim``, ISSUE 7): the CLI ``-simulate`` path and the service
``kind="sim"`` jobs run the fleet (the benchmark,
``python3 benchmark/run.py --workload W --seed N --seconds S --trace 0|1``,
has no walker cell yet) — per-(seed, walk-id) deterministic draws,
shard_map across the mesh, the ``engine/pipeline.py`` dispatch window,
and importance splitting over a fingerprint-novelty seen-set.  This
class remains the single-device scan oracle (its chunk kernel is the
shape the fleet's was grown from) and the backend for callers that
want the legacy shared-key-stream draw; ``device_simulate(...,
fleet=True)`` delegates to the fleet.

Semantics match TLC's SimulationWorker: each walk starts at the initial
state and repeatedly jumps to a successor chosen uniformly at random
from the full (action x binding) successor list — which is exactly the
kernel's lane space — checking invariants at every visited state, up to
a depth bound.  A walker with no enabled successor stays put (TLC ends
the walk; with -deadlock it is reported).

TPU structure (one host sync per CHUNK of steps, not per step):

* enabledness comes from the cheap guard pass over all lanes
  (vsr_kernel guard fns) — successors are never materialized for the
  draw;
* the drawn lane is applied with ``lax.switch`` over the 19 action
  bodies, one successor per walker;
* ``lax.scan`` advances all W walkers CHUNK steps inside one jit,
  recording (action id, lane param) histories as scan outputs that stay
  on device unless a violation needs replaying;
* on bag overflow the message table grows in place (zero padding
  changes no state content) and the chunk is re-run from its saved
  entry states — the walk segment is simply redrawn under the larger
  layout.

A violating walk replays its recorded (action, param) chain through the
materialize kernel into a full TRACE-format counterexample
(state_transfer_violation_trace.txt:3-7 format).
"""

from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp

from ..models import registry
from ..obs import RunObserver, closes_observer, spans
from .simulate import SimResult
from .spec import SpecModel
from .trace import TraceEntry

I32 = jnp.int32


def materialize_walk(kern, codec, spec, st0, aids, prms, n_steps,
                     cache=None):
    """Re-execute a recorded (action id, lane param) choice sequence
    from dense state `st0` through the materialize kernel into a
    TRACE-format counterexample — the ONE replay used by both the
    single-device simulator and the walker fleet (tpuvsr/sim).  Stops
    at `n_steps` or the first ``-1`` action (a frozen walker).
    `cache` maps action id -> jitted single-state materializer (pass
    the caller's dict to reuse compilations across replays)."""
    cache = {} if cache is None else cache
    loc = {a.name: a.location for a in spec.actions}
    st = {k: np.asarray(v) for k, v in st0.items()}
    out = [TraceEntry(position=1, action_name=None, location=None,
                      state=codec.decode(st))]
    for i in range(min(int(n_steps), len(aids))):
        aid = int(aids[i])
        if aid < 0:
            break
        fn = cache.get(aid)
        if fn is None:
            fn = jax.jit(jax.vmap(kern._action_fns()[aid],
                                  in_axes=(0, 0)))
            cache[aid] = fn
        batch = {k: np.asarray(v)[None] for k, v in st.items()}
        succ, en = fn(batch, jnp.asarray([int(prms[i])], jnp.int32))
        if not bool(np.asarray(en)[0]):
            raise AssertionError("replay chose a disabled lane")
        st = {k: np.asarray(v)[0] for k, v in succ.items()
              if not k.startswith("_")}
        name = kern.action_names[aid]
        out.append(TraceEntry(position=i + 2, action_name=name,
                              location=loc.get(name),
                              state=codec.decode(st)))
    return out


class DeviceSimulator:
    """``action_weights``: optional per-action sampling weights (dict
    action-name -> weight, or array over kernel action order).  When set,
    each step samples in two stages — an enabled *action* with
    probability proportional to its weight, then a uniformly random
    enabled lane within it — instead of TLC's uniform-over-successors
    draw.  With an unbounded bag the successor list is dominated by
    message-delivery lanes, so uniform-over-successors walks almost
    never exercise rare guard-windows like the SendGetState truncation
    (VSR.tla:491-516); action-stage weighting is the scheduler-bias
    knob that makes deep defect hunts tractable.

    ``swarm_sigma``: standard deviation of per-walker log-normal noise
    multiplied onto the weights, resampled every walk round — a swarm
    of differently-biased schedulers instead of one (diversifies the
    explored interleaving distribution at zero cost).

    ``guided``: importance splitting for rare-violation hunts.  At
    every chunk boundary the walker population is resampled with
    probability proportional to ``exp(beta * kern.hunt_score(state))``
    — walkers that progressed toward the violation are cloned, walkers
    that didn't are culled (their recorded histories are permuted
    consistently, so a violating clone still replays into a full
    counterexample trace).  A multilevel-splitting rare-event search
    the reference's checker has no analog of; it trades the uniform
    walk distribution for a massively higher hit rate on deep defects
    like the state-transfer data loss."""

    def __init__(self, spec: SpecModel, max_msgs=None, walkers=256,
                 chunk_steps=32, action_weights=None, swarm_sigma=0.0,
                 guided=False, split_beta=1.5, dispatch="grouped",
                 group_caps=None, model_factory=None):
        # model_factory(spec, max_msgs=..) -> (codec, kernel); default
        # is the hand-kernel registry (DeviceBFS parity)
        self._model_factory = model_factory or registry.make_model
        self.spec = spec
        self.W = walkers
        self.chunk = chunk_steps
        self.inv_names = list(spec.cfg.invariants)
        self.swarm_sigma = float(swarm_sigma)
        self._action_weights = action_weights
        self.guided = bool(guided)
        self.split_beta = float(split_beta)
        # "grouped": gather walkers by chosen action and apply each
        # action body only to its group (adaptive per-action caps,
        # grown on overflow) — ~n_actions/avg_groups times less action
        # compute per step than "dense", which evaluates every action
        # body for every walker (the round-3 profile bottleneck,
        # VERDICT item 4).
        self.dispatch = dispatch
        self.group_caps = group_caps      # per-action gather capacities
        self.log_w = None           # resolved against the kernel in _build
        self._build(max_msgs)

    def _build(self, max_msgs):
        spec = self.spec
        self.codec, self.kern = self._model_factory(spec,
                                                    max_msgs=max_msgs)
        kern = self.kern
        names = kern.action_names
        aw = self._action_weights
        if aw is None:
            self.log_w = None
        else:
            if isinstance(aw, dict):
                w = np.ones(len(names))
                for name, x in aw.items():
                    w[names.index(name)] = x
            else:
                w = np.asarray(aw, float)
            if w.shape != (len(names),) or (w <= 0).any():
                raise ValueError("action_weights must be positive, one "
                                 "per action")
            self.log_w = np.log(w)
        inv = kern.invariant_fn(self.inv_names)
        lane_aid = jnp.asarray(kern.lane_action)
        lane_prm = jnp.asarray(kern.lane_param)
        guards = kern._guard_fns()
        fns = kern._action_fns()

        def guard_all(st):
            outs = []
            for name, g in zip(names, guards):
                lanes = jnp.arange(kern._lane_count(name), dtype=I32)
                outs.append(jax.vmap(lambda ln, g=g: g(st, ln))(lanes))
            return jnp.concatenate(outs)

        W = self.W
        if self.group_caps is None:
            # starting caps: an even split plus slack; overflow at a
            # chunk grows the overflowing action's cap and redraws
            self.group_caps = [min(W, max(32, W // 4))] * len(names)

        def apply_dense(states, aid, prm, alive):
            """Per-walker successor for the chosen (action, param).

            Explicit compute-all-actions + mask-select.  A vmapped
            ``lax.switch`` lowers to the same all-branches select_n, but
            that lowering produced wrong bag contents on the TPU backend
            (headers lost while present/count landed — caught by the
            interpreter-confirmation check); the hand-rolled select is
            the same cost and lowers through plain jnp.where."""
            out = None
            for a, f in enumerate(fns):
                s_a, _en = jax.vmap(f, in_axes=(0, 0))(states, prm)
                m = aid == a
                if out is None:
                    out = {k: jnp.where(
                        m.reshape((-1,) + (1,) * (v.ndim - 1)), v, states[k])
                        for k, v in s_a.items() if not k.startswith("_")}
                else:
                    out = {k: jnp.where(
                        m.reshape((-1,) + (1,) * (s_a[k].ndim - 1)),
                        s_a[k], v) for k, v in out.items()}
            return out, jnp.zeros((len(names),), bool)

        caps = list(self.group_caps)

        def apply_grouped(states, aid, prm, alive):
            """Guard-gathered grouped dispatch: for each action, gather
            just the walkers that chose it (<= its cap), run that one
            action body on the small batch, scatter the successors
            back.  Action-body compute per step is sum(group sizes)
            ~= W instead of W x n_actions.  Per-action overflow is
            reported so the host can grow the cap and redraw the chunk
            deterministically (same keys -> same draws)."""
            out = {k: v for k, v in states.items()}
            ovf = []
            for a, f in enumerate(fns):
                C = caps[a]
                m = (aid == a) & alive
                ovf.append(m.sum() > C)
                (sel,) = jnp.nonzero(m, size=C, fill_value=W)
                ok = sel < W
                idx = jnp.clip(sel, 0, W - 1)
                st_a = {k: v[idx] for k, v in states.items()}
                s_a, _en = jax.vmap(f, in_axes=(0, 0))(st_a, prm[idx])
                dest = jnp.where(ok, sel, W).astype(I32)  # OOB drops
                for k in out:
                    out[k] = out[k].at[dest].set(s_a[k], mode="drop")
            return out, jnp.stack(ovf)

        apply_chosen = (apply_grouped if self.dispatch == "grouped"
                        else apply_dense)

        weighted = self.log_w is not None
        n_act = len(names)

        def chunk_fn(states, was_alive, keys, logw):
            def step(carry, key):
                (states, was_alive, bad, dead, err_any, ovf,
                 steps, d) = carry
                en = jax.vmap(guard_all)(states)          # [W, L]
                if weighted:
                    # stage 1: enabled action ~ weights (Gumbel-max);
                    # stage 2: uniform enabled lane within it
                    k1, k2 = jax.random.split(key)
                    act_en = jnp.zeros((en.shape[0], n_act), bool) \
                        .at[:, lane_aid].max(en)
                    g = jax.random.gumbel(k1, act_en.shape) + logw
                    a_star = jnp.argmax(jnp.where(act_en, g, -jnp.inf),
                                        axis=1)
                    v = jax.random.uniform(k2, en.shape)
                    in_act = en & (lane_aid[None, :] == a_star[:, None])
                    lane = jnp.argmax(jnp.where(in_act, v, -1.0), axis=1)
                else:
                    u = jax.random.uniform(key, en.shape)
                    lane = jnp.argmax(jnp.where(en, u, -1.0), axis=1)
                alive = en.any(axis=1)
                aid = lane_aid[lane]
                prm = lane_prm[lane]
                succ, ovf_a = apply_chosen(states, aid, prm, alive)
                sel = {k: alive.reshape((-1,) + (1,) * (v.ndim - 1))
                       for k, v in states.items()}
                states = {k: jnp.where(sel[k], succ[k], v)
                          for k, v in states.items()}
                err = alive & (succ["err"] != 0)
                iok = jax.vmap(inv)(succ)
                badw = alive & ~iok & ~err
                hit = badw.any() & (bad[0] < 0)
                bad = jnp.where(hit, jnp.stack(
                    [jnp.argmax(badw).astype(I32), d]), bad)
                dw = was_alive & ~alive
                hitd = dw.any() & (dead[0] < 0)
                dead = jnp.where(hitd, jnp.stack(
                    [jnp.argmax(dw).astype(I32), d]), dead)
                err_any = err_any | err.any()
                steps = steps + alive.sum()
                hist = (jnp.where(alive, aid, -1).astype(I32),
                        jnp.where(alive, prm, 0).astype(I32))
                return (states, alive, bad, dead, err_any,
                        ovf | ovf_a, steps, d + 1), hist

            init = (states, was_alive, jnp.full((2,), -1, I32),
                    jnp.full((2,), -1, I32), jnp.asarray(False),
                    jnp.zeros((n_act,), bool),
                    jnp.asarray(0, I32), jnp.asarray(0, I32))
            (states, alive, bad, dead, err_any, ovf, steps, _d), hist = \
                jax.lax.scan(step, init, keys)
            return states, alive, bad, dead, err_any, ovf, steps, hist

        self._chunk = jax.jit(chunk_fn)
        self._fresh_jit = True   # first dispatch after a (re)build is
        #                          charged to the "compile" phase
        if self.guided:
            if not hasattr(kern, "hunt_score"):
                raise ValueError(
                    "guided simulation needs a kernel hunt_score")
            self._score = jax.jit(jax.vmap(kern.hunt_score))
        self._mat = {}

    def _resample(self, rng, states, was_alive, hists):
        """Importance-splitting step: draw W walker indices with
        probability ~ exp(beta * hunt_score), permute walker state AND
        every recorded history chunk by the draw (clones inherit their
        parent's past, so traces replay exactly)."""
        scores = np.asarray(self._score(states)).astype(np.float64)
        if scores.max() == scores.min():
            return states, was_alive, hists, scores.max()
        z = self.split_beta * (scores - scores.max())
        p = np.exp(z)
        p /= p.sum()
        sel = jnp.asarray(rng.choice(self.W, size=self.W, p=p), jnp.int32)
        states = {k: v[sel] for k, v in states.items()}
        was_alive = was_alive[sel]
        hists = [(ha[:, sel], hp[:, sel]) for ha, hp in hists]
        return states, was_alive, hists, scores.max()

    def _round_logw(self, key):
        """Per-walker action log-weights for one walk round (base
        weights + optional swarm noise), or a dummy scalar when
        running TLC-uniform."""
        if self.log_w is None:
            return jnp.zeros(())
        logw = jnp.asarray(self.log_w, jnp.float32)[None, :]
        logw = jnp.broadcast_to(logw, (self.W, logw.shape[1]))
        if self.swarm_sigma > 0.0:
            noise = jax.random.normal(key, logw.shape) * self.swarm_sigma
            logw = logw + noise
        return logw

    def _grow_msgs(self, batches):
        """Double MAX_MSGS and pad the given dense batches."""
        old = self.codec.shape.MAX_MSGS
        self._build(old * 2)
        return [self.codec.pad_msgs(b, old) for b in batches]

    @closes_observer
    def run(self, num=1000, depth=100, seed=0, check_deadlock=False,
            log=None, max_seconds=None, obs=None) -> SimResult:
        """Run `num` walks of `depth` steps (W at a time, `chunk` steps
        per device sync)."""
        obs = RunObserver.ensure(obs, "device-sim", self.spec, log=log)
        self._obs_active = obs          # closes_observer finalizes it
        spec, codec = self.spec, self.codec
        res = SimResult()
        t0 = time.time()
        obs.start(t0, backend=jax.default_backend())
        init_dense = [codec.encode(st) for st in spec.init_states()]
        init = {k: np.repeat(np.stack([d[k] for d in init_dense])[:1],
                             self.W, axis=0) for k in init_dense[0]}
        bad0 = spec.check_invariants(
            codec.decode({k: np.asarray(v[0]) for k, v in init.items()}))
        if bad0:
            res.ok = False
            res.violated_invariant = bad0
            return obs.finish(res)
        key = jax.random.PRNGKey(seed)
        rng = np.random.default_rng(seed ^ 0x5EED)
        init = {k: jnp.asarray(v) for k, v in init.items()}
        stop = False
        best_score = 0
        while res.walks < num and not stop:
            states = init
            was_alive = jnp.ones((self.W,), bool)
            hists = []          # [(ha [k, W], hp [k, W])] device arrays
            d = 0
            key, wkey = jax.random.split(key)
            logw = self._round_logw(wkey)
            while d < depth:
                k = min(self.chunk, depth - d)
                key, sub = jax.random.split(key)
                keys = jax.random.split(sub, k)
                while True:
                    with obs.span(spans.build_phase(self._fresh_jit),
                                  depth=d):
                        (nstates, alive, bad, dead, err_any, ovf, steps,
                         hist) = self._chunk(states, was_alive, keys,
                                             logw)
                        err_any.block_until_ready()
                    self._fresh_jit = False
                    obs.count("dispatches")
                    with obs.span(spans.HOST_SYNC):
                        err_any_h = bool(err_any)
                        ovf = np.asarray(ovf)
                    if err_any_h:
                        # bag overflow inside the chunk: grow the table,
                        # pad saved entry states, redraw the chunk
                        init, states = self._grow_msgs([init, states])
                        obs.grow("message_table",
                                 self.codec.shape.MAX_MSGS)
                        if log:
                            log(f"message table grown to "
                                f"{self.codec.shape.MAX_MSGS} slots")
                        continue
                    if ovf.any():
                        # a dispatch group overflowed its gather cap:
                        # double the caps of the flagged actions and
                        # redraw the chunk (same keys, same draws —
                        # deterministic, so the grown caps now fit)
                        for a in np.nonzero(ovf)[0]:
                            self.group_caps[a] = min(
                                self.W, self.group_caps[a] * 2)
                            obs.grow("dispatch_group",
                                     self.group_caps[a])
                            if log:
                                log(f"dispatch group for "
                                    f"{self.kern.action_names[a]} grown "
                                    f"to {self.group_caps[a]} "
                                    f"(recompiling)")
                        self._build(self.codec.shape.MAX_MSGS)
                        continue
                    break
                hists.append(hist)
                with obs.span(spans.HOST_SYNC):
                    res.steps += int(steps)
                    bad = np.asarray(bad)
                    dead = np.asarray(dead)
                # report whichever event happened at the earlier step of
                # the chunk; within one step deadlocks are checked first
                # (matching the per-step engine semantics)
                dead_first = (check_deadlock and dead[0] >= 0
                              and (bad[0] < 0 or dead[1] <= bad[1]))
                if dead_first:
                    w, ds = int(dead[0]), int(dead[1])
                    res.ok = False
                    res.deadlocks += 1
                    res.trace = self._replay(init, hists, w, d + ds)
                    res.violated_invariant = None
                    return obs.finish(res)
                if bad[0] >= 0:
                    w, ds = int(bad[0]), int(bad[1])
                    res.ok = False
                    res.trace = self._replay(init, hists, w, d + ds + 1)
                    confirmed = spec.check_invariants(res.trace[-1].state)
                    if confirmed is None:
                        # The device invariant kernel flagged a state the
                        # interpreter (the semantic oracle) accepts: an
                        # engine bug, never a spec violation — fail loudly
                        # rather than emit a bogus counterexample.
                        from ..core.values import TLAError
                        err = TLAError(
                            "device/interpreter divergence: device "
                            "invariant kernel reported a violation at "
                            f"walker {w} depth {d + ds + 1}, but the "
                            "interpreter accepts the replayed state")
                        err.trace = res.trace
                        raise err
                    res.violated_invariant = confirmed
                    return obs.finish(res)
                states, was_alive = nstates, alive
                d += k
                if self.guided and d < depth:
                    states, was_alive, hists, sc = self._resample(
                        rng, states, was_alive, hists)
                    best_score = max(best_score, int(sc))
                if max_seconds and time.time() - t0 > max_seconds:
                    stop = True
                    break
            res.walks += self.W
            obs.progress(walks=res.walks, steps=res.steps,
                         extra=(f"best score {best_score}"
                                if self.guided else None))
        return obs.finish(res)

    def _replay(self, init, hists, w, n_steps):
        """Re-execute walker `w`'s first `n_steps` recorded choices into
        a TRACE-format counterexample."""
        aids = np.concatenate([np.asarray(ha)[:, w] for ha, _hp in hists])
        prms = np.concatenate([np.asarray(hp)[:, w] for _ha, hp in hists])
        st = {k: np.asarray(v[w]) for k, v in init.items()}
        return materialize_walk(self.kern, self.codec, self.spec, st,
                                aids, prms, n_steps, cache=self._mat)


def device_simulate(spec: SpecModel, num=1000, depth=100, seed=0,
                    walkers=256, max_msgs=None, check_deadlock=False,
                    log=None, max_seconds=None, chunk_steps=32,
                    action_weights=None, swarm_sigma=0.0,
                    guided=False, split_beta=1.5, obs=None,
                    fleet=False) -> SimResult:
    if fleet:
        # delegate to the sharded walker fleet (tpuvsr/sim): guided
        # maps onto fingerprint-novelty importance splitting
        from ..sim import fleet_simulate
        return fleet_simulate(spec, num=num, depth=depth, seed=seed,
                              walkers=walkers, max_msgs=max_msgs,
                              chunk_steps=chunk_steps,
                              action_weights=action_weights,
                              swarm_sigma=swarm_sigma,
                              split=True if guided else None,
                              check_deadlock=check_deadlock, log=log,
                              max_seconds=max_seconds, obs=obs)
    sim = DeviceSimulator(spec, max_msgs=max_msgs, walkers=walkers,
                          chunk_steps=chunk_steps,
                          action_weights=action_weights,
                          swarm_sigma=swarm_sigma, guided=guided,
                          split_beta=split_beta)
    return sim.run(num=num, depth=depth, seed=seed,
                   check_deadlock=check_deadlock, log=log,
                   max_seconds=max_seconds, obs=obs)
