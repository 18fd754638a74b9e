"""Shared test/smoke harness pieces: the inline counter spec and the
stub device kernel that drives the REAL DeviceBFS/PagedBFS/ShardedBFS/
DeviceSimulator loops without the reference corpus mount (ISSUE 2
introduced the hook; ISSUE 3 promotes the stubs here so
``tests/test_obs.py``, ``tests/test_resilience.py`` and
``scripts/fault_matrix.py`` share one copy).

The stub kernel implements exactly the attribute contract the engines
consume (``action_names`` / ``n_lanes`` / ``_guard_fns`` /
``_action_fns`` / ``step_all`` / ``fingerprint`` / ``invariant_fn``),
over a two-counter state space with 16 reachable states and level
sizes [1, 2, 3, 4, 3, 2, 1] — small enough that every engine path
(growth, spill, checkpoint, fault, rescue) completes in seconds on the
CPU backend.
"""

from __future__ import annotations

import numpy as np

from .engine.spec import SpecModel
from .frontend.cfg import parse_cfg_text
from .frontend.parser import parse_module_text

COUNTER = """---- MODULE ObsCounter ----
EXTENDS Naturals
CONSTANTS Limit
VARIABLES x, y

Init == x = 0 /\\ y = 0

IncX ==
    /\\ x < Limit
    /\\ x' = x + 1
    /\\ UNCHANGED y

IncY ==
    /\\ y < Limit
    /\\ y' = y + 1
    /\\ UNCHANGED x

Next == IncX \\/ IncY

Bound == x + y <= 2 * Limit
====
"""
COUNTER_CFG = ("CONSTANTS\n    Limit = 3\n"
               "INIT Init\nNEXT Next\nINVARIANT Bound\n")

#: the counter spec's exact fixpoint — the oracle every engine/fault
#: path is checked against
STUB_DISTINCT = 16
STUB_LEVELS = [1, 2, 3, 4, 3, 2, 1]

#: the ``inv_free`` fixture's reduced fixpoint under the ample-set
#: partial-order reduction (ISSUE 16): with IncX/IncY independent and
#: invisible, every state expands ONE action — the 4 x 4 grid
#: collapses to a single interleaving per level, the (3,3) deadlock
#: survives, and generated-kept/generated-full gives the cut ratio
#: oracle 6/9 ≈ 0.67
POR_STUB_DISTINCT = 7
POR_STUB_LEVELS = [1, 1, 1, 1, 1, 1, 1]
POR_STUB_KEPT = 6
POR_STUB_FULL = 9


#: the dead-action fixture text (ISSUE 13): `Limit > 5` folds FALSE
#: under the cfg's Limit = 3, so Jump can never fire — the bounds
#: pass proves it dead and the engines prune it from the lane tables
DEAD_ACTION = """Jump ==
    /\\ Limit > 5
    /\\ x' = x + 2
    /\\ UNCHANGED y

"""


def counter_spec(inv_bound=None, inv_x_bound=None, dead_action=False,
                 nonlinear_guard=False, limit=None, inv_free=False):
    """The inline two-counter spec (16 states, diameter 6).

    With ``inv_bound`` the Bound invariant tightens to
    ``x + y <= inv_bound`` — reachable violations for bounds < 6, so
    engine violation/trace paths are testable without the reference
    (pair with ``stub_model_factory(inv_bound=...)`` so the device
    kernel's invariant agrees with the interpreter's).

    ``inv_x_bound`` instead tightens to ``x <= inv_x_bound`` — the
    UNIQUE-WITNESS variant: the first reachable violating state is
    ``(inv_x_bound + 1, 0)``, which is the only violation at its BFS
    level and has exactly one parent/action, so every engine on every
    mesh size must surface the bit-identical counterexample trace
    (the elastic-resume trace oracle, ISSUE 5).

    ``dead_action`` adds a Jump action whose guard constant-folds to
    FALSE under the cfg (the ISSUE 13 dead-action-pruning fixture;
    pair with ``stub_model_factory(dead_action=True)``).
    ``nonlinear_guard`` makes IncX's guard ``x * x < Limit`` — outside
    the bounds pass's interval domain, so tightening must be REFUSED
    (bounds{tightened:false}); note it also shrinks the reachable
    space (x stops at 2 under Limit = 3).  ``limit`` overrides the
    cfg's Limit binding.

    ``inv_free`` replaces Bound with ``Limit >= 0`` — an invariant
    reading NEITHER counter, which makes IncX/IncY independent AND
    invisible (both also carry ``x' = x + 1`` monotone witnesses):
    the ISSUE 16 fixture on which the ample-set partial-order
    reduction is live on every engine, single-device and sharded.
    The reduced space is ``POR_STUB_DISTINCT`` states (of 16) and the
    (Limit, Limit) deadlock survives."""
    src = COUNTER
    if inv_free:
        src = src.replace("Bound == x + y <= 2 * Limit",
                          "Bound == Limit >= 0")
    if inv_x_bound is not None:
        src = src.replace("Bound == x + y <= 2 * Limit",
                          f"Bound == x <= {int(inv_x_bound)}")
    elif inv_bound is not None:
        src = src.replace("Bound == x + y <= 2 * Limit",
                          f"Bound == x + y <= {int(inv_bound)}")
    if nonlinear_guard:
        src = src.replace("/\\ x < Limit", "/\\ x * x < Limit")
    if dead_action:
        src = src.replace("Next == IncX \\/ IncY",
                          DEAD_ACTION + "Next == IncX \\/ IncY \\/ Jump")
    cfg = COUNTER_CFG
    if limit is not None:
        cfg = cfg.replace("Limit = 3", f"Limit = {int(limit)}")
    return SpecModel(parse_module_text(src), parse_cfg_text(cfg))


def stub_model_factory(limit=3, inv_bound=None, inv_x_bound=None,
                       dead_action=False):
    """A ``model_factory`` producing a (codec, kernel) pair for the
    counter spec — drives the real device engines with no reference
    kernel registered.  ``inv_bound``/``inv_x_bound`` mirror
    ``counter_spec``'s tightened invariants (the kernel and the
    interpreter must agree on what violates).  ``dead_action`` adds
    the Jump lane matching ``counter_spec(dead_action=True)`` — its
    guard is always false, so a bounds-on engine prunes it and a
    bounds-off engine carries the dead lane (bit-identical results;
    the ISSUE 13 pruning fixture)."""
    import jax
    import jax.numpy as jnp

    class _Shape:
        MAX_MSGS = 4

    class StubCodec:
        MSG_KEYS = ()

        def __init__(self):
            self.shape = _Shape()

        def zero_state(self):
            # "status" is the plane the level kernel sizes buffers by
            return {"status": 0, "x": 0, "y": 0, "err": 0}

        def plane_bounds(self, ranges):
            # packed-frontier bit budgets (ISSUE 9): the stub layout
            # declares real (narrow) bounds so every tier-1 engine run
            # exercises the pack/unpack seam with a non-trivial ratio
            return {"status": (0, 1), "x": (0, limit + 1),
                    "y": (0, limit + 1), "err": (0, 1)}

        def encode(self, st):
            return {"status": np.int32(0), "x": np.int32(st["x"]),
                    "y": np.int32(st["y"]), "err": np.int32(0)}

        def decode(self, d):
            return {"x": int(np.asarray(d["x"])),
                    "y": int(np.asarray(d["y"]))}

        def pad_msgs(self, batch, old):
            return batch

    class StubKern:
        action_names = (["IncX", "IncY", "Jump"] if dead_action
                        else ["IncX", "IncY"])
        n_lanes = 3 if dead_action else 2

        def _lane_count(self, name):
            return 1

        def _guard_fns(self):
            fns = [lambda st, ln: st["x"] < limit,
                   lambda st, ln: st["y"] < limit]
            if dead_action:
                # the Jump guard constant-folds to FALSE in the spec
                # (Limit > 5 under Limit = 3); the kernel mirrors it
                fns.append(lambda st, ln: (st["x"] < limit)
                           & jnp.asarray(False))
            return fns

        def _action_fns(self):
            def incx(st, ln):
                succ = {"status": st["status"], "x": st["x"] + 1,
                        "y": st["y"], "err": jnp.int32(0)}
                return succ, st["x"] < limit

            def incy(st, ln):
                succ = {"status": st["status"], "x": st["x"],
                        "y": st["y"] + 1, "err": jnp.int32(0)}
                return succ, st["y"] < limit

            def jump(st, ln):
                succ = {"status": st["status"], "x": st["x"] + 2,
                        "y": st["y"], "err": jnp.int32(0)}
                return succ, (st["x"] < limit) & jnp.asarray(False)
            return ([incx, incy, jump] if dead_action
                    else [incx, incy])

        lane_action = (np.array([0, 1, 2], np.int32) if dead_action
                       else np.array([0, 1], np.int32))
        lane_param = (np.array([0, 0, 0], np.int32) if dead_action
                      else np.array([0, 0], np.int32))

        def step_all(self, st):
            succs, ens = [], []
            for f in self._action_fns():
                s, e = f(st, jnp.int32(0))
                succs.append(s)
                ens.append(e)
            return ({k: jnp.stack([s[k] for s in succs])
                     for k in succs[0]}, jnp.stack(ens))

        def fingerprint(self, st):
            x = jnp.uint32(st["x"])
            y = jnp.uint32(st["y"])
            return jnp.stack([x * jnp.uint32(7) + y + jnp.uint32(1),
                              x + jnp.uint32(1), y + jnp.uint32(1),
                              jnp.uint32(99)])

        def fingerprint_batch(self, batch):
            arr = {k: jnp.asarray(v) for k, v in batch.items()}
            return jax.vmap(self.fingerprint)(arr)

        def invariant_fn(self, names):
            if inv_x_bound is not None:
                return lambda st: st["x"] <= inv_x_bound
            if inv_bound is None:
                return lambda st: jnp.asarray(True)
            return lambda st: st["x"] + st["y"] <= inv_bound

        def hunt_score(self, st):
            # guided-simulation fixture: deeper x = closer to the
            # tightened inv_x_bound violation (mirrors the VSR
            # kernel's state-transfer distance score)
            return jnp.asarray(st["x"], jnp.float32)

    return lambda spec, max_msgs=None: (StubCodec(), StubKern())


def stub_device_engine(cls=None, spec=None, inv_bound=None,
                       dead_action=False, **kw):
    """A small DeviceBFS (or `cls`) instance over the counter spec and
    the stub kernel — the standard harness for engine-loop tests.
    Extra keywords (``pipeline=...``, ``chunk_tiles=...``) reach the
    engine constructor; ``dead_action`` builds the ISSUE 13
    dead-action fixture (spec + kernel both carry the never-enabled
    Jump)."""
    from .engine.device_bfs import DeviceBFS
    cls = cls or DeviceBFS
    return cls(spec or counter_spec(inv_bound,
                                    dead_action=dead_action),
               model_factory=kw.pop("model_factory", None)
               or stub_model_factory(inv_bound=inv_bound,
                                     dead_action=dead_action),
               hash_mode="full", tile_size=kw.pop("tile_size", 4),
               fpset_capacity=kw.pop("fpset_capacity", 1 << 8),
               next_capacity=kw.pop("next_capacity", 1 << 6), **kw)


def stub_engine_factory(spec, **engine_kw):
    """A ``Supervisor`` engine factory over the stub kernel: builds the
    device or paged engine at the requested tile (the degrade ladder's
    knob) on `spec`; `engine_kw` (e.g. ``pipeline=2``) is forwarded."""
    from .engine.device_bfs import DeviceBFS
    from .engine.paged_bfs import PagedBFS

    def make(kind, tile):
        cls = PagedBFS if kind == "paged" else DeviceBFS
        return cls(spec, model_factory=stub_model_factory(),
                   hash_mode="full", tile_size=tile,
                   fpset_capacity=1 << 8, next_capacity=1 << 6,
                   **engine_kw)
    return make


def stub_sharded_engine(n_devices=2, spec=None, inv_x_bound=None,
                        **kw):
    """A small ShardedBFS over the counter spec and the stub kernel on
    the first `n_devices` virtual devices — the standard harness for
    sharded engine-loop tests (elastic resume, exchange retry, mesh
    supervision) without the reference mount."""
    import numpy as np

    import jax
    from jax.sharding import Mesh

    from .parallel.sharded_bfs import ShardedBFS
    mesh = Mesh(np.array(jax.devices()[:n_devices]), ("d",))
    return ShardedBFS(
        spec or counter_spec(inv_x_bound=inv_x_bound), mesh,
        model_factory=kw.pop("model_factory", None)
        or stub_model_factory(inv_x_bound=inv_x_bound),
        tile=kw.pop("tile", 4), bucket_cap=kw.pop("bucket_cap", 64),
        next_capacity=kw.pop("next_capacity", 1 << 6),
        fpset_capacity=kw.pop("fpset_capacity", 1 << 8), **kw)


def stub_bfs_engine(engine, sym=False, **kw):
    """One of the three BFS engines by name — "device", "paged" or
    "sharded" (two virtual devices) — over the counter spec, or over
    the SymPair fixture (`sym`): the harness of the tests that hold
    every engine to one rule."""
    if engine == "sharded":
        make = stub_sym_sharded if sym else stub_sharded_engine
        return make(n_devices=2, **kw)
    from .engine.paged_bfs import PagedBFS
    make = stub_sym_engine if sym else stub_device_engine
    return make(cls=PagedBFS if engine == "paged" else None, **kw)


def stub_fleet(spec=None, inv_bound=None, inv_x_bound=None,
               walkers=64, n_devices=1, **kw):
    """A small walker fleet (tpuvsr/sim) over the counter spec and the
    stub kernel — the tier-1 harness for fleet determinism, splitting,
    rescue/resume and hunt tests (ISSUE 7)."""
    from .sim.fleet import FleetSimulator
    return FleetSimulator(
        spec or counter_spec(inv_bound=inv_bound,
                             inv_x_bound=inv_x_bound),
        walkers=walkers, n_devices=n_devices,
        model_factory=stub_model_factory(inv_bound=inv_bound,
                                         inv_x_bound=inv_x_bound),
        chunk_steps=kw.pop("chunk_steps", 4),
        min_walkers=kw.pop("min_walkers", 8), **kw)


def stub_trace_records(n=8, depth=6, seed=0, spec=None, mutate=None,
                       drop_vars=(), blank_every=None,
                       drop_actions=False):
    """Deterministic TRACE.jsonl records from host random walks of the
    counter spec — the tier-1 fixture for the batched trace validator
    (ISSUE 8).  Each record is a full observation of a genuine walk
    (so it MUST validate) unless mutated:

    * ``mutate=(i, s[, delta])`` corrupts trace i's event s by shifting
      its first observed variable by ``delta`` (default +7) — off any
      reachable transition, so the validator must report trace i
      diverging at EXACTLY event s;
    * ``drop_vars`` removes variables from every observation and
      ``blank_every=k`` blanks every k-th event entirely (partial
      observation: the candidate set grows past 1);
    * ``drop_actions`` removes the recorded action names.
    """
    import random
    spec = spec or counter_spec()
    rng = random.Random(seed)
    drop = set(drop_vars)
    inits = list(spec.init_states())
    records = []
    for i in range(n):
        st = rng.choice(inits)
        init = {k: str(v) for k, v in sorted(st.items())
                if k not in drop}
        events = []
        for s in range(depth):
            succs = list(spec.successors(st))
            if not succs:
                break
            action, st = rng.choice(succs)
            if blank_every and (s + 1) % blank_every == 0:
                events.append({})
                continue
            ev = {"vars": {k: str(v) for k, v in sorted(st.items())
                           if k not in drop}}
            if not drop_actions:
                ev["action"] = action.name
            if not ev["vars"]:
                del ev["vars"]
            events.append(ev)
        records.append({"trace": f"t-{i:04d}", "init": init,
                        "events": events})
    if mutate is not None:
        i, s = mutate[0], mutate[1]
        delta = mutate[2] if len(mutate) > 2 else 7
        ev = records[i]["events"][s]
        var = sorted(ev.get("vars") or {"x": "0"})[0]
        old = int(ev.get("vars", {}).get(var, 0))
        ev.setdefault("vars", {})[var] = str(old + delta)
    return records


def stub_validator(spec=None, batch=64, n_devices=1, cand_cap=4,
                   chunk_steps=4, **kw):
    """A small :class:`tpuvsr.validate.BatchValidator` over the counter
    spec and the stub kernel — the tier-1 harness for validator
    determinism, divergence localization, rescue/resume and service
    tests (ISSUE 8)."""
    from .validate.batch import BatchValidator
    return BatchValidator(spec or counter_spec(), batch=batch,
                          n_devices=n_devices, cand_cap=cand_cap,
                          chunk_steps=chunk_steps,
                          model_factory=stub_model_factory(), **kw)


# ---------------------------------------------------------------------
# symmetric fixture (ISSUE 11): a two-slot write-once register over a
# symmetric model-value set — the tier-1 stand-in for the defect
# fixture's SYMMETRY Permutations(Values).  16 reachable states
# collapse to 5 orbits under the full S3 group (orbit factor 3.2), and
# every orbit invariant (NoPair) has reachable violations, so the
# symmetry-on-vs-off verdict/trace oracles run without the reference
# mount.
# ---------------------------------------------------------------------
SYMPAIR = """---- MODULE ObsSymPair ----
CONSTANTS Vals
VARIABLES a, b

Init == a = 0 /\\ b = 0

WriteA ==
    /\\ a = 0
    /\\ \\E v \\in Vals : a' = v
    /\\ UNCHANGED b

WriteB ==
    /\\ b = 0
    /\\ \\E v \\in Vals : b' = v
    /\\ UNCHANGED a

Next == WriteA \\/ WriteB

Symm == Permutations(Vals)

NoPair == a = 0 \\/ b = 0

AllOk == TRUE
====
"""
SYMPAIR_CFG = ("CONSTANTS\n    Vals = {v1, v2, v3}\n"
               "INIT Init\nNEXT Next\nSYMMETRY Symm\nINVARIANT {inv}\n")

#: exact fixpoints of the SymPair fixture — the symmetry A/B oracle
SYMPAIR_DISTINCT = 16          # symmetry off: all orbit members
SYMPAIR_ORBITS = 5             # symmetry on: one state per orbit
SYMPAIR_LEVELS = [1, 6, 9]
SYMPAIR_ORBIT_LEVELS = [1, 2, 2]


def sym_pair_spec(inv_pair=False, symmetry=True):
    """The symmetric two-slot fixture.  ``inv_pair`` swaps in the
    NoPair invariant (first violations at depth 2 — a full orbit of
    9 witnesses, so traces agree between symmetry on/off only modulo
    orbit representative).  ``symmetry=False`` drops the SYMMETRY
    declaration (the cfg-level A/B leg)."""
    cfg = SYMPAIR_CFG.replace("{inv}",
                              "NoPair" if inv_pair else "AllOk")
    if not symmetry:
        cfg = cfg.replace("SYMMETRY Symm\n", "")
    return SpecModel(parse_module_text(SYMPAIR), parse_cfg_text(cfg))


def stub_sym_factory(inv_pair=False):
    """``model_factory`` for the SymPair fixture: a codec/kernel pair
    whose ``a``/``b`` planes hold value ids (0 = unset) and declare
    the ``SYM_PLANES`` orbit table engine/canon.py consumes."""
    import jax
    import jax.numpy as jnp

    from .core.values import ModelValue

    class _Shape:
        MAX_MSGS = 4
        V = 3

    class SymCodec:
        MSG_KEYS = ()

        def __init__(self, values):
            self.shape = _Shape()
            self.values = values                   # id-1 -> ModelValue
            self.value_id = {v: i + 1 for i, v in enumerate(values)}

        def zero_state(self):
            return {"status": 0, "a": 0, "b": 0, "err": 0}

        def plane_bounds(self, ranges):
            V = self.shape.V
            return {"status": (0, 1), "a": (0, V), "b": (0, V),
                    "err": (0, 1)}

        def encode(self, st):
            def enc(v):
                return np.int32(self.value_id.get(v, 0))
            return {"status": np.int32(0), "a": enc(st["a"]),
                    "b": enc(st["b"]), "err": np.int32(0)}

        def decode(self, d):
            def dec(x):
                i = int(np.asarray(x))
                return self.values[i - 1] if i else 0
            return {"a": dec(d["a"]), "b": dec(d["b"])}

        def pad_msgs(self, batch, old):
            return batch

    class SymKern:
        action_names = ["WriteA", "WriteB"]
        V = 3
        n_lanes = 6
        # the plane -> orbit table (ISSUE 11): both registers hold
        # bare value ids, so a permutation remaps every lane
        SYM_PLANES = {"a": "all", "b": "all"}

        def _lane_count(self, name):
            return self.V

        def _guard_fns(self):
            return [lambda st, ln: st["a"] == 0,
                    lambda st, ln: st["b"] == 0]

        def _action_fns(self):
            def wa(st, ln):
                succ = {"status": st["status"], "a": ln + 1,
                        "b": st["b"], "err": jnp.int32(0)}
                return succ, st["a"] == 0

            def wb(st, ln):
                succ = {"status": st["status"], "a": st["a"],
                        "b": ln + 1, "err": jnp.int32(0)}
                return succ, st["b"] == 0
            return [wa, wb]

        lane_action = np.array([0] * 3 + [1] * 3, np.int32)
        lane_param = np.array([0, 1, 2, 0, 1, 2], np.int32)

        def step_all(self, st):
            succs, ens = [], []
            for fn in self._action_fns():
                for ln in range(self.V):
                    s, e = fn(st, jnp.int32(ln))
                    succs.append(s)
                    ens.append(e)
            return ({k: jnp.stack([s[k] for s in succs])
                     for k in succs[0]}, jnp.stack(ens))

        def fingerprint(self, st):
            a = jnp.uint32(st["a"])
            b = jnp.uint32(st["b"])
            return jnp.stack([a * jnp.uint32(8) + b + jnp.uint32(1),
                              a + jnp.uint32(1), b + jnp.uint32(1),
                              jnp.uint32(77)])

        def fingerprint_batch(self, batch):
            arr = {k: jnp.asarray(v) for k, v in batch.items()}
            return jax.vmap(self.fingerprint)(arr)

        def invariant_fn(self, names):
            if inv_pair:
                return lambda st: (st["a"] == 0) | (st["b"] == 0)
            return lambda st: jnp.asarray(True)

        def hunt_score(self, st):
            return jnp.asarray(st["a"] + st["b"], jnp.float32)

    def make(spec, max_msgs=None):
        values = sorted((v for v in spec.ev.constants["Vals"]
                         if isinstance(v, ModelValue)),
                        key=lambda v: v.name)
        return SymCodec(values), SymKern()
    return make


def stub_sym_engine(cls=None, symmetry="auto", inv_pair=False, **kw):
    """A small DeviceBFS (or `cls`) over the SymPair fixture — the
    tier-1 harness for the symmetry-on-vs-off oracles (ISSUE 11)."""
    from .engine.device_bfs import DeviceBFS
    cls = cls or DeviceBFS
    return cls(sym_pair_spec(inv_pair=inv_pair),
               model_factory=stub_sym_factory(inv_pair=inv_pair),
               hash_mode="full", symmetry=symmetry,
               tile_size=kw.pop("tile_size", 4),
               fpset_capacity=kw.pop("fpset_capacity", 1 << 8),
               next_capacity=kw.pop("next_capacity", 1 << 6), **kw)


def stub_sym_sharded(n_devices=2, symmetry="auto", inv_pair=False,
                     **kw):
    """ShardedBFS over the SymPair fixture (canonicalize-before-
    bucketing: orbit-mates must hash to one shard)."""
    import jax
    from jax.sharding import Mesh

    from .parallel.sharded_bfs import ShardedBFS
    mesh = Mesh(np.array(jax.devices()[:n_devices]), ("d",))
    return ShardedBFS(
        sym_pair_spec(inv_pair=inv_pair), mesh,
        model_factory=stub_sym_factory(inv_pair=inv_pair),
        symmetry=symmetry, tile=kw.pop("tile", 4),
        bucket_cap=kw.pop("bucket_cap", 64),
        next_capacity=kw.pop("next_capacity", 1 << 6),
        fpset_capacity=kw.pop("fpset_capacity", 1 << 8), **kw)


# ---------------------------------------------------------------------
# liveness fixture (ISSUE 15): a stoppable modular ticker with WEAK
# FAIRNESS and temporal properties — the tier-1 stand-in for the A01
# liveness configs.  x cycles mod `modulus` (duplicate-heavy: the wrap
# edge targets a level-0 state) while Stop freezes the system, so
# []<>AtZero fails even under WF(Tick) (a stopped state's stuttering
# lasso is fair: Tick is disabled there) and the stop-free variant
# satisfies it.  Drives the REAL PagedBFS edge stream + DeviceGraph +
# fair-SCC machinery with no reference mount.
# ---------------------------------------------------------------------
TICKER = """---- MODULE ObsTicker ----
EXTENDS Naturals
VARIABLES x, stopped

Init ==
    /\\ x = 0
    /\\ stopped = FALSE

Tick ==
    /\\ stopped = FALSE
    /\\ x' = (x + 1) % {mod}
    /\\ UNCHANGED stopped

Stop ==
    /\\ stopped' = TRUE
    /\\ UNCHANGED x

Next ==
    \\/ Tick
    \\/ Stop

AtZero == x = 0
Hit == x = 2

Spec == Init /\\ [][Next]_vars
FairSpec == Init /\\ [][Next]_vars /\\ WF_vars(Tick)

AlwaysEventuallyZero == []<>AtZero
EventuallyHit == AtZero ~> Hit

vars == <<x, stopped>>
====
"""


def ticker_spec(spec_name="FairSpec", props=("AlwaysEventuallyZero",),
                modulus=3, stop=True):
    """The liveness fixture spec: ``2 * modulus`` reachable states
    (``modulus`` with ``stop=False``), dup-heavy wrap edges, and a
    PROPERTY cfg so ``liveness_check`` runs end to end.  The stop-free
    ``FairSpec`` satisfies []<>AtZero; every stoppable variant
    violates it by a fair stuttering lasso."""
    src = TICKER.replace("{mod}", str(int(modulus)))
    if not stop:
        src = src.replace("    \\/ Stop\n", "")
    cfg = parse_cfg_text(f"SPECIFICATION {spec_name}\nPROPERTY\n"
                         + "\n".join(props) + "\n")
    return SpecModel(parse_module_text(src), cfg)


def stub_ticker_factory(modulus=3, stop=True):
    """``model_factory`` for the Ticker fixture: the codec/kernel pair
    the PagedBFS edge stream and the DeviceGraph predicate batcher
    consume (ISSUE 15)."""
    import jax
    import jax.numpy as jnp

    class _Shape:
        MAX_MSGS = 4

    class TickCodec:
        MSG_KEYS = ()

        def __init__(self):
            self.shape = _Shape()

        def zero_state(self):
            return {"status": 0, "x": 0, "stopped": 0, "err": 0}

        def plane_bounds(self, ranges):
            return {"status": (0, 1), "x": (0, modulus - 1),
                    "stopped": (0, 1), "err": (0, 1)}

        def encode(self, st):
            return {"status": np.int32(0), "x": np.int32(st["x"]),
                    "stopped": np.int32(bool(st["stopped"])),
                    "err": np.int32(0)}

        def decode(self, d):
            return {"x": int(np.asarray(d["x"])),
                    "stopped": bool(int(np.asarray(d["stopped"])))}

        def pad_msgs(self, batch, old):
            return batch

    class TickKern:
        action_names = ["Tick", "Stop"] if stop else ["Tick"]
        n_lanes = 2 if stop else 1

        def _lane_count(self, name):
            return 1

        def _guard_fns(self):
            fns = [lambda st, ln: st["stopped"] == 0]
            if stop:
                fns.append(lambda st, ln: st["status"] == 0)  # TRUE
            return fns

        def _action_fns(self):
            def tick(st, ln):
                succ = {"status": st["status"],
                        "x": (st["x"] + 1) % modulus,
                        "stopped": st["stopped"], "err": jnp.int32(0)}
                return succ, st["stopped"] == 0

            def stp(st, ln):
                succ = {"status": st["status"], "x": st["x"],
                        "stopped": jnp.int32(1), "err": jnp.int32(0)}
                return succ, st["status"] == 0
            return [tick, stp] if stop else [tick]

        lane_action = (np.array([0, 1], np.int32) if stop
                       else np.array([0], np.int32))
        lane_param = (np.array([0, 0], np.int32) if stop
                      else np.array([0], np.int32))

        def step_all(self, st):
            succs, ens = [], []
            for f in self._action_fns():
                s, e = f(st, jnp.int32(0))
                succs.append(s)
                ens.append(e)
            return ({k: jnp.stack([s[k] for s in succs])
                     for k in succs[0]}, jnp.stack(ens))

        def fingerprint(self, st):
            x = jnp.uint32(st["x"])
            s = jnp.uint32(st["stopped"])
            return jnp.stack([x * jnp.uint32(2) + s + jnp.uint32(1),
                              x + jnp.uint32(1), s + jnp.uint32(1),
                              jnp.uint32(55)])

        def fingerprint_batch(self, batch):
            arr = {k: jnp.asarray(v) for k, v in batch.items()}
            return jax.vmap(self.fingerprint)(arr)

        def invariant_fn(self, names):
            return lambda st: jnp.asarray(True)

    return lambda spec, max_msgs=None: (TickCodec(), TickKern())


def canon_csr(csr_or_graph):
    """Per-src sorted CSR segments — the ONE comparison form of the
    documented streamed/two-pass bit-identity contract (ISSUE 15:
    edge order within one source's segment is unordered).  Accepts a
    DeviceGraph or a raw ``(indptr, aid, tid)`` triple; shared by the
    tests, ``scripts/liveness_speedup.py`` and
    ``scripts/fault_matrix.py`` so the oracle cannot drift."""
    indptr, aid, tid = getattr(csr_or_graph, "csr", csr_or_graph)
    return [sorted(zip(aid[indptr[u]:indptr[u + 1]],
                       tid[indptr[u]:indptr[u + 1]]))
            for u in range(len(indptr) - 1)]


def stub_graph_engine(spec=None, modulus=3, stop=True, **kw):
    """A small ``PagedBFS(retain_levels=True, edges=True)`` over the
    Ticker fixture — the standard harness for the streamed behavior
    graph (ISSUE 15).  ``edges="two-pass"``-style oracles pass
    ``edges=False`` and build the graph through
    ``DeviceGraph(mode="two-pass")``."""
    from .engine.paged_bfs import PagedBFS
    return PagedBFS(
        spec or ticker_spec(modulus=modulus, stop=stop),
        model_factory=stub_ticker_factory(modulus=modulus, stop=stop),
        hash_mode="full", tile_size=kw.pop("tile_size", 4),
        fpset_capacity=kw.pop("fpset_capacity", 1 << 8),
        next_capacity=kw.pop("next_capacity", 1 << 6),
        retain_levels=True, edges=kw.pop("edges", True), **kw)


def bad_counter_spec():
    """A counter-spec variant that FAILS the speclint frames pass
    (IncX leaves ``y`` unframed) — the admission-rejection fixture for
    the dispatch service: a job over this spec must die at the lint
    gate, before any device time (ISSUE 6)."""
    src = COUNTER.replace(
        "IncX ==\n    /\\ x < Limit\n    /\\ x' = x + 1\n"
        "    /\\ UNCHANGED y",
        "IncX ==\n    /\\ x < Limit\n    /\\ x' = x + 1")
    assert "UNCHANGED y" not in src.split("IncY")[0]
    return SpecModel(parse_module_text(src),
                     parse_cfg_text(COUNTER_CFG))


def subprocess_env(extra=None):
    """The hermetic environment for tpuvsr child processes in tests
    and drills: ``serve.pool.child_env``'s PYTHONPATH setup plus the
    test-only CPU forcing — CPU backend (a child of a process that
    holds the chip could not have it anyway) and 8 virtual devices.  Shared by the
    multiprocessing claim-race harness, ``scripts/serve_demo.py`` and
    ``scripts/fault_matrix.py``."""
    from .serve.pool import child_env
    env = child_env()
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    env.update(extra or {})
    return env


def true_argv():
    """The cheapest possible shell-job argv on this machine — shared
    by the serve tests and drills (one copy to patch for platforms
    without /bin/true)."""
    import os
    import sys as _sys
    if os.path.exists("/bin/true"):
        return ["/bin/true"]
    return [_sys.executable, "-c", "pass"]


#: the claim-racer child: loops ``claim_next`` over one spool until
#: nothing is claimable, finishing every claim as done — deliberately
#: importing ONLY the jax-free queue module, so racers start in
#: milliseconds and the race is tight.  The small sleep per claim
#: keeps a racer with an interpreter-startup head start from sweeping
#: the whole queue before its siblings issue their first claim (the
#: drill asserts the race actually overlapped).
_CLAIM_RACER = """\
import json, sys, time
from tpuvsr.service.queue import JobQueue
q = JobQueue(sys.argv[1])
owner = sys.argv[2]
got = []
while True:
    job = q.claim_next(owner=owner)
    if job is None:
        break
    q.finish(job.job_id, "done")
    got.append(job.job_id)
    time.sleep(0.02)
print(json.dumps(got))
"""


def claim_race(spool, workers=3, timeout=120):
    """The multi-process claim drill (ISSUE 14 satellite): spawn
    `workers` concurrent subprocesses racing ``claim_next`` over one
    spool; returns ``{owner: [job_id, ...]}`` of what each actually
    claimed.  The caller asserts exactly-once: the union covers every
    job, the owners' lists are disjoint."""
    import json as _json
    import subprocess
    import sys as _sys
    env = subprocess_env()
    procs = [
        subprocess.Popen(
            [_sys.executable, "-c", _CLAIM_RACER, spool, f"racer-{i}"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env)
        for i in range(workers)]
    out = {}
    for i, p in enumerate(procs):
        stdout, stderr = p.communicate(timeout=timeout)
        if p.returncode != 0:
            raise RuntimeError(f"claim racer {i} died rc="
                               f"{p.returncode}: {stderr[-500:]}")
        out[f"racer-{i}"] = _json.loads(stdout)
    return out


def stub_service_factory(spec, inv_bound=None, inv_x_bound=None,
                         **engine_kw):
    """The dispatch-service engine factory over the stub kernel: one
    factory covering all three supervised kinds — device/paged at the
    requested tile, sharded at the requested (tile, n_devices) mesh —
    with the tightened-invariant knobs threaded through so violation
    jobs stay kernel/interpreter-consistent.  This is what the service
    worker installs for ``stub: true`` jobs (tier-1: real engine
    loops, no reference mount)."""
    from .engine.device_bfs import DeviceBFS
    from .engine.paged_bfs import PagedBFS

    def make(kind, tile, n_devices=None):
        if kind == "sharded":
            return stub_sharded_engine(
                n_devices=n_devices or 2, spec=spec,
                inv_x_bound=inv_x_bound, tile=tile, **dict(engine_kw))
        cls = PagedBFS if kind == "paged" else DeviceBFS
        return cls(spec,
                   model_factory=stub_model_factory(
                       inv_bound=inv_bound, inv_x_bound=inv_x_bound),
                   hash_mode="full", tile_size=max(tile, 2),
                   fpset_capacity=1 << 8, next_capacity=1 << 6,
                   **dict(engine_kw))
    return make


def stub_sharded_factory(spec, **engine_kw):
    """A ``Supervisor`` engine factory for the MESH degrade ladder:
    builds the sharded engine at the requested (tile, n_devices) and
    the paged engine once the ladder falls off the mesh floor — the
    stub-kernel mirror of the supervisor's default factory."""
    from .engine.paged_bfs import PagedBFS

    def make(kind, tile, n_devices=None):
        if kind == "sharded":
            return stub_sharded_engine(n_devices=n_devices, spec=spec,
                                       tile=tile, **dict(engine_kw))
        return PagedBFS(spec, model_factory=stub_model_factory(),
                        hash_mode="full", tile_size=max(tile, 2),
                        fpset_capacity=1 << 8, next_capacity=1 << 6)
    return make
