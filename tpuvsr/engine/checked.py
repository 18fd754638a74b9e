"""What a BFS engine checks: one spec and five levers, made into the
codec, the kernel, the canon spec, the pack spec and the POR filter
that every engine's level program is built from.

`CheckedModel` owns one decision and the two things that follow from
it.  The decision: how `pack`, `symmetry`, `bounds`, `por` and `commit`
(with edge emission and a sharded mesh as the POR blockers they are)
resolve against a spec, and in which order a model is built for a
message-table bound: factory, dead-action pruning, invariants, the
fold order of a factory's kernel and the folded-table refusal, canon
spec, pack spec (tightened, and declared beside it), POR filter.  What
follows: the format in which a snapshot says what it was written under
(the four manifests of ``save_checkpoint``) with the refusals of a
resume under anything else, and what a run says of the levers at its
start (the ``run_start`` keys of obs/SCHEMA.md) and at its end (the
lever gauges).  The host-side uses of the kernel that do not depend on
an engine's frontier live here too: fingerprinting a dense batch
through the canon seam, replaying one recorded action, and turning a
pointer chain into a trace.

`DeviceBFS` (and so `PagedBFS`) and `ShardedBFS` each hold one as
``engine.model`` and keep what is theirs: expansion caps, traced
stages and the stored level program; the mesh, the sharded step and
its fills.  The names the engines' bodies and the tests read a model
by (``eng.kern``, ``eng._pk``, ...) are `of_model` attributes, listed
where each engine class begins.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from ..core.values import TLAError
from ..models import registry
from ..models.guard_tables import table_lanes
from .bounds import prune_kernel, resolve_bounds
from .canon import build_canon_spec, kernel_fold_order
from .pack import build_pack_spec
from .por import PORFilter, resolve_por
from .trace import TraceEntry


class of_model:
    """An engine attribute that is read off the engine's `model`: the
    engine never holds a second copy of what is checked."""

    def __init__(self, name):
        self.name = name

    def __get__(self, engine, owner=None):
        return self if engine is None else getattr(engine.model, self.name)


class CheckedModel:
    def __init__(self, spec, model_factory=None, *, pack="auto",
                 commit="fused", symmetry="auto", bounds="auto",
                 por="off", edges=False, sharded=False):
        if commit not in ("fused", "per-action"):
            raise TLAError(f"commit must be 'fused' or 'per-action' "
                           f"(got {commit!r})")
        self.spec = spec
        # level-kernel commit mode (ISSUE 10): "fused" is the
        # three-stage occupancy-packed tile pass, "per-action" the
        # serial-phase body (the sharded step: `step_all`); results are
        # bit-identical between the two (tests/test_commit.py)
        self.commit = commit
        # streamed edge emission (ISSUE 15): a host-paged seam; it
        # refuses symmetry (below) and blocks POR
        self.edges = bool(edges)
        # an owner-partitioned FPSet cannot probe a successor's
        # freshness locally: its POR filter is the static one
        self.sharded = bool(sharded)
        self.inv_names = list(spec.cfg.invariants)
        # model_factory(spec, max_msgs=..) -> (codec, kernel); default
        # is the hand-kernel registry, tests/the CLI can pass the
        # AST-compiled factory (lower/compile.make_compiled_model).
        # The kernel is built with an identity-only perm table
        # (fold_symmetry=False): the canon seam, not the P-fold hash,
        # owns the reduction, which makes -symmetry off a real A/B lever
        self._factory = model_factory or (
            lambda spec, max_msgs=None: registry.make_model(
                spec, max_msgs=max_msgs, fold_symmetry=False))
        # symmetry canonicalization (ISSUE 11): "auto" = on iff the
        # cfg declares SYMMETRY (TLC's semantics — declaring
        # Permutations IS enabling the reduction); True/False force.
        # When on, a CanonSpec (engine/canon.py) maps every successor
        # to the least element of its symmetry orbit PRE-FINGERPRINT
        # (and, sharded, pre-bucketing), so the FPSet and frontier hold
        # one entry per orbit
        self._symmetry_req = symmetry
        # packed frontier encoding (ISSUE 9): "auto" packs whenever the
        # codec declares plane_bounds (every registered layout + the
        # stub harness); False runs dense; True forces the interchange
        # format even without bounds (ratio 1.0).  Results are
        # bit-identical either way — the pack/unpack round trip is
        # exact for in-range values, which the widths lint pass proves.
        self._pack_req = pack
        # speclint bounds pre-pass (ISSUE 13): "auto" consumes the
        # interval-analysis facts iff the lint gate is live — dead
        # actions pruned from the kernel lane tables, packing
        # tightened to reachable intervals, fused expansion caps
        # seeded from static fanout (the engines').  False runs
        # declared widths and full action lists (the A/B lever);
        # results are bit-identical either way (tests/test_bounds.py)
        self.facts = resolve_bounds(spec, bounds)
        self.pruned = []
        # ample-set partial-order reduction (ISSUE 16): consume the
        # independence pass's facts behind the same resolve contract
        # as -bounds, with the soundness blockers (temporal
        # properties, edge emission, non-fused commit) refused here
        # for library callers and at argparse time for the CLI.
        # Constructor default is "off" — the reduction shrinks
        # distinct-state counts, so library callers opt in; the CLI's
        # -por defaults to auto
        self.por_facts = resolve_por(
            spec, por,
            temporal=bool(getattr(spec, "temporal_props", ())),
            edges=self.edges, commit=commit)
        self.por = None
        self.por_active = False

    # ------------------------------------------------------------------
    def build(self, max_msgs):
        """(Re)build codec, kernel and the lever specs bound to them
        for a message-table bound; called again on bag growth."""
        spec = self.spec
        self.codec, self.kern = self._factory(spec, max_msgs=max_msgs)
        # statically dead actions (bounds pass): drop them from the
        # kernel's lane tables — the guard matrix and staging queue
        # shrink, and a dead guard is never evaluated.  Dead actions
        # are never enabled, so results are bit-identical
        if self.facts is not None and self.facts.dead_actions:
            dead = [n for n in self.facts.dead_actions
                    if n in self.kern.action_names]
            if dead and len(dead) < len(self.kern.action_names):
                self.kern = prune_kernel(self.kern, dead)
                self.pruned = dead
        self.inv = self.kern.invariant_fn(self.inv_names)
        self._mat = {}          # action id -> jitted single-action fn
        # symmetry canonicalization spec: rebuilt with the codec (the
        # group table depends on V, the orbit plane table on the
        # kernel class); None = no reduction.  A custom model_factory
        # may hand us a pre-ISSUE-11 FOLDED kernel (its fingerprint
        # already min-hashes over the group): the fold IS the
        # reduction then — the canon seam stands down rather than
        # double-reduce, and -symmetry off is impossible to honor (the
        # fold is baked into the kernel), so forcing it is a loud
        # error, not a silent no-op
        self.sym_fold = kernel_fold_order(self.kern)
        if spec.symmetry_perms and self.sym_fold > 1:
            if self._symmetry_req is False:
                raise TLAError(
                    "symmetry=False requested but the model factory "
                    "built a kernel with a FOLDED perm table (its "
                    "fingerprints min-hash over the group); rebuild "
                    "it with fold_symmetry=False "
                    "(registry.make_model) to make -symmetry off real")
            self.canon = None
        else:
            self.canon = build_canon_spec(spec, self.codec, self.kern,
                                          self._symmetry_req)
        # the host-side canonical fingerprint (`fp_batch`): one
        # compiled program a model, as the kernel's own
        # `fingerprint_batch` is; made here and not in a run, so a
        # second run on this object finds it built
        self._canon_fp = (
            jax.jit(jax.vmap(self.canon.fingerprint_fn(self.kern)))
            if self.canon is not None else None)
        if self.edges and (self.canon is not None or self.sym_fold > 1):
            raise TLAError(
                "edge emission requires symmetry off: the behavior "
                "graph's nodes are concrete states, so orbit-folded "
                "fingerprints would merge distinct graph nodes "
                "(liveness keeps its SYMMETRY-off requirement)")
        # packed-frontier spec for THIS codec binding (rebuilt with the
        # codec on bag growth: MAX_MSGS changes the lane count).
        # Bounds tightening (ISSUE 13): reachable intervals intersect
        # the declared plane bounds — fewer bits/state, exact round
        # trip for every reachable state.  pk_decl keeps the
        # untightened spec for the bound_tightening_ratio gauge
        tighten = (self.facts.plane_tighten()
                   if self.facts is not None else {})
        if self._pack_req is False:
            self.pk = self.pk_decl = None
        else:
            force = self._pack_req is True
            self.pk = build_pack_spec(self.codec, spec=spec, force=force,
                                      tighten=tighten or None)
            self.pk_decl = (build_pack_spec(self.codec, spec=spec,
                                            force=force)
                            if tighten else self.pk)
        # ample-set filter bound to THIS kernel (ISSUE 16): rebuilt
        # with the kernel so the action-name alignment survives bag
        # growth and pruning.  por_active gates the device tables —
        # facts with no eligible action journal their digest but leave
        # every jitted graph untouched (bit-identical to por=off)
        self.por = (PORFilter(self.por_facts, self.kern,
                              sharded=self.sharded)
                    if self.por_facts is not None else None)
        self.por_active = (self.por is not None
                           and self.por.any_eligible
                           and self.commit == "fused")

    def symmetry_on(self):
        """True when this run's fingerprints are orbit-reduced —
        through the canon seam OR a factory-supplied folded kernel."""
        return self.canon is not None or (
            bool(self.spec.symmetry_perms) and self.sym_fold > 1)

    # ------------------------------------------------------------------
    # what a run says of the levers: at its start, in its snapshots
    # ------------------------------------------------------------------
    def announce(self, obs, pipeline):
        """The lever keys of the ``run_start`` journal event — key-set
        parity across all engines (obs/SCHEMA.md); a None is "off"."""
        obs.pipeline = pipeline
        obs.pack = self.pk is not None
        obs.commit = self.commit
        obs.symmetry = self.symmetry_on()
        obs.bounds = (self.facts.journal_doc()
                      if self.facts is not None else None)
        obs.edges = self.edges
        obs.por = (self.por.journal_doc()
                   if self.por is not None else None)

    def pack_manifest(self):
        return self.pk.manifest() if self.pk is not None else None

    def canon_manifest(self):
        return self.canon.manifest() if self.canon is not None else None

    def bounds_manifest(self):
        """The consumed facts (None = bounds off): the digest resume
        compatibility is judged by."""
        if self.facts is None:
            return None
        return {"digest": self.facts.digest,
                "tightened": self.facts.tightened}

    def por_manifest(self):
        """The consumed independence facts (None = POR off)."""
        return self.por.manifest() if self.por is not None else None

    def manifests(self):
        """``save_checkpoint``'s four lever keywords."""
        return {"pack": self.pack_manifest(),
                "canon": self.canon_manifest(),
                "bounds": self.bounds_manifest(),
                "por": self.por_manifest()}

    def check_manifests(self, ck, path):
        """Resume-seam policy: a snapshot records the levers it was
        written under, and resuming it under any other is a loud
        policy error, never a silent re-encode.  (Changed cfg
        constants or a changed SYMMETRY definition already fail the
        spec-digest check; this guards the engine-level switches.)
        Called AFTER a rebuild at the snapshot's MAX_MSGS: the
        pack-spec version digests the lane count."""
        # bounds (ISSUE 13): tightened packing and pruned lane ids
        # both depend on the facts
        theirs = (ck.get("bounds") or {}).get("digest")
        mine = self.facts.digest if self.facts is not None else None
        if theirs != mine:
            raise TLAError(
                f"checkpoint {path} was written under bounds facts "
                f"{theirs or 'off'} but this engine consumes "
                f"{mine or 'off'}; the tightened packing and pruned "
                f"action ids are not comparable — resume with the "
                f"matching -bounds setting (and the same cfg "
                f"constants)")
        # pack (ISSUE 9): a drifted widths table means the run would
        # pack fields into different budgets than the ones speclint
        # verified for the snapshot's trajectory.  pack=off on either
        # side is compatible by construction (snapshots load as dense
        # planes)
        ckpk = ck.get("pack")
        if ckpk and self.pk is not None and \
                ckpk.get("version") != self.pk.version:
            raise TLAError(
                f"checkpoint {path} was written under packing spec "
                f"{ckpk.get('version')} but this engine derives "
                f"{self.pk.version} from its widths table; refusing "
                f"to resume (rebuild with the matching spec/.cfg or "
                f"pass pack=False)")
        # canon (ISSUE 11): the FPSet slots hold fingerprints of a
        # different space, so the resumed run would silently re-admit
        # or drop states
        theirs = (ck.get("canon") or {}).get("version")
        mine = self.canon.version if self.canon is not None else None
        if theirs != mine:
            raise TLAError(
                f"checkpoint {path} was written with symmetry "
                f"canonicalization {theirs or 'off'} but this engine "
                f"runs {mine or 'off'}; the stored fingerprints are "
                f"not comparable — resume with the matching "
                f"-symmetry setting/group")
        # POR (ISSUE 16): the stored frontier/visited set cover a
        # DIFFERENT (reduced or full) slice of the space, so the
        # resumed run would silently drop or re-admit interleavings.
        # Facts with no eligible action run the full exploration: they
        # resume a snapshot that names none
        if self.por_active or ck.get("por"):
            theirs = (ck.get("por") or {}).get("digest")
            mine = self.por.digest if self.por is not None else None
            if theirs != mine:
                raise TLAError(
                    f"checkpoint {path} was written under POR facts "
                    f"{theirs or 'off'} but this engine consumes "
                    f"{mine or 'off'}; the explored state sets are not "
                    f"comparable — resume with the matching -por setting "
                    f"(and the same spec/cfg)")

    def row_bytes(self, packed=True):
        """Bytes of one frontier row at rest: the packed words where a
        pack spec is bound (and `packed`), the dense planes otherwise."""
        if packed and self.pk is not None:
            return self.pk.packed_bytes
        return sum(int(np.prod(np.shape(v)) or 1) * 4
                   for v in self.codec.zero_state().values())

    def gauges(self, obs, generated, distinct, por_counts):
        """The lever gauges of a run's end.  `por_counts`: the run's
        (generated kept, generated full, shortcut states)."""
        # frontier_bytes_per_state / pack_ratio (ISSUE 9): the at-rest
        # bytes one frontier row costs this run, and the dense/packed
        # ratio (1.0 when packing is off)
        obs.gauge("frontier_bytes_per_state", int(self.row_bytes()))
        obs.gauge("pack_ratio", round(
            self.row_bytes(packed=False) / self.row_bytes(), 3))
        # state_bound / dead_actions / bound_tightening_ratio
        # (ISSUE 13): what the static pre-pass proved and how many
        # pack bits it saved (declared bits / tightened bits; 1.0 when
        # untightened)
        if self.facts is not None:
            if self.facts.state_bound is not None:
                obs.gauge("state_bound", int(self.facts.state_bound))
            obs.gauge("dead_actions", len(self.pruned))
            ratio = 1.0
            if self.pk is not None and self.pk_decl is not None and \
                    self.pk.total_bits:
                ratio = self.pk_decl.total_bits / self.pk.total_bits
            obs.gauge("bound_tightening_ratio", round(ratio, 4))
        # guard_table_lanes (ISSUE 47): the lanes of the actions whose
        # guard, as the kernel hands it to the engines, is one table a
        # state (read off the functions: `guard_tables.lanes_of`
        # marks them); 0 on a kernel that has none
        obs.gauge("guard_table_lanes", table_lanes(self.kern))
        # por_cut_ratio / ample_states (ISSUE 16): generated kept /
        # generated full under the ample filter (1.0 when inert), and
        # how many expanded states took the shortcut with real work
        # elided
        if self.por is not None:
            kept, full, amp = (int(x) for x in por_counts)
            obs.gauge("por_cut_ratio",
                      round(kept / full, 4) if full else 1.0)
            obs.gauge("ample_states", amp)
            obs.gauge("por_eligible_actions", self.por.n_eligible)
        # symmetry canonicalization (ISSUE 11): group order this run
        # reduced by (1 = off), and the headline generated/distinct-
        # after-canon ratio — on a symmetry-on run it folds the orbit
        # factor on top of ordinary dedup, so the on-vs-off A/B reads
        # the orbit cut straight off the journal
        obs.gauge("symmetry_perms",
                  self.canon.perms if self.canon is not None
                  else self.sym_fold)
        if generated and distinct:
            obs.gauge("orbit_ratio", round(generated / distinct, 4))

    # ------------------------------------------------------------------
    # the kernel on the host
    # ------------------------------------------------------------------
    def fp_batch(self, batch):
        """Fingerprint a dense batch through the canonical seam (the
        host-side twin of the in-kernel fingerprint stage: init
        registration, resume re-routing).  One compiled program on
        either branch, traced once a batch shape: the kernel's jitted
        `fingerprint_batch` with canon off, `build`'s jit of the
        canonical fingerprint with it on."""
        if self.canon is None:
            return self.kern.fingerprint_batch(batch)
        return self._canon_fp(batch)

    def fetch_row(self, batch, i):
        """One dense state row from a frontier-format buffer (packed
        rows are unpacked host-side)."""
        if not isinstance(batch, dict):
            return self.pk.unpack_row_np(np.asarray(batch[i]))
        return {k: np.asarray(v[i]) for k, v in batch.items()}

    def materialize_one(self, st, aid, param):
        """Apply one recorded (action, lane param) to a single dense
        state — the trace-replay step."""
        fn = self._mat.get(aid)
        if fn is None:
            fn = jax.jit(jax.vmap(self.kern._action_fns()[aid],
                                  in_axes=(0, 0)))
            self._mat[aid] = fn
        batch = {k: np.asarray(v)[None] for k, v in st.items()}
        succ, en = fn(batch, jnp.asarray([param], jnp.int32))
        assert bool(np.asarray(en)[0]), "trace replay chose a disabled lane"
        return {k: np.asarray(v)[0] for k, v in succ.items()
                if not k.startswith("_")}

    def trace(self, pointers, init_states, gid, extra=None):
        """Walk the host pointer table (`pointers`: the parent gid,
        action and lane param of every gid) back from `gid` to an init
        state, then replay the recorded (action, param) chain through
        the kernel to materialize each state, emitting TRACE-format
        entries; `extra` is one more step past `gid`."""
        parent, action, param = pointers
        steps = []
        cur = gid
        while action[cur] >= 0:
            steps.append((int(action[cur]), int(param[cur])))
            cur = int(parent[cur])
        steps.reverse()
        if extra is not None:
            steps.append(extra)
        loc = {a.name: a.location for a in self.spec.actions}
        st = self.codec.encode(init_states[cur])
        out = [TraceEntry(position=1, action_name=None, location=None,
                          state=self.codec.decode(st))]
        for pos, (aid, prm) in enumerate(steps):
            st = self.materialize_one(st, aid, prm)
            name = self.kern.action_names[aid]
            out.append(TraceEntry(position=pos + 2, action_name=name,
                                  location=loc.get(name),
                                  state=self.codec.decode(st)))
        return out
