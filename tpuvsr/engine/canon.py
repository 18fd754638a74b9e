"""Device-native symmetry reduction: orbit-canonical state images
(ISSUE 11 tentpole).

TLC's SYMMETRY optimization — the single biggest algorithmic lever on
the reference corpus (VSR.cfg ``Permutations``, PAPER.md capability
#4) — stores one fingerprint per symmetry ORBIT instead of one per
state: before fingerprinting, a state is mapped to the least element
of its orbit under the cfg-declared permutation group, so every
orbit-mate dedups against the same FPSet entry and the reachable set
shrinks by up to |group| (6x at ``|Values| = 3``).  The host
interpreter has always done this (``spec.py:view_value`` takes the
min permuted image over ``value_key`` order); this module is the
device-side seam: a vmapped, jittable canonicalization kernel every
engine applies PRE-FINGERPRINT inside its jitted level/step/chunk
pass — no host round-trip per state.

Semantics (exactly TLC's): the canonical image is only used to
COMPUTE the fingerprint.  The frontier keeps the actually-generated
successor (one representative per orbit — the first one committed),
so trace replay walks real reachable states and counterexamples stay
valid; verdicts are orbit-level and engine-independent (the
federated-dispatch framing of arxiv 2606.02019 is why they must be).
Soundness requires the evaluated permutation set plus identity to be
a CLOSED group (orbit-mates must produce the same image set — TLC's
``Permutations(S)`` always is); ``group_table`` enforces it here and
the speclint symmetry pass (pass 4) reports it statically.

The permutation action on an encoded SoA state row is pure value-id
relabeling: the corpus's symmetric sets are model-value universes
whose ids live in specific planes (or plane columns) of the dense
layout.  Which planes, and how a permutation reaches them, is the
kernel's knowledge:

* kernels with a ``_permuted(st, perm)`` method (the whole registry
  family — it already backs their symmetry-folded hashing) supply the
  action directly, packed-entry encodings included;
* simpler layouts declare a ``SYM_PLANES`` table
  (``{plane: "all" | ("col", i)}``) and the generic table action
  applies ``perm[...]`` to the named planes/columns.

``orbit_planes`` derives the plane -> orbit table from those same
class attributes — it is what the speclint pass EMITS and what this
kernel CONSUMES, so lint and kernel can never disagree (ISSUE 11
satellite).

The minimization itself is a small sort-network over the group: the
(identity-first) ``[P, V+1]`` id table is enumerated per lane, each
image keyed by its flattened symmetric planes, and the lexicographic
least image wins — P is tiny (|Values|! <= 6 on the defect fixture),
so the whole pass is a handful of gathers and compares per state.
"""

from __future__ import annotations

import hashlib
import json

import numpy as np

import jax
import jax.numpy as jnp

from ..core.values import TLAError
from ..obs import spans


def kernel_fold_order(kern):
    """Group order a kernel's OWN fingerprint already folds over (the
    pre-ISSUE-11 style: registry kernels built with a multi-row perm
    table take the min over P hashes).  1 = unfolded — the engines'
    expected shape, where the CanonSpec owns the reduction."""
    perms = getattr(kern, "perms", None)
    if perms is None:
        return 1
    return int(np.asarray(perms).shape[0])


def orbit_planes(kern):
    """The plane -> orbit-action table for a kernel (class or
    instance): which planes of the encoded layout a value permutation
    touches, and how.  ``{plane: "all"}`` remaps every lane of the
    plane through the id table; ``{plane: ("col", i)}`` remaps column
    ``i`` of the plane's last axis.  Derived from ``SYM_PLANES`` when
    declared, else from the registry family's ``PERM_REP_KEYS`` /
    ``PERM_MSG_KEYS``; None when the kernel declares nothing (no
    device canonicalization possible).  The speclint symmetry pass
    emits exactly this table, so lint and kernel share one source."""
    sp = getattr(kern, "SYM_PLANES", None)
    if sp:
        return dict(sp)
    rep = tuple(getattr(kern, "PERM_REP_KEYS", ()) or ())
    msg = tuple(getattr(kern, "PERM_MSG_KEYS", ()) or ())
    if not rep and not msg:
        return None
    return {k: "all" for k in rep + msg}


def group_closed(perms):
    """True iff {identity} + perms is closed under composition (each
    perm is a dict ModelValue -> ModelValue; identity pairs dropped).
    Orbit canonicalization by min-over-enumerated-perms is only
    orbit-invariant for a closed group — the same precondition the
    host interpreter's ``view_value`` min has always had."""
    frozen = {frozenset(p.items()) for p in perms}
    frozen.add(frozenset())
    for p in perms:
        for q in perms:
            comp = {}
            keys = set(p) | set(q)
            for k in keys:
                v = p.get(q.get(k, k), q.get(k, k))
                if v is not k:
                    comp[k] = v
            if frozenset(comp.items()) not in frozen:
                return False
    return True


def group_table(spec, codec):
    """The evaluated SYMMETRY group as an identity-first ``[P, V+1]``
    value-id table (registry.value_perm_table), with the closure
    precondition enforced loudly — lint reports it statically, but
    canonicalization soundness must not depend on the lint gate being
    armed (TPUVSR_LINT=off exists)."""
    from ..models.registry import value_perm_table
    if not group_closed(spec.symmetry_perms):
        raise TLAError(
            "SYMMETRY permutation set is not closed under composition "
            "(plus identity): orbit canonicalization would be "
            "orbit-dependent and the checker would under- or "
            "over-merge states.  TLC's Permutations(S) is always "
            "closed; hand-written SYMMETRY sets must be too")
    return value_perm_table(spec, codec)


def _lex_less(a, b):
    """Lexicographic a < b over two equal-length uint32 key vectors:
    find the first differing lane, compare there."""
    neq = a != b
    i = jnp.argmax(neq)
    return neq.any() & (a[i] < b[i])


class CanonSpec:
    """The canonicalization kernel for one (spec, codec, kernel)
    binding: ``canonicalize`` maps one dense SoA state row to the
    lexicographic least element of its symmetry orbit.  Pure jnp —
    jit/vmap composable, so the engines run it INSIDE their jitted
    level kernels (the acceptance criterion: no host round-trip per
    state)."""

    def __init__(self, group, planes, kern=None):
        self.group = np.asarray(group, np.int32)     # [P, V+1], id 1st
        self.planes = dict(planes)
        self.kern = kern
        self._jgroup = jnp.asarray(self.group)
        payload = json.dumps(
            {"group": self.group.tolist(),
             "planes": {k: list(v) if isinstance(v, tuple) else v
                        for k, v in sorted(self.planes.items())}},
            sort_keys=True)
        #: digest of (group table, orbit plane table) — the snapshot
        #: compatibility key (ISSUE 11 satellite: resuming under a
        #: changed group/table is a policy error)
        self.version = "canon/1:" + hashlib.sha256(
            payload.encode()).hexdigest()[:16]

    @property
    def perms(self):
        """Group order, identity included."""
        return int(self.group.shape[0])

    def manifest(self):
        """Checkpoint-manifest record of this canonicalization spec."""
        return {"version": self.version, "perms": self.perms,
                "planes": sorted(self.planes)}

    # ------------------------------------------------------------------
    def _apply(self, st, perm):
        """One permutation's action on one dense state row.  Prefers
        the kernel's own ``_permuted`` (packed-entry layouts override
        it); falls back to the declarative SYM_PLANES table action."""
        if self.kern is not None and hasattr(self.kern, "_permuted"):
            return self.kern._permuted(st, perm)
        out = dict(st)
        for k, how in self.planes.items():
            v = jnp.asarray(st[k])
            if how == "all":
                out[k] = perm[v].astype(v.dtype)
            else:
                col = int(how[1])
                out[k] = v.at[..., col].set(
                    perm[v[..., col]].astype(v.dtype))
        return out

    def _key(self, st):
        """The comparison key of one image: the flattened symmetric
        planes (untouched planes are identical across all images of a
        state — and across orbit-mates — so they never discriminate)."""
        return jnp.concatenate(
            [jnp.asarray(st[k], jnp.uint32).reshape(-1)
             for k in sorted(self.planes)])

    def least(self, st):
        """One dense state row -> (the least element of its orbit,
        whether that is another image than the identity's): a small
        sort-network fold over the enumerated group."""
        if self.perms == 1:
            return st, jnp.asarray(False)
        best = self._apply(st, self._jgroup[0])      # identity image
        bkey = self._key(best)
        moved = jnp.asarray(False)
        for p in range(1, self.perms):
            cand = self._apply(st, self._jgroup[p])
            ckey = self._key(cand)
            less = _lex_less(ckey, bkey)
            bkey = jnp.where(less, ckey, bkey)
            best = {k: jnp.where(less, cand[k], best[k]) for k in best}
            moved = moved | less
        return best, moved

    def canonicalize(self, st):
        """One dense state row -> the least element of its orbit."""
        return self.least(st)[0]

    def fingerprint_fn(self, kern):
        """``st -> kern.fingerprint(canonicalize(st))`` — the one
        pre-fingerprint seam every engine hooks (fused/chunked commit
        stage 3, the paged insert path, the sharded pre-bucketing
        step, the fleet novelty set)."""
        def fingerprint(st):
            with jax.named_scope(spans.CANON):
                image = self.canonicalize(st)
            return kern.fingerprint(image)

        return fingerprint


def build_canon_spec(spec, codec, kern, symmetry="auto"):
    """Resolve the engine-level ``symmetry`` switch into a CanonSpec
    (or None).

    ``"auto"`` (every engine's default): canonicalize iff the cfg
    declares SYMMETRY — mirroring TLC, where declaring Permutations IS
    turning the optimization on.  ``True`` insists (a cfg without
    SYMMETRY is a loud error — there is no group to reduce by);
    ``False`` disables reduction entirely (the A/B leg: the engines
    then run identity-only fingerprints and store every orbit member).
    """
    enabled = (bool(spec.symmetry_perms) if symmetry == "auto"
               else bool(symmetry))
    if not enabled:
        return None
    if not spec.symmetry_perms:
        raise TLAError(
            "symmetry canonicalization requested (-symmetry on) but "
            "the cfg declares no SYMMETRY — there is no permutation "
            "group to reduce by")
    planes = orbit_planes(kern)
    if planes is None:
        raise TLAError(
            f"kernel {type(kern).__name__} declares no orbit plane "
            f"table (SYM_PLANES or PERM_REP_KEYS/PERM_MSG_KEYS): the "
            f"device canonicalization pass cannot know which planes "
            f"a value permutation touches.  Run -symmetry off or add "
            f"the table")
    missing = [k for k in planes if k not in codec.zero_state()]
    if missing:
        raise TLAError(
            f"orbit plane table names planes {missing} the codec "
            f"layout does not declare (lint/kernel drift)")
    return CanonSpec(group_table(spec, codec), planes, kern=kern)
