"""Kernel-native specs (ROADMAP R1 route B): a ``SpecModel``-shaped
object for a registered module name plus a cfg, built from committed
files only — no ``.tla``, no AST.

The device engines never evaluate the spec's AST on the hot path: they
take the transition relation, fingerprints and invariants from the
hand kernel (``models/registry._resolve``) and read only a narrow
facade from ``spec`` — cfg, constants, module name, init states, action
names/locations for trace printing, and a host-side invariant check on
decoded states.  This module provides exactly that facade:

* init states come from a committed TLC-format trace whose entry 1 is
  the module's complete initial state (``INIT_TRACES``); the codec
  round trip proves it fits the cfg's constants, and the pinned level
  sizes (scripts/pinned_levels_small.json, scripts/defect_window.json)
  are the check that it is the right one;
* ``check_invariants`` runs the kernel's own ``invariant_fn`` on
  ``codec.encode(state)``; ``walk_trace`` holds the kernel to a
  recorded TLC trace (the one committed oracle that does not come from
  the device engine itself);
* cfg ``SYMMETRY`` names a definition; what it evaluates to is
  committed knowledge of the module (``SYMMETRY_SETS``: the constant
  set whose full permutation group it is), so ``symmetry_perms`` is
  what ``SpecModel._symmetry_perms`` evaluates from the AST and the
  engines canonicalize as they do for a ``.tla``-loaded spec;
* anything else that needs the AST is refused loudly: a SYMMETRY name
  the table does not know, PROPERTY / SPECIFICATION, the speclint
  passes (``analysis.preflight`` logs one line and returns None;
  ``-bounds on`` / ``-por on`` / ``-lint`` exit 2), and the
  interpreter engine.

``engine.spec.load_spec`` resolves here when its spec argument is not
an existing file but a module name the registry knows.  Four modules
have a committed init trace today: VSR, VR_STATE_TRANSFER (ST03, the
base kernel of the analysis family), VR_REPLICA_RECOVERY_CP (CP06, the
family's last: crash with a checkpoint, log GC, recovery; its kernel
runs AS04's and RR05's as base classes) and
VR_REPLICA_RECOVERY_ASYNC_LOG (AL05: a crash keeps a log prefix; over
RR05 too).  The three analysis traces hold entry 1 alone, the state the
module's codec decodes for the zero state in view 1 (CP06's and AL05's
with their own planes: ``rep_app_state``, ``rep_rec_number``,
``rep_rec_recv``, ``rep_recv_dvc``, ``aux_restart``), and their action
locations are the line ranges the kernel class itself cites.  AL05's
differs from that state in one variable: ``rep_last_normal_view`` is 1,
not 0.  With 0 the one record of the real module
(scripts/recovery_fixpoints.json) is not reproduced: a replica that
recovers in view 1 takes last normal view 1 from the response and then
outranks, with an empty log, replicas that never left view 1, and
NoLogDivergence fails in level 16 of a check the record has clean to
its fixpoint; with 1 all 30 level sizes come out
(tests/test_native_al05.py, PR 53).  The other four modules are refused
by name until each has one.

**Which (module, ReplicaCount) the door admits.**  Every committed
trace is a state of three replicas, and Init is that state wherever
the cfg binds ``ReplicaCount = 3``.  At another R a trace cannot speak,
but "the codec's zero state in view 1" can: for a module ``INIT_AT_R``
lists, Init at a listed R is what the module's codec decodes for that
state at that R, and the rule is held to the committed trace every
time it is used (the same rule at the trace's own R must give the
trace's entry 1, value for value).  The table lists only what a tier-1
test holds to a plain reference: VR_STATE_TRANSFER at 3 and 5
(``tests/test_native_st03_r5.py``,
``benchmark/tools/state_transfer_reference.py``).  VSR,
VR_REPLICA_RECOVERY_CP and VR_REPLICA_RECOVERY_ASYNC_LOG have no
reference held to a record at another R and stay at 3:
any other R there, an even R and an R the table lacks are refused with
the R named — never a silently wrong state space.
"""

from __future__ import annotations

import itertools
import os
import re

import numpy as np

from ..core.values import TLAError, value_key
from ..engine.spec import Action
from ..frontend.tla_ast import Module
from ..interp.evalr import Evaluator
from .registry import REPO, _resolve

# module name -> committed TLC trace whose entry 1 is the full Init state
INIT_TRACES = {
    "VSR": os.path.join(REPO, "examples", "found_violation_trace.txt"),
    "VR_STATE_TRANSFER": os.path.join(
        REPO, "examples", "VR_STATE_TRANSFER_init_trace.txt"),
    "VR_REPLICA_RECOVERY_CP": os.path.join(
        REPO, "examples", "VR_REPLICA_RECOVERY_CP_init_trace.txt"),
    "VR_REPLICA_RECOVERY_ASYNC_LOG": os.path.join(
        REPO, "examples", "VR_REPLICA_RECOVERY_ASYNC_LOG_init_trace.txt"),
}

# module name -> the ReplicaCounts at which Init is "the module's codec's
# zero state in view 1", the committed trace's own R among them (see the
# module doc); a module that is not here starts from its trace alone
INIT_AT_R = {"VR_STATE_TRANSFER": (3, 5)}

# module name -> cfg SYMMETRY definition name -> the constant set the
# definition permutes (VSR.tla:151: symmValues == Permutations(Values))
SYMMETRY_SETS = {"VSR": {"symmValues": "Values"}}

_LOCATION = re.compile(
    r'name \|-> "(\w+)",\s*location \|-> "(line [^"]+)"')


class NativeSpec:
    """The facade the device engines read from a spec (see module doc)."""

    native = True

    def __init__(self, name, cfg):
        known = SYMMETRY_SETS.get(name, {})
        for what, val in (("SYMMETRY", cfg.symmetry
                           if cfg.symmetry not in known else None),
                          ("PROPERTY", cfg.properties),
                          ("SPECIFICATION", cfg.specification)):
            if val:
                raise TLAError(
                    f"native spec {name!r}: cfg {what} needs the .tla "
                    f"module's definitions; pass the module file "
                    f"instead of its name")
        self.module = Module(name=name)
        self.cfg = cfg
        self.ev = Evaluator(self.module, cfg.constants)
        self.temporal_props = []
        self.fairness = []
        self.symmetry_perms = _permutations(
            cfg.constants[known[cfg.symmetry]]) if cfg.symmetry else []
        self._codec_cls, self._kern_cls = _resolve(name)
        with open(INIT_TRACES[name]) as f:
            self._trace_text = f.read()
        # TLC's locations where the trace records them, else the line
        # range this kernel class itself cites (never a base class's)
        locs = {a: f"lines {lo}-{hi} of module {name}" for a, (lo, hi)
                in vars(self._kern_cls).get("ACTION_LINES", {}).items()}
        locs.update(_LOCATION.findall(self._trace_text))
        self.actions = [
            Action(name=a, expr=None,
                   location=locs.get(a, f"native kernel of module {name}"))
            for a in self._kern_cls.action_names]
        self._models = {}        # max_msgs -> (codec, kernel, inv fns)

    # -- host-side kernel binding --------------------------------------
    def model(self, max_msgs):
        """(codec, kernel, {invariant name -> jitted fn}) at a message
        table bound, cached."""
        if max_msgs not in self._models:
            codec = self._codec_cls(self.cfg.constants, max_msgs=max_msgs)
            self._models[max_msgs] = (codec, self._kern_cls(codec), {})
        return self._models[max_msgs]

    def _model_for(self, state):
        """The cached model whose (power-of-two) message table holds
        `state`'s bag."""
        n = len(state["messages"].items)
        return self.model(1 << max(4, (n - 1).bit_length()))

    # -- checkable interface (engine/spec.SpecModel's) ------------------
    def init_states(self):
        from ..frontend.trace_parse import parse_trace_text
        name = self.module.name
        first, *rest = re.split(r"\],\s*\n\[", self._trace_text.strip(), 1)
        st = parse_trace_text(first + "]\n>>" if rest else first,
                              self)[0].state
        R = self.cfg.constants.get("ReplicaCount")
        if name in INIT_AT_R and R != len(st["replicas"]):
            st = self._zero_init_at(R, st)
        codec, _, _ = self._model_for(st)
        try:
            fits = codec.decode(codec.encode(st)) == st
        except (TLAError, KeyError, IndexError):
            fits = False
        if not fits:
            raise TLAError(
                f"native spec {name!r}: the committed init "
                f"state ({INIT_TRACES[name]}) does not fit "
                f"this cfg's constants (ReplicaCount = {R})")
        yield st

    def _zero_init_at(self, R, anchor):
        """Init at a ReplicaCount the committed trace does not have:
        the codec's zero state in view 1 at the cfg's constants,
        admitted only where ``INIT_AT_R`` lists (module, R) and only
        while the same rule at the trace's own R gives `anchor`, the
        trace's entry 1."""
        name = self.module.name
        if R not in INIT_AT_R[name]:
            raise TLAError(
                f"native spec {name!r}: ReplicaCount = {R} is not "
                f"admitted; Init is held to a plain reference at "
                f"ReplicaCount in {list(INIT_AT_R[name])} "
                f"(models/native.INIT_AT_R)")

        def zero_in_view_1(constants):
            codec = self._codec_cls(constants, max_msgs=16)
            zero = codec.zero_state()
            zero["view"][:] = 1
            return codec.decode(zero)
        if zero_in_view_1(dict(self.cfg.constants, ReplicaCount=len(
                anchor["replicas"]))) != anchor:
            raise TLAError(
                f"native spec {name!r}: the committed init state "
                f"({INIT_TRACES[name]}) is not the codec's zero state "
                f"in view 1, so it says nothing of ReplicaCount = {R}")
        return zero_in_view_1(self.cfg.constants)

    def check_invariants(self, state):
        """Name of the first cfg invariant the kernel's invariant fn
        rejects on the encoded state, or None."""
        import jax
        codec, kern, inv = self._model_for(state)
        dense = codec.encode(state)
        for name in self.cfg.invariants:
            if name not in inv:
                inv[name] = jax.jit(kern.invariant_fn([name]))
            if not bool(inv[name](dense)):
                return name
        return None


def _permutations(values):
    """``Permutations(values)`` as ``SpecModel._symmetry_perms`` hands
    it to the engines: one dict ModelValue -> ModelValue a permutation,
    fixed points and the identity dropped."""
    elems = sorted(values, key=value_key)
    perms = ({a: b for a, b in zip(elems, image) if a is not b}
             for image in itertools.permutations(elems))
    return [p for p in perms if p]


def native_spec(name, cfg):
    """NativeSpec for a registered module name, None for a name the
    registry does not know (the caller then fails as on any missing
    file)."""
    try:
        _resolve(name)
    except KeyError:
        return None
    if name not in INIT_TRACES:
        raise TLAError(
            f"module {name!r} has a device kernel but no committed "
            f"init trace (models/native.INIT_TRACES): pass its .tla "
            f"file")
    return NativeSpec(name, cfg)


def walk_trace(spec, path, max_msgs=64):
    """Walk a recorded TLC trace through the kernel in one batch.

    Encodes every recorded state, expands all but the last with ONE
    ``kern.step_batch`` call, and requires that recorded state i+1 is
    among the successors of state i produced by lanes of the recorded
    action.  Returns (entries, ok): ``ok[i]`` is the conjunction of the
    cfg invariants on state i, from the kernel's invariant fn.  Raises
    TLAError on the first step the kernel cannot reproduce."""
    import jax
    from ..frontend.trace_parse import parse_trace_file
    entries = parse_trace_file(path, spec)
    codec, kern, _ = spec.model(max_msgs)
    dense = [codec.encode(e.state) for e in entries]
    batch = {k: np.stack([d[k] for d in dense]) for k in dense[0]}
    succs, en = kern.step_batch({k: v[:-1] for k, v in batch.items()})
    en = np.asarray(en)
    succs = {k: np.asarray(v) for k, v in succs.items()}
    lane_action = np.asarray(kern.lane_action)
    for i, e in enumerate(entries[1:]):
        aid = kern.action_names.index(e.action_name)
        lanes = np.nonzero(en[i] & (lane_action == aid))[0]
        if not any(int(succs["err"][i, ln]) == 0 and codec.decode(
                {k: v[i, ln] for k, v in succs.items()}) == e.state
                for ln in lanes):
            raise TLAError(
                f"kernel trace walk: no {e.action_name} lane of state "
                f"{e.position - 1} ({len(lanes)} enabled) reproduces "
                f"recorded state {e.position}")
    inv = jax.jit(jax.vmap(kern.invariant_fn(list(spec.cfg.invariants))))
    return entries, np.asarray(inv(batch))
