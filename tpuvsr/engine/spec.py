"""Spec model: binds a parsed module to a .cfg, decomposes SPECIFICATION
formulas, and exposes the checkable interface (init states, per-action
successor enumeration, invariants, VIEW projection, symmetry).

Replaces TLC's config binder + ModelConfig layer (SURVEY.md §1.2): INIT/
NEXT or SPECIFICATION (``Spec == Init /\\ [][Next]_vars /\\ WF_vars(Next)``
at VSR.tla:968 and the LivenessSpec split at A01:808-809), VIEW
(VSR.cfg:29), SYMMETRY (VSR.cfg:31), INVARIANT/PROPERTY registration.
"""

from __future__ import annotations

import os
from dataclasses import dataclass

from ..core.values import (FnVal, TLAError, permute_value, value_key)
from ..frontend.cfg import CfgModel
from ..frontend.tla_ast import Module
from ..interp.actions import ActionEnumerator
from ..interp.evalr import EMPTY_ENV, EvalCtx, Evaluator


@dataclass
class Action:
    name: str
    expr: tuple
    location: str   # "line a, col b to line c, col d of module M"


class SpecModel:
    def __init__(self, module: Module, cfg: CfgModel):
        self.module = module
        self.cfg = cfg
        missing = [c for c in module.constants if c not in cfg.constants]
        if missing:
            raise TLAError(f"cfg leaves constants unbound: {missing}")
        self.ev = Evaluator(module, cfg.constants)
        self.enum = ActionEnumerator(self.ev)

        self.init_name = cfg.init
        self.next_name = cfg.next
        self.fairness = []          # list of (subscript_expr, action_expr)
        self.temporal_props = list(cfg.properties)
        if cfg.specification:
            self._decompose_spec(cfg.specification)
        if not self.init_name or not self.next_name:
            raise TLAError("cfg must provide INIT/NEXT or SPECIFICATION")

        self.actions = self._action_list()
        self.invariants = [(name, self.module.defs[name])
                           for name in cfg.invariants]
        self.view_def = module.defs.get(cfg.view) if cfg.view else None
        self.symmetry_perms = self._symmetry_perms(cfg.symmetry)

    # ------------------------------------------------------------------
    def _decompose_spec(self, spec_name: str):
        d = self.module.defs.get(spec_name)
        if d is None:
            raise TLAError(f"SPECIFICATION {spec_name} not defined")
        conjuncts = []

        def flatten(e):
            if e[0] == "and":
                for x in e[1]:
                    flatten(x)
            else:
                conjuncts.append(e)
        flatten(d.body)

        def contains_temporal(e):
            if isinstance(e, list):
                return any(contains_temporal(x) for x in e)
            if not isinstance(e, tuple):
                return False
            if e and isinstance(e[0], str) and e[0] in (
                    "boxaction", "wf", "sf", "box", "diamond"):
                return True
            if e and e[0] == "binop" and e[1] == "leadsto":
                return True
            return any(contains_temporal(x) for x in e
                       if isinstance(x, (tuple, list)))

        for c in conjuncts:
            if c[0] == "boxaction":
                act, _sub = c[1], c[2]
                if act[0] == "id":
                    self.next_name = act[1]
                else:
                    self.next_name = "__Next__"
                    self.module.defs["__Next__"] = _synth_def("__Next__", act, self.module.name)
            elif c[0] in ("wf", "sf"):
                self.fairness.append((c[0], c[1], c[2]))
            elif c[0] == "id":
                sub = self.module.defs.get(c[1])
                if sub is not None and contains_temporal(sub.body):
                    # e.g. `Liveness` — a named conjunction of WF formulas
                    saved_init, saved_next = self.init_name, self.next_name
                    self._decompose_into(sub.body)
                    if self.init_name is None:
                        self.init_name = saved_init
                else:
                    if self.init_name is None or self.init_name == c[1]:
                        self.init_name = c[1]
                    else:
                        self.init_name = self.init_name  # keep first
            else:
                raise TLAError(f"cannot decompose spec conjunct {c!r}")

    def _decompose_into(self, body):
        def flatten(e, out):
            if e[0] == "and":
                for x in e[1]:
                    flatten(x, out)
            else:
                out.append(e)
        items = []
        flatten(body, items)
        for c in items:
            if c[0] in ("wf", "sf"):
                self.fairness.append((c[0], c[1], c[2]))
            elif c[0] == "boxaction":
                if c[1][0] == "id":
                    self.next_name = c[1][1]

    # ------------------------------------------------------------------
    def _action_list(self):
        d = self.module.defs.get(self.next_name)
        if d is None:
            raise TLAError(f"NEXT {self.next_name} not defined")
        actions = []

        def flatten_or(e):
            if e[0] == "or":
                for x in e[1]:
                    flatten_or(x)
            elif e[0] == "id" and e[1] in self.module.defs \
                    and not self.module.defs[e[1]].params:
                sub = self.module.defs[e[1]]
                actions.append(Action(
                    name=e[1], expr=sub.body,
                    location=f"line {sub.line0}, col {sub.col0} to line "
                             f"{sub.line1}, col {sub.col1} of module {sub.module}"))
            else:
                actions.append(Action(
                    name=self.next_name, expr=e,
                    location=f"line {d.line0}, col {d.col0} to line "
                             f"{d.line1}, col {d.col1} of module {d.module}"))
        flatten_or(d.body)
        return actions

    def _symmetry_perms(self, symm_name):
        """Evaluate the SYMMETRY definition to permutation dicts (TLC
        Permutations semantics, VSR.tla:151).  Identity is dropped."""
        if not symm_name:
            return []
        d = self.module.defs.get(symm_name)
        if d is None:
            raise TLAError(f"SYMMETRY {symm_name} not defined")
        val = self.ev.eval(d.body, EMPTY_ENV, EvalCtx({}))
        perms = []
        for p in val:
            if not isinstance(p, FnVal):
                raise TLAError("SYMMETRY must evaluate to a set of functions")
            mapping = {k: v for k, v in p.items if k is not v}
            if mapping:
                perms.append(mapping)
        return perms

    # ------------------------------------------------------------------
    # checkable interface
    # ------------------------------------------------------------------
    def init_states(self):
        d = self.module.defs[self.init_name]
        yield from self.enum.init_states(d.body)

    def successors(self, state):
        """Yield (action, successor_state) pairs."""
        for action in self.actions:
            for succ in self.enum.successors(action.expr, state):
                yield action, succ

    def check_invariants(self, state):
        """Return the name of the first violated invariant, or None."""
        ctx = EvalCtx(state)
        for name, d in self.invariants:
            if self.ev.eval(d.body, EMPTY_ENV, ctx) is not True:
                return name
        return None

    def eval_predicate(self, name, state):
        d = self.module.defs[name]
        return self.ev.eval(d.body, EMPTY_ENV, EvalCtx(state)) is True

    def view_value(self, state):
        """Project the state through VIEW (fingerprint identity), fold
        symmetry by taking the least permuted image (SURVEY.md §2.4)."""
        if self.view_def is not None:
            v = self.ev.eval(self.view_def.body, EMPTY_ENV, EvalCtx(state))
        else:
            v = FnVal(sorted(state.items()))
        if self.symmetry_perms:
            best = v
            best_key = value_key(v)
            for p in self.symmetry_perms:
                pv = permute_value(v, p)
                pk = value_key(pv)
                if pk < best_key:
                    best, best_key = pv, pk
            v = best
        return v


def _synth_def(name, body, modname):
    from ..frontend.tla_ast import Def
    return Def(name=name, params=[], body=body, module=modname)


def load_spec(tla_path: str, cfg_path: str) -> SpecModel:
    """Bind a module to a cfg.  `tla_path` is a ``.tla`` file — or,
    when no such file exists, the name of a module the kernel registry
    knows: that builds the AST-free native spec (models/native.py)
    from committed files.  Anything else fails as a missing file."""
    from ..frontend.cfg import parse_cfg_file
    if not os.path.exists(tla_path) and tla_path.isidentifier():
        from ..models.native import native_spec
        spec = native_spec(tla_path, parse_cfg_file(cfg_path))
        if spec is not None:
            return spec
    from ..frontend.parser import parse_module_file
    return SpecModel(parse_module_file(tla_path), parse_cfg_file(cfg_path))
