"""Engine-side consumption of the speclint bounds pass (ISSUE 13).

``analysis/passes/bounds.py`` computes the facts; this module is the
seam through which the engines trust them:

* :func:`resolve_bounds` — the one policy switch.  ``"auto"`` (every
  engine's default) consumes the facts iff the speclint gate is live:
  ``TPUVSR_LINT=off`` / ``-lint=off`` disables consumption too, because
  tightened packing derived from an unverified spec is exactly the
  silent-wrap hazard speclint exists to prevent.  Forcing ``True``
  under a disabled gate is a loud error (the CLI rejects the flag
  combination at parse time; this guards library callers).
* :func:`prune_kernel` — wraps a device kernel with the statically
  dead actions removed: the action list, guard/action function lists,
  flat lane tables and ``step_all`` rows all shrink, so the fused
  commit's chunk-wide guard matrix and the per-action staging queue
  never evaluate a guard that constant-folds to FALSE.  Dead actions
  are never enabled, so counts, level sizes, verdicts and traces are
  BIT-IDENTICAL to the unpruned kernel (the ``tests/test_bounds.py``
  oracles); only the ``action_expansions`` gauge loses its
  all-zero rows.

Checkpoint seam: engines record ``BoundsFacts.digest`` in snapshot
manifests and refuse to resume under a flipped ``-bounds`` or changed
facts (mirroring the pack/canon rules) — the packed frontier layout
and the lane-id space both depend on the facts.
"""

from __future__ import annotations

import numpy as np

from ..core.values import TLAError


def resolve_bounds(spec, req="auto"):
    """The engines' bounds switch -> :class:`BoundsFacts` or None.

    ``req``: ``"auto"`` (on iff the speclint gate is live) |
    True/"on" (forced; error when the gate is off) | False/"off"."""
    if req is False or req == "off":
        return None
    from ..analysis import lint_enabled
    if not lint_enabled(spec):
        if req is True or req == "on":
            raise TLAError(
                "bounds=on requires the speclint gate: TPUVSR_LINT=off "
                "/ -lint=off (or a native spec, which has no AST) "
                "disables the static analysis the "
                "tightened packing and pruned action lists would "
                "trust (drop -bounds on or re-enable lint)")
        return None
    from ..analysis.passes.bounds import analyze
    return analyze(spec)


class PrunedKernel:
    """A device kernel with statically dead actions removed.

    Implements exactly the attribute contract the engines consume
    (``action_names`` / ``n_lanes`` / ``_lane_count`` / ``_guard_fns``
    / ``_action_fns`` / ``lane_action`` / ``lane_param`` /
    ``step_all``); everything else (fingerprinting, invariants,
    symmetry tables, key tables) delegates to the wrapped kernel."""

    def __init__(self, kern, dead):
        names = list(kern.action_names)
        dead = [n for n in dead if n in names]
        keep = [n for n in names if n not in dead]
        if not keep:
            raise TLAError("prune_kernel: every action is dead — the "
                           "engine needs at least one live action "
                           "(run bounds=off to inspect the space)")
        self._base = kern
        self.pruned_actions = dead
        self.action_names = keep
        keep_aids = np.asarray([names.index(n) for n in keep],
                               np.int32)
        # flat lane tables: keep the lanes of live actions, renumber
        # action ids onto the filtered list (lane params unchanged)
        la = np.asarray(kern.lane_action, np.int32)
        self._lane_keep = np.where(np.isin(la, keep_aids))[0]
        remap = np.full(len(names), -1, np.int32)
        remap[keep_aids] = np.arange(len(keep), dtype=np.int32)
        self.lane_action = remap[la[self._lane_keep]]
        self.lane_param = np.asarray(kern.lane_param,
                                     np.int32)[self._lane_keep]
        self.n_lanes = int(self._lane_keep.shape[0])
        self._keep_idx = [names.index(n) for n in keep]

    def _lane_count(self, name):
        return self._base._lane_count(name)

    def _guard_fns(self):
        fns = self._base._guard_fns()
        return [fns[i] for i in self._keep_idx]

    def _action_fns(self):
        fns = self._base._action_fns()
        return [fns[i] for i in self._keep_idx]

    def step_all(self, st):
        succs, ens = self._base.step_all(st)
        idx = self._lane_keep
        return ({k: v[idx] for k, v in succs.items()}, ens[idx])

    def __getattr__(self, name):
        return getattr(self.__dict__["_base"], name)


def prune_kernel(kern, dead):
    """Wrap `kern` with the `dead` action names removed (no-op pass
    back when nothing would change)."""
    dead = [n for n in dead if n in kern.action_names]
    if not dead:
        return kern
    return PrunedKernel(kern, dead)
