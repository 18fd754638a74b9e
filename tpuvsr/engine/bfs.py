"""Breadth-first exhaustive model checking (interpreter backend).

The reference's runtime is TLC's BFS worker loop (SURVEY.md §3.1):
dequeue -> enumerate successors over every Next disjunct -> invariant
check -> VIEW projection -> symmetry canonicalization -> fingerprint
dedup -> enqueue, with parent pointers for trace reconstruction.  This
module is the faithful single-host implementation used as the oracle for
the TPU engine; states are deduplicated on the exact canonical view value
(collision-free, unlike TLC's 64-bit fingerprints).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

from ..core.values import TLAError
from ..resilience.faults import fault_point
from .spec import SpecModel
from .trace import TraceEntry, reconstruct_trace


@dataclass
class CheckResult:
    ok: bool = True
    distinct_states: int = 0
    states_generated: int = 0
    diameter: int = 0
    violated_invariant: str = None
    deadlock_state: dict = None
    trace: list = field(default_factory=list)
    # timing/trajectory fields set uniformly by RunObserver.finish —
    # engines never patch them post hoc (ISSUE 2 satellite)
    elapsed: float = 0.0
    states_per_sec: float = 0.0
    levels: list = None       # per-level frontier sizes, init included
    metrics: dict = None      # tpuvsr-metrics/1 document for this run
    error: str = None
    exchange: dict = None     # sharded-engine ICI exchange metrics


def bfs_check(spec: SpecModel, check_deadlock: bool = False,
              max_states: int = None, progress_every: float = 10.0,
              log=None, obs=None) -> CheckResult:
    from ..analysis import preflight
    from ..obs import RunObserver, spans
    preflight(spec, log=log)      # speclint gate (TPUVSR_LINT=off skips)
    obs = RunObserver.ensure(obs, "interp", spec, log=log,
                             progress_every=progress_every)
    res = CheckResult()
    t0 = time.time()
    obs.start(t0, backend="host")
    seen = {}           # canonical view value -> state id
    parents = {}        # state id -> (parent id, action name, action location)
    states = []         # state id -> state dict (kept for trace replay)
    frontier = []
    level_sizes = []

    def finish(depth):
        res.distinct_states = len(states)
        res.diameter = depth
        return obs.finish(res, levels=level_sizes)

    def register(state, parent_id, action):
        key = spec.view_value(state)
        sid = seen.get(key)
        if sid is None:
            sid = len(states)
            seen[key] = sid
            states.append(state)
            parents[sid] = (parent_id, action.name if action else None,
                            action.location if action else None)
            return sid, True
        return sid, False

    depth = 0
    try:
        for st in spec.init_states():
            res.states_generated += 1
            sid, fresh = register(st, None, None)
            if fresh:
                bad = spec.check_invariants(st)
                if bad:
                    res.ok = False
                    res.violated_invariant = bad
                    res.trace = reconstruct_trace(sid, parents, states)
                    return finish(depth)
                frontier.append(sid)
        level_sizes.append(len(frontier))

        while frontier:
            depth += 1
            fault_point("level", depth=depth, obs=obs)
            next_frontier = []
            with obs.span(spans.CHECK, depth=depth):
                for sid in frontier:
                    state = states[sid]
                    n_succ = 0
                    for action, succ in spec.successors(state):
                        n_succ += 1
                        res.states_generated += 1
                        tid, fresh = register(succ, sid, action)
                        if fresh:
                            bad = spec.check_invariants(succ)
                            if bad:
                                res.ok = False
                                res.violated_invariant = bad
                                res.trace = reconstruct_trace(
                                    tid, parents, states)
                                return finish(depth)
                            next_frontier.append(tid)
                    if n_succ == 0 and check_deadlock:
                        res.ok = False
                        res.error = "deadlock"
                        res.deadlock_state = state
                        res.trace = reconstruct_trace(
                            sid, parents, states)
                        return finish(depth)
                    if max_states and len(states) >= max_states:
                        res.error = f"state limit {max_states} reached"
                        return finish(depth)
                    obs.progress(depth=depth, distinct=len(states),
                                 generated=res.states_generated)
            if next_frontier:
                level_sizes.append(len(next_frontier))
            obs.level_done(depth, frontier=len(frontier),
                           distinct=len(states),
                           generated=res.states_generated)
            frontier = next_frontier
    except TLAError as e:
        res.ok = False
        res.error = str(e)
    return finish(depth)
