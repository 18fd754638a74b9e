"""Traffic kind `bfs-timed`: one exhaustive breadth-first check from
Init under the engine's own `max_seconds`, on the engine object that
set-up built and warmed.

The traffic file gives `engine` ("device": DeviceBFS on one chip;
"sharded": ShardedBFS over a 1-D mesh of every device), `engine_flags`
(constructor arguments beyond the configuration's capacities; empty =
the engine's defaults), `warmup_depth` and `trace_seconds`.  The
configuration gives the cfg, the widths, the capacities
(`assumed.engine.<engine>`), the oracles and `assumed.trace_depth`.

A traced run (`cell.trace_depth` set) is the same run under the same
budget, ended by that depth instead: whole levels 0..trace_depth,
nothing in flight dropped, the same states on both sides of a pair
however fast either side is.  `check` then holds it to the oracle's
first trace_depth + 1 levels and fails it if the budget ended it.
`trace_seconds` cuts the traced run only of a configuration without
the key (the rehearsal's vsr-small; run.py).
"""

import os
import time

import oracle


def build_engine(cell, spec):
    """The engine at the configuration's capacities."""
    kind = cell.traffic["engine"]
    kw = dict(cell.config["assumed"]["engine"][kind])
    kw.update(cell.traffic.get("engine_flags") or {})
    if kind == "device":
        from tpuvsr.engine.device_bfs import DeviceBFS
        return DeviceBFS(spec, **kw)
    if kind == "sharded":
        import jax
        import numpy as np
        from jax.sharding import Mesh

        from tpuvsr.parallel.sharded_bfs import ShardedBFS
        return ShardedBFS(spec, Mesh(np.array(jax.devices()), ("d",)), **kw)
    raise SystemExit(f"bfs-timed: unknown engine {kind!r}")


def pinned_levels(cell):
    """The oracle's level sizes through the depth this run may reach:
    the last pinned one, or a traced run's trace_depth."""
    levels = cell.oracle_levels()
    if cell.trace_depth is not None:
        levels = levels[:cell.trace_depth + 1]
    return levels


def _observer(cell, tag):
    """A journal and a metrics document per run: they also switch on
    the engine's detailed gauges (fpset_collision_rate)."""
    from tpuvsr.obs import RunObserver
    return RunObserver(
        journal_path=os.path.join(cell.out_dir, f"{tag}.journal.jsonl"),
        metrics_path=os.path.join(cell.out_dir, f"{tag}.metrics.json"),
        log=cell.log)


def setup(cell):
    from tpuvsr.engine.spec import load_spec
    spec = load_spec(cell.config["module"], cell.path(cell.config["cfg"]))
    state = {"walk": None}
    ce = cell.config["oracle"].get("counterexample")
    if ce:
        # the committed TLC counterexample on this backend's kernel:
        # every recorded step among step_batch's successors
        from tpuvsr.models.native import walk_trace
        t0 = time.time()
        entries, ok = walk_trace(spec, cell.path(ce["file"]))
        state["walk"] = {"states": len(entries),
                         "last_action": entries[-1].action_name,
                         "invariant_ok": [bool(x) for x in ok]}
        cell.log(f"trace walk: {len(entries)} states in "
                 f"{time.time() - t0:.1f}s")
    eng = build_engine(cell, spec)
    t0 = time.time()
    # same object, same programs: the window's run starts again from
    # Init and finds every program built
    warm = eng.run(max_depth=int(cell.traffic["warmup_depth"]),
                   obs=_observer(cell, "warmup"), log=cell.log)
    cell.log(f"warm-up to depth {cell.traffic['warmup_depth']}: "
             f"{warm.distinct_states} distinct in {time.time() - t0:.1f}s")
    state["engine"] = eng
    state["warmup_levels"] = list(warm.levels or [])
    return state


def window(cell, state, seconds):
    oracle_levels = pinned_levels(cell)
    res = state["engine"].run(
        max_seconds=seconds, max_depth=len(oracle_levels) - 1,
        obs=_observer(cell, "window"), log=cell.log)
    return {"result": res, "metrics_doc": res.metrics, "seconds": seconds,
            "elapsed_s": float(res.elapsed),
            "distinct": int(res.distinct_states),
            "levels": [int(x) for x in (res.levels or [])],
            "level_elapsed_s": [row["elapsed_s"]
                                for row in res.metrics["levels"]],
            "grows": int(res.metrics["counters"].get("grows", 0))}


def check(cell, state, obs):
    res = obs["result"]
    oracle_levels = pinned_levels(cell)
    timed_out = bool(res.error) and res.error.startswith("time budget")
    # DeviceBFS tests its budget at every chunk collect, so the level
    # it stopped in is partial; ShardedBFS only between levels
    partial = timed_out and cell.traffic["engine"] == "device"
    out, lost = oracle.level_comparisons(obs["levels"], oracle_levels,
                                         partial)
    out.append(oracle.compare("distinct.equals_sum_of_levels",
                              obs["distinct"], sum(obs["levels"])))
    out.append(oracle.compare("no_violation_before_pinned_depth",
                              [res.ok, res.violated_invariant],
                              [True, None]))
    if cell.trace_depth is not None:
        # a traced slice is whole levels: the depth ended it, never
        # the budget (which would leave its last level partial)
        reached = len(obs["levels"]) - 1
        out.append(oracle.compare(
            "stopped_at_trace_depth", [res.error, reached],
            ["not the time budget", cell.trace_depth],
            ok=not timed_out and reached == cell.trace_depth))
    else:
        # the run used its whole budget, or reached the last pinned
        # depth
        stop_ok = obs["elapsed_s"] >= obs["seconds"] if timed_out \
            else len(obs["levels"]) == len(oracle_levels)
        out.append(oracle.compare(
            "stopped_by_budget_or_at_last_pinned_depth",
            [res.error, obs["elapsed_s"], len(obs["levels"]) - 1],
            [f"time budget after >= {obs['seconds']:g}s", "or depth",
             len(oracle_levels) - 1], ok=stop_ok))
    out.append(oracle.compare(
        "warmup.levels", state["warmup_levels"],
        oracle_levels[:len(state["warmup_levels"])]))
    ce = cell.config["oracle"].get("counterexample")
    if ce:
        walk = state["walk"]
        want_ok = [True] * (ce["violates_at"] - 1) + [False]
        out.append(oracle.compare(
            "counterexample.walk",
            [walk["states"], walk["last_action"], walk["invariant_ok"]],
            [ce["states"], ce["last_action"], want_ok]))
    # operations are states: attempted = committed, failed = states
    # lost or invented against the oracle
    return {"comparisons": out, "attempted": obs["distinct"],
            "failed": lost}


def end_to_end(cell, obs):
    return {"distinct_per_s": obs["distinct"] / obs["elapsed_s"]}
