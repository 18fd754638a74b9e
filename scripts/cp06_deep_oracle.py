"""Write benchmark/oracles/checkpoint_recovery_levels_deep.json:
VR_REPLICA_RECOVERY_CP at the constants of the benchmark's cells
(benchmark/configs/vr-replica-recovery-cp.cfg) one level past what
`checkpoint_recovery_levels.json` holds, for the four-chip cell
`cp06-bfs-timed-4chip` (ShardedBFS), from two sources that are not the
sharded engine.

Two runs, both from Init, that must agree on every level size, on each
of the twenty-two per-action expansion counts and on the ten counters
and peaks of `CP06Kernel.COMMIT_STATS` over the committed states, at
EVERY depth (`through`), before anything is written:

1. the plain reference (benchmark/tools/checkpoint_recovery_reference.py):
   plain Python, nothing of tpuvsr imported.  `reference_counts` below
   is that file's `bfs`, line for line, with two things added: what it
   had counted is kept at every level's end (`through`), and the two
   quorum counters of `ST03Kernel.commit_stats` that its
   `commit_stats` leaves out are counted beside its own (SendDVC's
   quorum in the bag's tombstones, by `quorum_counts.waiting`; SendSV's
   in the receive-set);
2. `DeviceBFS` (fused commit) on whatever backend JAX has, at the
   capacities `vr-replica-recovery-cp.json` gives the one-chip cell
   with a next buffer that holds level 13, through the native door, run
   once to every depth's end by one engine object (`--engine-depths`).

Levels 0-12, and the counts at depth 12, must equal the oracle that is
there.  Minutes, not a test: the reference takes six for depth 13 and
holds 2.8 million views (8 GB), the engine three on the CPU.

Usage: python3 scripts/cp06_deep_oracle.py --reference-only --depth 13 > ref.json
       JAX_PLATFORMS=cpu python scripts/cp06_deep_oracle.py --depth 13 \\
           --reference-json ref.json [--check]

`--check` compares with the committed oracle and writes nothing; on the
chip (one chip: DeviceBFS), where the engine is what is in question:
`python scripts/cp06_deep_oracle.py --check --reference-json
benchmark/oracles/checkpoint_recovery_levels_deep.json`.
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "benchmark", "tools"))

MODULE = "VR_REPLICA_RECOVERY_CP"
CONFIG = os.path.join(REPO, "benchmark", "configs",
                      "vr-replica-recovery-cp.json")
CFG = os.path.join(REPO, "benchmark", "configs", "vr-replica-recovery-cp.cfg")
SHALLOW = os.path.join(REPO, "benchmark", "oracles",
                       "checkpoint_recovery_levels.json")
OUT = os.path.join(REPO, "benchmark", "oracles",
                   "checkpoint_recovery_levels_deep.json")
# `CP06Kernel.COMMIT_STATS`, by name (the reference also counts the two
# per-source peaks, which the dense layout cannot hold)
COUNTED = ("state_transfer_states", "bag_slots", "bag_tombstones",
           "bag_peak", "quorum_waiting_states",
           "svc_quorum_waiting_states", "recovering_states", "gc_states",
           "rec_set_peak", "dvc_set_peak")
PER_SOURCE = ("dvc_per_source", "rec_per_source")
SAME = ("level_sizes", "distinct", "generated", "action_expansions",
        "committed")


def quorum_waits(state, c):
    """(some replica waits on a StartViewChange quorum, on a
    DoViewChange quorum): in ViewChange with the quorum's send still to
    make, at least one record counted toward it and fewer than it
    needs.  SendDVC counts the StartViewChanges it has processed in its
    own view (delivered: count 0 in the bag), which is ST03's count
    (`quorum_counts.waiting`, whose states share the field names);
    SendSV counts its receive-set, as the family does since AS04."""
    import quorum_counts
    svc, _dvc_tombstones = quorum_counts.waiting(state, c)
    need = c.replicas // 2 + 1
    dvc = any(status == quorum_counts.reference.VIEW_CHANGE and not sent
              and 0 < len(received) < need
              for status, sent, received in zip(
                  state.rep_status, state.rep_sent_sv, state.rep_recv_dvc))
    return svc, dvc


def reference_counts(depth, log):
    """`checkpoint_recovery_reference.bfs` with `through` and the two
    quorum counters (module docstring)."""
    import checkpoint_recovery_reference as reference
    c, invariants = reference.read_cfg(CFG)
    N_VIEW = reference.N_VIEW
    peaks = reference.PEAKS

    def stats(state):
        svc, dvc = quorum_waits(state, c)
        return dict(reference.commit_stats(state),
                    quorum_waiting_states=svc or dvc,
                    svc_quorum_waiting_states=svc)

    init = reference.init_state(c)
    seen = {init[:N_VIEW]}
    frontier, sizes = [init], [1]
    fired = dict.fromkeys(reference.ACTIONS, 0)
    committed = dict.fromkeys(stats(init), 0)
    generated, conflicts = 1, 0
    through = []
    assert reference.violated(init, c, invariants) is None
    while frontier and len(sizes) <= depth:
        t0 = time.time()
        fresh, nxt = {}, []
        for state in frontier:
            for action, succ in reference.successors(state, c):
                generated += 1
                fired[action] += 1
                view = succ[:N_VIEW]
                if view in seen:
                    if fresh.get(view, succ[N_VIEW:]) != succ[N_VIEW:]:
                        conflicts += 1
                    continue
                seen.add(view)
                fresh[view] = succ[N_VIEW:]
                nxt.append(succ)
                for name, n in stats(succ).items():
                    committed[name] = (max(committed[name], n)
                                       if name in peaks
                                       else committed[name] + n)
                bad = reference.violated(succ, c, invariants)
                assert bad is None, (bad, len(sizes))
        frontier = nxt
        if nxt:
            sizes.append(len(nxt))
        through.append({"distinct": len(seen), "generated": generated,
                        "action_expansions": dict(fired),
                        "committed": {k: int(v)
                                      for k, v in committed.items()}})
        log(f"level {len(sizes) - 1}: {len(nxt)} states, {len(seen)} "
            f"distinct, {generated} generated, {time.time() - t0:.1f}s")
    return dict(through[-1], level_sizes=sizes, through=through,
                aux_conflicts=conflicts, violation=None,
                invariants=list(invariants), constants=c._asdict())


def at_depth(ref, depth):
    """What the reference's run had counted when level `depth` was
    whole."""
    return dict(ref["through"][depth - 1],
                level_sizes=ref["level_sizes"][:depth + 1])


def engine_runs(depths):
    """One `DeviceBFS`, run from Init to each of `depths`: what it
    counts through each, and what the sizes rest on at the deepest."""
    import jax
    from tpuvsr.engine.device_bfs import DeviceBFS
    from tpuvsr.engine.spec import load_spec
    with open(CONFIG) as f:
        config = json.load(f)
    # past the one-chip cell's pin a level outgrows its next buffer
    # (level 13 holds 1,376,353 rows): room for it, nothing else
    kw = dict(config["assumed"]["engine"]["device"])
    kw["next_capacity"] = max(kw["next_capacity"], 1 << 21)
    eng = DeviceBFS(load_spec(MODULE, CFG), **kw)
    outs = {}
    for depth in depths:
        t0 = time.time()
        res = eng.run(max_depth=depth,
                      log=lambda s: print(f"[engine] {s}", flush=True))
        assert res.ok and res.error == f"depth limit {depth} reached", \
            res.error
        counters, gauges = res.metrics["counters"], res.metrics["gauges"]
        outs[depth] = {
            "level_sizes": [int(x) for x in eng.level_sizes],
            "distinct": int(res.distinct_states),
            "generated": int(res.states_generated),
            "action_expansions": gauges["action_expansions"],
            "committed": {
                n: int((gauges if n.endswith("_peak") else counters)
                       .get(n, 0)) for n in COUNTED}}
        sizing = {"backend": jax.default_backend(), "engine": kw,
                  "depth": depth,
                  "need_seen": dict(zip(eng.kern.action_names,
                                        (int(x) for x in eng._need_seen))),
                  "caps": dict(zip(eng.kern.action_names,
                                   eng._expand_caps())),
                  "grows": int(counters.get("grows", 0)),
                  "grow_message_table": int(
                      counters.get("grow_message_table", 0)),
                  "seconds": round(time.time() - t0, 1)}
        print(json.dumps({"sizing": sizing}), flush=True)
    return outs, sizing


def counted(res):
    return dict(res, committed={n: int(res["committed"][n])
                                for n in COUNTED})


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--depth", type=int, default=13)
    ap.add_argument("--reference-only", action="store_true",
                    help="run the reference and print its JSON")
    ap.add_argument("--reference-json")
    ap.add_argument("--engine-depths", type=int, nargs="*",
                    help="the depths the engine is run to and compared "
                         "at (default: 8, 12 and --depth)")
    ap.add_argument("--check", action="store_true")
    args = ap.parse_args(argv)
    if args.reference_only:
        t0 = time.time()
        ref = reference_counts(args.depth, lambda s: print(
            f"[reference] {s}", file=sys.stderr, flush=True))
        ref["seconds"] = round(time.time() - t0, 1)
        import resource
        ref["peak_rss_mb"] = resource.getrusage(
            resource.RUSAGE_SELF).ru_maxrss // 1024
        print(json.dumps(ref))
        return 0
    with open(args.reference_json) as f:
        ref = json.load(f)
    assert len(ref["level_sizes"]) == args.depth + 1, ref["level_sizes"]
    assert not ref.get("violation") and not ref.get("aux_conflicts"), ref
    per_source = {n: ref["committed"][n] for n in PER_SOURCE}
    assert max(per_source.values()) <= 1, per_source
    # levels 0-12 and everything counted through 12: the oracle that is
    # there (the reference's own `bfs`, PR 46)
    with open(SHALLOW) as f:
        shallow = json.load(f)
    pin = shallow["complete_through_depth"]
    was = at_depth(ref, pin)
    for key in ("level_sizes", "distinct", "generated",
                "action_expansions"):
        assert was[key] == shallow[key], key
    for name, value in shallow["committed"].items():
        assert was["committed"][name] == value, name
    depths = sorted(set(args.engine_depths or [8, pin, args.depth]))
    eng, sizing = engine_runs(depths)
    for depth in depths:
        want = counted(at_depth(ref, depth))
        for key in SAME:
            assert want[key] == eng[depth][key], (depth, key, want[key],
                                                  eng[depth][key])
    print(f"engine == reference through depths {depths} on {SAME}",
          flush=True)
    if args.check:
        return 0
    want = counted(at_depth(ref, args.depth))
    doc = {
        "config": "benchmark/configs/vr-replica-recovery-cp.cfg "
                  f"({MODULE}, R=3, |Values|=2, timer=2, "
                  "NoProgressChangeLimit=0, CrashLimit=1, VIEW view, "
                  "symmetry off), "
                  f"max_msgs={sizing['engine']['max_msgs']} (never grown)",
        "provenance": (
            f"levels 0-{args.depth}: scripts/cp06_deep_oracle.py, two "
            "runs that agree at every depth's end on the level sizes, on "
            "every one of the twenty-two per-action expansion counts and "
            "on the ten counters and peaks of CP06Kernel.COMMIT_STATS "
            "over the committed states.  (1) the plain reference "
            "benchmark/tools/checkpoint_recovery_reference.py (its "
            "successors, invariants and commit_stats under its own "
            "breadth-first loop over the VIEW, with the two quorum "
            "counters counted beside them): plain Python on host values, "
            "nothing of tpuvsr imported, "
            f"{ref.get('seconds', 0):.0f} s, peak "
            f"{ref.get('peak_rss_mb', 0)} MB; aux_conflicts 0; none of "
            "its five invariants violated.  (2) DeviceBFS(commit="
            "'fused') through the native door at the one-chip cell's "
            "capacities (next buffer 1<<21 for level 13) on the "
            f"{sizing['backend']} backend, compared at depths {depths}, "
            f"{sizing['seconds']:.0f} s for the deepest, grows "
            f"{sizing['grows']}, grow_message_table "
            f"{sizing['grow_message_table']}.  Levels 0-{pin} and every "
            f"count through {pin} equal checkpoint_recovery_levels.json"),
        "commands": [
            "python3 scripts/cp06_deep_oracle.py --reference-only "
            f"--depth {args.depth} > ref.json",
            "JAX_PLATFORMS=cpu python scripts/cp06_deep_oracle.py "
            f"--depth {args.depth} --reference-json ref.json",
            "python scripts/cp06_deep_oracle.py --check --reference-json "
            "benchmark/oracles/checkpoint_recovery_levels_deep.json"
            "   # on one chip"],
        "used_by": "cp06-bfs-timed-4chip (ShardedBFS, fused step)",
        "complete_through_depth": args.depth,
        "distinct": want["distinct"], "generated": want["generated"],
        "action_expansions": want["action_expansions"],
        "committed": dict(want["committed"], **per_source),
        "through": ref["through"],
        "level_sizes": ref["level_sizes"]}
    with open(OUT, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    print(f"wrote {OUT}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
