"""Write benchmark/oracles/async_log_levels.json:
VR_REPLICA_RECOVERY_ASYNC_LOG at the constants of the benchmark's cell
(benchmark/configs/vr-replica-recovery-async-log.cfg) through one level
past the pinned depth, from the plain reference, held to one engine.

Two runs, both from Init, that must agree on every level size, on each
of the twenty per-action expansion counts and on the counters and peaks
over the committed states before anything is written:

1. the plain reference (benchmark/tools/async_log_reference.py): plain
   Python, its own breadth-first loop, nothing of tpuvsr imported;
2. `DeviceBFS` (fused commit) on whatever backend JAX has, at the
   capacities the configuration's file gives the cell
   (`assumed.engine.device`; past the cell's pinned depth with a next
   buffer that holds the deeper level), through the native door.

It also prints what the configuration's sizes rest on: the engine's
`need_seen` (the most lanes of each action one tile enabled, against
the caps), `grows`, the bag's peak.  Minutes, not a test: the reference
alone takes seven for depth 11 and keeps every level in memory (about
13 GB there: 4.8 KB a state).

Usage: JAX_PLATFORMS=cpu python scripts/al05_oracle.py [--depth 11]
           [--reference-json FILE] [--check]

`--reference-json` reads the reference's numbers from a file that
`async_log_reference.py CFG --depth N` printed earlier (or from the
committed oracle itself, which holds them under the same keys) instead
of running it again; with `--depth` below the file's the engine is
held to what the reference had counted when that level was whole (the
file's `through`); `--check` compares with the committed oracle and
writes nothing.  On the chip, where the engine is what is in question:
`python scripts/al05_oracle.py --check --reference-json
benchmark/oracles/async_log_levels.json --depth 10` (the cell's pin, at
the cell's capacities: `grows` must read 0) and again without
`--depth` (one level past it).
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "benchmark", "tools"))

MODULE = "VR_REPLICA_RECOVERY_ASYNC_LOG"
CONFIG = os.path.join(REPO, "benchmark", "configs",
                      "vr-replica-recovery-async-log.json")
CFG = os.path.join(REPO, "benchmark", "configs",
                   "vr-replica-recovery-async-log.cfg")
OUT = os.path.join(REPO, "benchmark", "oracles", "async_log_levels.json")
SAME = ("level_sizes", "distinct", "generated", "action_expansions",
        "committed")
# what the engine counts of the reference's `commit_stats` (the two
# per-source peaks are what the dense layout cannot hold)
COUNTED = ("state_transfer_states", "bag_slots", "bag_tombstones",
           "bag_peak", "quorum_waiting_states",
           "svc_quorum_waiting_states", "recovering_states",
           "prefix_survivor_states", "suffix_reply_states",
           "rec_set_peak", "dvc_set_peak")


def reference_run(depth, path):
    if path:
        with open(path) as f:
            res = json.load(f)
        have = len(res["level_sizes"]) - 1
        assert have >= depth >= 1, res["level_sizes"]
        if have > depth:
            # what the same run counted when level `depth` was whole
            res = dict(res["through"][depth - 1],
                       level_sizes=res["level_sizes"][:depth + 1])
        return res, None
    import async_log_reference as reference
    c, invariants = reference.read_cfg(CFG)
    assert invariants == reference.INVARIANTS
    t0 = time.time()
    res = reference.bfs(c, invariants, max_depth=depth,
                        log=lambda s: print(f"[reference] {s}",
                                            flush=True))
    return res, time.time() - t0


def engine_run(depth):
    import jax
    from tpuvsr.engine.device_bfs import DeviceBFS
    from tpuvsr.engine.spec import load_spec
    with open(CONFIG) as f:
        config = json.load(f)
    kw = config["assumed"]["engine"]["device"]
    if depth > config["oracle"]["levels"]["complete_through_depth"]:
        # past the cell's pin a level outgrows the cell's next buffer
        # (level 11 holds 1,495,327 rows): room for it, nothing else
        kw = dict(kw, next_capacity=max(kw["next_capacity"], 1 << 21))
    eng = DeviceBFS(load_spec(MODULE, CFG), **kw)
    t0 = time.time()
    res = eng.run(max_depth=depth,
                  log=lambda s: print(f"[engine] {s}", flush=True))
    assert res.ok and res.error == f"depth limit {depth} reached", res.error
    counters, gauges = res.metrics["counters"], res.metrics["gauges"]
    out = {"level_sizes": [int(x) for x in eng.level_sizes],
           "distinct": int(res.distinct_states),
           "generated": int(res.states_generated),
           "action_expansions": gauges["action_expansions"],
           "committed": {
               n: int((gauges if n.endswith("_peak") else counters)
                      .get(n, 0)) for n in COUNTED}}
    need = dict(zip(eng.kern.action_names,
                    (int(x) for x in eng._need_seen)))
    caps = dict(zip(eng.kern.action_names, eng._expand_caps()))
    sizing = {"backend": jax.default_backend(), "engine": kw,
              "need_seen": need, "caps": caps,
              "grows": int(counters.get("grows", 0)),
              "grow_message_table": int(
                  counters.get("grow_message_table", 0)),
              "guard_table_lanes": int(gauges["guard_table_lanes"]),
              "n_lanes": int(eng.kern.n_lanes),
              "level_elapsed_s": [row["elapsed_s"]
                                  for row in res.metrics["levels"]],
              "seconds": round(time.time() - t0, 1)}
    return out, sizing


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--depth", type=int, default=11)
    ap.add_argument("--reference-json")
    ap.add_argument("--check", action="store_true")
    args = ap.parse_args(argv)
    ref, ref_s = reference_run(args.depth, args.reference_json)
    # (the committed oracle, read back with --reference-json, carries
    # neither key: it was written only after both were clean)
    assert not ref.get("violation") and not ref.get("aux_conflicts"), ref
    eng, sizing = engine_run(args.depth)
    print(json.dumps({"sizing": sizing}, indent=1), flush=True)
    per_source = {n: ref["committed"][n]
                  for n in ("dvc_per_source", "rec_per_source")}
    assert max(per_source.values()) <= 1, per_source

    def counted(res):
        return dict(res, committed={n: int(res["committed"][n])
                                    for n in COUNTED})
    want = counted(ref)
    for key in SAME:
        assert want[key] == eng[key], (key, want[key], eng[key])
    print(f"engine == reference through depth {args.depth} on {SAME}",
          flush=True)
    if args.check:
        have = counted(reference_run(args.depth, OUT)[0])
        for key in SAME:
            assert have[key] == eng[key], key
        print(f"{OUT}: equal to both runs through depth {args.depth}")
        return 0
    assert "through" in ref, "a run cut from a deeper file writes no oracle"
    doc = {
        "config": "benchmark/configs/vr-replica-recovery-async-log.cfg "
                  f"({MODULE}, R=3, |Values|=2, timer=2, CrashLimit=1, "
                  "NoProgressChangeLimit=0, VIEW view, symmetry off), "
                  f"max_msgs={sizing['engine']['max_msgs']} (never grown)",
        "provenance": (
            f"levels 0-{args.depth}: scripts/al05_oracle.py, two runs "
            "that agree level for level, on every one of the twenty "
            "per-action expansion counts and on the eleven counters and "
            "peaks over the committed states.  (1) the plain reference "
            "benchmark/tools/async_log_reference.py: plain Python on host "
            "values, its own breadth-first loop over the VIEW, nothing of "
            "tpuvsr imported"
            + (f", {ref_s:.0f} s" if ref_s else "")
            + "; aux_conflicts 0; none of its four invariants violated. "
            f"(2) DeviceBFS(commit='fused') through the native door at "
            f"the cell's capacities (next buffer 1<<21 for the level past "
            f"the pin) on the {sizing['backend']} backend, "
            f"{sizing['seconds']:.0f} s, grows {sizing['grows']}, "
            f"grow_message_table {sizing['grow_message_table']}.  "
            "ShardedBFS on 2 virtual devices agrees through depth 5 "
            "(tests/test_native_al05.py)"),
        "commands": [
            "python3 benchmark/tools/async_log_reference.py "
            "benchmark/configs/vr-replica-recovery-async-log.cfg "
            f"--depth {args.depth} > ref.json",
            "JAX_PLATFORMS=cpu python scripts/al05_oracle.py "
            f"--depth {args.depth} --reference-json ref.json",
            "python scripts/al05_oracle.py --check --reference-json "
            "benchmark/oracles/async_log_levels.json   # on the chip"],
        "used_by": "al05-bfs-timed (DeviceBFS, fused body)",
        "complete_through_depth": args.depth,
        "distinct": ref["distinct"], "generated": ref["generated"],
        "action_expansions": ref["action_expansions"],
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
