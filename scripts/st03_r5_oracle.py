"""Write benchmark/oracles/state_transfer_r5_levels.json: VR_STATE_TRANSFER
at ReplicaCount = 5 (benchmark/configs/vr-state-transfer-r5.cfg) through
the pinned depth, from the plain reference, held to one engine.

Two runs, both from Init, that must agree on every level size, on each
of the sixteen per-action expansion counts, on the bag's peak and on
the five counters over the committed states before anything is
written:

1. the plain reference (benchmark/tools/state_transfer_reference.py,
   through benchmark/tools/quorum_counts.py for the counters): plain
   Python, its own breadth-first loop, nothing of tpuvsr imported;
2. `DeviceBFS` (fused commit) on whatever backend JAX has, at the
   capacities the configuration's file gives the cell
   (`assumed.engine.device`; past the cell's pinned depth with buffers
   that hold the deeper level), through the native door.

It also prints what the configuration's sizes rest on: the engine's
`need_seen` (the most lanes of each action one tile enabled, against
the caps), `grows`, the bag's peak.  Minutes, not a test: the
reference alone takes six for depth 8 and keeps every level in memory
(about 12 GB there: 4.5 KB a state).

Usage: JAX_PLATFORMS=cpu python scripts/st03_r5_oracle.py [--depth 8]
           [--reference-json FILE] [--check]

`--reference-json` reads the reference's numbers from a file that
`quorum_counts.py CFG --depth N` printed earlier (or from the committed
oracle itself, which holds them under the same keys) instead of
running it again; `--check` compares with the committed oracle and
writes nothing.  On the chip, where the engine is what is in question:
`python scripts/st03_r5_oracle.py --check --reference-json
benchmark/oracles/state_transfer_r5_levels.json`.
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "benchmark", "tools"))

CONFIG = os.path.join(REPO, "benchmark", "configs",
                      "vr-state-transfer-r5.json")
CFG = os.path.join(REPO, "benchmark", "configs", "vr-state-transfer-r5.cfg")
OUT = os.path.join(REPO, "benchmark", "oracles",
                   "state_transfer_r5_levels.json")
SAME = ("level_sizes", "distinct", "generated", "action_expansions",
        "bag_peak", "committed")


def reference_run(depth, path):
    if path:
        with open(path) as f:
            res = json.load(f)
        assert len(res["level_sizes"]) == depth + 1, res["level_sizes"]
        return res, None
    import quorum_counts
    import state_transfer_reference as reference
    c, invariants = reference.read_cfg(CFG)
    t0 = time.time()
    res = quorum_counts.run(c, reference.INVARIANTS, max_depth=depth,
                            log=lambda s: print(f"[reference] {s}",
                                                flush=True))
    assert set(invariants) <= set(reference.INVARIANTS)
    return res, time.time() - t0


def engine_run(depth):
    import jax
    from tpuvsr.engine.device_bfs import DeviceBFS
    from tpuvsr.engine.spec import load_spec
    with open(CONFIG) as f:
        config = json.load(f)
    kw = config["assumed"]["engine"]["device"]
    if depth > config["oracle"]["levels"]["complete_through_depth"]:
        # past the cell's pin a level outgrows the cell's buffers
        # (level 8 holds 2,177,749 rows): room for it, nothing else
        kw = dict(kw, next_capacity=max(kw["next_capacity"], 1 << 22))
    eng = DeviceBFS(load_spec("VR_STATE_TRANSFER", CFG), **kw)
    t0 = time.time()
    res = eng.run(max_depth=depth,
                  log=lambda s: print(f"[engine] {s}", flush=True))
    assert res.ok and res.error == f"depth limit {depth} reached", res.error
    counters, gauges = res.metrics["counters"], res.metrics["gauges"]
    import quorum_counts
    out = {"level_sizes": [int(x) for x in eng.level_sizes],
           "distinct": int(res.distinct_states),
           "generated": int(res.states_generated),
           "action_expansions": gauges["action_expansions"],
           "bag_peak": int(gauges["bag_peak"]),
           "committed": {n: int(counters.get(n, 0))
                         for n in quorum_counts.COUNTERS}}
    need = dict(zip(eng.kern.action_names,
                    (int(x) for x in eng._need_seen)))
    caps = dict(zip(eng.kern.action_names, eng._expand_caps()))
    sizing = {"backend": jax.default_backend(), "engine": kw,
              "need_seen": need, "caps": caps,
              "grows": int(counters.get("grows", 0)),
              "grow_message_table": int(
                  counters.get("grow_message_table", 0)),
              "seconds": round(time.time() - t0, 1)}
    return out, sizing


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--depth", type=int, default=8)
    ap.add_argument("--reference-json")
    ap.add_argument("--check", action="store_true")
    args = ap.parse_args(argv)
    ref, ref_s = reference_run(args.depth, args.reference_json)
    # (the committed oracle, read back with --reference-json, carries
    # neither key: it was written only after both were clean)
    assert not ref.get("violation") and not ref.get("aux_conflicts"), ref
    eng, sizing = engine_run(args.depth)
    print(json.dumps({"sizing": sizing}, indent=1), flush=True)
    for key in SAME:
        assert ref[key] == eng[key], (key, ref[key], eng[key])
    doc = {
        "config": "benchmark/configs/vr-state-transfer-r5.cfg "
                  "(VR_STATE_TRANSFER, R=5, |Values|=2, timer=2, "
                  "NoProgressChangeLimit=0, VIEW view, symmetry off), "
                  f"max_msgs={sizing['engine']['max_msgs']} (never grown)",
        "provenance": (
            f"levels 0-{args.depth}: scripts/st03_r5_oracle.py, two runs "
            "that agree level for level, on every one of the sixteen "
            "per-action expansion counts, on the bag's peak and on the "
            "five counters over the committed states.  (1) the plain "
            "reference benchmark/tools/state_transfer_reference.py "
            "(through tools/quorum_counts.py for the counters): plain "
            "Python on host values, its own breadth-first loop over the "
            "VIEW, nothing of tpuvsr imported"
            + (f", {ref_s:.0f} s" if ref_s else "")
            + "; aux_conflicts 0; none of its four invariants violated. "
            f"(2) DeviceBFS(commit='fused') through the native door at "
            f"the cell's capacities on the {sizing['backend']} backend, "
            f"{sizing['seconds']:.0f} s, grows {sizing['grows']}, "
            f"grow_message_table {sizing['grow_message_table']}"),
        "used_by": "st03-r5-bfs-timed (DeviceBFS, fused body)",
        "complete_through_depth": args.depth,
        "distinct": ref["distinct"], "generated": ref["generated"],
        "action_expansions": ref["action_expansions"],
        "bag_peak": ref["bag_peak"], "committed": ref["committed"],
        "level_sizes": ref["level_sizes"]}
    if args.check:
        with open(OUT) as f:
            have = json.load(f)
        for key in SAME + ("complete_through_depth",):
            assert have[key] == doc[key], key
        print(f"{OUT}: equal to both runs through depth {args.depth}")
        return 0
    with open(OUT, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    print(f"wrote {OUT}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
