#!/usr/bin/env python3
"""Run the control of `correct` at a cell's own size, in one process:

    python3 benchmark/tools/control_runs.py --workload defect-bfs-timed \
        --seeds 1,2,3 --bits 24 [--seconds N] [--trace 0|1]

Every run drives the cell with narrow fingerprints
(control.narrow_fingerprints) and has to print `correct: false`.  Exit
0 only if every run came out not correct.
"""

import argparse
import contextlib
import io
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))

import cells    # noqa: E402
import control  # noqa: E402
import run      # noqa: E402


def main():
    doc = cells.benchmark_doc()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--bits", type=int, default=24)
    ap.add_argument("--seconds", type=float, default=doc["run_seconds"])
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0,
                    help="1: the control on the traced slice (whole "
                         "levels to the configuration's trace_depth)")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    caught = 0
    seeds = args.seeds.split(",")
    for seed in seeds:
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf), \
                control.narrow_fingerprints(args.bits):
            run.main(["--workload", args.workload, "--seed", seed,
                      "--seconds", f"{args.seconds:g}",
                      "--trace", str(args.trace)]
                     + (["--rehearse"] if args.rehearse else []))
        rows = [json.loads(x) for x in buf.getvalue().strip().splitlines()]
        line = rows[-1]
        bad = [r for r in rows[:-1] if "compared" in r and not r["ok"]]
        caught += line["correct"] is False
        print(json.dumps({"seed": int(seed), "bits": args.bits,
                          "correct": line["correct"],
                          "failed": line["failed"],
                          "attempted": line["attempted"],
                          "not_ok": [[r["compared"], r["got"], r["want"]]
                                     for r in bad]}), flush=True)
    print(json.dumps({"control_runs": len(seeds), "not_correct": caught}))
    return 0 if caught == len(seeds) else 1


if __name__ == "__main__":
    sys.exit(main())
