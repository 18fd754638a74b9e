#!/usr/bin/env python3
"""Run one cell several times, one process per run, and print each
metric's median and spread.

    python3 benchmark/tools/sets.py --workload defect-bfs-timed \
        --seeds 11,12,13,14,15,16 [--seconds N] [--trace 0|1] [--tag set1]

The spread is the contract's: the distance between the first and third
quartile (statistics.quantiles, n=4) as a share of the median.  Each
run's last line goes to chiprun_out/sets/<workload>.<tag>.jsonl and its
log beside it.  This process never touches JAX, so every run has the
chip to itself.  The command and run_seconds come from BENCHMARK.json.
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import cells  # noqa: E402  (no JAX in it: the runs keep the chip)


def spread(values):
    median = statistics.median(values) if values else 0
    if len(values) < 2 or not median:       # a count that reads 0
        return None
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / median


def main():
    doc = cells.benchmark_doc()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=doc["run_seconds"])
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--tag", default="set")
    args = ap.parse_args()
    out_dir = os.path.join(ROOT, "chiprun_out", "sets")
    os.makedirs(out_dir, exist_ok=True)
    base = os.path.join(out_dir, f"{args.workload}.{args.tag}")
    lines = []
    for seed in args.seeds.split(","):
        cmd = doc["command"] + ["--workload", args.workload, "--seed", seed,
                                "--seconds", f"{args.seconds:g}",
                                "--trace", str(args.trace)]
        t0 = time.time()
        with open(f"{base}.seed{seed}.log", "w") as log:
            proc = subprocess.run(cmd, cwd=ROOT, stdout=subprocess.PIPE,
                                  stderr=log, text=True)
        tail = proc.stdout.strip().splitlines()[-2:]
        row = {"seed": int(seed), "rc": proc.returncode,
               "wall_s": time.time() - t0}
        try:
            row["line"] = json.loads(tail[-1])
            row["record"] = json.loads(tail[-2])
        except (IndexError, ValueError):
            row["stdout_tail"] = proc.stdout[-2000:]
        lines.append(row)
        with open(f"{base}.jsonl", "a") as f:
            f.write(json.dumps(row) + "\n")
        print(json.dumps(row), flush=True)
    good = [r["line"] for r in lines if "line" in r]
    names = sorted({k for ln in good for k in ln["metrics"]})
    summary = {"workload": args.workload, "tag": args.tag,
               "runs": len(lines),
               "correct": sum(bool(ln["correct"]) for ln in good)}
    for name in names:
        vals = [ln["metrics"][name]["value"] for ln in good
                if name in ln["metrics"]]
        summary[name] = {"values": vals,
                         "median": statistics.median(vals),
                         "spread": spread(vals)}
    print("SUMMARY " + json.dumps(summary), flush=True)
    return 0 if summary["correct"] == len(lines) else 1


if __name__ == "__main__":
    sys.exit(main())
