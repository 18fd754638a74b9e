#!/usr/bin/env python3
"""Look at one kept trace (BENCH_KEEP_TRACE=1): planes, lines, event
counts and the first events of each line; optionally write a trimmed
extract for tests/data/.

    python3 benchmark/tools/dump_trace.py <trace dir> [--fixture OUT.json.gz --events N]
"""

import argparse
import gzip
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import trace_reduce  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("directory")
    ap.add_argument("--fixture")
    ap.add_argument("--events", type=int, default=400)
    args = ap.parse_args()
    path = trace_reduce.find_xplane(args.directory)
    print("xplane", path, os.path.getsize(path))
    doc = trace_reduce.extract(path)
    for plane in doc["planes"]:
        print("PLANE", plane["name"])
        for line in plane["lines"]:
            print("  LINE", repr(line["name"]), len(line["events"]))
            for ev in line["events"][:4]:
                print("      ", ev)
    print(json.dumps(trace_reduce.reduce(doc), indent=1))
    if args.fixture:
        # a slice in time from the middle of the trace, every line cut
        # to the same interval, so nesting and gaps stay as recorded
        ops = [ev for p in doc["planes"]
               if trace_reduce.DEVICE_PLANE.match(p["name"])
               for ev in trace_reduce.ops_events(p)]
        ops.sort(key=lambda e: e[1])
        lo = ops[len(ops) // 2][1]
        hi = ops[min(len(ops) - 1, len(ops) // 2 + args.events)][1]
        small = {"planes": []}
        for plane in doc["planes"]:
            lines = []
            for line in plane["lines"]:
                evs = [[n, s - lo, d] for n, s, d in line["events"]
                       if lo <= s and s + d <= hi]
                if evs:
                    lines.append({"name": line["name"], "events": evs})
            if lines:
                small["planes"].append({"name": plane["name"],
                                        "lines": lines})
        with gzip.open(args.fixture, "wt") as f:
            json.dump(small, f)
        print("fixture", args.fixture, os.path.getsize(args.fixture))
        print(json.dumps(trace_reduce.reduce(small), indent=1))


if __name__ == "__main__":
    main()
