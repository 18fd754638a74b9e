#!/usr/bin/env python3
"""The longest device-idle gaps of one kept trace (BENCH_KEEP_TRACE=1),
one by one: when, how long, the host span the reducer attributes each
to, and every host span open at its middle from the outermost in - what
the host was doing while the device waited.

    python3 benchmark/tools/gap_list.py <trace dir> [--top N] [--only NAME]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import trace_reduce  # noqa: E402


def gaps_of(doc):
    """[(start, end)] of device 0's idle gaps, longest first, and the
    first busy instant."""
    for plane in doc["planes"]:
        if not trace_reduce.DEVICE_PLANE.match(plane["name"]):
            continue
        events = trace_reduce.ops_events(plane)
        if not events:
            continue
        cover = trace_reduce._union([(s, s + d) for _n, s, d in events])
        gaps = [(a[1], b[0]) for a, b in zip(cover, cover[1:])]
        return sorted(gaps, key=lambda g: g[0] - g[1]), cover[0][0]
    return [], 0


def open_at(spans, t):
    """Names of the host spans open at `t`, outermost first."""
    starts, ends, names = spans
    hit = [(int(ends[i] - starts[i]), names[i])
           for i in range(len(names)) if starts[i] <= t <= ends[i]]
    return [name for _len, name in sorted(hit, reverse=True)]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("directory")
    ap.add_argument("--top", type=int, default=20)
    ap.add_argument("--only", help="list only gaps attributed to NAME")
    args = ap.parse_args()
    path = trace_reduce.find_xplane(args.directory)
    if not path:
        sys.exit(f"no .xplane.pb under {args.directory}")
    doc = trace_reduce.extract(path)
    gaps, first = gaps_of(doc)
    spans = trace_reduce._host_spans(doc)
    shown = 0
    for gap in gaps[:200]:      # what the reducer examines
        name = trace_reduce._attribute(gap, spans)
        if args.only and name != args.only:
            continue
        mid = (gap[0] + gap[1]) // 2
        print(f"{(gap[1] - gap[0]) / 1e9:9.4f} s at "
              f"{(gap[0] - first) / 1e9:9.3f} s  {name}  <- "
              + " > ".join(open_at(spans, mid)[-6:]))
        shown += 1
        if shown >= args.top:
            break


if __name__ == "__main__":
    main()
