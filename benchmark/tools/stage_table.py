#!/usr/bin/env python3
"""Device seconds of one kept trace (BENCH_KEEP_TRACE=1) by stage of the
level program: the `jax.named_scope` names (`tpuvsr.level.*`,
`tpuvsr.shard.*`) that the program puts around its stages reach the
trace in a stat of every device operation's metadata.  Prints which
stat carries them, and the self time (an event that encloses others is
charged only what they leave uncovered) of the device's operations
under each stage.

    python3 benchmark/tools/stage_table.py <trace dir | file.xplane.pb>
        [--out FILE.json] [--slim SMALL.xplane.pb]
"""

import argparse
import importlib.util
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import trace_reduce  # noqa: E402

STAGE = re.compile(r"tpuvsr\.(?:level|shard)\.[a-z_]+")
UNSCOPED = "(no stage scope)"


def stage_of(stats):
    """(stat name, innermost stage) from an operation's stats: the last
    scope on the name stack is the innermost."""
    for key, value in stats:
        if isinstance(value, str):
            found = STAGE.findall(value)
            if found:
                return key, found[-1]
    return None, UNSCOPED


def xplane_pb2():
    """The XSpace message classes.  `jax.profiler.ProfileData` shows an
    event's own stats only; the name stack is a stat of the event's
    METADATA (one record per HLO instruction), which only the protocol
    buffer gives.  The module ships inside the tensorflow package and
    imports nothing of it: load it by path."""
    spec = importlib.util.find_spec("tensorflow")
    if spec is None:
        sys.exit("stage_table: no tensorflow package to take "
                 "tsl/profiler/protobuf/xplane_pb2.py from")
    path = os.path.join(spec.submodule_search_locations[0], "tsl",
                        "profiler", "protobuf", "xplane_pb2.py")
    mod_spec = importlib.util.spec_from_file_location("xplane_pb2", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def stats_of(plane, stats):
    """[(stat name, value)]; a ref_value names another stat record."""
    out = []
    for st in stats:
        which = st.WhichOneof("value")
        value = getattr(st, which) if which else None
        if which == "ref_value":
            value = plane.stat_metadata[value].name
        elif which == "bytes_value":
            value = value.decode("utf-8", "replace")
        out.append((plane.stat_metadata[st.metadata_id].name, value))
    return out


def self_seconds(events):
    """[(stage, self time)] for the events [(start, dur, stage)] of one
    line; a child is an event that starts inside the one on top."""
    out, stack = [], []     # stack: [end, self time, stage]

    def close():
        _end, self_ns, stage = stack.pop()
        out.append((stage, max(0, self_ns)))

    for start, dur, stage in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and start >= stack[-1][0]:
            close()
        if stack:
            stack[-1][1] -= min(dur, stack[-1][0] - start)
        stack.append([start + dur, dur, stage])
    while stack:
        close()
    return out


def table(path, slim=None):
    space = xplane_pb2().XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    totals, carriers, keys_seen, n_events, n_devices = {}, {}, {}, 0, 0
    small = type(space)()
    for plane in space.planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        lines = [ln for ln in plane.lines
                 if ln.name == trace_reduce.OPS_LINE]
        if not lines:
            continue
        n_devices += 1
        stage_by_id = {}
        for mid, md in plane.event_metadata.items():
            stats = [("name", md.name), ("display_name", md.display_name)]
            stats += stats_of(plane, md.stats)
            for key, value in stats[2:]:
                keys_seen.setdefault(key, str(value)[:160])
            stage_by_id[mid] = stage_of(stats)
        for line in lines:
            t0 = line.timestamp_ns * 1000
            events = []
            for ev in line.events:
                key, stage = stage_by_id[ev.metadata_id]
                if key:
                    carriers[key] = carriers.get(key, 0) + 1
                events.append((t0 + ev.offset_ps, ev.duration_ps, stage))
            n_events += len(events)
            for stage, ps in self_seconds(events):
                totals[stage] = totals.get(stage, 0) + ps
        if slim:    # this plane's operations only: small enough to keep
            copy = small.planes.add()
            copy.CopyFrom(plane)
            for i in reversed(range(len(copy.lines))):
                if copy.lines[i].name != trace_reduce.OPS_LINE:
                    del copy.lines[i]
            for line in copy.lines:
                for ev in line.events:
                    del ev.stats[:]
    if slim:
        with open(slim, "wb") as f:
            f.write(small.SerializeToString())
    n = max(1, n_devices)
    total = sum(totals.values()) or 1
    rows = [{"stage": k, "device_s": v / 1e12 / n,
             "share_pct": 100.0 * v / total}
            for k, v in sorted(totals.items(), key=lambda kv: -kv[1])]
    return {"xplane": path, "devices": n_devices,
            "device_events": n_events, "scope_stat": carriers,
            "stat_keys": keys_seen, "stages": rows}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("directory")
    ap.add_argument("--out")
    ap.add_argument("--slim", help="also write the device planes' "
                    "operations alone to this .xplane.pb")
    args = ap.parse_args()
    path = (args.directory if os.path.isfile(args.directory)
            else trace_reduce.find_xplane(args.directory))
    if not path:
        sys.exit(f"no .xplane.pb under {args.directory}")
    doc = table(path, slim=args.slim)
    print("stats of the device operations' metadata:")
    for k, v in doc["stat_keys"].items():
        print(f"  {k}: {v}")
    print("stat that carries the scopes (events):", doc["scope_stat"])
    for row in doc["stages"]:
        print(f"{row['device_s']:10.4f} s {row['share_pct']:6.2f} %  "
              f"{row['stage']}")
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=1)


if __name__ == "__main__":
    main()
