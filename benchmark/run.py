#!/usr/bin/env python3
"""The benchmark's one command:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, one cell, one run.  Gate (a TPU with the cell's chips, or
exit non-zero) -> set-up (everything before the window, `setup_s`) ->
window (the traffic kind's timed part) -> check against the copied
oracles, outside the window -> one JSON object on the last line.

With --trace 0 the line carries the cell's end-to-end metrics, with
--trace 1 its per-layer metrics, read from a profiler trace of a
shorter window and from the program's own counters.  The shorter
window ends at a depth, the configuration's `assumed.trace_depth`, so
that parent and change of a pair trace the same states however fast
either is; only a configuration without the key (the rehearsal's
vsr-small) is cut by seconds, the traffic file's `trace_seconds`.
--rehearse drives the same code on whatever backend JAX has (the CPU),
at the traffic file's `rehearse_config`, and prints no metric: a CPU
run is never a device number.
"""

import time

PROCESS_START = time.time()

import argparse     # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import shutil       # noqa: E402
import sys          # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)

import cells        # noqa: E402
import gate         # noqa: E402
import oracle       # noqa: E402
from meter import CompileMeter     # noqa: E402

OUT = os.path.join(ROOT, ".bench_out")
# what a traffic kind's window may report for the run's record
RECORD_KEYS = ("levels", "level_elapsed_s", "distinct", "elapsed_s", "grows",
               "verdict_s")
# a traced run whose device events span less of its window than this
# is flagged: its idle share and its time a state are not the cell's
LOW_COVERAGE = 0.5


def log(msg):
    print(f"[bench {time.time() - PROCESS_START:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def start_trace(directory):
    """The harness owns the profiler session (no Python tracer: the
    window's builds would drown the trace) and sets TPUVSR_PROFILE so
    that the program writes its TraceAnnotation spans into it; the
    program's own attempt to open a second session is refused and it
    carries on."""
    import jax
    os.environ["TPUVSR_PROFILE"] = directory
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(directory, profiler_options=opts)


def stop_trace():
    import jax
    jax.profiler.stop_trace()
    os.environ.pop("TPUVSR_PROFILE", None)


def read_layer_metrics(cell, obs, trace):
    out = {}
    for m in cell.metrics_for("per_layer"):
        value = cells.load_plugin("layer_metrics", m["name"]).read(
            obs, trace, cell)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="take whatever backend JAX has and print no "
                         "metric (CPU rehearsal of the control flow)")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "tpuvsr")):
        sys.exit("benchmark: the program (tpuvsr/) is not in this "
                 "checkout; there is nothing to measure")

    cell = cells.Cell(args.workload, rehearse=args.rehearse)
    cell.devices, cell.peaks = gate.require_devices(cell.chips,
                                                    args.rehearse)
    cell.seed = args.seed
    cell.log = log
    cell.out_dir = os.path.join(
        OUT, cell.name, f"seed{args.seed}-trace{args.trace}")
    shutil.rmtree(cell.out_dir, ignore_errors=True)
    os.makedirs(cell.out_dir)

    from tpuvsr.models.registry import ensure_compile_cache
    cache_dir = ensure_compile_cache()
    cell.meter = CompileMeter()
    kind = cells.load_plugin("traffic_kinds", cell.traffic["kind"])
    log(f"cell {cell.name}: config {cell.config['name']}, traffic "
        f"{cell.entry['traffic']}, {len(cell.devices)} x "
        f"{cell.devices[0].device_kind}, compile cache {cache_dir}")

    state = kind.setup(cell)
    built = cell.meter.snapshot()
    seconds = args.seconds
    trace_dir = None
    if args.trace:
        # a traced slice ends at the configuration's depth, under the
        # whole budget; only one without a depth is cut by seconds
        cell.trace_depth = cell.config.get("assumed", {}).get("trace_depth")
        if cell.trace_depth is None:
            seconds = min(seconds, float(cell.traffic.get("trace_seconds",
                                                           seconds)))
        trace_dir = os.path.join(cell.out_dir, "trace")
        start_trace(trace_dir)
    opened = time.time()
    setup_s = opened - PROCESS_START
    try:
        obs = kind.window(cell, state, seconds)
    finally:
        closed = time.time()
        if args.trace:
            stop_trace()
    setup_s += obs.get("setup_extra_s", 0.0)
    window_s = closed - opened - obs.get("setup_extra_s", 0.0)
    obs["window_builds"] = cell.meter.since(built)
    obs["window_s"] = window_s
    log(f"set-up {setup_s:.1f}s ({built}), window {window_s:.1f}s "
        f"(builds {obs['window_builds']})")

    checked = kind.check(cell, state, obs)
    comparisons = checked["comparisons"]
    for c in comparisons:
        print(json.dumps({"compared": c["name"], "ok": c["ok"],
                          "limit": c["limit"], "got": c["got"],
                          "want": c["want"]}), flush=True)
    correct = oracle.verdict(comparisons)

    device = gate.device_doc(cell.devices)
    line = {"correct": correct, "attempted": int(checked["attempted"]),
            "failed": int(checked["failed"]), "metrics": {},
            "device": device}
    trace = None
    if args.trace:
        import trace_reduce
        t0 = time.time()
        trace = trace_reduce.reduce_directory(trace_dir)
        log(f"trace reduced in {time.time() - t0:.1f}s: "
            + json.dumps({k: v for k, v in (trace or {}).items()
                          if k not in ("device_ops", "idle_gaps")}))
        if not os.environ.get("BENCH_KEEP_TRACE"):
            shutil.rmtree(trace_dir, ignore_errors=True)
        if trace is None and not args.rehearse:
            sys.exit("benchmark: the traced run recorded no operation "
                     "on a device")
        if trace:
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = window_s
            trace["coverage"] = coverage = trace_reduce.coverage(
                trace, window_s)
            if coverage is not None and coverage < LOW_COVERAGE:
                log(f"WARNING: the device's events span {coverage:.2f} of "
                    f"the traced window ({trace['span_s']:.2f} of "
                    f"{window_s:.2f} s): device.idle_share and "
                    "level.busy_us_per_state of this run are not the "
                    "cell's")
            line["breakdown"] = {"device_ops": trace["device_ops"],
                                 "idle_gaps": trace["idle_gaps"]}
    if args.rehearse:
        line["rehearsal"] = True
    elif args.trace:
        line["metrics"] = read_layer_metrics(cell, obs, trace)
    else:
        units = {m["name"]: m["unit"]
                 for m in cell.metrics_for("end_to_end")}
        values = dict(kind.end_to_end(cell, obs), setup_s=setup_s)
        line["metrics"] = {k: {"value": float(values[k]), "unit": units[k]}
                           for k in units}
    # the run's record: what it reached and what it built, on the line
    # before the last and, with the comparisons, in the output directory
    record = {"workload": cell.name, "seed": args.seed,
              "seconds": args.seconds, "trace": args.trace,
              "setup_s": setup_s, "window_s": window_s,
              "setup_builds": built, "window_builds": obs["window_builds"]}
    record.update((k, obs[k]) for k in RECORD_KEYS if k in obs)
    if cell.trace_depth is not None:
        record["trace_depth"] = cell.trace_depth
    if trace:
        record["device_opcodes"] = trace["device_opcodes"]
        record["coverage"] = trace["coverage"]
    print(json.dumps(record), flush=True)
    with open(os.path.join(cell.out_dir, "run.json"), "w") as f:
        json.dump(dict(record, line=line, comparisons=comparisons), f,
                  indent=1, default=str)
    # each number compared beside its limit: the last key of the last
    # line, and the last lines of standard error (what is kept of a run
    # that is not correct)
    line["compared"] = {
        c["name"]: {k: c[k] for k in ("ok", "got", "want", "limit")}
        for c in comparisons}
    for name, c in line["compared"].items():
        print(f"compared {name}: got {json.dumps(c['got'], default=str)} "
              f"want {json.dumps(c['want'], default=str)} limit "
              f"{c['limit']} {'ok' if c['ok'] else 'NOT OK'}",
              file=sys.stderr, flush=True)
    print(json.dumps(line, default=str), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
