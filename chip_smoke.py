#!/usr/bin/env python3
"""chip_smoke.py — the standing proof that the VSR checker starts on
the chip, from files git would commit.

    python chip_smoke.py             # one chip: phases A-D
    python chip_smoke.py --chips 4   # four chips: the sharded engine only

One process, no children that need the chip.  It never sets
``JAX_PLATFORMS``: first thing, it requires the backend JAX gives it to
be a TPU with the asked-for device count and exits non-zero otherwise,
before any phase.  Every phase checks its result against an oracle the
repo commits (exact counts, or the recorded counterexample) and any
failure is an uncaught exception: the run cannot end in 0.

  A  small, through the CLI in-process: `tpuvsr VSR -config
     examples/VSR_small.cfg -json -journal -metrics`, default engine
     flags — exit 0, 43,941 distinct, diameter 24, the 24 level sizes
     of scripts/pinned_levels_small.json, `platform: tpu` on run_start.
  B  real widths: load_spec("VSR", examples/VSR_defect.cfg) (R=3,
     |Values|=3, timer=3) into DeviceBFS(max_msgs=32).run(max_depth=N)
     — level sizes equal to scripts/defect_window.json.  Widths are
     never cut; depth is (DEFECT_DEPTH).
  C  the committed 30-state counterexample on the chip's kernel:
     every recorded step is among `kern.step_batch`'s successors under
     the recorded action; AcknowledgedWriteNotLost holds on states
     1-29 and fails on state 30 (models/native.walk_trace).
  D  served path: submit one check job (native VSR, small cfg) to a
     spool and drain it with the in-process single worker — state
     `done`, the same 43,941 and level sizes, `backend: tpu` on
     job_started.

Earlier stdout lines are one JSON object per phase (tile, compile and
run seconds, compilations counted, peak device memory); the last line
is exactly {"ok": true, "device": {...}}.  Logs go to stderr.
"""

import argparse
import contextlib
import io
import json
import os
import shutil
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
SMALL_CFG = os.path.join(REPO, "examples", "VSR_small.cfg")
DEFECT_CFG = os.path.join(REPO, "examples", "VSR_defect.cfg")
TRACE = os.path.join(REPO, "examples", "found_violation_trace.txt")
OUT = os.path.join(REPO, "chiprun_out", "chip_smoke")
# B's depth bound: the deepest whose cold-cache run keeps the whole
# script inside the smoke contract's 1200 s with a 2x margin (measured
# on the v5e, CHANGES.md PR 22): 4,095 distinct states.  Depth 7
# (14,143) outgrows the default next-frontier buffer, and every growth
# is one more build of the level program, about two and a half minutes
# on the chip machine's host; depth 9 = 148,897, depth 10 = 448,580.
DEFECT_DEPTH = 6
DEFECT_MAX_MSGS = 32     # the defect window's final message-table bound


def _oracle(name):
    with open(os.path.join(REPO, "scripts", name)) as f:
        return json.load(f)


class CompileMeter:
    """Backend compilations (count, seconds) and persistent-cache hits,
    from JAX's own monitoring events."""

    def __init__(self):
        import jax.monitoring as mon
        self.n = self.hits = 0
        self.secs = 0.0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.secs += secs

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return self.n, self.hits, self.secs


def report(phase, meter, before, t0, **fields):
    """One JSON line per phase: what is worth knowing about the run."""
    import jax
    n0, h0, s0 = before
    n1, h1, s1 = meter.snapshot()
    stats = jax.devices()[0].memory_stats() or {}
    wall = time.time() - t0
    doc = {"phase": phase, **fields,
           "wall_s": round(wall, 2),
           "compile_s": round(s1 - s0, 2),
           "run_s": round(wall - (s1 - s0), 2),
           "compilations": n1 - n0,
           "cache_hits": h1 - h0,
           "peak_bytes_in_use": stats.get("peak_bytes_in_use")}
    print(json.dumps(doc), flush=True)


def _journal_events(path, event):
    with open(path) as f:
        return [d for d in map(json.loads, f) if d.get("event") == event]


def _levels(metrics_path):
    """Level sizes from a -metrics document: row d's `frontier` is the
    size of level d-1."""
    with open(metrics_path) as f:
        return [row["frontier"] for row in json.load(f)["levels"]]


def phase_a(out, platform, meter):
    """Small config through the CLI in-process, default engine flags."""
    from tpuvsr.cli.main import main as cli
    pin = _oracle("pinned_levels_small.json")
    journal = os.path.join(out, "A.journal.jsonl")
    metrics = os.path.join(out, "A.metrics.json")
    before, t0 = meter.snapshot(), time.time()
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = cli(["VSR", "-config", SMALL_CFG, "-json",
                  "-journal", journal, "-metrics", metrics])
    doc = json.loads(buf.getvalue().strip().splitlines()[-1])
    assert rc == 0 and doc["ok"], (rc, doc)
    assert doc["distinct_states"] == pin["distinct"] == 43941, doc
    assert doc["diameter"] == pin["diameter"] == 24, doc
    assert _levels(metrics) == pin["level_sizes"], _levels(metrics)
    assert doc["device"]["platform"] == platform, doc["device"]
    (start,) = _journal_events(journal, "run_start")
    assert start["platform"] == platform, start
    report("A", meter, before, t0, distinct=doc["distinct_states"],
           diameter=doc["diameter"],
           grows=doc["metrics"]["counters"].get("grows", 0),
           platform=start["platform"],
           device_kind=start["device_kind"])


def phase_b(depth, meter):
    """Real widths: the defect configuration to a depth bound."""
    from tpuvsr.engine.device_bfs import DeviceBFS
    from tpuvsr.engine.spec import load_spec
    want = _oracle("defect_window.json")["level_sizes"][:depth + 1]
    spec = load_spec("VSR", DEFECT_CFG)
    before, t0 = meter.snapshot(), time.time()
    eng = DeviceBFS(spec, max_msgs=DEFECT_MAX_MSGS)
    res = eng.run(max_depth=depth,
                  log=lambda m: print(f"[B] {m}", file=sys.stderr))
    assert res.ok and res.violated_invariant is None, res.error
    assert list(eng.level_sizes) == want, (eng.level_sizes, want)
    assert res.distinct_states == sum(want), res.distinct_states
    assert eng.codec.shape.MAX_MSGS == DEFECT_MAX_MSGS
    report("B", meter, before, t0, depth=depth, tile=eng.tile,
           lanes=eng.L, max_msgs=eng.codec.shape.MAX_MSGS,
           distinct=res.distinct_states, level_sizes=eng.level_sizes,
           grows=res.metrics["counters"].get("grows", 0))
    return spec


def phase_c(spec, meter):
    """The committed counterexample, held against the kernel."""
    from tpuvsr.models.native import walk_trace
    before, t0 = meter.snapshot(), time.time()
    entries, ok = walk_trace(spec, TRACE)
    assert len(entries) == 30 and entries[-1].action_name == "ReceiveSV"
    assert spec.cfg.invariants == ["AcknowledgedWriteNotLost"]
    assert ok[:29].all() and not ok[29], ok.tolist()
    report("C", meter, before, t0, states=len(entries),
           violation_at=30, invariant=spec.cfg.invariants[0])


def phase_d(out, platform, meter):
    """Served path: one check job through a spool and the in-process
    single worker (`serve --drain`, no --workers N: on a one-chip host
    only one process can have the chip)."""
    from tpuvsr.service.api import main as svc
    pin = _oracle("pinned_levels_small.json")
    spool = os.path.join(out, "D.spool")
    before, t0 = meter.snapshot(), time.time()

    def call(*argv):
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = svc(list(argv))
        assert rc == 0, (argv, rc, buf.getvalue())
        return buf.getvalue()

    job_id = json.loads(call("submit", "VSR", "-config", SMALL_CFG,
                             "--spool", spool, "--json"))["job_id"]
    call("serve", "--drain", "--spool", spool)
    doc = json.loads(call("status", job_id, "--spool", spool, "--json"))
    assert doc["state"] == "done", doc
    result = doc["result"]
    assert result["distinct"] == pin["distinct"], result
    assert result["levels"] == pin["level_sizes"], result
    (started,) = _journal_events(doc["journal"], "job_started")
    assert started["backend"] == platform, started
    report("D", meter, before, t0, state=doc["state"],
           distinct=result["distinct"], attempts=doc.get("attempts"),
           backend=started["backend"])


def phase_four_chips(meter):
    """ShardedBFS over every device to the small config's fixpoint."""
    import numpy as np

    import jax
    from jax.sharding import Mesh

    from tpuvsr.engine.spec import load_spec
    from tpuvsr.parallel.sharded_bfs import ShardedBFS
    pin = _oracle("pinned_levels_small.json")
    spec = load_spec("VSR", SMALL_CFG)
    mesh = Mesh(np.array(jax.devices()), ("d",))
    before, t0 = meter.snapshot(), time.time()
    # per-device capacities sized so the run needs no table/frontier
    # growth (each is a recompile): 43,941 states over D shards
    eng = ShardedBFS(spec, mesh, fpset_capacity=1 << 16)
    res = eng.run(log=lambda m: print(f"[4] {m}", file=sys.stderr))
    assert res.ok and res.error is None, res.error
    assert res.distinct_states == pin["distinct"], res.distinct_states
    assert res.diameter == pin["diameter"], res.diameter
    assert list(eng.level_sizes) == pin["level_sizes"], eng.level_sizes
    shard = [int(x) for x in eng._dev_distinct]
    assert sum(shard) == pin["distinct"] and min(shard) > 0, shard
    report("sharded", meter, before, t0, devices=eng.D, tile=eng.tile,
           distinct=res.distinct_states, diameter=res.diameter,
           # every distinct state is one FPSet entry and one frontier
           # row on the device that owns its fingerprint
           frontier_rows_per_device=shard,
           fpset_occupancy_per_device=[round(x / eng.fp_cap, 4)
                                       for x in shard],
           grows=res.metrics["counters"].get("grows", 0))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded engine on a 4-device "
                         "mesh (the driver never gives this)")
    args = ap.parse_args()

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) != args.chips:
        sys.exit(f"chip_smoke: need {args.chips} TPU device(s); JAX "
                 f"gives {len(devs)} x {devs[0].platform} "
                 f"({devs[0].device_kind})")
    sys.path.insert(0, REPO)
    import inspect

    from tpuvsr.engine.device_bfs import DeviceBFS
    from tpuvsr.models.registry import ensure_compile_cache
    cache = ensure_compile_cache()
    print(json.dumps({
        "phase": "start", "compile_cache": cache, "jax": jax.__version__,
        # the tile A and D run at: the CLI and the service pass none
        "default_tile": inspect.signature(DeviceBFS).parameters[
            "tile_size"].default}), flush=True)
    shutil.rmtree(OUT, ignore_errors=True)    # a rerun starts clean
    os.makedirs(OUT)
    meter = CompileMeter()
    if args.chips == 4:
        phase_four_chips(meter)
    else:
        phase_a(OUT, "tpu", meter)
        spec = phase_b(DEFECT_DEPTH, meter)
        phase_c(spec, meter)
        phase_d(OUT, "tpu", meter)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
