#!/usr/bin/env python
"""Trace-validation drill: round-trip known-good and mutated traces
through the batched validator (ISSUE 8 acceptance demo).

Tier-1 (no reference mount, CPU backend, seconds) on the stub
harness, the drill proves the two directions of the contract:

  accepted   a checker-produced counterexample trace (by construction
             a real spec path) and a batch of recorded genuine walks
             — every one validates end to end, including a partial-
             observation variant (dropped variable + fully-blanked
             events) whose candidate sets do the nondeterminism
             bookkeeping;
  diverged   the same traces with ONE event mutated off the reachable
             transition relation — the validator localizes the first
             divergence at EXACTLY the mutated trace/step and reports
             the spec-side enabled set there, bit-identically between
             the interpreter reference validator and the batched
             device engine.

With the reference corpus mounted, the drill additionally derives a
TRACE.jsonl record from the reference's state-transfer violation
trace dump (``*state_transfer*trace*.txt``, TLC format) and validates
it against VR_STATE_TRANSFER.tla — the real-corpus form of the same
round-trip.

A throughput leg (default 2048 stub traces through the device-mesh
validator) records ``traces_per_s``; ``--out FILE`` writes the JSON
artifact (the ``scripts/compare_bench.py`` traces/s gate input;
cross-backend comparisons are advisory there).  It is a drill, not
the benchmark: that is
``python3 benchmark/run.py --workload W --seed N --seconds S --trace 0|1``.

    python scripts/validate_demo.py [--traces N] [--out FILE]

Prints one JSON object; exit 0 iff every expectation holds.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

if __name__ == "__main__":
    os.environ["JAX_PLATFORMS"] = os.environ.get(
        "TPUVSR_DEMO_BACKEND", "cpu")
    _fl = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _fl:
        os.environ["XLA_FLAGS"] = (
            _fl + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, REPO)

REFERENCE = "/root/reference/vsr-revisited/paper"


def _reference_roundtrip(out):
    """The reference leg: a record derived from the state-transfer
    violation trace dump validates against VR_STATE_TRANSFER.tla
    (mounted corpora only; absent mount = leg skipped, not failed)."""
    spec_path = os.path.join(
        REFERENCE, "analysis/03-state-transfer/VR_STATE_TRANSFER.tla")
    dumps = glob.glob(os.path.join(
        REFERENCE, "**/*state_transfer*trace*.txt", ), recursive=True)
    if not (os.path.exists(spec_path) and dumps):
        out["reference"] = "skipped (no reference mount)"
        return None
    from tpuvsr.engine.spec import load_spec
    from tpuvsr.frontend.trace_parse import parse_trace_file
    from tpuvsr.validate import host_validate_batch
    from tpuvsr.validate.traces import (record_from_entries,
                                        traces_from_records)
    spec = load_spec(spec_path,
                     os.path.splitext(spec_path)[0] + ".cfg")
    entries = parse_trace_file(dumps[0], spec)
    rec = record_from_entries(entries, tid="st03-violation")
    good = host_validate_batch(
        spec, traces_from_records([rec], spec))
    bad_rec = json.loads(json.dumps(rec))
    ev = bad_rec["events"][len(bad_rec["events"]) // 2]
    var = sorted(ev.get("vars") or {"op": "0"})[0]
    ev.setdefault("vars", {})[var] = "12345"
    bad = host_validate_batch(
        spec, traces_from_records([bad_rec], spec))
    out["reference"] = {
        "dump": os.path.relpath(dumps[0], REFERENCE),
        "events": len(rec["events"]),
        "accepted": good.ok,
        "mutated_diverged_at": (bad.first_divergence or {}).get("step"),
    }
    return good.ok and not bad.ok


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--traces", type=int, default=2048,
                    help="throughput-leg batch size (default 2048)")
    ap.add_argument("--out", default=None, metavar="FILE",
                    help="write the bench attachment JSON to FILE")
    args = ap.parse_args(argv)

    import jax

    from tpuvsr.testing import (counter_spec, stub_model_factory,
                                stub_trace_records, stub_validator)
    from tpuvsr.validate import host_validate_batch
    from tpuvsr.validate.batch import batch_validate
    from tpuvsr.validate.traces import (record_from_entries,
                                        traces_from_records)

    out = {"checks": {}}
    checks = out["checks"]
    spec = counter_spec()

    # -- leg 1: checker-trace round-trip -------------------------------
    # a counterexample the checker itself produced is by construction
    # a real spec path: validating it must accept; shifting one event
    # off the transition relation must diverge exactly there
    from tpuvsr.testing import stub_fleet
    viol = stub_fleet(walkers=32, n_devices=2, inv_x_bound=2).run(
        num=64, depth=8, seed=1)
    rec = record_from_entries(viol.trace, tid="counterexample")
    vspec = counter_spec(inv_x_bound=2)
    good = host_validate_batch(
        vspec, traces_from_records([rec], vspec))
    checks["counterexample_roundtrip_accepted"] = bool(good.ok)
    mut_step = len(rec["events"]) - 1
    bad_rec = json.loads(json.dumps(rec))
    bad_rec["events"][mut_step]["vars"]["x"] = "99"
    bad = host_validate_batch(
        vspec, traces_from_records([bad_rec], vspec))
    fd = bad.first_divergence or {}
    checks["mutated_counterexample_diverges_at_step"] = (
        not bad.ok and fd.get("step") == mut_step
        and bool(fd.get("enabled")))

    # -- leg 2: batch of recorded walks, host vs device ----------------
    recs = stub_trace_records(n=256, depth=6, seed=3, mutate=(100, 2))
    traces = traces_from_records(recs, spec)
    hres = host_validate_batch(spec, traces)
    bres = stub_validator(batch=128, n_devices=2).run(traces)
    checks["device_matches_interpreter"] = (
        json.dumps(bres.divergences, sort_keys=True)
        == json.dumps(hres.divergences, sort_keys=True)
        and bres.accepted == hres.accepted == 255
        and bres.first_divergence["trace"] == "t-0100"
        and bres.first_divergence["step"] == 2)

    # -- leg 3: partial observation ------------------------------------
    part = traces_from_records(
        stub_trace_records(n=64, depth=6, seed=4, drop_vars=("y",),
                           blank_every=3), spec)
    pres = stub_validator(batch=64, n_devices=2).run(part)
    checks["partial_observation_accepted"] = bool(pres.ok)

    # -- leg 4: the reference round-trip (mount-gated) -----------------
    ref_ok = _reference_roundtrip(out)
    if ref_ok is not None:
        checks["reference_roundtrip"] = bool(ref_ok)

    # -- leg 5: throughput ---------------------------------------------
    n = max(64, args.traces)
    big = traces_from_records(
        stub_trace_records(n=n, depth=6, seed=5), spec)
    t0 = time.time()
    tres = batch_validate(spec, big, batch=min(n, 1024),
                          model_factory=stub_model_factory(),
                          confirm=False)
    wall = time.time() - t0
    checks["throughput_batch_accepted"] = bool(tres.ok)
    out.update({
        "backend": jax.default_backend(),
        "devices": len(jax.devices()),
        "traces": tres.traces_checked,
        "batch": min(n, 1024),
        "elapsed_s": round(tres.elapsed, 3),
        "wall_s": round(wall, 3),
        "traces_per_s": round(tres.traces_per_sec, 1),
    })
    out["ok"] = all(checks.values())
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1, sort_keys=True)
    print(json.dumps(out, indent=1, default=str))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
