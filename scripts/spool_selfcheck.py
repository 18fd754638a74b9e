#!/usr/bin/env python
"""Spool self-check: validate a spool's own job history against a
spool-state spec through the trace validator (ISSUE 20).

The durable data plane's ``jobs`` stream IS a trace of the job state
machine — so the same machinery that validates counterexample traces
against a TLA+ spec (ISSUE 8) can validate the SERVICE's own journal
against a model of itself.  The check:

  1. reads the spool's ``jobs`` stream through the spool DRIVER
     (``fs`` / ``objstore`` / ``quorum`` — whatever the spool is
     configured as), so replicated spools self-check through the same
     quorum-merge read path the service uses;
  2. projects each job's history into one TRACE.jsonl record over the
     integer-coded state machine (``st`` = index into
     ``service.queue.STATES``) plus the claim epoch (``epoch`` =
     the ``attempts`` recorded on each ``running`` transition);
  3. validates the batch against the inline ``SpoolJob`` spec below —
     legal job-state transitions only, and claim EXCLUSIVITY per
     epoch: the only action that may touch ``epoch`` is ``Claim``,
     which bumps it by exactly one (a replayed/zombie epoch, an epoch
     skip, or any illegal state hop is a divergence localized at the
     exact journal record);
  4. proves the check has teeth by corrupting one projected record
     (an event's ``st`` rewritten to 0 — no action re-enters
     ``queued``) and requiring the validator to flag EXACTLY that
     step.

Given no spool, the drill builds one: a preempt-requeue job (two
claim epochs), a plain job and a cancelled job drained by the real
worker over ``--spool-driver`` (default quorum).

    python scripts/spool_selfcheck.py [SPOOL]
        [--spool-driver fs|objstore|quorum] [--trace-out FILE]

Prints one JSON object; exit 0 iff the spool's history validates AND
the corrupted leg diverges at the exact corrupted record.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

if __name__ == "__main__":
    os.environ["JAX_PLATFORMS"] = "cpu"
    _fl = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _fl:
        os.environ["XLA_FLAGS"] = (
            _fl + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, REPO)

#: the job state machine as a spec — the mirror of
#: ``service.queue.LEGAL`` with states coded by their index in
#: ``service.queue.STATES`` (queued=0 admitted=1 running=2 done=3
#: violated=4 failed=5 preempted-requeued=6 cancelled=7).  ``Claim``
#: is the ONLY action that changes ``epoch``, and only by +1: claim
#: exclusivity per epoch, checkable from the journal alone.
SPOOL_SPEC = r"""---- MODULE SpoolJob ----
EXTENDS Naturals
CONSTANTS MaxEpoch
VARIABLES st, epoch

Init == st = 0 /\ epoch = 0

Admit ==
    /\ st = 0
    /\ st' = 1
    /\ UNCHANGED epoch

Claim ==
    /\ (st = 1 \/ st = 6)
    /\ epoch < MaxEpoch
    /\ st' = 2
    /\ epoch' = epoch + 1

Done ==
    /\ st = 2
    /\ st' = 3
    /\ UNCHANGED epoch

Violate ==
    /\ st = 2
    /\ st' = 4
    /\ UNCHANGED epoch

Fail ==
    /\ (st = 0 \/ st = 2)
    /\ st' = 5
    /\ UNCHANGED epoch

Requeue ==
    /\ st = 2
    /\ st' = 6
    /\ UNCHANGED epoch

Cancel ==
    /\ (st = 0 \/ st = 1 \/ st = 2 \/ st = 6)
    /\ st' = 7
    /\ UNCHANGED epoch

Next == Admit \/ Claim \/ Done \/ Violate \/ Fail \/ Requeue \/ Cancel

Legal == st <= 7 /\ epoch <= MaxEpoch
====
"""

SPOOL_CFG = ("CONSTANTS\n    MaxEpoch = %d\n"
             "INIT Init\nNEXT Next\nINVARIANT Legal\n")

#: journal state name -> spec action name
ACTION = {"admitted": "Admit", "running": "Claim", "done": "Done",
          "violated": "Violate", "failed": "Fail",
          "preempted-requeued": "Requeue", "cancelled": "Cancel"}


def spool_spec(max_epoch=6):
    from tpuvsr.engine.spec import SpecModel
    from tpuvsr.frontend.cfg import parse_cfg_text
    from tpuvsr.frontend.parser import parse_module_text
    return SpecModel(parse_module_text(SPOOL_SPEC),
                     parse_cfg_text(SPOOL_CFG % int(max_epoch)))


def project(spool):
    """TRACE.jsonl records (one per job) from the spool's ``jobs``
    stream, read through the spool's configured driver."""
    from tpuvsr.service.queue import STATES, JobQueue
    code = {s: i for i, s in enumerate(STATES)}
    q = JobQueue(spool)
    recs, _ = q.drv.read("jobs", None)
    jobs, order = {}, []
    for rec in recs:
        jid = rec.get("job_id")
        if rec.get("op") == "submit":
            jobs.setdefault(jid, {"events": [], "epoch": 0})
            order.append(jid)
        elif rec.get("op") == "state" and rec.get("state") in ACTION:
            j = jobs.setdefault(jid, {"events": [], "epoch": 0})
            if jid not in order:
                order.append(jid)
            st = rec["state"]
            if st == "running":
                j["epoch"] = int(rec.get("attempts", j["epoch"] + 1))
            j["events"].append({
                "action": ACTION[st],
                "vars": {"st": str(code[st]),
                         "epoch": str(j["epoch"])}})
    return [{"trace": jid, "init": {"st": "0", "epoch": "0"},
             "events": jobs[jid]["events"]}
            for jid in order if jobs[jid]["events"]]


def _demo_spool(tmp, driver):
    """A small real spool: a preempt-requeued job (two claim epochs),
    a plain job and a cancel — all through the actual worker."""
    from tpuvsr.service.queue import JobQueue
    from tpuvsr.service.worker import Worker
    spool = os.path.join(tmp, "spool")
    q = JobQueue(spool, driver=driver)
    q.submit("<stub:requeued>", engine="device",
             flags={"stub": True, "inject": "kill@level=3"})
    q.submit("<stub:plain>", engine="device", flags={"stub": True})
    victim = q.submit("<stub:cancelled>", engine="device",
                      flags={"stub": True})
    q.cancel(victim.job_id)
    Worker(q, devices=1, light_threads=0).drain()
    return spool


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("spool", nargs="?", default=None,
                    help="spool to self-check (default: build a "
                         "demo spool and check that)")
    ap.add_argument("--spool-driver", default="quorum",
                    choices=("fs", "objstore", "quorum"),
                    help="driver for the built demo spool "
                         "(an existing SPOOL auto-detects)")
    ap.add_argument("--trace-out", default=None, metavar="FILE",
                    help="also write the projected TRACE.jsonl here")
    args = ap.parse_args(argv)

    import shutil
    from tpuvsr.validate import host_validate_batch
    from tpuvsr.validate.traces import save_traces, traces_from_records

    tmp = None
    spool = args.spool
    if spool is None:
        tmp = tempfile.mkdtemp(prefix="tpuvsr-spool-selfcheck-")
        spool = _demo_spool(tmp, args.spool_driver)
    try:
        records = project(spool)
        max_epoch = max((int(e["vars"]["epoch"])
                         for r in records for e in r["events"]),
                        default=0) + 2
        spec = spool_spec(max_epoch)
        if args.trace_out:
            save_traces(args.trace_out, records)
        res = host_validate_batch(spec,
                                  traces_from_records(records, spec))

        # the teeth: corrupt ONE record — the longest job history,
        # final event's st rewritten to 0 ("queued"; no action
        # re-enters it) — and demand divergence EXACTLY there
        victim = max(records, key=lambda r: len(r["events"]))
        bad = json.loads(json.dumps(victim))
        k = len(bad["events"]) - 1
        bad["events"][k]["vars"]["st"] = "0"
        bres = host_validate_batch(spec,
                                   traces_from_records([bad], spec))
        fd = bres.first_divergence or {}
        out = {
            "spool": spool,
            "driver": json.load(open(os.path.join(
                spool, "spooldrv.json")))["driver"]
            if os.path.exists(os.path.join(spool, "spooldrv.json"))
            else "fs",
            "jobs": len(records),
            "events": sum(len(r["events"]) for r in records),
            "accepted": bool(res.ok),
            "corrupted_job": victim["trace"],
            "corrupted_step": k,
            "corrupted_diverged_at": fd.get("step"),
            "corrupted_flagged": (not bres.ok
                                  and fd.get("step") == k
                                  and fd.get("trace")
                                  == victim["trace"]),
        }
        out["ok"] = bool(out["accepted"] and out["corrupted_flagged"]
                         and out["jobs"] > 0)
    finally:
        if tmp:
            shutil.rmtree(tmp, ignore_errors=True)
    print(json.dumps(out, indent=1, default=str))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
