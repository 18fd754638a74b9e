"""Sim-dispatch smoke: submit 3 ``kind="sim"`` jobs, drain the queue.

The ISSUE 7 service-mode acceptance drill, end to end in one process
on the stub harness (no reference mount, CPU backend, seconds) —
``serve_demo.py``'s walker-fleet twin:

  clean      a fleet hunt over the tightened-invariant counter spec —
             collects its deduped violations, terminal state
             ``violated``, every unique violation carrying a
             TRACE-format counterexample
  rejected   a spec that fails the speclint frames pass — the
             admission gate kills it at ``queued -> failed``; it
             never reaches ``running`` and costs zero device time
             (the same gate BFS jobs go through)
  preempt    a SIGTERM-style preemption (injected kill mid-chunk) on
             the same hunt — the job requeues with its walker-frontier
             rescue snapshot, resumes, and reports a violation set and
             headline trace BIT-IDENTICAL to the clean job's (the
             fleet's per-(seed, walk-id) determinism contract holding
             across the dispatcher)

Every lifecycle transition must be visible in the per-job journals
(``job_*`` events interleaved with ``sim_chunk``/``hunt_violation``/
``rescue_checkpoint``).

Prints one JSON object; exit 0 iff every expectation holds.

    python scripts/hunt_demo.py
"""

from __future__ import annotations

import json
import os
import shutil
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

#: the one hunt configuration all three jobs (and the oracle) share
HUNT_FLAGS = {"stub": True, "inv_x_bound": 2, "walkers": 32,
              "depth": 8, "num": 64, "seed": 1, "chunk_steps": 4}


def main():
    from tpuvsr.obs import read_journal
    from tpuvsr.service.queue import JobQueue
    from tpuvsr.service.worker import Worker

    tmp = tempfile.mkdtemp(prefix="tpuvsr-hunt-demo-")
    out = {"jobs": {}}
    try:
        q = JobQueue(os.path.join(tmp, "spool"))
        clean = q.submit("<stub:hunt-clean>", engine="device",
                         kind="sim", flags=dict(HUNT_FLAGS))
        rejected = q.submit("<stub:hunt-rejected>", engine="device",
                            kind="sim",
                            flags={"stub": True, "stub_bad": True})
        preempt = q.submit("<stub:hunt-preempt>", engine="device",
                           kind="sim",
                           flags=dict(HUNT_FLAGS,
                                      inject="kill@level=1"))
        runs = Worker(q, devices=2).drain()

        checks = {}
        jc = q.get(clean.job_id)
        evs_c = [e["event"]
                 for e in read_journal(q.journal_path(clean.job_id))]
        checks["clean_hunt_violated_with_unique_traces"] = (
            jc.state == "violated"
            and len(jc.result["violations"]) > 1
            and all(v.get("trace") for v in jc.result["violations"])
            and len({v["dedup"] for v in jc.result["violations"]})
            == len(jc.result["violations"]))
        checks["clean_journal_lifecycle"] = (
            ["job_submitted", "job_admitted", "job_started"]
            == [e for e in evs_c if e.startswith("job_")][:3]
            and evs_c[-1] == "job_done"
            and "sim_chunk" in evs_c and "hunt_violation" in evs_c)

        jr = q.get(rejected.job_id)
        evs_r = [e["event"]
                 for e in read_journal(q.journal_path(rejected.job_id))]
        checks["rejected_by_speclint"] = (
            jr.state == "failed" and jr.reason == "speclint"
            and bool((jr.result or {}).get("speclint")))
        checks["rejected_never_ran"] = (
            "job_started" not in evs_r and "run_start" not in evs_r
            and jr.attempts == 0)

        jp = q.get(preempt.job_id)
        evs_p = [e["event"]
                 for e in read_journal(q.journal_path(preempt.job_id))]
        checks["preempt_requeued_then_completed"] = (
            jp.state == "violated" and jp.attempts == 2
            and "job_requeued" in evs_p
            and "rescue_checkpoint" in evs_p)
        checks["preempt_bit_identical_to_clean_hunt"] = (
            jp.result is not None and jc.result is not None
            and jp.result["violations"] == jc.result["violations"]
            and jp.result["trace"] == jc.result["trace"]
            and jp.result["violated"] == jc.result["violated"]
            and jp.result["walks"] == jc.result["walks"])

        for job, evs in ((jc, evs_c), (jr, evs_r), (jp, evs_p)):
            out["jobs"][job.spec] = {
                "state": job.state, "attempts": job.attempts,
                "reason": job.reason, "journal_events": evs,
            }
        out["runs"] = runs
        out["stats"] = q.stats()
        out["unique_violations"] = (len(jc.result["violations"])
                                    if jc.result else 0)
        out["checks"] = checks
        out["ok"] = all(checks.values())
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(json.dumps(out, indent=1, default=str))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
