"""Traffic kind `served-jobs`: a closed loop of one client on the
served path, in-process (one process per chip): `submit` to a fresh
spool, `serve --drain` with the single in-process worker, `status
--json`.  A new job starts while fewer than --seconds have passed since
the window opened; the job in flight always finishes; at least one job.

A job during which a program was written to the persistent compile
cache was a cold build: it filled the cache and is counted as set-up,
so that every measured job is what one submission costs on a warm
cache.  The traffic file gives `submit_args` (further arguments of the
`submit` verb).
"""

import contextlib
import io
import json
import os
import shutil
import statistics
import time

import oracle


def _svc(*argv):
    from tpuvsr.service.api import main as svc
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = svc(list(argv))
    if rc != 0:
        raise RuntimeError(f"service {argv} exited {rc}: {buf.getvalue()}")
    return buf.getvalue()


def _events(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def setup(cell):
    spool = os.path.join(cell.out_dir, f"spool-{cell.seed}")
    shutil.rmtree(spool, ignore_errors=True)
    return {"spool": spool, "cfg": cell.path(cell.config["cfg"])}


def _one_job(cell, state):
    before = cell.meter.snapshot()
    t0 = time.time()
    job_id = json.loads(_svc(
        "submit", cell.config["module"], "-config", state["cfg"],
        "--spool", state["spool"], "--json",
        *cell.traffic.get("submit_args", [])))["job_id"]
    _svc("serve", "--drain", "--spool", state["spool"])
    doc = json.loads(_svc("status", job_id, "--spool", state["spool"],
                          "--json"))
    verdict_s = time.time() - t0
    job = {"job_id": job_id, "verdict_s": verdict_s, "status": doc,
           "builds": cell.meter.since(before)}
    if doc.get("journal"):
        job["events"] = _events(doc["journal"])
    if doc.get("metrics"):
        with open(doc["metrics"]) as f:
            job["metrics_doc"] = json.load(f)
    cell.log(f"job {job_id}: {doc.get('state')} in {verdict_s:.2f}s, "
             f"builds {job['builds']}")
    return job


def window(cell, state, seconds):
    opened = time.time()
    jobs, fill_s = [], 0.0
    while not jobs or time.time() - opened < seconds:
        job = _one_job(cell, state)
        if not jobs and not fill_s and job["builds"]["cache_writes"]:
            # a cold build: this job filled the compile cache.  It is
            # set-up, and the window opens again
            fill_s = time.time() - opened
            state["fill_job"] = job
            # the measured job is to cost what the first job of a new
            # process costs: drop the traced and compiled functions the
            # fill job left in this process
            import jax
            jax.clear_caches()
            opened = time.time()
            continue
        jobs.append(job)
    last = jobs[-1]
    return {"jobs": jobs, "setup_extra_s": fill_s,
            "elapsed_s": time.time() - opened,
            "verdict_s": statistics.median(j["verdict_s"] for j in jobs),
            "metrics_doc": last.get("metrics_doc"),
            "events": last.get("events", [])}


def check(cell, state, obs):
    want = cell.config["oracle"]
    levels = cell.oracle_levels()
    platform = cell.devices[0].platform
    out, failed = [], 0
    for job in obs["jobs"] + ([state["fill_job"]]
                              if "fill_job" in state else []):
        doc = job["status"]
        result = doc.get("result") or {}
        started = [e for e in job.get("events", [])
                   if e.get("event") == "job_started"]
        got = [doc.get("state"), result.get("distinct"),
               result.get("diameter"), result.get("levels"),
               [e.get("backend") for e in started]]
        c = oracle.compare(
            f"job {job['job_id']}: state, distinct, diameter, levels, "
            f"backend", got,
            ["done", want["distinct"], want["diameter"], levels,
             [platform]])
        failed += not c["ok"]
        out.append(c)
    return {"comparisons": out, "attempted": len(out), "failed": failed}


def end_to_end(cell, obs):
    return {"verdict_s": obs["verdict_s"]}
