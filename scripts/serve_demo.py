"""Serving-tier demo: lifecycle smoke + the ISSUE 14 saturation drill
+ the ISSUE 18 abuse drill.

Five legs, end to end on the stub harness (no reference mount, CPU
backend), printing one JSON object; exit 0 iff every check holds:

  lifecycle   the original ISSUE 6 three-job drill (clean /
              speclint-rejected / preempt-requeue-bit-identical) —
              unchanged, now riding the fair-share pop order.

  saturation  the ISSUE 14 acceptance drill: HUNDREDS of queued jobs
              across 3 tenants and all four job kinds (shell, check,
              sim, validate) drained by 2 *worker processes* over one
              spool.  Checks: no starvation (every tenant's jobs all
              reach a terminal state, engine verdicts exact), fair
              interleaving (each tenant's mean completion rank stays
              near the global mean — no tenant waits for the others
              to finish), both workers actually claim work, and every
              job is claimed exactly once (attempts == job_started
              count per journal).

  scaling     near-linear worker scaling on sleep-shell jobs: the
              2-worker drain rate must be >= 1.6x the 1-worker rate
              (rates measured first-claim -> last-terminal off the
              spool log, so process startup is excluded).

  bit_identity byte-identical outcomes vs single-worker serial drain:
              the same deterministic job set (violating check, clean
              check, mutated-trace interp validate, seeded fleet
              hunt, shell) drained serially and by 2 concurrent
              workers; results and journals must agree modulo
              timestamps/worker-id (the projection below).

  abuse       the ISSUE 18 hardened-front-door drill: an
              unauthenticated client (401), a flooding tenant (429
              with Retry-After off the per-tenant token bucket) and
              an oversized body (413) are all rejected at the door,
              every denial is journaled and folded onto /v1/metrics,
              and the legit tenant's job still completes with the
              exact stub fixpoint.

    python scripts/serve_demo.py [--spool-driver fs|objstore|quorum]

``--spool-driver`` (ISSUE 20) runs every leg's spool over the named
spool driver — the acceptance bar is that the saturation leg passes
UNCHANGED over ``quorum`` (the replicated control log carries the
same exactly-once story as one filesystem).

Sizes honor TPUVSR_DEMO_SHELL_JOBS / TPUVSR_DEMO_SCALE_JOBS for
heavier manual runs; the defaults keep the whole demo tier-1 friendly.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

if __name__ == "__main__":
    os.environ["JAX_PLATFORMS"] = "cpu"
    _fl = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _fl:
        os.environ["XLA_FLAGS"] = (
            _fl + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, REPO)

N_SHELL = int(os.environ.get("TPUVSR_DEMO_SHELL_JOBS", "192"))
N_SCALE = int(os.environ.get("TPUVSR_DEMO_SCALE_JOBS", "20"))
#: long enough that the sleep dominates per-job queue overhead (claim
#: fsyncs + subprocess spawn), so the ratio reads WORKER parallelism
SCALE_SLEEP = 0.3
TENANTS = ("acme", "blue", "cobra")

#: the spool driver every leg runs over (--spool-driver; None = fs).
#: Only NEW-spool creations pass it; re-opens auto-detect from the
#: spool's persisted spooldrv.json (the ISSUE 20 contract).
SPOOL_DRIVER = None


def _new_queue(spool, **kw):
    from tpuvsr.service.queue import JobQueue
    if SPOOL_DRIVER:
        kw.setdefault("driver", SPOOL_DRIVER)
    return JobQueue(spool, **kw)

#: the journal projection for the bit-identity oracle — everything a
#: run MEANS, nothing about when/where it ran ("journals modulo
#: timestamps/worker-id")
STABLE_EVENT_KEYS = {
    "level_done": ("depth", "frontier", "distinct", "generated"),
    "violation": ("kind", "name"),
    "divergence": ("trace", "step"),
    "hunt_violation": ("name", "walk", "depth"),
    "run_end": ("ok",),
    "job_done": ("state",),
}


def _true_argv():
    from tpuvsr.testing import true_argv
    return true_argv()


def _sleep_argv(seconds):
    return [sys.executable, "-c", f"import time; time.sleep({seconds})"]


def _strip_volatile(result):
    if not isinstance(result, dict):
        return result
    return {k: v for k, v in result.items()
            if k not in ("elapsed_s", "supervisor")
            and "per_s" not in k}


def _journal_projection(q, job_id):
    from tpuvsr.obs import read_journal
    out = []
    for ev in read_journal(q.journal_path(job_id)):
        keys = STABLE_EVENT_KEYS.get(ev["event"])
        if keys:
            out.append((ev["event"],) + tuple(ev.get(k) for k in keys))
    return out


# ---------------------------------------------------------------------
# leg 1: lifecycle (the original ISSUE 6 drill)
# ---------------------------------------------------------------------
def demo_lifecycle(tmp, out):
    from tpuvsr.obs import read_journal
    from tpuvsr.service.queue import JobQueue
    from tpuvsr.service.worker import Worker, result_summary
    from tpuvsr.testing import STUB_DISTINCT, STUB_LEVELS

    q = _new_queue(os.path.join(tmp, "spool-life"))
    clean = q.submit("<stub:clean>", engine="device",
                     flags={"stub": True})
    rejected = q.submit("<stub:rejected>", engine="device",
                        flags={"stub": True, "stub_bad": True})
    preempt = q.submit("<stub:preempt>", engine="device",
                       flags={"stub": True, "inv_x_bound": 2,
                              "inject": "kill@level=2"})
    runs = Worker(q, devices=2).drain()

    from tpuvsr.engine.device_bfs import DeviceBFS
    from tpuvsr.testing import counter_spec, stub_model_factory
    eng = DeviceBFS(counter_spec(inv_x_bound=2),
                    model_factory=stub_model_factory(inv_x_bound=2),
                    hash_mode="full", tile_size=4,
                    fpset_capacity=1 << 8, next_capacity=1 << 6)
    preempt_oracle = result_summary(eng.run())

    checks = {}
    jc = q.get(clean.job_id)
    evs_c = [e["event"]
             for e in read_journal(q.journal_path(clean.job_id))]
    checks["clean_done_exact_fixpoint"] = (
        jc.state == "done"
        and jc.result["distinct"] == STUB_DISTINCT
        and jc.result["levels"] == STUB_LEVELS)
    checks["clean_journal_lifecycle"] = (
        ["job_submitted", "job_admitted", "job_started"]
        == [e for e in evs_c if e.startswith("job_")][:3]
        and evs_c[-1] == "job_done")

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
    checks["preempt_bit_identical_to_oracle"] = (
        jp.result is not None
        and jp.result.get("violated") == preempt_oracle.get("violated")
        and jp.result.get("trace") == preempt_oracle.get("trace")
        and jp.result["distinct"] == preempt_oracle["distinct"])

    out["lifecycle"] = {"runs": runs, "stats": q.stats(),
                        "checks": checks}
    return checks


# ---------------------------------------------------------------------
# leg 2: saturation — hundreds of jobs, 3 tenants, 4 kinds, 2 workers
# ---------------------------------------------------------------------
def demo_saturation(tmp, out):
    from tpuvsr.obs import read_journal
    from tpuvsr.serve.fairshare import FairSharePolicy
    from tpuvsr.serve.pool import WorkerPool
    from tpuvsr.service.queue import JobQueue
    from tpuvsr.testing import stub_trace_records, subprocess_env
    from tpuvsr.validate import save_traces

    spool = os.path.join(tmp, "spool-sat")
    q = _new_queue(spool)
    true_argv = _true_argv()
    age_every = 0.5

    shell_ids = []
    for i in range(N_SHELL):
        j = q.submit(f"shell-{i:03d}", kind="shell",
                     tenant=TENANTS[i % 3],
                     priority=(5 if i % 7 == 0 else 0),
                     flags={"argv": true_argv, "timeout": 60})
        shell_ids.append(j.job_id)
    engine_jobs = {}
    for t, inv in zip(TENANTS, (None, 2, None)):
        flags = {"stub": True}
        if inv:
            flags["inv_x_bound"] = inv
        engine_jobs[f"check-{t}"] = q.submit(
            f"<stub:check-{t}>", engine="device", kind="check",
            tenant=t, flags=flags)
    engine_jobs["sim-acme"] = q.submit(
        "<stub:sim>", kind="sim", tenant="acme",
        flags={"stub": True, "inv_x_bound": 2, "walkers": 32,
               "depth": 12, "num": 96, "seed": 7})
    tp = os.path.join(tmp, "SAT_TRACE.jsonl")
    save_traces(tp, stub_trace_records(n=6, depth=5, mutate=(2, 1)))
    engine_jobs["validate-blue"] = q.submit(
        "<stub:validate>", kind="validate", tenant="blue",
        flags={"stub": True, "traces": tp, "interp": True})

    t0 = time.time()
    pool = WorkerPool(
        spool, 2, devices=4, drain=True, env=subprocess_env(),
        extra_args=["--age-every", str(age_every)]).start()
    rcs = pool.wait(timeout=420)
    elapsed = time.time() - t0
    q = JobQueue(spool)
    jobs = {j.job_id: j for j in q.jobs()}

    checks = {"workers_exited_clean": rcs == [0, 0]}
    # no starvation: EVERY tenant's jobs all reached a terminal state
    per_tenant_done = {t: 0 for t in TENANTS}
    incomplete = []
    for j in jobs.values():
        if j.state in ("done", "violated"):
            per_tenant_done[j.tenant] += 1
        else:
            incomplete.append((j.job_id, j.tenant, j.state, j.reason))
    checks["every_tenant_complete"] = not incomplete
    # engine verdicts exact across the saturated queue
    checks["check_verdicts_exact"] = (
        jobs[engine_jobs["check-acme"].job_id].result["distinct"] == 16
        and jobs[engine_jobs["check-blue"].job_id].state == "violated"
        and jobs[engine_jobs["check-blue"].job_id].result["violated"]
        == "Bound"
        and jobs[engine_jobs["check-cobra"].job_id].state == "done")
    checks["sim_found_violation"] = (
        jobs[engine_jobs["sim-acme"].job_id].state == "violated")
    vres = jobs[engine_jobs["validate-blue"].job_id].result
    checks["validate_divergence_localized"] = (
        jobs[engine_jobs["validate-blue"].job_id].state == "violated"
        and vres["divergences"][0]["trace"] == "t-0002"
        and vres["divergences"][0]["step"] == 1)
    # fair interleaving: each tenant's SHELL jobs complete around the
    # global mean rank, not tenant-after-tenant (DRR at work)
    done_order = sorted(
        (jobs[jid] for jid in shell_ids),
        key=lambda j: (j.updated_ts, j.seq))
    ranks = {t: [] for t in TENANTS}
    for rank, j in enumerate(done_order):
        ranks[j.tenant].append(rank)
    n = len(done_order)
    means = {t: (sum(r) / len(r) if r else 0.0)
             for t, r in ranks.items()}
    spread = (max(means.values()) - min(means.values())) / max(1, n)
    checks["tenants_interleaved"] = spread < 0.30
    # every job claimed exactly once per attempt, by 2 real workers
    owners = set()
    exactly_once = True
    for jid, j in jobs.items():
        evs = read_journal(q.journal_path(jid))
        starts = [e for e in evs if e["event"] == "job_started"]
        if len(starts) != max(1, j.attempts):
            exactly_once = False
        owners.update(e["worker"] for e in evs
                      if e["event"] == "sched_decision")
    checks["claimed_exactly_once"] = exactly_once
    checks["both_workers_claimed"] = len(owners) == 2
    pol = FairSharePolicy(age_every=age_every)
    out["saturation"] = {
        "jobs": len(jobs), "tenants": len(TENANTS),
        "kinds": sorted({j.kind for j in jobs.values()}),
        "workers": 2, "elapsed_s": round(elapsed, 2),
        "aging_wait_bound_s": pol.max_wait_bound(0, 5),
        "tenant_mean_ranks": {t: round(m, 1)
                              for t, m in means.items()},
        "rank_spread": round(spread, 3),
        "incomplete": incomplete[:8],
        "worker_rcs": rcs, "checks": checks,
    }
    return checks


# ---------------------------------------------------------------------
# leg 3: scaling — 2 workers >= 1.6x the drain rate of 1
# ---------------------------------------------------------------------
def _drain_rate(spool, workers):
    """Jobs/second between the first claim and the last terminal
    transition, read off the spool log (startup excluded)."""
    from tpuvsr.serve.pool import WorkerPool
    from tpuvsr.service.queue import TERMINAL, JobQueue
    from tpuvsr.testing import subprocess_env
    q = _new_queue(spool)
    n = 0
    for i in range(N_SCALE):
        q.submit(f"sleep-{i:03d}", kind="shell",
                 tenant=TENANTS[i % 3],
                 flags={"argv": _sleep_argv(SCALE_SLEEP),
                        "timeout": 60})
        n += 1
    # one light thread per worker: the ratio must measure WORKER
    # scaling, not the multi-runner's thread scaling inside one
    pool = WorkerPool(spool, workers, devices=2, drain=True,
                      env=subprocess_env(),
                      extra_args=["--light-threads", "1"]).start()
    rcs = pool.wait(timeout=420)
    t_start, t_end = None, None
    # read the state records through the spool DRIVER (ISSUE 20), so
    # the same scan works whether they live in jobs.jsonl or the
    # quorum replicas
    recs, _ = q.drv.read("jobs", None)
    for rec in recs:
        if rec.get("op") != "state":
            continue
        if rec["state"] == "running":
            ts = rec.get("ts")
            t_start = ts if t_start is None else min(t_start, ts)
        if rec["state"] in TERMINAL:
            ts = rec.get("ts")
            t_end = ts if t_end is None else max(t_end, ts)
    q.refresh()
    done = sum(1 for j in q.jobs() if j.state == "done")
    if done != n or rcs != [0] * workers or not t_start or not t_end:
        return 0.0, {"done": done, "rcs": rcs}
    return n / max(1e-6, t_end - t_start), {"done": done, "rcs": rcs}


def demo_scaling(tmp, out):
    attempts = []
    for attempt in range(2):
        r1, d1 = _drain_rate(
            os.path.join(tmp, f"spool-w1-{attempt}"), 1)
        r2, d2 = _drain_rate(
            os.path.join(tmp, f"spool-w2-{attempt}"), 2)
        ratio = r2 / r1 if r1 else 0.0
        attempts.append({"rate_1w": round(r1, 2),
                         "rate_2w": round(r2, 2),
                         "ratio": round(ratio, 2),
                         "detail": {"w1": d1, "w2": d2}})
        if ratio >= 1.6:
            break       # one retry absorbs transient machine load
    checks = {"near_linear_scaling": ratio >= 1.6}
    out["scaling"] = {"jobs": N_SCALE, "sleep_s": SCALE_SLEEP,
                      **attempts[-1], "attempts": attempts,
                      "checks": checks}
    return checks


# ---------------------------------------------------------------------
# leg 4: bit-identity — multi-worker outcomes == serial drain
# ---------------------------------------------------------------------
def _submit_identity_set(q, tmp):
    from tpuvsr.testing import stub_trace_records
    from tpuvsr.validate import save_traces
    tp = os.path.join(tmp, "ID_TRACE.jsonl")
    if not os.path.exists(tp):
        save_traces(tp, stub_trace_records(n=5, depth=6,
                                           mutate=(1, 3)))
    jobs = {}
    jobs["check-viol"] = q.submit(
        "<stub:check-viol>", engine="device", tenant="acme",
        flags={"stub": True, "inv_x_bound": 2})
    jobs["check-clean"] = q.submit(
        "<stub:check-clean>", engine="device", tenant="blue",
        flags={"stub": True})
    jobs["validate"] = q.submit(
        "<stub:validate>", kind="validate", tenant="cobra",
        flags={"stub": True, "traces": tp, "interp": True})
    jobs["sim"] = q.submit(
        "<stub:sim>", kind="sim", tenant="acme",
        flags={"stub": True, "inv_x_bound": 2, "walkers": 32,
               "depth": 12, "num": 96, "seed": 7})
    jobs["shell"] = q.submit(
        "shell-id", kind="shell", tenant="blue",
        flags={"argv": _true_argv(), "timeout": 60})
    return jobs


def _outcomes(q, jobs):
    q.refresh()
    out = {}
    for label, job in jobs.items():
        j = q.get(job.job_id)
        out[label] = {"state": j.state,
                      "result": _strip_volatile(j.result),
                      "journal": _journal_projection(q, job.job_id)}
    return out


def demo_bit_identity(tmp, out):
    from tpuvsr.service.queue import JobQueue
    from tpuvsr.service.worker import Worker

    serial_spool = os.path.join(tmp, "spool-serial")
    qs = _new_queue(serial_spool)
    serial_jobs = _submit_identity_set(qs, tmp)
    Worker(qs, devices=2, owner="serial", light_threads=0).drain()
    serial = _outcomes(qs, serial_jobs)

    multi_spool = os.path.join(tmp, "spool-multi")
    qm = _new_queue(multi_spool)
    multi_jobs = _submit_identity_set(qm, tmp)
    workers = [Worker(JobQueue(multi_spool), devices=2,
                      owner=f"w{i}", light_threads=0)
               for i in range(2)]
    threads = [threading.Thread(target=w.drain) for w in workers]
    for t in threads:
        t.start()
    for t in threads:
        t.join(600)
    multi = _outcomes(qm, multi_jobs)

    checks, diffs = {}, {}
    for label in serial_jobs:
        same = serial[label] == multi[label]
        checks[f"identical_{label}"] = same
        if not same:
            diffs[label] = {"serial": serial[label],
                            "multi": multi[label]}
    out["bit_identity"] = {
        "jobs": sorted(serial_jobs),
        "serial_states": {k: v["state"] for k, v in serial.items()},
        "multi_states": {k: v["state"] for k, v in multi.items()},
        "diffs": diffs, "checks": checks,
    }
    return checks


# ---------------------------------------------------------------------
# leg 5: abuse — the hardened front door (ISSUE 18)
# ---------------------------------------------------------------------
def demo_abuse(tmp, out):
    import http.client
    from tpuvsr.obs import read_journal
    from tpuvsr.serve.guard import Guard
    from tpuvsr.serve.http import ServiceHTTP
    from tpuvsr.service.queue import JobQueue
    from tpuvsr.service.worker import Worker
    from tpuvsr.testing import STUB_DISTINCT, STUB_LEVELS

    spool = os.path.join(tmp, "spool-abuse")
    _new_queue(spool)
    with open(os.path.join(spool, "tokens.json"), "w") as f:
        json.dump({"legit": "tok-legit", "flood": "tok-flood"}, f)
    guard = Guard(spool, rate=0.5, burst=2.0)
    svc = ServiceHTTP(spool, guard=guard).start()

    def req(method, path, body=None, token=None, headers=None):
        conn = http.client.HTTPConnection("127.0.0.1", svc.port,
                                          timeout=10)
        hdrs = dict(headers or {})
        if token:
            hdrs["Authorization"] = f"Bearer {token}"
        data = None
        if body is not None:
            data = json.dumps(body).encode()
            hdrs["Content-Type"] = "application/json"
        conn.request(method, path, body=data, headers=hdrs)
        resp = conn.getresponse()
        raw = resp.read()
        try:
            doc = json.loads(raw or b"{}")
        except ValueError:
            doc = {"raw": raw.decode(errors="replace")}
        ra = resp.getheader("Retry-After")
        conn.close()
        return resp.status, doc, ra

    checks = {}
    try:
        submit = {"spec": "<stub:legit>", "engine": "device",
                  "flags": {"stub": True}}
        code, doc, _ = req("POST", "/v1/jobs", body=submit,
                           token="tok-legit")
        legit_id = doc.get("job_id")
        checks["legit_accepted"] = code == 200
        # an unauthenticated client and an oversized body bounce at
        # the door — neither ever reaches the queue
        checks["unauthenticated_401"] = req(
            "POST", "/v1/jobs", body=submit)[0] == 401
        checks["oversized_body_413"] = req(
            "POST", "/v1/jobs", body=submit, token="tok-legit",
            headers={"Content-Length": str(guard.max_body + 1)}
        )[0] == 413
        # the flood: 10 rapid submissions against a 0.5/s budget
        flood = [req("POST", "/v1/jobs",
                     body={"spec": f"SPAM-{i}", "kind": "shell",
                           "flags": {"argv": _true_argv()}},
                     token="tok-flood")
                 for i in range(10)]
        denied = [f for f in flood if f[0] == 429]
        checks["flood_throttled_429"] = len(denied) >= 7
        checks["429_carries_retry_after"] = all(f[2] for f in denied)
        # the legit tenant's verdict is untouched by the abuse
        q = JobQueue(spool)
        Worker(q, devices=1).drain()
        legit = q.get(legit_id)
        checks["legit_verdict_exact"] = (
            legit.state == "done"
            and legit.result["distinct"] == STUB_DISTINCT
            and legit.result["levels"] == STUB_LEVELS)
        # every denial journaled AND folded onto /v1/metrics
        ev = [e["event"] for e in read_journal(
            os.path.join(spool, "guard.jsonl"))]
        checks["every_denial_journaled"] = (
            ev.count("rate_limited") == len(denied)
            and "auth_denied" in ev)
        conn = http.client.HTTPConnection("127.0.0.1", svc.port,
                                          timeout=10)
        conn.request("GET", "/v1/metrics",
                     headers={"Authorization": "Bearer tok-legit"})
        resp = conn.getresponse()
        text = resp.read().decode()
        conn.close()
        checks["denials_on_metrics"] = (
            resp.status == 200
            and f"tpuvsr_rate_limited_total {len(denied)}" in text
            and "tpuvsr_auth_denied_total 1" in text)
    finally:
        svc.stop()
    out["abuse"] = {"flood_429s": len(denied),
                    "flood_codes": [f[0] for f in flood],
                    "legit_state": legit.state,
                    "checks": checks}
    return checks


def main(argv=()):
    global SPOOL_DRIVER
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--spool-driver", default=None,
                    choices=("fs", "objstore", "quorum"))
    SPOOL_DRIVER = ap.parse_args(list(argv)).spool_driver
    tmp = tempfile.mkdtemp(prefix="tpuvsr-serve-demo-")
    out = {}
    checks = {}
    try:
        for leg in (demo_lifecycle, demo_saturation, demo_scaling,
                    demo_bit_identity, demo_abuse):
            for k, v in leg(tmp, out).items():
                checks[f"{leg.__name__}.{k}"] = v
        out["checks"] = checks
        out["ok"] = all(checks.values())
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(json.dumps(out, indent=1, default=str))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
