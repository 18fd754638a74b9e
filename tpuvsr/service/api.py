"""Service CLI verbs: ``serve`` / ``submit`` / ``status`` /
``cancel`` / ``telemetry``.

The query surface of the dispatch service is deliberately thin: the
queue spool IS the database and each job's journal + metrics doc ARE
its API records — these verbs only fold and print them.

    python -m tpuvsr submit SPEC.tla [-config F] [--engine E]
                     [--priority N] [--devices N] [--tenant T] ...
    python -m tpuvsr serve  [--spool DIR] [--drain] [--devices N]
                     [--workers N] [--http PORT] [--tenant-weight T=W]
    python -m tpuvsr status [JOB] [--spool DIR] [--json] [--tail N]
    python -m tpuvsr cancel JOB [--spool DIR]
    python -m tpuvsr telemetry [SPOOL] [--watch] [--json | --prom]

``telemetry`` (ISSUE 17) folds the spool's journals through
:class:`tpuvsr.obs.telemetry.TelemetryAggregator` and prints the
fleet view — per-tenant latency histograms, DRR fairness vs actual
device-seconds, worker utilization, throughput windows, SLO breaches.
``--watch`` repolls on an interval; ``--prom`` prints the Prometheus
text exposition the HTTP front serves at ``GET /v1/metrics``.

``submit`` / ``status`` / ``cancel`` import neither jax nor the
engines — they are milliseconds against a live spool.  ``serve``
hosts a :class:`tpuvsr.service.worker.Worker` (one process, many
jobs); ``--drain`` exits when nothing is claimable (the smoke/demo
mode), without it the worker polls for new submissions until
``--max-seconds``.  The serving tier (ISSUE 14, ``tpuvsr/serve``)
rides the same verb: ``--workers N`` spawns N worker processes over
the shared spool (the parent supervises + sweeps stale claims),
``--http PORT`` raises the wire API (submit/status/cancel/list +
chunked journal streaming; ``--workers 0`` = front only), and the
fair-share knobs (``--tenant-weight``, ``--age-every``) shape the
deficit-round-robin pop order.

The spool location resolves as ``--spool`` > ``TPUVSR_SPOOL`` >
``./.tpuvsr-spool``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

from ..exitcodes import EX_USAGE
from .queue import JobQueue, QueueError

VERBS = ("serve", "submit", "status", "cancel", "telemetry")


def default_spool():
    return os.environ.get("TPUVSR_SPOOL", ".tpuvsr-spool")


def _flag_pairs(items):
    """--flag KEY=VALUE (repeatable) -> dict, values parsed as JSON
    scalars when possible."""
    out = {}
    for item in items or []:
        if "=" not in item:
            raise ValueError(f"--flag wants KEY=VALUE, got {item!r}")
        k, v = item.split("=", 1)
        try:
            out[k] = json.loads(v)
        except ValueError:
            out[k] = v
    return out


def build_parser():
    p = argparse.ArgumentParser(
        prog="tpuvsr", description="verification dispatch service")
    sub = p.add_subparsers(dest="verb", required=True)

    sp = sub.add_parser("submit", help="enqueue a verification job")
    sp.add_argument("spec", nargs="?", default=None,
                    help="path to the .tla module (omit with --stub)")
    sp.add_argument("-config", "--config", default=None)
    sp.add_argument("--engine", default="auto",
                    choices=["auto", "device", "paged", "sharded"])
    sp.add_argument("--priority", type=int, default=0)
    sp.add_argument("--tenant", default=None,
                    help="fair-share tenant this job bills to "
                         "(ISSUE 14): deficit-round-robin pop order "
                         "and --tenant-weight quotas group by it")
    sp.add_argument("--devices", type=int, default=1)
    sp.add_argument("--devices-min", type=int, default=None,
                    help="elastic floor (sharded): the scheduler may "
                         "shrink the mesh to this")
    sp.add_argument("--devices-max", type=int, default=None,
                    help="elastic ceiling (sharded): grow bound")
    sp.add_argument("--maxstates", type=int, default=None)
    sp.add_argument("--maxseconds", type=float, default=None)
    sp.add_argument("--pipeline", type=int, default=None)
    sp.add_argument("--inject", default=None,
                    help="deterministic fault plan for this job "
                         "(tpuvsr/resilience/faults.py grammar)")
    sp.add_argument("--sim", action="store_true",
                    help="submit a kind=\"sim\" job: a walker-fleet "
                         "defect hunt (tpuvsr/sim) instead of a BFS "
                         "check")
    sp.add_argument("--walkers", type=int, default=None,
                    help="sim jobs: fleet size (default 512)")
    sp.add_argument("--depth", type=int, default=None,
                    help="sim jobs: walk depth bound (default 100)")
    sp.add_argument("--num", type=int, default=None,
                    help="sim jobs: stop after N walks (default "
                         "10000; --hunt for the continuous mode)")
    sp.add_argument("--seed", type=int, default=None,
                    help="sim jobs: fleet RNG seed (walk i replays "
                         "identically for any walker count/mesh)")
    sp.add_argument("--split", action="store_true",
                    help="sim jobs: importance splitting (fingerprint-"
                         "novelty kill/clone at chunk boundaries)")
    sp.add_argument("--hunt", action="store_true",
                    help="sim jobs: continuous hunt — run until "
                         "cancelled/preempted, collecting deduped "
                         "violations")
    sp.add_argument("--validate", default=None,
                    metavar="TRACES.jsonl",
                    help="submit a kind=\"validate\" job: check every "
                         "recorded implementation trace in the file "
                         "against the spec (tpuvsr/validate) instead "
                         "of a BFS check")
    sp.add_argument("--batch", type=int, default=None,
                    help="validate jobs: traces per round (default "
                         "1024)")
    sp.add_argument("--batch-per-device", type=int, default=None,
                    help="validate jobs: tie the round size to the "
                         "device allocation (elastic trace-batch "
                         "placement: batch = N * devices, rescaled "
                         "when the scheduler reshapes the job)")
    sp.add_argument("--interp", action="store_true",
                    help="validate jobs: use the interpreter "
                         "reference validator — a LIGHT job the "
                         "worker's multi-runner threads handle with "
                         "zero devices (ISSUE 14)")
    sp.add_argument("--lint-only", action="store_true",
                    help="check jobs: speclint report only, no "
                         "engine run — a LIGHT job (zero devices, "
                         "multi-runner lane)")
    sp.add_argument("--stub", action="store_true",
                    help="run the inline counter spec on the stub "
                         "kernel (tier-1 smoke path, no reference "
                         "mount)")
    sp.add_argument("--flag", action="append", default=[],
                    metavar="KEY=VALUE",
                    help="extra job flag (repeatable; JSON values)")
    sp.add_argument("--spool", default=None)
    sp.add_argument("--spool-driver", default=None,
                    choices=("fs", "objstore", "quorum"),
                    help="spool driver for a NEW spool (ISSUE 20); "
                         "an existing spool's persisted choice always "
                         "wins, absent config means fs")
    sp.add_argument("--spool-replicas", type=int, default=None,
                    metavar="N",
                    help="quorum driver replica count (default 3)")
    sp.add_argument("--json", action="store_true")

    sv = sub.add_parser("serve", help="run the dispatch worker(s)")
    sv.add_argument("--spool", default=None)
    sv.add_argument("--spool-driver", default=None,
                    choices=("fs", "objstore", "quorum"),
                    help="spool driver for a NEW spool (ISSUE 20): "
                         "fs (single filesystem, the default), "
                         "objstore (CAS-record claims + epoch "
                         "fencing), quorum (replicated log over N "
                         "directories); an existing spool's persisted "
                         "choice always wins")
    sv.add_argument("--spool-replicas", type=int, default=None,
                    metavar="N",
                    help="quorum driver replica count (default 3)")
    sv.add_argument("--host-lease-timeout", type=float, default=None,
                    help="seconds after which a host whose lease "
                         "record went silent is dead and ALL its "
                         "claims are swept at once (default: the "
                         "heartbeat timeout)")
    sv.add_argument("--drain", action="store_true",
                    help="exit when nothing is claimable")
    sv.add_argument("--devices", type=int, default=None,
                    help="device pool size (default: every visible "
                         "device); with --workers N each worker owns "
                         "a devices/N group")
    sv.add_argument("--workers", type=int, default=1,
                    help="worker processes over the shared spool "
                         "(ISSUE 14): 1 = drain in-process (the "
                         "original mode), N>1 = spawn N serve "
                         "subprocesses and supervise them, 0 = no "
                         "workers (HTTP front only)")
    sv.add_argument("--worker-id", default=None,
                    help="this worker's identity in claim files and "
                         "journals (default: worker-<pid>)")
    sv.add_argument("--max-restarts", type=int, default=3,
                    help="--workers N>1: how many times the parent "
                         "respawns one dead (nonzero-exit) worker "
                         "slot, with exponential backoff; journaled "
                         "as worker_respawn in <spool>/pool.jsonl "
                         "(0 = sweep stale claims only, the pre-"
                         "ISSUE-15 behavior)")
    sv.add_argument("--http", type=int, default=None, metavar="PORT",
                    help="raise the HTTP front on PORT (0 = an "
                         "ephemeral port, printed on stderr): "
                         "submit/status/cancel/list + streamed "
                         "journal tails over the wire "
                         "(tpuvsr/serve/http.py)")
    sv.add_argument("--tenant-weight", action="append", default=[],
                    metavar="TENANT=W",
                    help="fair-share weight for a tenant "
                         "(repeatable; default 1.0 each): a weight-2 "
                         "tenant gets two pops per deficit-round-"
                         "robin round where a weight-1 tenant gets "
                         "one")
    sv.add_argument("--age-every", type=float, default=60.0,
                    help="priority-aging rate: +1 effective priority "
                         "per this many seconds waited (0 disables; "
                         "bounds every job's wait at age_every * "
                         "(top_priority - its_priority + 1))")
    sv.add_argument("--light-threads", type=int, default=2,
                    help="multi-runner threads for light jobs "
                         "(shell / interp-validate / lint-only; 0 "
                         "disables the side lane)")
    sv.add_argument("--heartbeat-timeout", type=float, default=None,
                    help="seconds after which a cross-host claim "
                         "with no heartbeat is recoverable "
                         "(default 300)")
    sv.add_argument("--max-jobs", type=int, default=None)
    sv.add_argument("--max-seconds", type=float, default=None)
    sv.add_argument("--tpu-devices", type=int, default=None,
                    help="reachable TPU devices for the cpu-vs-tpu "
                         "placement advisory (default: "
                         "TPUVSR_TPU_DEVICES env, else 0)")
    sv.add_argument("--bench-dir", default=None,
                    help="directory of BENCH_r*.json docs for the "
                         "cross-backend throughput advisory "
                         "(default: the repo root)")
    sv.add_argument("--quiet", action="store_true")
    # front-door hardening (ISSUE 18) — see serve/guard.py: auth is
    # on whenever <spool>/tokens.json (or --auth-tokens) exists; the
    # limiter / backpressure / breaker knobs are opt-in
    sv.add_argument("--tls-cert", default=None, metavar="PEM",
                    help="serve the HTTP front over TLS with this "
                         "certificate chain")
    sv.add_argument("--tls-key", default=None, metavar="PEM",
                    help="private key for --tls-cert (omit when the "
                         "key is in the cert file)")
    sv.add_argument("--auth-tokens", default=None, metavar="JSON",
                    help="per-tenant bearer tokens file (default "
                         "<spool>/tokens.json; absent = open mode)")
    sv.add_argument("--rate", type=float, default=None,
                    metavar="PER_S",
                    help="per-tenant token-bucket refill "
                         "(submissions/second; denials are 429 with "
                         "Retry-After)")
    sv.add_argument("--burst", type=float, default=None,
                    help="token-bucket capacity (default: --rate)")
    sv.add_argument("--max-inflight", type=int, default=None,
                    metavar="N",
                    help="per-tenant cap on unfinished jobs (429 "
                         "past it)")
    sv.add_argument("--high-water", type=int, default=None,
                    metavar="N",
                    help="queue-depth backpressure: 503 new "
                         "submissions while the backlog exceeds N")
    sv.add_argument("--max-body", type=int, default=None,
                    metavar="BYTES",
                    help="request body cap (413 past it; default "
                         "1 MiB)")
    sv.add_argument("--breaker-threshold", type=int, default=3,
                    metavar="K",
                    help="circuit breaker: trip a (tenant, spec) "
                         "after K failures in --breaker-window "
                         "seconds (fail-fast 'breaker-open')")
    sv.add_argument("--breaker-window", type=float, default=60.0)
    sv.add_argument("--breaker-cooldown", type=float, default=2.0,
                    help="seconds before a tripped breaker half-opens "
                         "for one probe (doubles per re-trip)")

    st = sub.add_parser("status", help="queue / per-job status")
    st.add_argument("job_id", nargs="?", default=None)
    st.add_argument("--spool", default=None)
    st.add_argument("--json", action="store_true")
    st.add_argument("--tail", type=int, default=0, metavar="N",
                    help="with a JOB: print the last N journal events")

    ca = sub.add_parser("cancel", help="cancel a job")
    ca.add_argument("job_id")
    ca.add_argument("--spool", default=None)
    ca.add_argument("--json", action="store_true")

    te = sub.add_parser("telemetry",
                        help="fold the spool's journals into the "
                             "fleet telemetry view (ISSUE 17)")
    te.add_argument("spool_pos", nargs="?", default=None,
                    metavar="SPOOL",
                    help="spool directory (also --spool / "
                         "TPUVSR_SPOOL)")
    te.add_argument("--spool", default=None)
    te.add_argument("--watch", action="store_true",
                    help="repoll and redraw every --interval seconds "
                         "until interrupted")
    te.add_argument("--interval", type=float, default=2.0)
    te.add_argument("--json", action="store_true",
                    help="print the tpuvsr-telemetry/1 snapshot "
                         "document")
    te.add_argument("--prom", action="store_true",
                    help="print the Prometheus text exposition "
                         "(format 0.0.4), as GET /v1/metrics serves")
    te.add_argument("--window", type=float, default=10.0,
                    help="fold window seconds (default 10)")
    te.add_argument("--slo-queue-wait", type=float, default=None,
                    metavar="SECONDS",
                    help="SLO watchdog: journal slo_breach when any "
                         "tenant's p99 queue wait exceeds this")
    te.add_argument("--no-breach-journal", action="store_true",
                    help="fold only — never append slo_breach events "
                         "or publish baselines (pure read)")
    return p


def _queue(args):
    return JobQueue(args.spool or default_spool(),
                    driver=getattr(args, "spool_driver", None),
                    replicas=getattr(args, "spool_replicas", None))


def cmd_submit(args):
    if not args.spec and not args.stub:
        print("submit: a SPEC path (or --stub) is required",
              file=sys.stderr)
        return EX_USAGE
    try:
        flags = _flag_pairs(args.flag)
    except ValueError as e:
        print(f"submit: {e}", file=sys.stderr)
        return EX_USAGE
    q = _queue(args)
    for k in ("maxstates", "maxseconds", "pipeline", "inject",
              "walkers", "depth", "num", "seed"):
        v = getattr(args, k)
        if v is not None:
            flags[k] = v
    if args.stub:
        flags["stub"] = True
    if args.split:
        flags["split"] = True
    if args.hunt:
        flags["hunt"] = True
    if args.validate and args.sim:
        print("submit: --validate and --sim are different job kinds "
              "(a trace-validation batch vs a walker-fleet hunt); "
              "pick one", file=sys.stderr)
        return EX_USAGE
    if args.interp and not args.validate:
        print("submit: --interp selects the interpreter validator; "
              "it needs --validate", file=sys.stderr)
        return EX_USAGE
    if args.lint_only and (args.sim or args.validate):
        print("submit: --lint-only is a check-job mode (speclint "
              "report, no engine run); it conflicts with "
              "--sim/--validate", file=sys.stderr)
        return EX_USAGE
    if args.lint_only:
        flags["lint_only"] = True
    if args.validate:
        if args.interp:
            flags["interp"] = True
        if args.maxstates is not None:
            # mirrors the CLI's -maxstates/-validate exit-2 contract:
            # the worker would silently ignore it otherwise
            print("submit: --maxstates bounds BFS; a validate job is "
                  "bounded by its trace file and --maxseconds",
                  file=sys.stderr)
            return EX_USAGE
        flags["traces"] = args.validate
        if args.batch is not None:
            flags["batch"] = args.batch
        if args.batch_per_device is not None:
            flags["batch_per_device"] = args.batch_per_device
    elif args.batch is not None or args.batch_per_device is not None:
        print("submit: --batch/--batch-per-device size a validate "
              "job's trace rounds; they need --validate",
              file=sys.stderr)
        return EX_USAGE
    kind = ("validate" if args.validate
            else "sim" if args.sim else "check")
    if not args.sim and (args.split or args.hunt
                         or args.walkers is not None
                         or args.depth is not None
                         or args.num is not None
                         or args.seed is not None):
        print("submit: --walkers/--depth/--num/--seed/--split/--hunt "
              "need --sim (they describe a walker-fleet job; check "
              "jobs take --maxstates/--maxseconds)", file=sys.stderr)
        return EX_USAGE
    job = q.submit(args.spec or "<stub:ObsCounter>",
                   cfg=args.config, engine=args.engine, kind=kind,
                   flags=flags, tenant=args.tenant,
                   priority=args.priority, devices=args.devices,
                   devices_min=args.devices_min,
                   devices_max=args.devices_max)
    if args.json:
        print(json.dumps(job.to_dict(), default=str))
    else:
        print(f"submitted {job.job_id} ({job.spec}, engine "
              f"{job.engine}, priority {job.priority}"
              + (f", tenant {job.tenant}" if job.tenant else "")
              + ")")
    return 0


def _fold_progress(journal_path, out, fold, nonempty):
    """The shared journal fold behind the per-kind progress rows:
    line-by-line JSON parse tolerating torn tails, ``fold(event, ev,
    out)`` per parsed event, ``out`` returned only when ``nonempty``
    says the journal actually carried that kind's progress (None
    otherwise, like an unreadable file — the caller omits the row)."""
    try:
        with open(journal_path) as f:
            for line in f:
                try:
                    ev = json.loads(line)
                except ValueError:
                    continue
                fold(ev.get("event"), ev, out)
    except OSError:
        return None
    return out if nonempty(out) else None


def _sim_progress(journal_path):
    """Sim-specific per-job progress folded from the journal: the
    latest chunk's walks/steps/depth, best novelty, and the unique
    violation count — the fleet's analog of the BFS level rows
    (ISSUE 7 satellite)."""
    def fold(e, ev, out):
        if e == "sim_chunk":
            out["walks"] = ev.get("walks", out["walks"])
            out["steps"] = ev.get("steps", out["steps"])
            out["depth"] = ev.get("depth", out["depth"])
        elif e == "split" and ev.get("novelty_best") is not None:
            out["novelty_best"] = ev["novelty_best"]
        elif e == "hunt_violation":
            out["unique_violations"] += 1
        elif e == "hunt_elastic":
            out["walkers"] = ev.get("to", out["walkers"])

    return _fold_progress(
        journal_path,
        {"walks": 0, "steps": 0, "depth": 0, "novelty_best": None,
         "unique_violations": 0, "walkers": None}, fold,
        lambda o: (o["walks"] or o["steps"]
                   or o["unique_violations"]))


def _validate_progress(journal_path):
    """Validate-specific per-job progress folded from the journal:
    cumulative traces checked / divergences from the latest
    ``validate_chunk``, plus the first divergence's location — the
    trace-validation analog of the sim rows (ISSUE 8)."""
    def fold(e, ev, out):
        if e == "validate_chunk":
            out["traces"] = ev.get("traces", out["traces"])
            out["divergences"] = ev.get("divergences",
                                        out["divergences"])
            out["step"] = ev.get("depth", out["step"])
        elif e == "run_end" and ev.get("traces") is not None:
            # chunk rows are mid-round progress; the run summary has
            # the final totals
            out["traces"] = ev["traces"]
            out["divergences"] = ev.get("divergences",
                                        out["divergences"])
        elif e == "divergence" and out["first_divergence"] is None:
            out["first_divergence"] = {"trace": ev.get("trace"),
                                       "step": ev.get("step")}

    return _fold_progress(
        journal_path,
        {"traces": 0, "divergences": 0, "step": 0,
         "first_divergence": None}, fold,
        lambda o: o["traces"] or o["divergences"])


def job_doc(q, job, tail=0):
    """One job's status document — THE job record both query surfaces
    serve verbatim: the ``status`` verb prints it and the HTTP front's
    ``GET /v1/jobs/<id>`` returns it (ISSUE 14: the CLI is one client
    among many, so the document is built once, here).  ``exit_code``
    is the unified table's code for the job's state
    (``tpuvsr/exitcodes.py``; None while non-terminal)."""
    from ..exitcodes import state_exit
    doc = job.to_dict()
    doc["exit_code"] = state_exit(job.state)
    jp = q.journal_path(job.job_id)
    mp = q.metrics_path(job.job_id)
    doc["journal"] = jp if os.path.exists(jp) else None
    doc["metrics"] = mp if os.path.exists(mp) else None
    if job.kind == "sim" and os.path.exists(jp):
        doc["sim"] = _sim_progress(jp)
    if job.kind == "validate" and os.path.exists(jp):
        doc["validate"] = _validate_progress(jp)
    tail = max(0, int(tail or 0))    # a negative tail must not turn
    #                                  into "everything but the head"
    if tail and os.path.exists(jp):
        rows = []
        with open(jp) as f:
            for line in f.readlines()[-tail:]:
                try:
                    rows.append(json.loads(line))
                except ValueError:
                    pass
        doc["journal_tail"] = rows
    return doc


def cmd_status(args):
    q = _queue(args)
    if args.job_id:
        try:
            job = q.get(args.job_id)
        except QueueError as e:
            print(f"status: {e}", file=sys.stderr)
            return EX_USAGE
        doc = job_doc(q, job, tail=args.tail)
        tail = doc.get("journal_tail", [])
        if args.json:
            print(json.dumps(doc, default=str))
        else:
            for k in ("job_id", "state", "exit_code", "kind", "tenant",
                      "spec", "engine", "priority", "devices",
                      "attempts", "reason"):
                print(f"{k}: {doc.get(k)}")
            if doc.get("rescue"):
                print(f"rescue: {doc['rescue']}")
            if doc.get("sim"):
                s = doc["sim"]
                print(f"sim: {s['walks']} walks, {s['steps']} steps, "
                      f"depth {s['depth']}, "
                      f"{s['unique_violations']} unique violation(s)"
                      + (f", best novelty {s['novelty_best']}"
                         if s["novelty_best"] is not None else ""))
            if doc.get("validate"):
                v = doc["validate"]
                fd = v.get("first_divergence")
                print(f"validate: {v['traces']} trace(s) checked, "
                      f"{v['divergences']} divergence(s)"
                      + (f", first at trace {fd['trace']} event "
                         f"{fd['step']}" if fd else ""))
            if doc.get("result"):
                r = {k: v for k, v in doc["result"].items()
                     if k not in ("trace", "violations")}
                if doc["result"].get("violations") is not None:
                    r["violations"] = len(doc["result"]["violations"])
                print(f"result: {json.dumps(r, default=str)}")
            for ev in tail:
                print(f"  {ev.get('event')}: "
                      + ", ".join(f"{k}={v}" for k, v in ev.items()
                                  if k not in ("event", "ts",
                                               "run_id")))
        return 0
    jobs = [j.to_dict() for j in q.jobs()]
    from ..serve.fairshare import TenantLedger
    tenants = TenantLedger.fold(q.jobs())
    if args.json:
        # the queue fold plus the fleet telemetry fold in one doc
        # (ISSUE 17): dashboards scraping `status --json` get the
        # same tpuvsr-telemetry/1 snapshot /v1/telemetry serves
        from ..obs.telemetry import TelemetryAggregator
        agg = TelemetryAggregator(q.spool, journal_breaches=False)
        agg.poll()
        print(json.dumps({"stats": q.stats(), "jobs": jobs,
                          "tenants": tenants,
                          "spool": q.spool_status(),
                          "telemetry": agg.snapshot()}, default=str))
    else:
        st = q.stats()
        print("queue: " + ", ".join(f"{k}={v}" for k, v in st.items()
                                    if v and k != "total")
              + f" (total {st['total']})")
        sp = q.spool_status()
        if sp["driver"] != "fs" or sp["replicas"]:
            reps = sp["replicas"]
            print(f"  spool: driver={sp['driver']}"
                  + (f" replicas={reps['live']}/{reps['total']} live"
                     + (f" (lost: {reps['lost']})" if reps["lost"]
                        else "") if reps else ""))
        for j in jobs:
            print(f"  {j['job_id']:>18} {j['state']:>20} "
                  f"prio={j['priority']} dev={j['devices']} "
                  f"attempts={j['attempts']} "
                  f"tenant={j.get('tenant') or '-'} {j['spec']}")
        if len(tenants) > 1 or "-" not in tenants:
            for t, row in sorted(tenants.items()):
                print(f"  tenant {t}: {row['jobs']} job(s), "
                      f"{row['queued']} queued, {row['active']} "
                      f"active, {row['done']} done, "
                      f"{row['service_s']}s served")
    return 0


def cmd_cancel(args):
    q = _queue(args)
    try:
        job = q.cancel(args.job_id)
    except QueueError as e:
        print(f"cancel: {e}", file=sys.stderr)
        return EX_USAGE
    note = ("cancel requested (running job rescues at the next level "
            "boundary)" if job.state == "running" else "cancelled")
    if args.json:
        print(json.dumps({"job_id": job.job_id, "state": job.state,
                          "note": note}))
    else:
        print(f"{job.job_id}: {note}")
    return 0


def cmd_telemetry(args):
    """``tpuvsr telemetry [SPOOL] [--watch] [--json | --prom]`` — the
    CLI face of the fleet telemetry fold.  Imports neither jax nor the
    engines (the aggregator is pure stdlib), so it is milliseconds
    against a live spool and safe to leave running beside a serve."""
    from ..obs.telemetry import (TelemetryAggregator, prometheus_text,
                                 render_watch)
    spool = args.spool_pos or args.spool or default_spool()
    if not os.path.isdir(spool):
        print(f"telemetry: no spool at {spool!r}", file=sys.stderr)
        return EX_USAGE
    slo = {}
    if args.slo_queue_wait is not None:
        slo["queue_wait_p99_s"] = args.slo_queue_wait
    agg = TelemetryAggregator(
        spool, window_s=args.window, slo=slo,
        journal_breaches=not args.no_breach_journal)

    def emit():
        agg.poll()
        snap = agg.snapshot()
        if args.prom:
            print(prometheus_text(snap), end="")
        elif args.json:
            print(json.dumps(snap, default=str))
        else:
            print(render_watch(snap))

    if not args.watch:
        emit()
        return 0
    try:
        while True:
            emit()
            print("---", flush=True)
            time.sleep(max(0.1, args.interval))
    except KeyboardInterrupt:
        pass
    return 0


def _policy_from_args(args):
    from ..serve.fairshare import FairSharePolicy
    try:
        weights = _flag_pairs(args.tenant_weight)
    except ValueError as e:
        raise ValueError(f"--tenant-weight wants TENANT=WEIGHT: {e}")
    return FairSharePolicy(weights=weights, age_every=args.age_every)


def _guard_from_args(args, spool):
    """The serve verb's admission guard (ISSUE 18).  Always built —
    a default Guard still enforces the body cap and honours a
    spool-local tokens.json — with the limiter / backpressure /
    breaker knobs layered on from the flags."""
    from ..serve.guard import Guard
    kw = {}
    if args.auth_tokens is not None:
        kw["tokens_path"] = args.auth_tokens
    if args.max_body is not None:
        kw["max_body"] = args.max_body
    return Guard(
        spool, rate=args.rate, burst=args.burst,
        max_inflight=args.max_inflight, high_water=args.high_water,
        breaker_k=args.breaker_threshold,
        breaker_window=args.breaker_window,
        breaker_cooldown=args.breaker_cooldown, **kw)


def _serve_pool(args, q, log, t0, http):
    """``serve --workers N`` (N > 1): spawn N worker subprocesses
    over the spool and stay a thin supervisor — sweep stale claims on
    a cadence (a SIGKILLed child's jobs requeue onto the survivors)
    and host the optional HTTP front."""
    from ..serve.pool import WorkerPool
    passthrough = ["--age-every", str(args.age_every),
                   "--light-threads", str(args.light_threads)]
    for tw in args.tenant_weight:
        passthrough += ["--tenant-weight", tw]
    if args.heartbeat_timeout is not None:
        passthrough += ["--heartbeat-timeout",
                        str(args.heartbeat_timeout)]
    # the placement advisory flags must reach the children too — a
    # child falling back to auto-detection would contradict an
    # explicit --tpu-devices/--bench-dir on the parent
    if args.tpu_devices is not None:
        passthrough += ["--tpu-devices", str(args.tpu_devices)]
    if args.bench_dir is not None:
        passthrough += ["--bench-dir", args.bench_dir]
    # the breaker runs IN the workers (it guards device time): each
    # child builds its own guard from the same thresholds
    passthrough += ["--breaker-threshold", str(args.breaker_threshold),
                    "--breaker-window", str(args.breaker_window),
                    "--breaker-cooldown", str(args.breaker_cooldown)]
    if args.quiet:
        passthrough.append("--quiet")
    pool = WorkerPool(
        q.spool, args.workers, devices=args.devices,
        drain=args.drain, max_seconds=args.max_seconds,
        max_jobs=args.max_jobs, extra_args=passthrough, log=log,
        max_restarts=args.max_restarts)
    pool.start()
    while True:
        # respawn BEFORE the liveness check: a tick where every child
        # died nonzero must relaunch, not drain the pool (ISSUE 15
        # satellite — the ROADMAP item 2 respawn residual).  A slot
        # waiting out its backoff counts as pending, not drained
        pool.respawn_dead()
        if not pool.alive() and not pool.pending_respawn():
            break
        # the pool parent IS this host's lease writer (ISSUE 20):
        # every sweep tick renews the lease a SURVIVOR host judges us
        # by — if we go silent past --host-lease-timeout, all of our
        # workers' claims are swept in one pass
        q.host_heartbeat()
        q.recover_stale(log=log)
        time.sleep(0.5)
    codes = pool.wait()
    q.recover_stale(log=log)
    q.refresh()
    print(json.dumps({"workers": args.workers, "worker_rcs": codes,
                      "stats": q.stats(),
                      "http": http.address if http else None,
                      "elapsed_s": round(time.time() - t0, 3)}))
    return 0 if all(c == 0 for c in codes) else 70


def cmd_serve(args):
    q = JobQueue(args.spool or default_spool(),
                 driver=args.spool_driver,
                 replicas=args.spool_replicas,
                 host_lease_timeout=args.host_lease_timeout,
                 **({"heartbeat_timeout": args.heartbeat_timeout}
                    if args.heartbeat_timeout is not None else {}))
    log = (None if args.quiet
           else lambda m: print(f"[tpuvsr] {m}", file=sys.stderr))
    t0 = time.time()
    guard = _guard_from_args(args, q.spool)
    http = None
    if args.http is not None:
        from ..serve.http import ServiceHTTP
        http = ServiceHTTP(q.spool, port=args.http, log=log,
                           guard=guard, tls_cert=args.tls_cert,
                           tls_key=args.tls_key).start()
        print(f"[tpuvsr] http front: {http.address}", file=sys.stderr)
    try:
        if args.workers == 0:
            # front-only mode: no drain loop, submissions land on the
            # spool for workers elsewhere
            if http is None:
                print("serve: --workers 0 without --http serves "
                      "nothing", file=sys.stderr)
                return EX_USAGE
            end = (None if args.max_seconds is None
                   else t0 + args.max_seconds)
            try:
                while end is None or time.time() < end:
                    time.sleep(0.2)
            except KeyboardInterrupt:
                pass
            q.refresh()     # fold submissions the front's own queue
            #                 instance appended while we slept
            print(json.dumps({"workers": 0, "http": http.address,
                              "stats": q.stats(),
                              "elapsed_s": round(time.time() - t0,
                                                 3)}))
            return 0
        try:
            policy = _policy_from_args(args)
        except ValueError as e:
            print(f"serve: {e}", file=sys.stderr)
            return EX_USAGE
        if args.workers > 1:
            return _serve_pool(args, q, log, t0, http)
        from .worker import Worker
        tpu = args.tpu_devices
        if tpu is None:
            from .scheduler import detect_tpu_devices
            tpu = detect_tpu_devices()
        devices = args.devices
        if devices is None:
            # a pool child with a pinned device group (ISSUE 18):
            # its DevicePool budget IS the slice size — never count
            # the whole host's devices from inside a pinned slot
            group = os.environ.get("TPUVSR_DEVICE_GROUP")
            if group and ":" in group:
                try:
                    devices = max(1, int(group.split(":")[1]))
                except ValueError:
                    pass
        w = Worker(q, devices=devices, log=log,
                   tpu_devices=tpu, bench_dir=args.bench_dir,
                   owner=args.worker_id, policy=policy,
                   light_threads=args.light_threads, guard=guard)
        runs = w.drain(max_jobs=args.max_jobs,
                       max_seconds=args.max_seconds,
                       idle_exit=args.drain)
        stats = q.stats()
        print(json.dumps({"runs": runs, "stats": stats,
                          "processed": w.processed,
                          "http": http.address if http else None,
                          "elapsed_s": round(time.time() - t0, 3)}))
        return 0
    finally:
        if http is not None:
            http.stop()


def main(argv=None):
    parser = build_parser()
    args = parser.parse_args(argv)
    return {"submit": cmd_submit, "status": cmd_status,
            "cancel": cmd_cancel, "serve": cmd_serve,
            "telemetry": cmd_telemetry}[args.verb](args)


if __name__ == "__main__":
    sys.exit(main())
