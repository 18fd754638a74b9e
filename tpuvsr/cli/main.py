"""TLC-flag-compatible command line (SURVEY.md §5 config/flag system).

    python -m tpuvsr SPEC.tla [-config FILE.cfg] [options]

The reference corpus's specs and cfgs run unchanged; flags mirror the
TLC CLI that the reference's README drives (workers/simulation/depth):

  -config FILE     model file (default: SPEC base name + .cfg)
  -workers N|auto  accepted for TLC compatibility (the device engine
                   parallelizes across lanes/devices instead of threads)
  -simulate        simulation mode (random walks) instead of BFS —
                   runs on the sharded walker fleet (tpuvsr/sim) for
                   specs with a device kernel, the interpreter
                   otherwise
  -validate FILE   trace-validation mode (tpuvsr/validate, ISSUE 8):
                   check every recorded implementation trace in FILE
                   (TRACE.jsonl — one JSON object per line, see the
                   README "Trace validation" section) against the
                   spec's next-state relation, partial observations
                   tracked as candidate-state sets (arxiv 2404.16075).
                   Runs batched on the device mesh for specs with a
                   compiled kernel (traces vmapped + shard_mapped, the
                   fleet idiom), through the interpreter otherwise (or
                   under -engine interp/-fpset host).  Reports the
                   first divergence per trace: event index, recorded
                   event, the spec-side enabled action set at that
                   point, and invariant metadata.  Divergence reports
                   are bit-identical across mesh sizes, batch sizes
                   and rescue/resume seams.  Exit 0 all accepted, 12
                   divergences found, 75 preempted (rescue snapshot
                   written to -checkpointdir; rerun with -recover)
  -batch N         -validate: traces checked per round (default 1024;
                   the OOM-degrade ladder halves it)
  -depth N         walk depth in simulation mode (default 100)
  -num N           number of walks (default 10000; TLC runs forever)
  -seed N          simulation RNG seed.  Fleet walks are a pure
                   function of (seed, walk id): a violation replays
                   bit-identically at any -walkers count, any mesh
                   shape, and across a rescue/resume seam
  -walkers N       fleet size (default 1024; 10^5+ is the intended
                   scale — walkers are vmapped and shard_mapped
                   across every visible device)
  -split           importance splitting: fingerprint-novelty
                   kill/clone at chunk boundaries (deep-defect hunts;
                   trades walker-count replay-independence for hit
                   rate)
  -hunt            continuous defect hunt: collect every violation
                   (deduped fleet-wide, each replayed to a TRACE
                   counterexample) instead of stopping at the first
  -engine E        auto | device | interp | sharded (default auto:
                   the jit+vmap device engine for specs with a
                   compiled kernel, the interpreter otherwise;
                   sharded = the multi-chip engine over every visible
                   device — frontier and fingerprint set
                   hash-partitioned over a 1-D mesh)
  -fpset NAME      fingerprint-set implementation, mirroring TLC's
                   pluggable-FPSet class flag: auto (default) | hbm
                   (the HBM-resident device table — forces the device
                   engine) | paged (HBM fingerprints + host-RAM-paged
                   frontier — the spill tier for defect-scale runs,
                   TLC's disk-backed queue analog) | host (the
                   interpreter's in-memory set — forces the
                   interpreter engine)
  -maxstates N     stop BFS after N distinct states
  -deadlock        enable deadlock reporting (note: TLC's flag of the
                   same name *disables* its default-on check; the
                   reference corpus only runs deadlock-off)
  -checkpoint N    write an engine snapshot every N minutes (device
                   BFS; TLC's -checkpoint)
  -checkpointdir P snapshot directory (default: <spec>.ckpt)
  -recover PATH    resume a BFS run from a snapshot (TLC's -recover)
  -commit MODE     fused | per-action (default fused): level-kernel
                   commit mode.  fused runs the occupancy-packed
                   three-stage tile pass (chunk-wide guard matrix ->
                   work-queue compaction -> single-commit tiles: ONE
                   FPSet insert batch + ONE scatter per frontier tile
                   instead of n_actions of each, expansion caps sized
                   by exact enabled counts).  per-action is the
                   historical serial-phase body.  Results are
                   bit-identical either way (README "The level
                   kernel")
  -pipeline K      device/paged/sharded BFS dispatch window: keep up
                   to K level-kernel dispatches in flight, blocking
                   only on the oldest, so host-side work (journal,
                   metrics, spill compaction, checkpoint staging)
                   overlaps device compute (default 2 on every device
                   engine — the sharded step donates its buffers since
                   ISSUE 9, so the old K-generations-in-HBM cost of a
                   sharded window is gone; 1 = the synchronous
                   pre-pipeline behavior).  Counts, level sizes and
                   violation traces are bit-identical for every K
                   (README "Pipelining")
  -symmetry MODE   on | off (default: on when the cfg declares
                   SYMMETRY, off otherwise — TLC's semantics, where
                   declaring Permutations IS enabling the reduction):
                   device-native symmetry reduction (engine/canon.py).
                   With on, every successor is canonicalized to the
                   least element of its symmetry orbit PRE-FINGERPRINT
                   inside the jitted level kernel, so the FPSet and
                   frontier hold ONE entry per orbit (up to |Values|!
                   fewer distinct states); verdicts are identical to
                   off (traces may differ by orbit representative).
                   Snapshots record the canonicalization spec —
                   resuming with a flipped -symmetry or changed group
                   is a policy error.  Liveness checking keeps its
                   existing SYMMETRY-off requirement, and trace
                   validation tracks concrete states (-symmetry on
                   conflicts with PROPERTY cfgs and -validate)
  -spill DIR       paged engine: NVMe/disk spill tier for the host
                   frontier pages (ISSUE 11, CAPACITY.md mitigation
                   2).  Pages beyond the RAM budget flush to
                   append-only level files under DIR and re-read
                   sequentially; the 189 M host-RAM packed-state
                   ceiling becomes a disk-priced 10^9-state one.
                   Implies -fpset paged; conflicts with -engine
                   device/interp/sharded, -fpset host/hbm,
                   -simulate/-validate/-supervise and temporal
                   properties (retain_levels needs resident levels)
  -edges MODE      on | off (default: on for PROPERTY cfgs, meaning-
                   less otherwise): behavior-graph edge stream
                   (ISSUE 15).  With on, the level kernel's fused
                   commit resolves every enabled lane's successor
                   fingerprint to a graph node id on device and
                   appends (src, action, dst) edges to a device
                   buffer drained into an incremental host CSR
                   builder — liveness graph construction becomes a
                   near-free rider on the safety BFS instead of a
                   second full re-expansion pass (the two-pass path,
                   kept under -edges off as the bit-identity oracle).
                   Snapshots carry the stream (gid column + edge rows
                   + retained levels), so a preempted temporal run
                   resumes to a bit-identical CSR and verdict.
                   Conflicts: -simulate/-validate/-symmetry on/
                   -engine interp/-fpset host; -edges on needs a
                   PROPERTY cfg (checked after the cfg loads)
  -pack MODE       on | off (default on): packed bit-planed frontier
                   encoding (engine/pack.py) — the at-rest frontier,
                   host spill pages and the sharded exchange move
                   ceil(total_bits/32) uint32 words per state instead
                   of one word per field, with the per-field bit
                   budgets taken from the speclint widths pass.
                   Results are bit-identical on/off (README "Packed
                   frontier").  Device engines only: explicit -pack on
                   with -engine interp/-fpset host is an error
  -lint            run the speclint static analyzer (tpuvsr/analysis)
                   over the bound spec and exit: 0 clean/warnings,
                   1 errors.  With -json the report is one JSON object.
                   -lint=off disables the engines' fail-fast pre-flight
                   gate (equivalent to TPUVSR_LINT=off).
  -json            emit a one-line JSON result summary (includes a
                   "metrics" object: phase timers, counters, gauges
                   from the obs collector)
  -metrics FILE    dump the full tpuvsr-metrics/1 document (phase
                   timers, counters, gauges, per-level trajectory) to
                   FILE as JSON, and render a final stats table on
                   stderr (schema: tpuvsr/obs/SCHEMA.md)
  -journal FILE    append a JSONL run journal (run_start/level_done/
                   checkpoint/spill/grow/violation/run_end plus the
                   resilience events fault/retry/degrade/
                   rescue_checkpoint) to FILE; a -recover resume
                   pointed at the same FILE continues the same journal
                   with cumulative elapsed
  -supervise       run the BFS under the resilience supervisor
                   (tpuvsr/resilience): RESOURCE_EXHAUSTED degrades
                   (tile halving, then hbm -> paged fallback) with
                   bounded exponential-backoff retries resuming from
                   the latest snapshot, and SIGTERM/SIGINT checkpoint
                   at the next level boundary and exit with the
                   resumable code 75 (rerun with -recover, or drive
                   the loop with scripts/supervise.py).  With
                   -engine sharded the ladder is mesh-aware: per-shard
                   tile halving, then mesh shrink to the largest
                   usable power-of-two device count (the resume
                   re-hash-partitions the snapshot onto the smaller
                   mesh), then single-device paged fallback.
                   Device/paged/sharded BFS only; implies
                   level-boundary checkpointing to -checkpointdir
                   when -checkpoint is not given.
  -inject SPEC     arm the deterministic fault-injection plan
                   (tpuvsr/resilience/faults.py grammar, e.g.
                   "oom@level=3,corrupt-ckpt:frontier.npz",
                   "oom@shard=0", "exchange-drop:3@shard=0"); the
                   TPUVSR_FAULT env var arms the same plan

Environment: TPUVSR_PROFILE=DIR wraps the engine fixpoint loop in
jax.profiler.trace(DIR) with per-level/per-phase TraceAnnotation
spans (view with TensorBoard / Perfetto).  TPUVSR_FAULT=SPEC arms
fault injection (same grammar as -inject).

Mutually exclusive flags (argparse errors, exit code 2, before any
spec is loaded): -fpset host with -engine device; -fpset hbm/paged
with -engine interp; -supervise with -simulate/-engine interp/
-fpset host; -engine sharded with -simulate or any non-auto -fpset
(its fingerprint set is always the mesh-sharded HBM table);
-walkers/-split/-hunt without -simulate, or with
-engine interp/-fpset host (the fleet is a device backend);
explicit -pack on with -engine interp/-fpset host (the packed
frontier is a device-engine format; the interpreter has no dense
frontier to pack); explicit -commit with -engine interp/
-fpset host/-simulate/-validate
(it configures the BFS level kernel); explicit -symmetry with
-engine interp/-fpset host (the interpreter always applies the
declared SYMMETRY itself) and -symmetry on with -validate (trace
validation tracks concrete states) or a PROPERTY cfg (liveness keeps
SYMMETRY off — checked after the cfg loads); -spill with
-engine device/interp/sharded, -fpset host/hbm,
-simulate/-validate/-supervise (the spill tier is the paged engine's
host-page store); -bounds on with -lint=off (tightened facts from an
unverified spec cannot be trusted), with -engine interp/-fpset host,
or with -simulate/-validate (the fleet and the validator consume no
bounds facts — a forced flag must not be silently inert);
-por on with -lint=off/-engine interp/-fpset host/-simulate/
-validate/-edges on/-commit per-action, or a PROPERTY cfg (the
ample-set reduction preserves invariant/deadlock verdicts, not the
behavior graph — the cfg conflict is checked after it loads);
-validate with -simulate/-hunt/-supervise/-deadlock/
-maxstates/-checkpoint/-engine sharded/-fpset hbm|paged (validation
is its own engine mode: rescue checkpoints are preemption-driven, the
batch validator owns its mesh, and traces have no deadlock notion);
-batch without -validate.

Exit codes (the unified contract in tpuvsr/exitcodes.py): 0 ok;
1 speclint errors (-lint); 2 bad flags; 12 safety/temporal violation
(TLC's code); 75 preempted-but-resumable (a -supervise run caught
SIGTERM/SIGINT and wrote a rescue snapshot — rerun with -recover to
continue).  The dispatch service maps these to job terminal states
from the same table.

Service verbs (ISSUE 6 + the ISSUE 14 serving tier; tpuvsr/service +
tpuvsr/serve — README "Service"):

    python -m tpuvsr submit SPEC.tla [-config F] [--engine E]
                     [--priority N] [--devices N] [--tenant T] ...
    python -m tpuvsr serve  [--spool DIR] [--drain] [--workers N]
                     [--http PORT] [--tenant-weight T=W]
                     [--tls-cert PEM] [--rate N] [--high-water N]
                     [--breaker-threshold K]
                     [--spool-driver fs|objstore|quorum] ...
    python -m tpuvsr status [JOB] [--spool DIR] [--json] [--tail N]
    python -m tpuvsr cancel JOB [--spool DIR]

turn the checker into a long-running verification dispatcher: a
durable job queue with speclint admission, a mesh scheduler with
elastic shrink/grow of live sharded runs, and per-job journals +
metrics docs as the query surface.  The front door is hardened
(ISSUE 18, tpuvsr/serve/guard.py — README "Hardening the front
door"): bearer-token auth off a spool-local tokens.json, optional
TLS, per-tenant token-bucket rate limits (429 + Retry-After),
queue-depth backpressure (503), and a per-(tenant, spec) circuit
breaker that fail-fasts crash-looping submissions before they touch
a device.  The control plane itself is durable across machines
(ISSUE 20, tpuvsr/service/spooldrv.py — README "Multi-host data
plane"): pluggable spool drivers (fs / objstore / quorum) with
claim-epoch fencing, a quorum-replicated control log that survives
a lost replica, and host-lease failover that sweeps a dead host's
claims in one pass.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

from ..exitcodes import EX_LINT, EX_OK, EX_VIOLATION


def build_parser():
    p = argparse.ArgumentParser(
        prog="tpuvsr", add_help=True, prefix_chars="-",
        description="TPU-native TLA+ model checker for the VSR corpus")
    p.add_argument("spec", help="path to the .tla module")
    p.add_argument("-config", help=".cfg model file")
    p.add_argument("-workers", default="auto")
    p.add_argument("-simulate", action="store_true")
    p.add_argument("-validate", default=None, metavar="TRACES.jsonl",
                   help="validate recorded implementation traces "
                        "(one JSON object per line) against the spec "
                        "instead of checking/simulating: per step the "
                        "next-state relation is constrained to "
                        "transitions consistent with the recorded "
                        "event; partial observations are tracked as "
                        "candidate-state sets (tpuvsr/validate).  "
                        "Exit 0 accepted / 12 diverged / 75 preempted")
    p.add_argument("-batch", type=int, default=None, metavar="N",
                   help="-validate: traces per round (default 1024)")
    p.add_argument("-depth", type=int, default=100)
    p.add_argument("-num", type=int, default=10000)
    p.add_argument("-seed", type=int, default=0)
    p.add_argument("-engine",
                   choices=["auto", "device", "interp", "sharded"],
                   default="auto")
    p.add_argument("-fpset", choices=["auto", "hbm", "paged", "host"],
                   default="auto")
    p.add_argument("-walkers", type=int, default=None, metavar="N",
                   help="simulation: walker-fleet size (default 1024; "
                        "the fleet replays any violation identically "
                        "for a fixed -seed at ANY walker count/mesh "
                        "shape — tpuvsr/sim)")
    p.add_argument("-split", action="store_true",
                   help="simulation: importance splitting — walkers "
                        "carry a fingerprint-novelty score; low-"
                        "novelty walkers are killed and respawned as "
                        "clones of high-novelty ones at chunk "
                        "boundaries (deep-defect hunts)")
    p.add_argument("-hunt", action="store_true",
                   help="simulation: continuous defect hunt — collect "
                        "EVERY violation (deduped fleet-wide, each "
                        "replayed to a TRACE counterexample) instead "
                        "of stopping at the first; bounded by "
                        "-num/-maxseconds")
    p.add_argument("-maxstates", type=int, default=None)
    p.add_argument("-deadlock", action="store_true")
    p.add_argument("-checkpoint", type=float, default=None,
                   metavar="MINUTES")
    p.add_argument("-checkpointdir", default=None)
    p.add_argument("-recover", default=None, metavar="PATH")
    p.add_argument("-json", action="store_true")
    p.add_argument("-maxseconds", type=float, default=None)
    p.add_argument("-commit", choices=["fused", "per-action"],
                   default=None, metavar="MODE",
                   help="level-kernel commit mode (default fused): "
                        "'fused' runs the occupancy-packed three-stage "
                        "tile pass — chunk-wide guard matrix, "
                        "work-queue compaction, ONE FPSet insert batch "
                        "+ ONE scatter per tile; 'per-action' runs the "
                        "historical n_actions serial phases.  Results "
                        "are bit-identical either way")
    p.add_argument("-pipeline", type=int, default=None, metavar="K",
                   help="device/paged/sharded BFS dispatch window: "
                        "keep K level-kernel dispatches in flight, "
                        "blocking only on the oldest (default 2 on "
                        "every device engine — the sharded step "
                        "donates its buffers; 1 = synchronous).  "
                        "Results are bit-identical for every K")
    p.add_argument("-symmetry", choices=["on", "off"], default=None,
                   metavar="MODE",
                   help="device-native symmetry reduction (default: "
                        "on iff the cfg declares SYMMETRY): states "
                        "are canonicalized to orbit representatives "
                        "pre-fingerprint inside the level kernel, so "
                        "the FPSet/frontier hold one entry per orbit "
                        "(engine/canon.py).  Verdicts are identical "
                        "on/off; traces may differ by orbit "
                        "representative")
    p.add_argument("-spill", default=None, metavar="DIR",
                   help="paged engine: disk spill tier for host "
                        "frontier pages — pages beyond the RAM "
                        "budget flush to append-only level files "
                        "under DIR (implies -fpset paged)")
    p.add_argument("-edges", choices=["on", "off"], default=None,
                   metavar="MODE",
                   help="behavior-graph edge stream for temporal "
                        "properties (default: on for PROPERTY cfgs): "
                        "the level kernel emits (src, action, dst) "
                        "edges during the safety BFS itself — "
                        "liveness graph construction becomes a "
                        "near-free rider on the run instead of a "
                        "second full re-expansion pass.  -edges off "
                        "falls back to the two-pass path (the "
                        "bit-identity oracle).  -edges on requires a "
                        "PROPERTY cfg and conflicts with -simulate/"
                        "-validate/-symmetry on/-engine interp/"
                        "-fpset host")
    p.add_argument("-pack", choices=["on", "off"], default=None,
                   metavar="MODE",
                   help="packed bit-planed frontier encoding "
                        "(default on for the device engines): the "
                        "at-rest frontier / spill pages / sharded "
                        "exchange move packed uint32 word planes "
                        "sized by the speclint widths pass.  Results "
                        "are bit-identical on/off")
    p.add_argument("-lower", action="store_true",
                   help="compile the device kernel's guards/actions/"
                        "invariants from the spec AST (tpuvsr/lower) "
                        "instead of the hand-written kernel; falls "
                        "back to the hand kernel for modules beyond "
                        "the lowerer's surface")
    p.add_argument("-bounds", choices=["on", "off"], default=None,
                   metavar="MODE",
                   help="speclint bounds pre-pass consumption (default "
                        "on while the lint gate is live): the symbolic "
                        "interval analysis (pass 6) tightens the "
                        "packed-frontier bit budgets to REACHABLE "
                        "ranges, prunes statically dead actions from "
                        "the kernel lane tables, and seeds the fused "
                        "commit's expansion caps from static fanout "
                        "bounds.  off runs declared-widths packing and "
                        "full action lists.  Results are bit-identical "
                        "on/off; snapshots record the facts digest "
                        "(resuming under a flipped -bounds is a policy "
                        "error)")
    p.add_argument("-por", choices=["on", "off"], default=None,
                   metavar="MODE",
                   help="ample-set partial-order reduction in the "
                        "fused commit (default on while the lint gate "
                        "is live and no blocker applies): the speclint "
                        "independence pass (pass 7) proves pairwise "
                        "action commutativity; at states where one "
                        "independent invisible action suffices, the "
                        "level kernel expands only that action.  "
                        "Invariant and deadlock verdicts are "
                        "bit-identical on/off; state/transition COUNTS "
                        "may shrink.  Refused (forced on errors; auto "
                        "stays off) under temporal properties, "
                        "-edges on, -commit per-action, -simulate/"
                        "-validate, or -lint=off.  Snapshots record "
                        "the facts digest (resuming under a flipped "
                        "-por is a policy error)")
    p.add_argument("-lint", nargs="?", const="full", default=None,
                   choices=["full", "off"], metavar="MODE",
                   help="run the speclint static analyzer and exit "
                        "(plain -lint), or -lint=off to disable the "
                        "engine pre-flight gate")
    p.add_argument("-metrics", default=None, metavar="FILE",
                   help="dump the tpuvsr-metrics/1 JSON document "
                        "(phase timers, counters, per-level rows) to "
                        "FILE and print a stats table on stderr")
    p.add_argument("-journal", default=None, metavar="FILE",
                   help="append the JSONL run journal to FILE "
                        "(continues across -recover)")
    p.add_argument("-supervise", action="store_true",
                   help="run the BFS under the resilience supervisor: "
                        "OOM degrades (tile halving -> paged fallback) "
                        "with backoff retries; SIGTERM/SIGINT "
                        "checkpoints at the next level boundary and "
                        "exits with the resumable code 75")
    p.add_argument("-inject", default=None, metavar="SPEC",
                   help="arm deterministic fault injection (grammar: "
                        "oom@level=N, oom@shard=S, kill@level=N, "
                        "corrupt-ckpt:FILE[@level=N], "
                        "exchange-drop[:K]@shard=S; comma-separated; "
                        ":K = K consecutive drops)")
    return p


def validate_args(parser, args):
    """Flag-conflict validation at parse time: documented mutual
    exclusions fail with argparse's usage error (exit code 2) instead
    of a late engine failure."""
    if args.pipeline is not None and args.pipeline < 1:
        parser.error(f"-pipeline must be >= 1 (got {args.pipeline})")
    if args.commit is not None:
        if args.engine == "interp" or args.fpset == "host":
            parser.error("-commit configures the device level kernel; "
                         "it cannot be combined with -engine interp/"
                         "-fpset host")
        if args.simulate or args.validate is not None:
            parser.error("-commit configures the BFS level kernel; it "
                         "cannot be combined with -simulate/-validate "
                         "(the fleet and the validator have their own "
                         "dispatch packing)")
    if args.fpset == "host" and args.engine == "device":
        parser.error("-fpset host requires -engine interp (the host "
                     "fingerprint set only exists in the interpreter)")
    if args.fpset in ("hbm", "paged") and args.engine == "interp":
        parser.error(f"-fpset {args.fpset} requires the device engine")
    if args.engine == "sharded":
        if args.simulate:
            parser.error("-engine sharded checks by BFS; simulation "
                         "runs on the device/interp engines")
        if args.fpset != "auto":
            parser.error(f"-engine sharded always uses the "
                         f"mesh-sharded HBM fingerprint set; it "
                         f"cannot be combined with -fpset "
                         f"{args.fpset}")
    if args.supervise and args.simulate:
        parser.error("-supervise supervises BFS runs, not simulation")
    for flag, given in (("-walkers", args.walkers is not None),
                        ("-split", args.split),
                        ("-hunt", args.hunt)):
        if given and not args.simulate:
            parser.error(f"{flag} needs -simulate (it configures the "
                         f"walker fleet)")
        if given and (args.engine == "interp"
                      or args.fpset == "host"):
            parser.error(f"{flag} needs the device fleet backend; it "
                         f"cannot be combined with -engine interp/"
                         f"-fpset host")
    if args.walkers is not None and args.walkers < 1:
        parser.error(f"-walkers must be >= 1 (got {args.walkers})")
    if args.hunt and args.deadlock:
        parser.error("-hunt collects invariant violations only (it "
                     "has no deadlock counterexample path); use plain "
                     "-simulate -deadlock")
    if args.supervise and (args.engine == "interp"
                           or args.fpset == "host"):
        parser.error("-supervise needs the device/paged/sharded "
                     "engine (the interpreter has no "
                     "checkpoint/degrade ladder)")
    if args.symmetry is not None and (args.engine == "interp"
                                      or args.fpset == "host"):
        parser.error("-symmetry configures the device "
                     "canonicalization kernel; the interpreter "
                     "always applies the declared SYMMETRY itself "
                     "(drop the flag or the -engine interp/-fpset "
                     "host selection)")
    if args.symmetry == "on" and args.validate is not None:
        parser.error("-symmetry on cannot be combined with -validate: "
                     "trace validation tracks CONCRETE states (an "
                     "observation may pin any variable to a specific "
                     "value), so orbit-equivalent candidates are not "
                     "interchangeable")
    if args.spill is not None:
        if args.engine in ("device", "interp", "sharded"):
            parser.error(f"-spill is the paged engine's host-page "
                         f"disk tier; it cannot be combined with "
                         f"-engine {args.engine} (device is HBM-only, "
                         f"sharded shards over HBM, the interpreter "
                         f"has no paged frontier)")
        if args.fpset in ("host", "hbm"):
            parser.error(f"-spill needs -fpset paged (or auto); "
                         f"-fpset {args.fpset} selects an engine "
                         f"without host frontier pages")
        if args.simulate or args.validate is not None:
            parser.error("-spill tiers the BFS frontier; it cannot "
                         "be combined with -simulate/-validate")
        if args.supervise:
            parser.error("-spill cannot be combined with -supervise "
                         "(the supervisor's degrade ladder manages "
                         "its own hbm -> paged fallback; run -fpset "
                         "paged -spill directly)")
    if args.edges == "on":
        if args.simulate or args.validate is not None:
            parser.error("-edges on streams the BFS behavior graph; "
                         "it cannot be combined with -simulate/"
                         "-validate (neither builds one)")
        if args.symmetry == "on":
            parser.error("-edges on cannot be combined with "
                         "-symmetry on: the behavior graph's nodes "
                         "are concrete states (liveness keeps its "
                         "SYMMETRY-off requirement)")
        if args.engine == "interp" or args.fpset == "host":
            parser.error("-edges on needs the paged device engine "
                         "(the edge stream rides the level kernel); "
                         "it cannot be combined with -engine interp/"
                         "-fpset host — the interpreter builds its "
                         "own graph")
    if args.pack == "on" and (args.engine == "interp"
                              or args.fpset == "host"):
        parser.error("-pack on needs a device engine (the packed "
                     "frontier is the device engines' interchange "
                     "format; the interpreter has no dense frontier "
                     "to pack)")
    if args.bounds == "on":
        if args.lint == "off":
            parser.error("-bounds on cannot be combined with "
                         "-lint=off: the tightened packing and pruned "
                         "action lists consume the speclint bounds "
                         "pass — an unverified spec's bounds cannot "
                         "be trusted (drop -lint=off or run "
                         "-bounds off)")
        if args.engine == "interp" or args.fpset == "host":
            parser.error("-bounds on configures the device engines' "
                         "static pre-pass consumption (tightened "
                         "packing, pruned lane tables); it cannot be "
                         "combined with -engine interp/-fpset host")
        if args.simulate or args.validate is not None:
            parser.error("-bounds on configures the BFS engines; the "
                         "fleet and the validator consume no bounds "
                         "facts (a forced flag must not be silently "
                         "inert) — drop -bounds on or run BFS mode")
    if args.por == "on":
        # ample-set POR (ISSUE 16): verdict-sound only inside the
        # fused BFS commit with the speclint gate live — every other
        # mode must refuse a forced flag rather than run it inert
        if args.lint == "off":
            parser.error("-por on cannot be combined with -lint=off: "
                         "the ample-set filter consumes the speclint "
                         "independence pass — commutativity facts "
                         "from an unverified spec cannot be trusted "
                         "(drop -lint=off or run -por off)")
        if args.engine == "interp" or args.fpset == "host":
            parser.error("-por on configures the device engines' "
                         "fused commit (the ample-set filter lives in "
                         "the level kernel); it cannot be combined "
                         "with -engine interp/-fpset host")
        if args.simulate or args.validate is not None:
            parser.error("-por on configures the BFS engines; the "
                         "fleet and the validator consume no "
                         "independence facts (a forced flag must not "
                         "be silently inert) — drop -por on or run "
                         "BFS mode")
        if args.edges == "on":
            parser.error("-por on cannot be combined with -edges on: "
                         "the reduced run omits transitions by "
                         "design, so the streamed behavior graph "
                         "would be incomplete (and the two share the "
                         "FPSet gids column)")
        if args.commit == "per-action":
            parser.error("-por on needs the fused commit (the "
                         "ample-set filter is a stage of the fused "
                         "level kernel); it cannot be combined with "
                         "-commit per-action")
    if args.validate is not None:
        # trace validation is its own engine mode (ISSUE 8): the
        # check/simulate mode switches and their engine shapes don't
        # compose with it — say so at parse time, not mid-run
        if args.simulate:
            parser.error("-validate checks recorded traces; it cannot "
                         "be combined with -simulate (the two are "
                         "different engine modes)")
        if args.hunt or args.split or args.walkers is not None:
            parser.error("-walkers/-split/-hunt configure the "
                         "simulation fleet; they cannot be combined "
                         "with -validate")
        if args.supervise:
            parser.error("-validate runs its own rescue/resume and "
                         "OOM batch-halving ladder; it cannot be "
                         "combined with -supervise (use the dispatch "
                         "service for requeue loops)")
        if args.deadlock:
            parser.error("-validate has no deadlock notion (a trace "
                         "ending early is simply shorter); it cannot "
                         "be combined with -deadlock")
        if args.maxstates is not None:
            parser.error("-maxstates bounds BFS; -validate is bounded "
                         "by the trace file and -maxseconds")
        if args.checkpoint is not None:
            parser.error("-validate checkpoints are preemption-driven "
                         "rescues (SIGTERM -> snapshot -> exit 75), "
                         "not periodic; -checkpoint cannot be "
                         "combined with it (-checkpointdir sets the "
                         "rescue directory, -recover resumes)")
        if args.engine == "sharded":
            parser.error("-validate shards its trace batch over the "
                         "mesh itself; it cannot be combined with "
                         "-engine sharded (the BFS mesh engine)")
        if args.fpset in ("hbm", "paged"):
            parser.error(f"-fpset {args.fpset} configures the BFS "
                         f"fingerprint set; -validate keeps its "
                         f"candidate sets per trace (use -fpset host/"
                         f"-engine interp for the interpreter "
                         f"validator)")
    if args.batch is not None:
        if args.validate is None:
            parser.error("-batch sizes the -validate round; it needs "
                         "-validate")
        if args.batch < 1:
            parser.error(f"-batch must be >= 1 (got {args.batch})")
    if args.inject:
        from ..resilience.faults import FaultPlan
        try:
            FaultPlan.parse(args.inject)
        except ValueError as e:
            parser.error(f"-inject: {e}")


def _pick_engine(requested, fpset, spec):
    # -fpset mirrors TLC's pluggable FPSet class selection: the HBM
    # table only exists in the device engine, the host set only in the
    # interpreter (BASELINE.json north_star gating).  Conflicting
    # fpset/engine combinations are rejected at parse time by
    # validate_args (exit code 2), so only consistent ones reach here.
    if fpset == "hbm":
        return "device"
    if fpset == "paged":
        return "paged"
    if fpset == "host":
        return "interp"
    if requested != "auto":
        return requested
    # modules with a compiled device kernel (models/registry.py) run on
    # the device engine; everything else on the interpreter
    from ..models.registry import has_device_model
    return "device" if has_device_model(spec) else "interp"


def _format_divergence(rec):
    """Render one divergence record the way violation traces render:
    the recorded event that no spec transition matches, plus the
    spec-side enabled set at that point."""
    lines = [f"Error: trace {rec['trace']} diverges at event "
             f"{rec['step']}."]
    ev = rec.get("event") or {}
    if ev.get("action"):
        lines.append(f"  recorded action: {ev['action']}")
    if ev.get("vars"):
        lines.append("  recorded observation: "
                     + ", ".join(f"{k} = {v}"
                                 for k, v in sorted(ev["vars"].items())))
    if rec.get("reason") == "no-init-state":
        lines.append("  no spec init state matches the trace's init "
                     "observation")
    lines.append(f"  candidate states at the divergence: "
                 f"{rec.get('candidates', 0)}")
    enabled = rec.get("enabled") or []
    if enabled:
        lines.append("  spec-side enabled actions there:")
        for e in enabled:
            loc = f"  ({e['location']})" if e.get("location") else ""
            par = (f"[{e['param']}]" if e.get("param") is not None
                   else "")
            lines.append(f"    {e['action']}{par}{loc}")
    else:
        lines.append("  no spec action is enabled there (the spec "
                     "deadlocks where the implementation continued)")
    if rec.get("invariant"):
        lines.append(f"  note: every candidate state violated "
                     f"invariant {rec['invariant']} from event "
                     f"{rec['invariant_step']} on")
    return "\n".join(lines)


def _run_validate(args, spec, engine, obs, log, summary_metrics):
    """The -validate execution branch (ISSUE 8): load TRACE.jsonl,
    run the batched device validator (interpreter fallback), report
    the first divergence, and map the ending onto the unified
    exit-code table (0 accepted / 12 diverged / 75 preempted)."""
    from ..core.values import TLAError
    from ..exitcodes import EX_RESUMABLE
    from ..validate import host_validate_batch, load_traces
    try:
        traces = load_traces(args.validate, spec)
    except (OSError, TLAError) as e:
        print(f"[tpuvsr] -validate: {e}", file=sys.stderr)
        return 2
    log(f"validating {len(traces)} trace(s) from {args.validate}")
    if engine == "interp":
        if args.recover:
            log(f"-recover {args.recover} ignored: the interpreter "
                f"validator keeps no rescue snapshots (it restarts "
                f"from trace 0)")
        res = host_validate_batch(spec, traces, obs=obs, log=log,
                                  max_seconds=args.maxseconds)
    else:
        from ..resilience.supervisor import (Preempted,
                                             PreemptionGuard)
        from ..validate import ObservationUnsupported
        from ..validate.batch import BatchValidator
        ckpt_dir = args.checkpointdir or (
            os.path.splitext(args.spec)[0] + ".ckpt")
        try:
            # encodability is pre-flighted BEFORE the journal-backed
            # observer is handed over, so a fallback run still writes
            # the user's -journal/-metrics through the same observer
            bv = BatchValidator(spec, batch=args.batch or 1024,
                                pipeline=args.pipeline, log=log)
            bv.check_observations(traces)
        except ObservationUnsupported as e:
            # the codec cannot express some observation as encoded-
            # leaf comparisons — the interpreter validator is fully
            # general, so fall back instead of failing the run
            log(f"{e}; falling back to the interpreter validator")
            if args.recover:
                log(f"-recover {args.recover} ignored: the "
                    f"interpreter validator keeps no rescue "
                    f"snapshots (it restarts from trace 0)")
            res = host_validate_batch(spec, traces, obs=obs, log=log,
                                      max_seconds=args.maxseconds)
            bv = None
        try:
            if bv is not None:
                with PreemptionGuard(log=log):
                    res = bv.run(traces, checkpoint_path=ckpt_dir,
                                 resume_from=args.recover, obs=obs,
                                 log=log, max_seconds=args.maxseconds)
        except Preempted as p:
            log(f"{p}; rerun with -recover {p.path} to continue "
                f"(exit {EX_RESUMABLE})")
            return EX_RESUMABLE
    summary = {"mode": "validate", "ok": res.ok,
               "traces": res.traces_checked,
               "accepted": res.accepted,
               "divergences": len(res.divergences or []),
               "first_divergence": res.first_divergence,
               "traces_per_sec": round(res.traces_per_sec, 1),
               "error": res.error,
               "elapsed_s": round(res.elapsed, 3),
               "metrics": summary_metrics(res.metrics)}
    if res.divergences:
        print(_format_divergence(res.divergences[0]),
              file=sys.stderr)
    if args.json:
        print(json.dumps(summary))
    else:
        for k, v in summary.items():
            if k != "first_divergence":
                print(f"{k}: {v}")
    return EX_OK if res.ok else EX_VIOLATION


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    # dispatch-service verbs (ISSUE 6): `python -m tpuvsr serve|submit|
    # status|cancel ...` routes to tpuvsr/service/api.py before the
    # TLC-compatible parser ever sees the argv (a positional spec named
    # "serve" is implausible; use ./serve to check a file of that name)
    if argv and argv[0] in ("serve", "submit", "status", "cancel",
                            "telemetry"):
        from ..service.api import main as service_main
        return service_main(argv)
    parser = build_parser()
    args = parser.parse_args(argv)
    validate_args(parser, args)
    if args.lower:
        os.environ["TPUVSR_COMPILED"] = "1"
    if args.lint == "off":
        os.environ["TPUVSR_LINT"] = "off"
    if args.inject:
        from ..resilience import faults
        faults.install(args.inject)
    from ..engine.spec import load_spec
    from ..engine.trace import format_trace

    cfg_path = args.config or os.path.splitext(args.spec)[0] + ".cfg"
    spec = load_spec(args.spec, cfg_path)

    if getattr(spec, "native", False):
        # a kernel-native spec (models/native.py) has no AST: whatever
        # needs one is a loud exit 2, never a silently inert flag
        for flag, on in (("-lint", args.lint == "full"),
                         ("-bounds on", args.bounds == "on"),
                         ("-por on", args.por == "on"),
                         ("-lower", args.lower),
                         ("-engine interp / -fpset host",
                          args.engine == "interp"
                          or args.fpset == "host")):
            if on:
                parser.error(
                    f"{flag} needs the module's AST; the native spec "
                    f"{spec.module.name!r} is built from its device "
                    f"kernel alone — pass the .tla file instead of "
                    f"the module name")

    if args.lint == "full":
        # lint-only mode: full report (all five passes), no dispatch
        from ..analysis import run_lint
        report = run_lint(spec)
        print(report.to_json() if args.json else report.render())
        return report.exit_code

    # spec-dependent -symmetry/-spill conflicts (exit 2, like the
    # parse-time ones — the cfg had to load first)
    if args.symmetry == "on" and spec.temporal_props:
        parser.error("-symmetry on cannot be combined with temporal "
                     "properties: liveness checking requires SYMMETRY "
                     "off (the reference cfg comments insist, and the "
                     "behavior graph must distinguish orbit members)")
    if args.symmetry == "on" and not spec.symmetry_perms:
        parser.error("-symmetry on: the cfg declares no SYMMETRY — "
                     "there is no permutation group to reduce by")
    if args.spill is not None and spec.temporal_props:
        parser.error("-spill cannot be combined with temporal "
                     "properties (the liveness graph enumeration "
                     "needs whole levels resident)")
    if args.edges == "on" and not spec.temporal_props:
        parser.error("-edges on: the cfg declares no PROPERTY — "
                     "there is no temporal check to consume the "
                     "behavior-graph stream")
    if args.por == "on" and spec.temporal_props:
        parser.error("-por on cannot be combined with temporal "
                     "properties: the reduced run preserves "
                     "invariant/deadlock verdicts, not the full "
                     "behavior graph the liveness checker consumes")

    engine = _pick_engine(args.engine, args.fpset, spec)
    if args.spill is not None:
        if engine == "interp":
            # auto-resolution landed on the interpreter (no compiled
            # kernel): dropping the disk-tier request silently would
            # betray the flag — same loud contract as the explicit
            # -engine interp conflict
            parser.error("-spill needs the paged device engine; this "
                         "spec resolved to the interpreter (no "
                         "compiled device kernel)")
        engine = "paged"            # -spill implies the paged engine
    if args.por == "on" and engine == "interp":
        # same loud contract as -spill: auto-resolution landing on
        # the interpreter must not leave a forced -por silently inert
        parser.error("-por on needs a compiled device kernel (the "
                     "ample-set filter is a stage of the fused level "
                     "kernel); this spec resolved to the interpreter")
    if args.pipeline is None:
        # default 2 on every device engine (ISSUE 9: the sharded step
        # now donates its buffers, so the K-generations-in-HBM cost
        # that made its window opt-in is gone)
        args.pipeline = 2
    # packed frontier (ISSUE 9): default on for device engines ("auto"
    # packs whenever the codec declares plane_bounds — every
    # registered layout); -pack off runs the dense format
    pack_kw = False if args.pack == "off" else "auto"
    # level-kernel commit mode (ISSUE 10): fused is the default
    commit_kw = args.commit or "fused"
    # symmetry canonicalization (ISSUE 11): on iff declared, unless
    # the flag forces it
    symmetry_kw = {"on": True, "off": False}.get(args.symmetry, "auto")
    # bounds pre-pass consumption (ISSUE 13): "auto" = on iff the
    # speclint gate is live (engine/bounds.resolve_bounds)
    bounds_kw = {"on": True, "off": False}.get(args.bounds, "auto")
    # ample-set POR (ISSUE 16): "auto" = on iff the speclint gate is
    # live and no soundness blocker applies (engine/por.resolve_por);
    # forced-on conflicts were rejected above, so resolve_por's own
    # TLAError only fires for spec-level refusals (poisoned facts)
    por_kw = args.por or "auto"
    spill_kw = ({"spill_dir": args.spill} if args.spill is not None
                else {})

    def log(msg):
        print(f"[tpuvsr] {msg}", file=sys.stderr)

    if args.supervise and engine == "interp":
        log("-supervise needs the device/paged engine; this spec "
            "resolved to the interpreter — running unsupervised")
        args.supervise = False
    if args.simulate and engine == "interp" and (
            args.walkers is not None or args.split or args.hunt):
        log("-walkers/-split/-hunt need a compiled device kernel "
            "(the walker fleet); this spec resolved to the "
            "interpreter — running plain host simulation")

    device = None
    if engine in ("device", "paged", "sharded"):
        if engine == "sharded":
            # multi-host env (TPUVSR_MH_*): jax.distributed must
            # initialize before the backend is touched, for BOTH the
            # supervised and plain sharded paths (a supervised pack
            # that skips this sees only local devices and its
            # rank-agreement degenerates to single-process)
            from ..parallel.multihost import init_from_env
            init_from_env()
        from ..models.registry import device_doc
        device = device_doc()
        log("backend: {platform} ({device_kind} x {device_count})"
            .format(**device))
    mode = ("trace validation" if args.validate
            else "simulation" if args.simulate else "BFS")
    log(f"spec {spec.module.name}, engine {engine}, {mode}")

    # speclint pre-flight: same gate the engines run, surfaced here as
    # a clean exit instead of a traceback (the engines' own call then
    # hits the per-spec cache).  -lint=off / TPUVSR_LINT=off bypasses.
    from ..analysis import LintError, preflight
    try:
        preflight(spec, log=log)
    except LintError as e:
        print(f"[tpuvsr] {e}", file=sys.stderr)
        return EX_LINT

    # observability: one RunObserver rides the whole engine run —
    # journal (JSONL event stream), metrics collector, profiler hooks.
    # Supervised runs get per-attempt observers from the supervisor
    # instead (same journal file, fresh run_id per attempt).
    from ..obs import RunObserver
    obs = None if args.supervise else RunObserver(
        journal_path=args.journal, metrics_path=args.metrics, log=log)

    def summary_metrics(m):
        """The -json merge: collector output minus the per-level rows
        (those live in the -metrics file; the one-line summary stays
        one line)."""
        if not m:
            return None
        return {k: m[k] for k in ("run_id", "phases", "counters",
                                  "gauges") if k in m}

    if args.validate:
        # trace-validation mode (ISSUE 8): its own engine, its own
        # exit-code handling — the branch returns directly
        return _run_validate(args, spec, engine, obs, log,
                             summary_metrics)

    if args.simulate:
        if engine in ("device", "paged"):
            # the walker fleet (tpuvsr/sim) is the simulation backend
            # (it supersedes engine/device_sim's scan loop): sharded
            # across every visible device, deterministic per
            # (seed, walk id) at any walker count/mesh shape
            from ..sim import fleet_simulate, run_hunt
            walkers = args.walkers or 1024
            split = True if args.split else None
            if args.hunt:
                res = run_hunt(spec, walkers=walkers,
                               depth=args.depth, seed=args.seed,
                               num=args.num, split=split,
                               pipeline=args.pipeline,
                               max_seconds=args.maxseconds,
                               symmetry=symmetry_kw,
                               obs=obs, log=log)
            else:
                res = fleet_simulate(
                    spec, num=args.num, depth=args.depth,
                    seed=args.seed, walkers=walkers, split=split,
                    pipeline=args.pipeline,
                    check_deadlock=args.deadlock, log=log,
                    max_seconds=args.maxseconds, obs=obs,
                    symmetry=symmetry_kw)
        else:
            from ..engine.simulate import simulate
            res = simulate(spec, num=args.num, depth=args.depth,
                           seed=args.seed, check_deadlock=args.deadlock,
                           log=log, time_budget=args.maxseconds,
                           obs=obs)
        summary = {"mode": "simulate", "ok": res.ok,
                   "walks": res.walks, "steps": res.steps,
                   "violated": res.violated_invariant,
                   "elapsed_s": round(res.elapsed, 3),
                   "metrics": summary_metrics(res.metrics)}
        if getattr(res, "walkers", 0):
            summary["walkers"] = res.walkers
        if getattr(res, "violations", None) is not None:
            summary["unique_violations"] = len(res.violations)
    else:
        if engine in ("device", "paged", "sharded"):
            from ..engine.device_bfs import DeviceBFS
            from ..engine.paged_bfs import PagedBFS
            ckpt_dir = args.checkpointdir or (
                os.path.splitext(args.spec)[0] + ".ckpt")
            if args.supervise:
                # resilience supervisor: OOM retry/degrade ladder +
                # SIGTERM/SIGINT -> rescue checkpoint + resumable exit
                from ..resilience.supervisor import (EXIT_RESUMABLE,
                                                     Preempted,
                                                     Supervisor)
                sup = Supervisor(
                    spec, engine=engine,
                    checkpoint_path=ckpt_dir,
                    # no explicit -checkpoint: snapshot every level
                    # boundary so a degrade/rescue never loses more
                    # than the in-flight level
                    checkpoint_every=(args.checkpoint * 60.0
                                      if args.checkpoint else None),
                    journal_path=args.journal,
                    metrics_path=args.metrics, log=log,
                    engine_kwargs={"pipeline": args.pipeline,
                                   "pack": pack_kw,
                                   "commit": commit_kw,
                                   "symmetry": symmetry_kw,
                                   "bounds": bounds_kw,
                                   "por": por_kw})
                try:
                    res = sup.run(max_states=args.maxstates,
                                  max_seconds=args.maxseconds,
                                  check_deadlock=args.deadlock,
                                  resume_from=args.recover)
                except Preempted as p:
                    log(f"{p}; rerun with -recover {p.path} to "
                        f"continue (exit {EXIT_RESUMABLE})")
                    return EXIT_RESUMABLE
                eng = sup.engine
                log(f"supervised run done: {sup.summary()}")
            elif engine == "sharded":
                # multi-chip BFS over every visible device (the mesh
                # is the whole device set; multi-host runs set the
                # TPUVSR_MH_* env — jax.distributed was initialized
                # with the backend above, so devices() spans hosts)
                import numpy as np

                import jax
                from jax.sharding import Mesh

                from ..parallel.sharded_bfs import ShardedBFS
                mesh = Mesh(np.array(jax.devices()), ("d",))
                log(f"sharded mesh: {mesh.shape['d']} devices")
                eng = ShardedBFS(spec, mesh, pipeline=args.pipeline,
                                 pack=pack_kw, commit=commit_kw,
                                 symmetry=symmetry_kw,
                                 bounds=bounds_kw, por=por_kw)
                res = eng.run(
                    max_states=args.maxstates,
                    max_seconds=args.maxseconds,
                    check_deadlock=args.deadlock, log=log, obs=obs,
                    checkpoint_path=(ckpt_dir if args.checkpoint or
                                     args.recover else None),
                    checkpoint_every=(args.checkpoint * 60.0
                                      if args.checkpoint else
                                      30 * 60.0 if args.recover
                                      else None),
                    resume_from=args.recover)
            else:
                # temporal properties need the behavior graph: run the
                # safety BFS through the paged engine with level
                # retention so the device graph builder reuses the
                # enumeration instead of re-running it
                want_graph = bool(spec.temporal_props) and \
                    not spec.symmetry_perms
                if want_graph:
                    # edge stream on by default (ISSUE 15): the
                    # behavior graph flows out of the safety BFS;
                    # -edges off keeps the two-pass re-expansion
                    # (DeviceGraph mode="two-pass") as the oracle
                    eng = PagedBFS(spec, retain_levels=True,
                                   edges=args.edges != "off",
                                   pipeline=args.pipeline,
                                   pack=pack_kw, commit=commit_kw,
                                   symmetry=symmetry_kw,
                                   bounds=bounds_kw)
                elif engine == "paged":
                    eng = PagedBFS(spec, pipeline=args.pipeline,
                                   pack=pack_kw, commit=commit_kw,
                                   symmetry=symmetry_kw,
                                   bounds=bounds_kw, por=por_kw,
                                   **spill_kw)
                else:
                    eng = DeviceBFS(spec, pipeline=args.pipeline,
                                    pack=pack_kw, commit=commit_kw,
                                    symmetry=symmetry_kw,
                                    bounds=bounds_kw, por=por_kw)
                res = eng.run(
                    max_states=args.maxstates,
                    max_seconds=args.maxseconds,
                    check_deadlock=args.deadlock, log=log, obs=obs,
                    checkpoint_path=(ckpt_dir if args.checkpoint or
                                     args.recover else None),
                    # checkpoint_every=None means "every level
                    # boundary"; a resumed run without an explicit
                    # -checkpoint gets TLC's default 30-minute
                    # cadence instead of an unrequested full
                    # snapshot per level
                    checkpoint_every=(args.checkpoint * 60.0
                                      if args.checkpoint else
                                      30 * 60.0 if args.recover
                                      else None),
                    resume_from=args.recover)
        else:
            if args.checkpoint or args.recover:
                log("checkpoint/recover is a device-engine feature; "
                    "ignored for the interpreter")
            from ..engine.bfs import bfs_check
            res = bfs_check(spec, check_deadlock=args.deadlock,
                            max_states=args.maxstates, log=log, obs=obs)
        summary = {"mode": "bfs", "ok": res.ok,
                   "distinct_states": res.distinct_states,
                   "states_generated": res.states_generated,
                   "diameter": res.diameter,
                   "states_per_sec": round(res.states_per_sec, 1),
                   "violated": res.violated_invariant,
                   "error": res.error,
                   "elapsed_s": round(res.elapsed, 3),
                   "metrics": summary_metrics(res.metrics)}
        if args.supervise:
            summary["supervisor"] = sup.summary()
        if res.ok and not res.error and spec.temporal_props:
            from ..engine.liveness import liveness_check
            log(f"checking temporal properties: "
                f"{', '.join(spec.temporal_props)}")
            graph = None
            if engine in ("device", "paged", "sharded") and \
                    not spec.symmetry_perms:
                # device-built behavior graph, streamed out of the
                # safety BFS itself (ISSUE 15; -edges off keeps the
                # historical two-pass re-expansion as the oracle).
                # A resumed edge-stream run restores its retained
                # blocks + edge rows from the snapshot, so reuse
                # works across -recover too; runs without retained
                # blocks (supervised/sharded, or a snapshot written
                # without the stream) re-enumerate from scratch.
                from ..core.values import TLAError
                from ..engine.device_liveness import DeviceGraph
                gmode = "two-pass" if args.edges == "off" else "stream"
                if args.supervise or engine == "sharded":
                    graph = DeviceGraph(spec, log=log, mode=gmode)
                else:
                    try:
                        graph = DeviceGraph(spec, engine=eng,
                                            result=res, log=log)
                    except (TLAError, ValueError) as e:
                        log(f"retained enumeration unusable ({e}); "
                            f"re-enumerating for the liveness graph")
                        graph = DeviceGraph(spec, log=log, mode=gmode)
            # the liveness pass gets its own observer segment in the
            # same journal (second run_start/run_end pair, engine
            # "liveness"); the -metrics file stays the BFS engine's
            lobs = RunObserver(journal_path=args.journal, log=log)
            lres = liveness_check(spec, max_states=args.maxstates,
                                  log=log, graph=graph, obs=lobs)
            summary["liveness"] = summary_metrics(lres.metrics)
            summary["properties_ok"] = lres.ok
            if not lres.ok:
                res.ok = False
                res.trace = lres.trace
                summary["ok"] = False
                summary["violated"] = lres.property_name or lres.error
                res.violated_invariant = lres.property_name
                print(f"Error: Temporal property "
                      f"{lres.property_name or lres.error} is violated.",
                      file=sys.stderr)

    if not res.ok and res.trace:
        print(f"Error: Invariant {res.violated_invariant} is violated.",
              file=sys.stderr)
        print(format_trace(res.trace))
    if device:
        summary["device"] = device
    if args.json:
        print(json.dumps(summary))
    else:
        for k, v in summary.items():
            print(f"{k}: {v}")
    # TLC's code 12 = safety violation (tpuvsr/exitcodes.py table)
    return EX_OK if res.ok else EX_VIOLATION


if __name__ == "__main__":
    sys.exit(main())
