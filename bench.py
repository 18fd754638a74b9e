"""Headline benchmark: distinct states/sec of the device BFS engine on
the shrunken flagship config (BASELINE.json configs[0]: VSR.tla with
R=3, C=1, Values={v1}, StartViewOnTimerLimit=1 — 43,941 distinct
states, diameter 24), checked to fixpoint.

Prints ONE JSON line {metric, value, unit, vs_baseline, ...}.
vs_baseline = device distinct states/sec over the single-thread
interpreter oracle's distinct states/sec on the same spec (the stand-in
for the reference's explicit-state checker; the reference publishes no
throughput figures — SURVEY.md §6).

Robustness (round-1 failure modes):
* the metric JSON is ALWAYS emitted — on SIGTERM/SIGINT, on an internal
  deadline short of the driver timeout, and on any crash — carrying
  whatever was measured so far plus a `phase` marker;
* the device JAX gives this process is stated in the JSON
  (`backend`, `device`), and the bench FAILS (exit 2) when that is not
  an accelerator: no probe child, no CPU fallback — a number from a
  CPU run is never a benchmark number (its rewrite is ROADMAP S0);
* the spec is the kernel-native VSR (tpuvsr/models/native.py), built
  from committed files, so there is no interpreter baseline leg and no
  stub-fixture fallback round.
"""

import json
import os
import signal
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

BUDGET_S = float(os.environ.get("BENCH_BUDGET_S", "2400"))
T0 = time.time()
DEADLINE = T0 + 0.92 * BUDGET_S

# round-artifact attachments: key -> scripts/<file>
ATTACHMENTS = (("defect_hunt", "hunt_result.json"),
               ("sim_scale", "sim_scale.json"),
               ("validate_demo", "validate_demo.json"),
               ("defect_bfs_window", "defect_window.json"),
               ("hunt_ablation", "hunt_ablation.json"),
               ("liveness_speedup", "liveness_speedup.json"),
               ("sim_scale_wide", "sim_scale_wide.json"),
               ("multihost", "multihost.json"),
               ("recovery_fixpoints", "recovery_fixpoints.json"),
               # round-5 artifacts: the AST->kernel compiler's pinned
               # fixpoint, the occupancy-calibrated exchange ratio, the
               # tile-1024 miscompile repro ladder, shipped-constant
               # liveness/safety runs, and the RR05 deep pin
               ("compiled_kernel_fixpoint", "lower_fixpoint.json"),
               ("exchange_stats", "exchange_stats.json"),
               ("miscompile_repro", "miscompile_repro.json"),
               ("liveness_shipped", "liveness_shipped.json"),
               ("shipped_probe", "a01_shipped_probe.json"),
               ("shipped_pin", "shipped_pin.json"),
               ("rr05_deep", "rr05_deep.json"))

RESULT = {
    "metric": "VSR.tla BFS distinct states/sec (R=3, |Values|=1, timer=1)",
    "value": 0.0,
    "unit": "states/sec",
    "vs_baseline": 0.0,
    "backend": "unknown",
    "phase": "startup",
}
_EMITTED = False


def emit(code=0):
    global _EMITTED
    if not _EMITTED:
        _EMITTED = True
        print(json.dumps(RESULT), flush=True)
    if code is not None:
        os._exit(code)


def _on_signal(signum, frame):
    RESULT["phase"] += f" (signal {signum})"
    emit(1)


def _is_cpu_backend(b):
    return str(b).startswith("cpu")


def _perf_gate(result):
    """Diff this run's metrics against the newest BENCH_r*.json via
    scripts/compare_bench.py and embed the verdict (ISSUE 3 satellite:
    the perf gate rides the round driver's own artifact instead of
    needing a separate CI step).  Cross-backend comparisons (a
    CPU round against a TPU round, or vice versa) are marked
    advisory: ok=None."""
    import contextlib
    import glob
    import io
    import tempfile
    try:
        sys.path.insert(0, os.path.join(REPO, "scripts"))
        import compare_bench
        prev = sorted(glob.glob(os.path.join(REPO, "BENCH_r*.json")))
        if not prev:
            return {"skipped": "no BENCH_r*.json baseline in the repo"}
        # the round driver wraps the bench RESULT under "parsed" (and
        # leaves it null when the last stdout line wasn't the metric
        # JSON) — walk newest-first for a round with a usable number
        baseline, base_doc = None, None
        for cand_path in reversed(prev):
            with open(cand_path) as f:
                doc = json.load(f)
            if isinstance(doc.get("parsed"), dict):
                doc = doc["parsed"]
            if compare_bench.throughput(doc, "distinct_per_s")[0] \
                    is not None:
                baseline, base_doc = cand_path, doc
                break
        if baseline is None:
            return {"skipped": "no BENCH_r*.json round carries a "
                               "usable distinct_per_s baseline"}
        pct = float(os.environ.get("BENCH_MAX_REGRESSION_PCT", "15"))
        cand = {k: result.get(k)
                for k in ("value", "metrics", "backend")}
        fd, cpath = tempfile.mkstemp(suffix=".json")
        with os.fdopen(fd, "w") as f:
            json.dump(cand, f)
        # the baseline may have been unwrapped from the driver's
        # "parsed" field — hand compare_bench the unwrapped doc
        fd, bpath = tempfile.mkstemp(suffix=".json")
        with os.fdopen(fd, "w") as f:
            json.dump(base_doc, f)
        buf = io.StringIO()
        try:
            with contextlib.redirect_stdout(buf), \
                    contextlib.redirect_stderr(buf):
                rc = compare_bench.main(
                    [bpath, cpath, "--max-regression", str(pct)])
        finally:
            os.unlink(cpath)
            os.unlink(bpath)
        same = (_is_cpu_backend(base_doc.get("backend", ""))
                == _is_cpu_backend(result.get("backend", "")))
        return {
            "baseline": os.path.basename(baseline),
            "baseline_backend": base_doc.get("backend"),
            "candidate_backend": result.get("backend"),
            "max_regression_pct": pct,
            "exit_code": rc,
            "ok": (rc == 0) if same else None,
            "advisory": not same,
            "detail": buf.getvalue().strip().splitlines()[:8],
        }
    except Exception as e:  # noqa: BLE001 — the gate never kills bench
        return {"error": f"{type(e).__name__}: {e}"}


def main():
    import jax
    dev = jax.devices()[0]
    backend = f"{dev.platform} ({dev.device_kind})"
    RESULT["backend"] = backend
    RESULT["device"] = {"platform": dev.platform,
                        "kind": dev.device_kind,
                        "count": len(jax.devices())}
    print(f"bench: backend = {backend}", file=sys.stderr)
    if dev.platform == "cpu":
        print("bench: no accelerator — a CPU run is not a benchmark "
              "(run it through the chip tool)", file=sys.stderr)
        RESULT["phase"] = "no-accelerator"
        emit(2)

    from __graft_entry__ import _small_spec
    from tpuvsr.engine.device_bfs import DeviceBFS

    # the kernel-native spec has no interpreter (no AST), so there is
    # no single-thread baseline leg: vs_baseline stays null until S0
    # rewrites this benchmark
    spec = _small_spec()

    # device engine: compile+warm on a depth-limited run, then measure a
    # fresh full run on the SAME instance (jits are cached by closure)
    RESULT["phase"] = "compile"
    tile = int(os.environ.get("BENCH_TILE", "256"))
    # fused mode (default): whole fixpoint in O(1) dispatches
    fused = os.environ.get("BENCH_FUSED", "1") != "0"
    RESULT["mode"] = "fused" if fused else "chunked"
    eng = DeviceBFS(spec, tile_size=tile, fpset_capacity=1 << 21,
                    next_capacity=1 << 15, expand_mult=2,
                    expand_mults={"ReceiveMatchingSVC": 4, "SendDVC": 4})
    runner = eng.run_fused if fused else eng.run
    t0 = time.time()
    runner(max_depth=6)
    compile_s = time.time() - t0
    RESULT["compile_s"] = round(compile_s, 1)
    print(f"bench: compile+warmup {compile_s:.1f}s", file=sys.stderr)

    # BENCH_SUPERVISE=1: run the timed headline through the resilience
    # supervisor (ISSUE 5 satellite) so a real TPU OOM degrades through
    # the tile ladder (and the paged fallback) instead of killing the
    # round; the supervisor outcome (attempts, degrades list,
    # resharded-from) lands in the round doc as RESULT["supervisor"]
    if os.environ.get("BENCH_SUPERVISE", "0") == "1" and not fused:
        from tpuvsr.engine.paged_bfs import PagedBFS
        from tpuvsr.resilience.supervisor import Supervisor
        sup = Supervisor(
            spec, engine="device", tile_size=tile,
            engine_factory=lambda kind, t:
                (PagedBFS if kind == "paged" else DeviceBFS)(
                    spec, tile_size=t, fpset_capacity=1 << 21,
                    next_capacity=1 << 15, expand_mult=2,
                    expand_mults={"ReceiveMatchingSVC": 4,
                                  "SendDVC": 4}),
            log=lambda m: print(f"bench: {m}", file=sys.stderr))

        def runner(**kw):
            kw.pop("log", None)     # the supervisor logs through its own
            r = sup.run(**kw)
            RESULT["supervisor"] = sup.summary()
            return r

    RESULT["phase"] = "device-bfs"
    t0 = time.time()
    res = runner(max_seconds=max(30.0, DEADLINE - time.time()),
                 log=lambda m: print(f"bench: {m}", file=sys.stderr))
    # self-check fires on a completed run that misses the pinned count,
    # AND on a partial (time-budget) run that OVERcounts — the space is
    # pinned complete, so distinct > 43941 is a mis-exploration even
    # when the run was cut short (ADVICE r4)
    if fused and (res.distinct_states != 43941 if res.error is None
                  else res.distinct_states > 43941):
        # self-check against the pinned fixpoint: a fused-pass
        # miscount must never become the graded number silently —
        # fall back to the chunked engine (tile-1024 precedent:
        # width-dependent TPU mis-exploration)
        RESULT["fused_mismatch_distinct"] = res.distinct_states
        RESULT["fused_mismatch_partial"] = res.error
        RESULT["mode"] = "chunked (fused self-check failed)"
        what = (f"{res.distinct_states} != 43941" if res.error is None
                else f"{res.distinct_states} > 43941 on a partial run "
                     f"({res.error})")
        print(f"bench: FUSED SELF-CHECK FAILED ({what}); falling back",
              file=sys.stderr)
        eng2 = DeviceBFS(spec, tile_size=tile, fpset_capacity=1 << 21,
                         next_capacity=1 << 15, expand_mult=2,
                         expand_mults={"ReceiveMatchingSVC": 4,
                                       "SendDVC": 4})
        eng2.run(max_depth=6)
        runner = eng2.run
        res = runner(max_seconds=max(30.0, DEADLINE - time.time()))
    dev_sps = res.states_generated / res.elapsed
    distinct_sps = res.distinct_states / res.elapsed
    RESULT.update({
        "phase": "done" if not res.error else f"partial: {res.error}",
        "value": round(distinct_sps, 1),
        "vs_baseline": None,
        "distinct_states": res.distinct_states,
        "states_generated": res.states_generated,
        "diameter": res.diameter,
        "elapsed_s": round(res.elapsed, 2),
        "generated_per_s": round(dev_sps, 1),
        "reached_fixpoint": res.error is None,
        # tpuvsr-metrics/1 document of the timed run (phase timers,
        # counters, per-level trajectory) — BENCH_*.json files become
        # directly diffable via scripts/compare_bench.py
        "metrics": res.metrics,
    })
    # supervisor outcome + mesh identity (ISSUE 5): degrades list and
    # resharded-from make a degraded/resharded round self-describing;
    # compare_bench treats mesh-size mismatches as advisory
    g = (res.metrics or {}).get("gauges", {})
    RESULT.setdefault("supervisor", None)
    RESULT["mesh_devices"] = g.get("mesh_devices")
    RESULT["resharded_from"] = g.get("resharded_from")
    # packed-frontier identity (ISSUE 9): at-rest bytes one frontier
    # row costs the headline run and the dense/packed ratio;
    # compare_bench gates on bytes/state regressions (cross-layout
    # comparisons advisory, like pipeline depth)
    RESULT["frontier_bytes_per_state"] = g.get(
        "frontier_bytes_per_state")
    RESULT["pack_ratio"] = g.get("pack_ratio")
    # defect-layout sizing (the CAPACITY.md headline — derived from
    # the in-repo defect cfg, no reference mount needed): the ISSUE 9
    # acceptance anchor is a >=4x bytes/state cut at MAX_MSGS=48
    try:
        from tpuvsr.analysis.passes.widths import derive_ranges_from
        from tpuvsr.engine.pack import build_pack_spec
        from tpuvsr.frontend.cfg import parse_cfg_file
        from tpuvsr.models.vsr import VSRCodec
        dcfg = parse_cfg_file(os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "examples", "VSR_defect.cfg"))
        dpk = build_pack_spec(
            VSRCodec(dcfg.constants, max_msgs=48),
            ranges=derive_ranges_from(dcfg.constants, "VSR"))
        RESULT["defect_pack"] = {
            "max_msgs": 48, "dense_bytes": dpk.dense_bytes,
            "packed_bytes": dpk.packed_bytes,
            "ratio": round(dpk.ratio, 2)}
    except Exception as e:           # sizing is advisory, never fatal
        RESULT["defect_pack"] = {"error": str(e)}
    # second timed run on the same engine: separates machine noise from
    # real throughput (VERDICT r3 item 8 asked the r2->r3 CPU drop be
    # explained with two runs; the identified cause — the CP06 header
    # columns widening EVERY model's m_hdr plane 9 -> 11 — is fixed by
    # the per-codec NHDR, see models/vsr.py)
    if time.time() < DEADLINE - 60 and res.error is None:
        res2 = runner(max_seconds=max(30.0, DEADLINE - time.time()))
        RESULT["run2_distinct_per_s"] = round(
            res2.distinct_states / res2.elapsed, 1)
    # the headline run's dispatch window (fused = 1 dispatch, chunked
    # = the engine default); compare_bench treats depth mismatches
    # between rounds as advisory
    RESULT["pipeline_depth"] = (res.metrics or {}).get(
        "gauges", {}).get("pipeline_depth")
    # level-kernel commit mode + occupancy (ISSUE 10): compare_bench
    # treats commit mismatches between docs as advisory (like pipeline
    # depth) and gates occupancy regressions
    RESULT["commit"] = (res.metrics or {}).get(
        "gauges", {}).get("commit_mode")
    RESULT["occupancy"] = (res.metrics or {}).get(
        "gauges", {}).get("occupancy")
    RESULT["inserts_per_tile"] = (res.metrics or {}).get(
        "gauges", {}).get("inserts_per_tile")
    # symmetry reduction identity (ISSUE 11): the group order the
    # headline run canonicalized by (1 = off — the shipped cfg
    # declares SYMMETRY, so the device default is on) and the
    # generated/distinct-after-canon ratio; compare_bench gates
    # orbit_ratio drops and distinct growth at matching modes
    RESULT["symmetry_perms"] = g.get("symmetry_perms")
    RESULT["orbit_ratio"] = g.get("orbit_ratio")
    # bounds pre-pass identity (ISSUE 13): pack bits saved by interval
    # tightening (1.0 = untightened/off) and the static state bound;
    # compare_bench treats ratio mismatches between docs as advisory,
    # like pipeline depth
    RESULT["bound_tightening_ratio"] = g.get("bound_tightening_ratio")
    RESULT["state_bound"] = g.get("state_bound")
    # A/B the chunked engine's dispatch window on the same probe
    # (ISSUE 4 acceptance): -pipeline 1 vs -pipeline 2 must explore
    # the identical space; the throughput delta is the window's win
    if time.time() < DEADLINE - 240 and res.error is None:
        RESULT["phase"] = "pipeline-ab"
        try:
            ab = {}
            for K in (1, 2):
                e = DeviceBFS(spec, tile_size=tile,
                              fpset_capacity=1 << 21,
                              next_capacity=1 << 15, expand_mult=2,
                              expand_mults={"ReceiveMatchingSVC": 4,
                                            "SendDVC": 4},
                              pipeline=K)
                e.run(max_depth=6)      # compile + warm
                r = e.run(max_seconds=max(30.0,
                                          DEADLINE - time.time()))
                ab[f"pipeline{K}"] = {
                    "distinct": r.distinct_states,
                    "generated": r.states_generated,
                    "distinct_per_s": round(
                        r.distinct_states / r.elapsed, 1),
                    "elapsed_s": round(r.elapsed, 2),
                    "reached_fixpoint": r.error is None,
                    "overlap_saved_s": r.metrics["gauges"].get(
                        "overlap_saved_s"),
                }
            # counts are only comparable when neither run was cut by
            # the time budget (the K=2 run starts later and gets a
            # strictly smaller budget; truncation differences are not
            # a semantics violation) — None = not comparable
            ab["counts_identical"] = (
                ab["pipeline1"]["distinct"]
                == ab["pipeline2"]["distinct"]
                and ab["pipeline1"]["generated"]
                == ab["pipeline2"]["generated"]
            ) if (ab["pipeline1"]["reached_fixpoint"]
                  and ab["pipeline2"]["reached_fixpoint"]) else None
            # cross-level chaining (ISSUE 9 lever 3): the window that
            # SURVIVES level boundaries — the host-round-trip-per-level
            # cost the chunked window still pays disappears
            if time.time() < DEADLINE - 90:
                e = DeviceBFS(spec, tile_size=tile,
                              fpset_capacity=1 << 21,
                              next_capacity=1 << 15, expand_mult=2,
                              expand_mults={"ReceiveMatchingSVC": 4,
                                            "SendDVC": 4},
                              pipeline=2)
                e.run_chained(max_depth=6)      # compile + warm
                r = e.run_chained(max_seconds=max(
                    30.0, DEADLINE - time.time()))
                ab["chained"] = {
                    "distinct": r.distinct_states,
                    "generated": r.states_generated,
                    "distinct_per_s": round(
                        r.distinct_states / r.elapsed, 1),
                    "elapsed_s": round(r.elapsed, 2),
                    "reached_fixpoint": r.error is None,
                }
                if ab["chained"]["reached_fixpoint"] and \
                        ab["counts_identical"]:
                    ab["counts_identical"] = (
                        ab["chained"]["distinct"]
                        == ab["pipeline1"]["distinct"]
                        and ab["chained"]["generated"]
                        == ab["pipeline1"]["generated"])
            # commit-mode A/B (ISSUE 10 acceptance spot-check): the
            # occupancy-packed fused commit vs the historical
            # per-action serial phases — counts must be IDENTICAL,
            # the throughput delta is the tentpole's win
            if time.time() < DEADLINE - 90:
                e = DeviceBFS(spec, tile_size=tile,
                              fpset_capacity=1 << 21,
                              next_capacity=1 << 15, expand_mult=2,
                              expand_mults={"ReceiveMatchingSVC": 4,
                                            "SendDVC": 4},
                              pipeline=2, commit="per-action")
                e.run(max_depth=6)      # compile + warm
                r = e.run(max_seconds=max(30.0,
                                          DEADLINE - time.time()))
                m = (r.metrics or {}).get("gauges", {})
                ab["per_action_commit"] = {
                    "distinct": r.distinct_states,
                    "generated": r.states_generated,
                    "distinct_per_s": round(
                        r.distinct_states / r.elapsed, 1),
                    "elapsed_s": round(r.elapsed, 2),
                    "reached_fixpoint": r.error is None,
                    "occupancy": m.get("occupancy"),
                    "inserts_per_tile": m.get("inserts_per_tile"),
                }
                if ab["per_action_commit"]["reached_fixpoint"] and \
                        ab["counts_identical"]:
                    ab["counts_identical"] = (
                        ab["per_action_commit"]["distinct"]
                        == ab["pipeline1"]["distinct"]
                        and ab["per_action_commit"]["generated"]
                        == ab["pipeline1"]["generated"])
            # symmetry A/B (ISSUE 11 acceptance): the shipped cfg
            # declares SYMMETRY, so the headline already runs
            # orbit-canonical; the off leg measures how many distinct
            # states the reduction is folding away.  Counts are NOT
            # expected to match — the ratio IS the result (bounded by
            # wall clock: the unreduced space can be |Values|! larger)
            if time.time() < DEADLINE - 120:
                e = DeviceBFS(spec, tile_size=tile,
                              fpset_capacity=1 << 21,
                              next_capacity=1 << 15, expand_mult=2,
                              expand_mults={"ReceiveMatchingSVC": 4,
                                            "SendDVC": 4},
                              symmetry=False)
                e.run(max_depth=6)      # compile + warm
                r = e.run(max_seconds=max(
                    30.0, min(DEADLINE - time.time(), 300.0)))
                on = ab["pipeline1"]
                ab["symmetry_off"] = {
                    "distinct": r.distinct_states,
                    "generated": r.states_generated,
                    "distinct_per_s": round(
                        r.distinct_states / r.elapsed, 1),
                    "reached_fixpoint": r.error is None,
                    "orbit_cut": (round(r.distinct_states
                                        / on["distinct"], 3)
                                  if r.error is None
                                  and on["reached_fixpoint"]
                                  else None),
                }
            # bounds A/B (ISSUE 13 acceptance): declared-widths
            # packing + full action lists vs the tightened default —
            # counts must be IDENTICAL (the facts only change the
            # representation, never the explored space); the
            # bound_tightening_ratio is the static win
            if time.time() < DEADLINE - 90:
                e = DeviceBFS(spec, tile_size=tile,
                              fpset_capacity=1 << 21,
                              next_capacity=1 << 15, expand_mult=2,
                              expand_mults={"ReceiveMatchingSVC": 4,
                                            "SendDVC": 4},
                              pipeline=2, bounds=False)
                e.run(max_depth=6)      # compile + warm
                r = e.run(max_seconds=max(30.0,
                                          DEADLINE - time.time()))
                ab["bounds_off"] = {
                    "distinct": r.distinct_states,
                    "generated": r.states_generated,
                    "distinct_per_s": round(
                        r.distinct_states / r.elapsed, 1),
                    "elapsed_s": round(r.elapsed, 2),
                    "reached_fixpoint": r.error is None,
                }
                if ab["bounds_off"]["reached_fixpoint"] and \
                        ab["counts_identical"]:
                    ab["counts_identical"] = (
                        ab["bounds_off"]["distinct"]
                        == ab["pipeline1"]["distinct"]
                        and ab["bounds_off"]["generated"]
                        == ab["pipeline1"]["generated"])
            # POR A/B (ISSUE 16 acceptance): the ample-set filter
            # consumes speclint pass 7's independence facts inside the
            # fused commit.  The VERDICT must be identical to the
            # unreduced run, but distinct/generated may legitimately
            # SHRINK, so this leg is deliberately NOT folded into
            # counts_identical; por_cut_ratio (kept/full successor
            # work, 1.0 = filter inert on this spec) is the measured
            # win
            if time.time() < DEADLINE - 90:
                e = DeviceBFS(spec, tile_size=tile,
                              fpset_capacity=1 << 21,
                              next_capacity=1 << 15, expand_mult=2,
                              expand_mults={"ReceiveMatchingSVC": 4,
                                            "SendDVC": 4},
                              pipeline=2, por="on")
                e.run(max_depth=6)      # compile + warm
                r = e.run(max_seconds=max(30.0,
                                          DEADLINE - time.time()))
                m = (r.metrics or {}).get("gauges", {})
                ab["por_on"] = {
                    "distinct": r.distinct_states,
                    "generated": r.states_generated,
                    "distinct_per_s": round(
                        r.distinct_states / r.elapsed, 1),
                    "elapsed_s": round(r.elapsed, 2),
                    "reached_fixpoint": r.error is None,
                    "por_cut_ratio": m.get("por_cut_ratio"),
                    "ample_states": m.get("ample_states"),
                    "por_eligible_actions": m.get(
                        "por_eligible_actions"),
                    "distinct_shrunk_or_equal": (
                        r.distinct_states
                        <= ab["pipeline1"]["distinct"]
                        if r.error is None
                        and ab["pipeline1"]["reached_fixpoint"]
                        else None),
                    "verdict_identical": (
                        r.ok == res.ok
                        and r.violated_invariant
                        == res.violated_invariant
                        if r.error is None else None),
                }
                RESULT["por_cut_ratio"] = m.get("por_cut_ratio")
                RESULT["por_eligible_actions"] = m.get(
                    "por_eligible_actions")
            RESULT["pipeline_ab"] = ab
            print(f"bench: pipeline A/B "
                  f"{ab['pipeline1']['distinct_per_s']} -> "
                  f"{ab['pipeline2']['distinct_per_s']} distinct/s"
                  + (f" -> chained "
                     f"{ab['chained']['distinct_per_s']}"
                     if "chained" in ab else "")
                  + f", counts_identical={ab['counts_identical']}",
                  file=sys.stderr)
        except Exception as e:  # noqa: BLE001 — A/B never kills bench
            RESULT["pipeline_ab"] = {
                "error": f"{type(e).__name__}: {e}"}
        RESULT["phase"] = "done"
    RESULT["perf_gate"] = _perf_gate(RESULT)
    if RESULT["perf_gate"].get("ok") is False:
        print(f"bench: PERF GATE FAILED vs "
              f"{RESULT['perf_gate']['baseline']}: "
              f"{RESULT['perf_gate']['detail']}", file=sys.stderr)
    RESULT["regression_note"] = (
        "r2->r3 CPU headline dropped 8399->6564 distinct/s because r3 "
        "widened the shared message-header plane from 9 to 11 columns "
        "for CP06's flag/cp fields, growing every model's hashed bytes "
        "per slot; r4 makes the width per-codec (NHDR=9 again for "
        "VSR/A01/I01/ST03/AS04/RR05/AL05, 11 only for CP06)")
    # attach measured round artifacts (each records its own backend):
    # guided-hunt time-to-violation (scripts/defect_hunt.py),
    # configs[2]-scale simulation throughput (scripts/sim_scale.py),
    # paged defect-config BFS window (scripts/defect_bfs_window.py),
    # hunt sampling-mode ablation (scripts/hunt_ablation.py), and the
    # device-vs-interpreter liveness graph build
    # (scripts/liveness_speedup.py)
    _attach_and_lift()
    print(f"bench: device {res.distinct_states} distinct "
          f"({res.error or 'fixpoint'}), {dev_sps:.0f} generated/s, "
          f"{distinct_sps:.0f} distinct/s, diameter {res.diameter}",
          file=sys.stderr)
    emit(None)


def _attach_and_lift():
    """Attach the recorded round artifacts and lift their headline
    numbers to the round-doc top level (shared by the reference
    headline and the reference-absent stub round)."""
    for key, fname in ATTACHMENTS:
        p = os.path.join(REPO, "scripts", fname)
        if os.path.exists(p):
            try:
                with open(p) as f:
                    loaded = json.load(f)
            except ValueError:
                continue
            RESULT[key] = loaded
    # walker-fleet simulation headline (ISSUE 7): walkers / walks/s /
    # split mode of the fleet-rebuilt sim_scale probe lifted to the
    # round-doc top level, so scripts/compare_bench.py's walks/s gate
    # diffs rounds directly (cross-walker-count drops are advisory)
    sc = RESULT.get("sim_scale")
    if isinstance(sc, dict) and sc.get("walks_per_s") is not None:
        RESULT["sim_walkers"] = sc.get("walkers")
        RESULT["sim_walks_per_s"] = sc.get("walks_per_s")
        RESULT["sim_split_enabled"] = bool(sc.get("split_enabled"))
    # batched trace validation headline (ISSUE 8): traces/s, round
    # size and divergence-localization health of the validate_demo
    # drill lifted to the round-doc top level, so compare_bench's
    # traces/s gate diffs rounds directly (cross-backend/batch drops
    # are advisory)
    vd = RESULT.get("validate_demo")
    if isinstance(vd, dict) and vd.get("traces_per_s") is not None:
        RESULT["validate_traces_per_s"] = vd.get("traces_per_s")
        RESULT["validate_batch"] = vd.get("batch")
        RESULT["validate_traces"] = vd.get("traces")
        RESULT["validate_ok"] = bool(vd.get("ok"))
    # streamed liveness headline (ISSUE 15): edge count, emission
    # rate and graph-construction overhead of the liveness_speedup
    # A/B's largest pin lifted to the round-doc top level, so
    # scripts/compare_bench.py's gate_liveness diffs rounds directly
    # (streamed-vs-two-pass mode mismatches are advisory)
    ls = RESULT.get("liveness_speedup")
    if isinstance(ls, dict) and ls.get("edges_per_s") is not None:
        RESULT["edges"] = ls.get("edges")
        RESULT["edges_per_s"] = ls.get("edges_per_s")
        RESULT["graph_overhead_ratio"] = ls.get(
            "graph_overhead_ratio")
        RESULT["liveness_check_s"] = ls.get("check_s")
        RESULT["liveness_mode"] = ls.get("mode")
    hr = RESULT.get("defect_hunt")
    if isinstance(hr, dict) and hr.get("split_enabled") is not None:
        RESULT["hunt_split_enabled"] = bool(hr.get("split_enabled"))
        RESULT["hunt_time_to_violation_s"] = hr.get(
            "time_to_violation_s")
    # headline the defect-scale number when a TPU window ran (the r4
    # verdict's graded target: >= 10x the CPU window's 1,160 distinct/s)
    dw = RESULT.get("defect_bfs_window")
    if isinstance(dw, dict) and not str(dw.get("backend", "")).startswith(
            "cpu"):
        RESULT["defect_tpu_distinct_per_s"] = dw.get("distinct_per_s")
        RESULT["defect_tpu_vs_cpu_window"] = dw.get("vs_cpu_window_1160")
    _embed_telemetry()
    _embed_spool()


def _embed_telemetry():
    """Embed a tpuvsr-telemetry/1 snapshot in the round doc
    (ISSUE 17): run one stub job through a throwaway service spool,
    fold its journals with the streamed aggregator, and record the
    fleet-level series (queue-wait/run-time histograms, per-window
    rates, worker utilization) next to the engine headline — every
    BENCH_r*.json from r07 on carries them, and compare_bench's
    ``gate_telemetry`` fold-determinism drill activates on rounds
    that do.  Since ISSUE 18 the drill also exercises the serving
    guard, so the round doc records ``rate_limited`` /
    ``breaker_trips`` counters and the measured fast-fail rate
    (``guard_reject_per_s``, gated by ``gate_guard``)."""
    import shutil
    import tempfile
    tmp = tempfile.mkdtemp(prefix="tpuvsr-bench-telemetry-")
    try:
        from tpuvsr.obs.telemetry import TelemetryAggregator
        from tpuvsr.service.queue import JobQueue
        from tpuvsr.service.worker import Worker
        q = JobQueue(os.path.join(tmp, "spool"))
        q.submit("<stub>", engine="device", tenant="bench",
                 flags={"stub": True})
        Worker(q, devices=1).drain()
        # guard drill (ISSUE 18): fold one throttled tenant and one
        # breaker trip into the round doc, and time the fast-fail
        # path — rejections/sec is serving-tier health (a slow
        # rejector turns the rate limiter into a DoS amplifier);
        # scripts/compare_bench.py's gate_guard diffs it between
        # rounds at matching limiter configs
        from tpuvsr.serve.guard import Guard, GuardDenied, spec_digest
        guard = Guard(q.spool, rate=0.001, burst=1.0, breaker_k=1)
        RESULT["guard_limiter"] = {"rate": 0.001, "burst": 1.0,
                                   "breaker_k": 1}
        denials = 0
        t0g = time.time()
        for _ in range(200):
            try:
                guard.admit_submission("bench", ts=time.time())
            except GuardDenied:
                denials += 1
        reject_s = time.time() - t0g
        guard.breaker_record("bench", spec_digest("<stub>", None),
                             False, ts=time.time())
        agg = TelemetryAggregator(q.spool, journal_breaches=False)
        agg.poll()
        snap = agg.snapshot()
        RESULT["telemetry"] = snap
        g = snap.get("guard") or {}
        RESULT["rate_limited"] = g.get("rate_limited")
        RESULT["breaker_trips"] = g.get("breaker_trips")
        RESULT["guard_reject_per_s"] = round(
            denials / max(reject_s, 1e-9), 1)
    except Exception as e:  # noqa: BLE001 — the embed never kills bench
        RESULT["telemetry"] = {"error": f"{type(e).__name__}: {e}"}
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _embed_spool():
    """Embed spool-driver op rates in the round doc (ISSUE 20): time
    record appends, claim/release cycles and a full-stream fold on a
    throwaway spool for each driver (fs / objstore / quorum), so
    rounds carry the data plane's control-path cost next to the
    engine headline.  ``scripts/compare_bench.py``'s ``gate_spool``
    diffs the rates between rounds at MATCHING drivers; cross-driver
    spreads (quorum pays W-replica fsyncs per append) are expected
    and advisory only."""
    import shutil
    import tempfile
    out = {}
    n_app, n_claim = 256, 64
    for name in ("fs", "objstore", "quorum"):
        tmp = tempfile.mkdtemp(prefix=f"tpuvsr-bench-spool-{name}-")
        try:
            from tpuvsr.service.spooldrv import open_driver
            drv = open_driver(os.path.join(tmp, "spool"), driver=name)
            t0 = time.time()
            for i in range(n_app):
                drv.append("bench", {"op": "tick", "i": i})
            t_app = time.time() - t0
            t0 = time.time()
            for i in range(n_claim):
                drv.try_claim(f"j{i:04d}", owner="bench", epoch=1)
                drv.release_claim(f"j{i:04d}", epoch=1)
            t_claim = time.time() - t0
            t0 = time.time()
            recs, _ = drv.read("bench", None)
            t_fold = time.time() - t0
            out[name] = {
                "appends_per_s": round(n_app / max(t_app, 1e-9), 1),
                "claims_per_s": round(n_claim / max(t_claim, 1e-9), 1),
                "fold_ms": round(t_fold * 1000.0, 2),
                "records_folded": len(recs),
            }
        except Exception as e:  # noqa: BLE001 — never kills bench
            out[name] = {"error": f"{type(e).__name__}: {e}"}
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
    RESULT["spool"] = out


if __name__ == "__main__":
    # registered here, not at import
    signal.signal(signal.SIGTERM, _on_signal)
    signal.signal(signal.SIGINT, _on_signal)
    try:
        main()
    except BaseException as e:  # noqa: BLE001 — always emit the JSON
        RESULT["phase"] += f" (error: {type(e).__name__}: {e})"
        import traceback
        traceback.print_exc()
        emit(1)
    emit(0)
