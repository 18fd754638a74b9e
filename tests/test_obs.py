"""Observability layer (tpuvsr/obs) tests.

Golden-schema half (no reference needed): the journal JSONL and the
metrics document from interpreter runs must validate against the
tpuvsr-journal/1 / tpuvsr-metrics/1 schemas, and the collector must
set CheckResult timing fields uniformly.

Device half (reference-gated, CPU backend like every device test):
* interp and device runs of the same spec emit journals whose shared
  event types carry IDENTICAL key sets (the drift-proofing the golden
  files exist for);
* the device phase timers (compile + dispatch + host_sync + check)
  sum to within 10% of wall-clock elapsed (ISSUE 2 acceptance);
* a -checkpoint/-recover pair appended to ONE journal file yields a
  continuous event stream with cumulative elapsed preserved.
"""

import io
import json
import os
import re
import subprocess
import sys
import time

import pytest

from tests.conftest import requires_reference, vsr_spec
from tpuvsr.engine.bfs import bfs_check
from tpuvsr.engine.spec import SpecModel
from tpuvsr.frontend.cfg import parse_cfg_text
from tpuvsr.frontend.parser import parse_module_text
from tpuvsr.obs import (Journal, Metrics, RunObserver, new_span_id,
                        new_trace_id, read_journal, root_span,
                        trace_env, trace_scope, validate_journal_line,
                        validate_metrics)
# the inline counter spec + stub device kernel live in tpuvsr.testing
# (shared with tests/test_resilience.py and scripts/fault_matrix.py)
from tpuvsr.testing import COUNTER, COUNTER_CFG, counter_spec


# ---------------------------------------------------------------------
# collector unit tests
# ---------------------------------------------------------------------
def test_metrics_timers_are_exclusive_and_sum():
    m = Metrics()
    with m.timer("outer"):
        time.sleep(0.02)
        with m.timer("inner"):
            time.sleep(0.02)
    # inner time is carved OUT of outer: both ~20ms, not outer ~40ms
    assert m.phases["inner"] >= 0.015
    assert m.phases["outer"] >= 0.015
    assert m.phases["outer"] < m.phases["inner"] + 0.05
    total = sum(m.phases.values())
    assert 0.03 <= total <= 0.2


def test_metrics_same_phase_nesting_accumulates_once():
    m = Metrics()
    with m.timer("check"):
        with m.timer("check"):
            time.sleep(0.01)
    assert 0.008 <= m.phases["check"] <= 0.1


def test_metrics_drain_closes_open_frames():
    m = Metrics()
    m.begin("check")
    m.begin("dispatch")
    time.sleep(0.01)
    m.drain()
    assert not m._stack
    assert "dispatch" in m.phases and "check" in m.phases


def test_validate_metrics_rejects_malformed():
    m = Metrics()
    doc = m.to_dict(run_id="r", engine="interp", elapsed_s=0.0)
    validate_metrics(doc)
    with pytest.raises(ValueError):
        validate_metrics({k: v for k, v in doc.items()
                          if k != "phases"})
    bad = dict(doc)
    bad["schema"] = "tpuvsr-metrics/999"
    with pytest.raises(ValueError):
        validate_metrics(bad)


def test_validate_journal_line_rejects_unknown_and_missing():
    with pytest.raises(ValueError):
        validate_journal_line({"event": "nope", "ts": 0, "run_id": "r"})
    with pytest.raises(ValueError):
        validate_journal_line({"event": "level_done", "ts": 0,
                               "run_id": "r", "depth": 1})


def test_progress_formatter_is_uniform():
    lines = []
    obs = RunObserver(log=lines.append, progress_every=0.0)
    obs.start(time.time() - 2.0, backend="host")
    obs.progress(depth=3, distinct=100, generated=400, force=True)
    obs.progress(walks=20, steps=900, force=True)
    assert lines[0].startswith("depth 3: 100 distinct, 400 generated")
    assert "distinct/s" in lines[0] and "gen/s" in lines[0]
    assert lines[1].startswith("20 walks, 900 steps")
    assert "steps/s" in lines[1]


def test_progress_throttles():
    lines = []
    obs = RunObserver(log=lines.append, progress_every=3600.0)
    obs.start(time.time())
    assert not obs.progress(depth=1, distinct=1, generated=1)
    assert obs.progress(depth=1, distinct=1, generated=1, force=True)
    assert len(lines) == 1


# ---------------------------------------------------------------------
# interpreter engines emit schema-valid artifacts (no reference)
# ---------------------------------------------------------------------
def test_interp_bfs_journal_and_metrics(tmp_path):
    jp = str(tmp_path / "run.jsonl")
    mp = str(tmp_path / "metrics.json")
    obs = RunObserver(journal_path=jp, metrics_path=mp)
    res = bfs_check(counter_spec(), obs=obs)
    assert res.ok
    # collector-set result fields (ISSUE 2 satellite: first-class,
    # uniform — not patched post hoc per engine)
    assert res.levels == [1, 2, 3, 4, 3, 2, 1]
    assert res.elapsed > 0
    assert res.states_per_sec == pytest.approx(
        res.states_generated / res.elapsed, rel=1e-6)
    events = read_journal(jp)          # validates every line
    kinds = [e["event"] for e in events]
    assert kinds[0] == "run_start" and kinds[-1] == "run_end"
    assert kinds.count("level_done") == 7
    assert events[0]["resumed"] is False
    end = events[-1]
    assert end["ok"] is True and end["distinct"] == 16
    # per-level rows mirror the journal
    doc = validate_metrics(json.load(open(mp)))
    assert doc == res.metrics
    assert [r["frontier"] for r in doc["levels"]] == [1, 2, 3, 4, 3, 2, 1]
    assert doc["levels"][-1]["distinct"] == 16
    # phases cover the wall clock (interp: everything under "check")
    assert sum(doc["phases"].values()) <= res.elapsed * 1.05
    assert sum(doc["phases"].values()) >= res.elapsed * 0.5


def test_interp_bfs_violation_event(tmp_path):
    jp = str(tmp_path / "viol.jsonl")
    cfg = ("CONSTANTS\n    Limit = 3\n"
           "INIT Init\nNEXT Next\nINVARIANT Small\n")
    src = COUNTER.replace("Bound == x + y <= 2 * Limit",
                          "Small == x + y <= 2")
    spec = SpecModel(parse_module_text(src), parse_cfg_text(cfg))
    res = bfs_check(spec, obs=RunObserver(journal_path=jp))
    assert not res.ok and res.violated_invariant == "Small"
    events = read_journal(jp)
    viol = [e for e in events if e["event"] == "violation"]
    assert len(viol) == 1
    assert viol[0]["kind"] == "invariant" and viol[0]["name"] == "Small"
    assert events[-1]["event"] == "run_end"
    assert events[-1]["ok"] is False


def test_interp_simulate_metrics():
    from tpuvsr.engine.simulate import simulate
    res = simulate(counter_spec(), num=5, depth=10, seed=3)
    doc = validate_metrics(res.metrics)
    assert doc["engine"] == "interp-sim"
    assert doc["walks"] == 5 and doc["steps"] == res.steps


def test_observer_rearm_on_reuse(tmp_path):
    # one observer across two runs (the checkpoint/recover idiom):
    # the second segment must journal too, not silently vanish
    jp = str(tmp_path / "reuse.jsonl")
    obs = RunObserver(journal_path=jp)
    bfs_check(counter_spec(), obs=obs)
    bfs_check(counter_spec(), obs=obs)
    kinds = [e["event"] for e in read_journal(jp)]
    assert kinds.count("run_start") == 2
    assert kinds.count("run_end") == 2


def test_default_observer_always_collects():
    res = bfs_check(counter_spec())
    validate_metrics(res.metrics)
    assert res.levels and res.states_per_sec > 0


# ---------------------------------------------------------------------
# compare_bench gate
# ---------------------------------------------------------------------
def _metrics_doc(distinct_per_s, pipeline_depth=None):
    m = Metrics()
    m.gauge("distinct_per_s", distinct_per_s)
    if pipeline_depth is not None:
        m.gauge("pipeline_depth", pipeline_depth)
    return m.to_dict(run_id="r", engine="device", elapsed_s=1.0,
                     distinct=1000)


def test_compare_bench_gates_regression(tmp_path):
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "scripts"))
    import compare_bench
    base = tmp_path / "base.json"
    good = tmp_path / "good.json"
    bad = tmp_path / "bad.json"
    base.write_text(json.dumps(_metrics_doc(1000.0)))
    good.write_text(json.dumps(_metrics_doc(950.0)))
    bad.write_text(json.dumps(_metrics_doc(500.0)))
    assert compare_bench.main([str(base), str(good)]) == 0
    assert compare_bench.main([str(base), str(bad)]) == 1
    # 60% tolerance admits the slow candidate
    assert compare_bench.main([str(base), str(bad),
                               "--max-regression", "60"]) == 0
    # legacy bench.py RESULT line (top-level "value")
    legacy = tmp_path / "legacy.json"
    legacy.write_text(json.dumps({"value": 990.0}))
    assert compare_bench.main([str(base), str(legacy)]) == 0
    junk = tmp_path / "junk.json"
    junk.write_text("{}")
    assert compare_bench.main([str(base), str(junk)]) == 2
    scalar = tmp_path / "scalar.json"
    scalar.write_text("5")         # valid JSON, not an object
    assert compare_bench.main([str(base), str(scalar)]) == 2


def test_compare_bench_pipeline_depth_mismatch_is_advisory(tmp_path):
    """ISSUE 4 satellite: a -pipeline 1 doc vs a -pipeline 2 doc
    measures a different dispatch regime — a drop beyond tolerance is
    advisory (exit 0), not a regression (exit 1)."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "scripts"))
    import compare_bench
    base = tmp_path / "base.json"
    slow = tmp_path / "slow.json"
    base.write_text(json.dumps(_metrics_doc(1000.0, pipeline_depth=1)))
    slow.write_text(json.dumps(_metrics_doc(500.0, pipeline_depth=2)))
    assert compare_bench.main([str(base), str(slow)]) == 0
    # same depth on both sides: the regression gate still bites
    slow_same = tmp_path / "slow_same.json"
    slow_same.write_text(json.dumps(
        _metrics_doc(500.0, pipeline_depth=1)))
    assert compare_bench.main([str(base), str(slow_same)]) == 1
    # depth absent from one side (pre-pipeline docs): not a mismatch
    legacy = tmp_path / "legacy_slow.json"
    legacy.write_text(json.dumps(_metrics_doc(500.0)))
    assert compare_bench.main([str(base), str(legacy)]) == 1


def _liveness_doc(distinct_per_s, edges_per_s, check_s, mode):
    d = _metrics_doc(distinct_per_s)
    d["liveness_speedup"] = {"edges_per_s": edges_per_s,
                             "check_s": check_s, "mode": mode,
                             "edges": 1000,
                             "graph_overhead_ratio": 0.1}
    return {"parsed": d, "metrics": d}


def test_compare_bench_gate_liveness(tmp_path):
    """ISSUE 15 satellite: edges/s drops and check_s growth fail at
    matching graph-construction modes; a streamed-vs-two-pass mode
    mismatch is advisory, like pipeline depth."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "scripts"))
    import compare_bench
    base = tmp_path / "base.json"
    base.write_text(json.dumps(
        _liveness_doc(1000.0, 5000.0, 10.0, "stream")))

    def rc(name, doc):
        p = tmp_path / name
        p.write_text(json.dumps(doc))
        return compare_bench.main([str(base), str(p)])
    # within tolerance
    assert rc("good.json",
              _liveness_doc(1000.0, 4800.0, 10.5, "stream")) == 0
    # edges/s regression at matching mode: fail
    assert rc("slow_edges.json",
              _liveness_doc(1000.0, 2000.0, 10.0, "stream")) == 1
    # check_s GROWTH at matching mode: fail (cost metric, inverted)
    assert rc("slow_check.json",
              _liveness_doc(1000.0, 5000.0, 30.0, "stream")) == 1
    # mode mismatch: advisory even with both off tolerance
    assert rc("mode_mismatch.json",
              _liveness_doc(1000.0, 2000.0, 30.0, "two-pass")) == 0
    # bench.py's LIFTED round-doc form (liveness_check_s /
    # liveness_mode at the top level, attachment stripped) feeds the
    # same gate: check_s growth still bites
    lifted = {"parsed": dict(_metrics_doc(1000.0),
                             edges_per_s=5000.0,
                             liveness_check_s=30.0,
                             liveness_mode="stream"),
              "metrics": _metrics_doc(1000.0)}
    assert rc("lifted_slow.json", lifted) == 1
    # liveness section absent from one side: gate stands down
    assert rc("no_liveness.json",
              {"metrics": _metrics_doc(1000.0)}) == 0


def _por_doc(distinct_per_s, cut=None, eligible=2):
    d = _metrics_doc(distinct_per_s)
    if cut is not None:
        d["gauges"].update(por_cut_ratio=cut, ample_states=3,
                           por_eligible_actions=eligible)
    return d


def test_compare_bench_gate_por(tmp_path):
    """ISSUE 16 satellite: por_cut_ratio GROWTH (the reduction
    weakened — cost metric, inverted gate) fails at matching por
    modes; on/off toggles and different ample filters are advisory,
    like the symmetry and commit mismatches."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "scripts"))
    import compare_bench
    base = tmp_path / "base.json"
    base.write_text(json.dumps(_por_doc(1000.0, cut=0.6667)))

    def rc(name, doc):
        p = tmp_path / name
        p.write_text(json.dumps(doc))
        return compare_bench.main([str(base), str(p)])
    # within tolerance
    assert rc("good.json", _por_doc(1000.0, cut=0.68)) == 0
    # cut ratio grew beyond tolerance at matching mode: fail
    assert rc("weak.json", _por_doc(1000.0, cut=0.95)) == 1
    # POR toggled off in the candidate: advisory
    assert rc("toggled.json", _por_doc(1000.0)) == 0
    # different ample filters (eligible-action counts): advisory
    assert rc("filters.json",
              _por_doc(1000.0, cut=0.95, eligible=1)) == 0
    # inert filter on both sides (0 eligible): informational only
    inert = tmp_path / "inert_base.json"
    inert.write_text(json.dumps(_por_doc(1000.0, cut=1.0, eligible=0)))
    p = tmp_path / "inert_cand.json"
    p.write_text(json.dumps(_por_doc(1000.0, cut=1.0, eligible=0)))
    assert compare_bench.main([str(inert), str(p)]) == 0


# ---------------------------------------------------------------------
# CLI flags (interp engine; no reference needed)
# ---------------------------------------------------------------------
def test_cli_metrics_journal_flags(tmp_path):
    (tmp_path / "ObsCounter.tla").write_text(COUNTER)
    (tmp_path / "ObsCounter.cfg").write_text(COUNTER_CFG)
    mp, jp = tmp_path / "m.json", tmp_path / "j.jsonl"
    r = subprocess.run(
        [sys.executable, "-m", "tpuvsr",
         str(tmp_path / "ObsCounter.tla"), "-engine", "interp",
         "-json", "-metrics", str(mp), "-journal", str(jp)],
        capture_output=True, text=True, timeout=420,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "PYTHONPATH": os.path.dirname(os.path.dirname(
                 os.path.abspath(__file__))),
             "HOME": "/root"})
    assert r.returncode == 0, r.stderr
    out = json.loads(r.stdout.strip().splitlines()[-1])
    # -json carries the collector summary (phases/counters/gauges);
    # the per-level trajectory stays in the -metrics file only
    assert out["metrics"]["phases"].get("check", 0) > 0
    assert "levels" not in out and "levels" not in out["metrics"]
    doc = validate_metrics(json.load(open(mp)))
    assert doc["module"] == "ObsCounter"
    assert [r_["frontier"] for r_ in doc["levels"]] == [
        1, 2, 3, 4, 3, 2, 1]
    events = read_journal(str(jp))
    assert events[0]["event"] == "run_start"
    assert events[-1]["event"] == "run_end"
    # final stats table rendered on stderr for -metrics runs
    assert "phase seconds:" in r.stderr


# ---------------------------------------------------------------------
# device engines driven through a stub kernel (no reference needed):
# exercises the REAL DeviceBFS/PagedBFS loops — dispatch accounting,
# journal events, checkpoint/recover continuity — on the inline
# counter spec via the model_factory hook (stubs: tpuvsr/testing.py)
# ---------------------------------------------------------------------
import numpy as np

from tpuvsr.testing import stub_device_engine as _stub_device_engine
from tpuvsr.testing import stub_model_factory as _stub_factory


def test_stub_device_bfs_journal_metrics(tmp_path):
    jp = str(tmp_path / "dev.jsonl")
    mp = str(tmp_path / "dev.json")
    eng = _stub_device_engine()
    res = eng.run(obs=RunObserver(journal_path=jp, metrics_path=mp))
    assert res.ok and res.distinct_states == 16
    assert res.levels == [1, 2, 3, 4, 3, 2, 1]
    assert res.states_per_sec > 0 and res.elapsed > 0
    events = read_journal(jp)
    kinds = [e["event"] for e in events]
    assert kinds[0] == "run_start" and kinds[-1] == "run_end"
    assert kinds.count("level_done") == 7
    assert events[0]["engine"] == "device"
    doc = validate_metrics(json.load(open(mp)))
    assert doc["counters"]["dispatches"] >= 7
    ph = doc["phases"]
    core = sum(ph.get(k, 0.0) for k in ("compile", "dispatch",
                                        "host_sync", "inflight",
                                        "check", "init",
                                        "boundary", "finish"))
    # ISSUE 2 acceptance: the four core phases cover >=90% of elapsed
    assert core >= 0.90 * res.elapsed, (ph, res.elapsed)
    assert sum(ph.values()) <= 1.05 * res.elapsed
    assert ph.get("compile", 0) > 0      # first dispatch charged there
    assert 0 < doc["gauges"]["fpset_occupancy"] <= 1.0
    assert "fpset_collision_rate" in doc["gauges"]


def test_stub_device_interp_journal_key_sets_match(tmp_path):
    ji, jd = str(tmp_path / "i.jsonl"), str(tmp_path / "d.jsonl")
    ri = bfs_check(counter_spec(), obs=RunObserver(journal_path=ji))
    rd = _stub_device_engine().run(obs=RunObserver(journal_path=jd))
    assert ri.distinct_states == rd.distinct_states == 16
    assert ri.levels == rd.levels

    def keysets(events):
        out = {}
        for e in events:
            out.setdefault(e["event"], set()).update(e.keys())
        return out
    ki, kd = keysets(read_journal(ji)), keysets(read_journal(jd))
    for ev in set(ki) & set(kd):
        assert ki[ev] == kd[ev], f"{ev} keys drifted between engines"
    for ev in ("run_start", "level_done", "run_end"):
        assert ev in ki and ev in kd


def test_stub_paged_bfs_spill_events(tmp_path):
    from tpuvsr.engine.paged_bfs import PagedBFS
    jp = str(tmp_path / "paged.jsonl")
    eng = _stub_device_engine(cls=PagedBFS, chunk_tiles=1)
    res = eng.run(obs=RunObserver(journal_path=jp))
    assert res.ok and res.distinct_states == 16
    events = read_journal(jp)
    spills = [e for e in events if e["event"] == "spill"]
    assert spills, "paged run must journal its host page-outs"
    # bytes reflect REAL transfer volume: the packed row (ISSUE 9; the
    # stub layout packs 4 dense planes into one uint32 word)
    rb = eng._state_row_bytes()
    assert rb == 4 and eng._pk is not None
    assert all(e["bytes"] == e["rows"] * rb for e in spills)
    doc = validate_metrics(res.metrics)
    assert doc["counters"]["spill_rows"] == sum(
        e["rows"] for e in spills)
    assert doc["counters"]["spill_bytes"] > 0


def test_stub_recover_continues_one_journal(tmp_path):
    """ISSUE 2 acceptance: a checkpoint/recover pair pointed at the
    same journal file yields ONE continuous journal with cumulative
    elapsed preserved."""
    ckpt = str(tmp_path / "stub.ckpt")
    jp = str(tmp_path / "run.jsonl")
    eng1 = _stub_device_engine()
    res1 = eng1.run(max_depth=3, checkpoint_path=ckpt,
                    obs=RunObserver(journal_path=jp))
    assert res1.error                          # depth-limited
    eng2 = _stub_device_engine()
    res2 = eng2.run(resume_from=ckpt,
                    obs=RunObserver(journal_path=jp))
    assert res2.ok and res2.distinct_states == 16
    events = read_journal(jp)
    starts = [e for e in events if e["event"] == "run_start"]
    assert [s["resumed"] for s in starts] == [False, True]
    ends = [e for e in events if e["event"] == "run_end"]
    assert len(ends) == 2
    assert any(e["event"] == "checkpoint" for e in events)
    # cumulative elapsed across the recover seam: segment 2 continues
    # the clock from the SNAPSHOT's recorded elapsed (res1.elapsed
    # additionally includes the post-snapshot tail — fsync-heavy
    # checkpoint writes — which the resumed timeline legitimately
    # does not)
    ck_ev = [e for e in events if e["event"] == "checkpoint"][-1]
    assert res2.elapsed >= ck_ev["elapsed_s"]
    assert ends[1]["elapsed_s"] >= ck_ev["elapsed_s"]
    # level_done depths continue instead of restarting at 1
    seg2 = events[events.index(starts[1]):]
    seg2_levels = [e["depth"] for e in seg2
                   if e["event"] == "level_done"]
    assert seg2_levels and min(seg2_levels) == 4
    # resumed exploration matches an uninterrupted oracle
    res3 = _stub_device_engine().run()
    assert res2.distinct_states == res3.distinct_states
    assert res2.levels == res3.levels


def test_stub_device_sim_metrics():
    from tpuvsr.engine.device_sim import DeviceSimulator
    sim = DeviceSimulator(counter_spec(), walkers=8, chunk_steps=4,
                          model_factory=_stub_factory())
    res = sim.run(num=8, depth=12, seed=1)
    assert res.ok and res.walks == 8 and res.steps > 0
    doc = validate_metrics(res.metrics)
    assert doc["engine"] == "device-sim"
    assert doc["counters"]["dispatches"] >= 3
    assert doc["phases"].get("compile", 0) > 0
    assert doc["gauges"]["steps_per_s"] > 0


@pytest.mark.skipif(len(__import__("jax").devices()) < 2,
                    reason="needs 2 virtual devices")
def test_stub_sharded_journal_and_shard_metrics(tmp_path):
    import jax
    from jax.sharding import Mesh
    from tpuvsr.parallel.sharded_bfs import ShardedBFS
    jp = str(tmp_path / "sharded.jsonl")
    mp = str(tmp_path / "sharded.json")
    mesh = Mesh(np.array(jax.devices()[:2]), ("d",))
    eng = ShardedBFS(counter_spec(), mesh, tile=4, bucket_cap=64,
                     next_capacity=1 << 6, fpset_capacity=1 << 8,
                     model_factory=_stub_factory())
    res = eng.run(obs=RunObserver(journal_path=jp, metrics_path=mp))
    assert res.ok and res.distinct_states == 16
    assert res.levels == [1, 2, 3, 4, 3, 2, 1]
    events = read_journal(jp)
    assert events[0]["engine"] == "sharded"
    assert [e["event"] for e in events].count("level_done") == 7
    doc = validate_metrics(json.load(open(mp)))
    # per-shard distinct counts, reduced on host 0
    shard = doc["gauges"]["shard_distinct"]
    assert len(shard) == 2 and sum(shard) == 16
    assert doc["gauges"]["exchange_useful_rows"] >= 15
    assert doc["counters"]["dispatches"] >= 7
    ph = doc["phases"]
    core = sum(ph.get(k, 0.0) for k in ("compile", "dispatch",
                                        "host_sync", "inflight",
                                        "check", "init",
                                        "boundary", "finish"))
    assert core >= 0.90 * res.elapsed, (ph, res.elapsed)


# ---------------------------------------------------------------------
# device engine (reference-gated, CPU backend)
# ---------------------------------------------------------------------
@requires_reference
def test_device_and_interp_journals_share_key_sets(tmp_path):
    from tpuvsr.engine.device_bfs import DeviceBFS
    spec = vsr_spec(values=("v1",), timer=0)
    ji = str(tmp_path / "interp.jsonl")
    jd = str(tmp_path / "device.jsonl")
    mi = str(tmp_path / "interp.json")
    md = str(tmp_path / "device.json")
    ri = bfs_check(vsr_spec(values=("v1",), timer=0),
                   obs=RunObserver(journal_path=ji, metrics_path=mi))
    eng = DeviceBFS(spec, tile_size=8)
    rd = eng.run(obs=RunObserver(journal_path=jd, metrics_path=md))
    assert ri.ok and rd.ok
    assert ri.distinct_states == rd.distinct_states
    assert ri.levels == rd.levels == eng.level_sizes
    ei, ed = read_journal(ji), read_journal(jd)

    def keysets(events):
        out = {}
        for e in events:
            out.setdefault(e["event"], set()).update(e.keys())
        return out
    ki, kd = keysets(ei), keysets(ed)
    for ev in set(ki) & set(kd):
        assert ki[ev] == kd[ev], f"{ev} keys drifted between engines"
    # both journals cover the golden event vocabulary for a clean run
    for ev in ("run_start", "level_done", "run_end"):
        assert ev in ki and ev in kd
    # metrics documents carry the same key sets too
    di = validate_metrics(json.load(open(mi)))
    dd = validate_metrics(json.load(open(md)))
    assert set(di) == set(dd)


@requires_reference
def test_device_phase_timers_sum_to_elapsed(tmp_path):
    """ISSUE 2 acceptance: compile + dispatch + host-sync + check sum
    to within 10% of wall-clock elapsed on a device run with
    -metrics."""
    from tpuvsr.engine.device_bfs import DeviceBFS
    mp = str(tmp_path / "m.json")
    eng = DeviceBFS(vsr_spec(values=("v1",), timer=0), tile_size=8)
    res = eng.run(obs=RunObserver(metrics_path=mp))
    assert res.ok
    doc = validate_metrics(json.load(open(mp)))
    ph = doc["phases"]
    core = sum(ph.get(k, 0.0) for k in ("compile", "dispatch",
                                        "host_sync", "inflight",
                                        "check", "init",
                                        "boundary", "finish"))
    assert core >= 0.90 * res.elapsed, (ph, res.elapsed)
    assert sum(ph.values()) <= 1.05 * res.elapsed, (ph, res.elapsed)
    assert doc["counters"]["dispatches"] >= 1
    assert 0.0 < doc["gauges"]["fpset_occupancy"] <= 1.0
    assert doc["gauges"]["distinct_per_s"] > 0


@requires_reference
def test_recover_continues_one_journal(tmp_path):
    """ISSUE 2 acceptance: a -checkpoint/-recover pair pointed at the
    same journal yields ONE continuous journal with cumulative elapsed
    preserved."""
    from tpuvsr.engine.device_bfs import DeviceBFS
    ckpt = str(tmp_path / "vsr.ckpt")
    jp = str(tmp_path / "run.jsonl")
    spec = vsr_spec(values=("v1",), timer=1)
    eng1 = DeviceBFS(spec, tile_size=32)
    res1 = eng1.run(max_depth=4, checkpoint_path=ckpt,
                    obs=RunObserver(journal_path=jp))
    assert res1.error                   # depth-limited
    eng2 = DeviceBFS(vsr_spec(values=("v1",), timer=1), tile_size=32)
    res2 = eng2.run(max_depth=7, resume_from=ckpt,
                    obs=RunObserver(journal_path=jp))
    events = read_journal(jp)
    starts = [e for e in events if e["event"] == "run_start"]
    assert [s["resumed"] for s in starts] == [False, True]
    # the resumed segment appended to the same file, after segment 1
    ends = [e for e in events if e["event"] == "run_end"]
    assert len(ends) == 2
    ckpts = [e for e in events if e["event"] == "checkpoint"]
    assert ckpts, "checkpointed run must journal checkpoint events"
    # cumulative elapsed: segment 2 continues the clock from the
    # snapshot's recorded elapsed
    assert res2.elapsed >= ckpts[-1]["elapsed_s"]
    assert ends[1]["elapsed_s"] >= ckpts[-1]["elapsed_s"]
    # level_done depths continue across the seam instead of restarting
    seg2_levels = [e["depth"] for e in events[events.index(starts[1]):]
                   if e["event"] == "level_done"]
    assert seg2_levels and min(seg2_levels) == 5
    assert ends[1]["distinct"] == res2.distinct_states
    # the resumed run matches an uninterrupted oracle
    eng3 = DeviceBFS(vsr_spec(values=("v1",), timer=1), tile_size=32)
    res3 = eng3.run(max_depth=7)
    assert res2.distinct_states == res3.distinct_states
    assert res2.levels == res3.levels


# ---------------------------------------------------------------------
# end-to-end trace correlation (ISSUE 17)
# ---------------------------------------------------------------------
def test_trace_helper_units():
    tids = {new_trace_id() for _ in range(64)}
    assert len(tids) == 64
    tid = tids.pop()
    assert re.fullmatch(r"[0-9a-f]{16}", tid)
    # the root span is DERIVABLE by any process that knows the trace
    assert root_span(tid) == "r" + tid[:8]
    assert root_span(tid) == root_span(tid)
    assert re.fullmatch(r"[0-9a-f]{8}", new_span_id())
    # trace_env omits unset members so a child never sees "None"
    assert trace_env(tid, parent_span="aaaa0001") == {
        "TPUVSR_TRACE_ID": tid, "TPUVSR_PARENT_SPAN": "aaaa0001"}
    assert trace_env() == {}


def test_trace_scope_sets_scrubs_and_restores_env(monkeypatch):
    monkeypatch.setenv("TPUVSR_TRACE_ID", "outer-trace")
    monkeypatch.setenv("TPUVSR_SPAN_ID", "outer-span")
    monkeypatch.delenv("TPUVSR_PARENT_SPAN", raising=False)
    with trace_scope("feedfacefeedface", parent_span="aaaa0001"):
        assert os.environ["TPUVSR_TRACE_ID"] == "feedfacefeedface"
        assert os.environ["TPUVSR_PARENT_SPAN"] == "aaaa0001"
        # the scope SCRUBS members it does not set — a child must not
        # inherit the outer scope's span as its own
        assert "TPUVSR_SPAN_ID" not in os.environ
    assert os.environ["TPUVSR_TRACE_ID"] == "outer-trace"
    assert os.environ["TPUVSR_SPAN_ID"] == "outer-span"
    assert "TPUVSR_PARENT_SPAN" not in os.environ


def test_journal_trace_stamping_and_env_suppression(tmp_path,
                                                    monkeypatch):
    p = str(tmp_path / "j.jsonl")
    # explicit context: stamped verbatim on every line
    j = Journal(p, run_id="r1", trace_id="feedfacefeedface",
                span_id="rfeedface")
    j.write("worker_heartbeat", job_id="x", worker="w0")
    j.close()
    # inherited context (trace_scope): the journal mints its OWN
    # segment span under the scope's parent
    with trace_scope("feedfacefeedface", parent_span="aaaa0001"):
        j2 = Journal(p, run_id="r2")
        j2.write("worker_heartbeat", job_id="x", worker="w0")
        j2.close()
        assert j2.span_id not in (None, "aaaa0001")
    # explicit "" suppresses the env fallback entirely (a threaded
    # worker's service journal beside a sibling job's scope)
    monkeypatch.setenv("TPUVSR_TRACE_ID", "contamination")
    j3 = Journal(p, run_id="r3", trace_id="", span_id="",
                 parent_span="")
    j3.write("worker_heartbeat", job_id="x", worker="w0")
    j3.close()
    rows = read_journal(p)
    assert rows[0]["trace_id"] == "feedfacefeedface"
    assert rows[0]["span_id"] == "rfeedface"
    assert rows[1]["trace_id"] == "feedfacefeedface"
    assert rows[1]["parent_span"] == "aaaa0001"
    assert rows[1]["span_id"] == j2.span_id
    assert "trace_id" not in rows[2] and "span_id" not in rows[2]


def test_stub_job_trace_chain_service_to_engine(tmp_path):
    """One stub job's journal reconstructs the whole story: submit
    (service root span) -> attempt (worker span parented on root) ->
    engine segment (minted span parented on the attempt)."""
    from tpuvsr.service import JobQueue, Worker
    q = JobQueue(str(tmp_path / "spool"))
    j = q.submit("<stub>", engine="device", flags={"stub": True})
    assert re.fullmatch(r"[0-9a-f]{16}", j.trace_id)
    Worker(q, devices=1).drain()
    assert q.get(j.job_id).state == "done"
    events = read_journal(q.journal_path(j.job_id))
    assert events
    # ONE trace: every event of the job carries the submit-minted id
    assert all(e.get("trace_id") == j.trace_id for e in events)
    by_kind = {}
    for e in events:
        by_kind.setdefault(e["event"], []).append(e)
    root = root_span(j.trace_id)
    sub = by_kind["job_submitted"][0]
    assert sub["span_id"] == root and "parent_span" not in sub
    started = by_kind["job_started"][0]
    attempt = started["span_id"]
    assert attempt != root and started["parent_span"] == root
    done = by_kind["job_done"][0]
    assert done["span_id"] == attempt
    # the engine-run segment minted its own span under the attempt
    rs = by_kind["run_start"][0]
    seg = rs["span_id"]
    assert seg not in (root, attempt)
    assert rs["parent_span"] == attempt
    for kind in ("level_done", "run_end"):
        assert all(e["span_id"] == seg for e in by_kind[kind])
    assert all(e["trace_id"] == j.trace_id
               for e in by_kind["sched_decision"])


def test_worker_pool_shell_jobs_propagate_trace_env(tmp_path):
    """Across PROCESS boundaries: each shell child of a 2-worker pool
    sees its submitting job's trace_id and the attempt span as
    TPUVSR_PARENT_SPAN — and no TPUVSR_SPAN_ID (the child's journals
    mint their own segment spans)."""
    from tpuvsr.serve import WorkerPool
    from tpuvsr.service import JobQueue
    from tpuvsr.testing import subprocess_env
    spool = str(tmp_path / "spool")
    q = JobQueue(spool)
    dump = ("import os, sys, json; "
            "json.dump({k: os.environ.get(k) for k in "
            "('TPUVSR_TRACE_ID', 'TPUVSR_SPAN_ID', "
            "'TPUVSR_PARENT_SPAN')}, open(sys.argv[1], 'w'))")
    jobs = []
    for i in range(4):
        out = str(tmp_path / f"env{i}.json")
        job = q.submit(f"env{i}", kind="shell",
                       flags={"argv": [sys.executable, "-c", dump,
                                       out],
                              "timeout": 60})
        jobs.append((job, out))
    pool = WorkerPool(spool, 2, devices=2, drain=True,
                      env=subprocess_env()).start()
    assert pool.wait(timeout=120) == [0, 0]
    q2 = JobQueue(spool)
    for job, out in jobs:
        assert q2.get(job.job_id).state == "done"
        with open(out) as f:
            seen = json.load(f)
        assert seen["TPUVSR_TRACE_ID"] == job.trace_id
        assert seen["TPUVSR_SPAN_ID"] is None
        parent = seen["TPUVSR_PARENT_SPAN"]
        assert parent and parent != root_span(job.trace_id)
        # the parent handed down IS the attempt span journaled at
        # job_started
        events = read_journal(q.journal_path(job.job_id))
        started = [e for e in events if e["event"] == "job_started"]
        assert started[-1]["span_id"] == parent
        assert all(e.get("trace_id") == job.trace_id for e in events)


def _trace_story():
    tid = "feedfacefeedface"
    root = "rfeedface"
    return tid, [
        {"event": "job_submitted", "ts": 100.0, "run_id": "svc",
         "job_id": "j1", "spec": "s.tla", "engine": "device",
         "trace_id": tid, "span_id": root},
        {"event": "sched_decision", "ts": 100.4, "run_id": "svc",
         "job_id": "j1", "tenant": None, "policy": "drr",
         "trace_id": tid, "span_id": root},
        {"event": "job_started", "ts": 100.5, "run_id": "svc",
         "job_id": "j1", "attempt": 1, "devices": 1,
         "trace_id": tid, "span_id": "aaaa0001",
         "parent_span": root},
        {"event": "run_start", "ts": 100.6, "run_id": "r1",
         "schema": "tpuvsr-journal/1", "engine": "device",
         "module": "M", "backend": "cpu", "resumed": False,
         "trace_id": tid, "span_id": "bbbb0001",
         "parent_span": "aaaa0001"},
        {"event": "fault", "ts": 104.0, "run_id": "r1",
         "kind": "oom", "depth": 2, "action": "degrade",
         "trace_id": tid, "span_id": "bbbb0001",
         "parent_span": "aaaa0001"},
        {"event": "run_end", "ts": 111.4, "run_id": "r1", "ok": True,
         "elapsed_s": 10.8, "distinct": 9, "trace_id": tid,
         "span_id": "bbbb0001", "parent_span": "aaaa0001"},
        {"event": "job_done", "ts": 111.5, "run_id": "svc",
         "job_id": "j1", "state": "done", "elapsed_s": 11.5,
         "trace_id": tid, "span_id": "aaaa0001",
         "parent_span": root},
    ]


def test_trace_view_span_tree_and_perfetto(tmp_path):
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "scripts"))
    import trace_view
    tid, story = _trace_story()
    jp = str(tmp_path / "j1.jsonl")
    with open(jp, "w") as f:
        for ev in story:
            f.write(json.dumps(ev) + "\n")
        f.write('{"event": "torn')              # held back, not fatal
    events = trace_view.load_events(jp)
    assert len(events) == len(story)
    got_tid, spans = trace_view.build_spans(events)
    assert got_tid == tid
    assert set(spans) == {"rfeedface", "aaaa0001", "bbbb0001"}
    assert spans["aaaa0001"]["parent"] == "rfeedface"
    assert spans["bbbb0001"]["parent"] == "aaaa0001"
    assert trace_view._label(spans["rfeedface"]) == "service"
    assert trace_view._label(spans["aaaa0001"]) == "attempt"
    assert trace_view._label(spans["bbbb0001"]) == "engine-run"
    buf = io.StringIO()
    trace_view.render_tree(got_tid, spans, out=buf)
    tree = buf.getvalue()
    assert f"trace {tid}" in tree
    # the tree nests service -> attempt -> engine-run and surfaces
    # the fault as a mark line
    assert tree.index("[service]") < tree.index("[attempt]") \
        < tree.index("[engine-run]")
    assert "! fault" in tree
    rows = trace_view.perfetto_events(got_tid, spans)
    slices = [r for r in rows if r["ph"] == "X"]
    instants = [r for r in rows if r["ph"] == "i"]
    assert len(slices) == 3 and len(instants) == 1
    assert instants[0]["name"] == "fault"
    by_span = {r["args"]["span_id"]: r for r in slices}
    assert by_span["aaaa0001"]["ts"] == 100.5 * 1e6
    # an old journal with no trace keys folds into ONE untraced span
    legacy = str(tmp_path / "legacy.jsonl")
    with open(legacy, "w") as f:
        for ev in story[:3]:
            ev = {k: v for k, v in ev.items()
                  if k not in ("trace_id", "span_id", "parent_span")}
            f.write(json.dumps(ev) + "\n")
    got, spans = trace_view.build_spans(trace_view.load_events(legacy))
    assert got is None and set(spans) == {"untraced"}


# ---------------------------------------------------------------------
# the one span primitive, build counters, named stages (ISSUE 25)
# ---------------------------------------------------------------------
class _Recorder:
    """An injected annotation factory: records every TraceAnnotation
    the observer would open, with its attributes, and its close."""

    def __init__(self):
        self.log = []           # ("open", name, attrs) | ("close", name)

    def __call__(self, name, **attrs):
        rec = self

        class _Ann:
            def __enter__(self):
                rec.log.append(("open", name, attrs))

            def __exit__(self, *exc):
                rec.log.append(("close", name))
                return False
        return _Ann()

    def opened(self):
        return [e[1] for e in self.log if e[0] == "open"]

    def assert_nested(self):
        stack = []
        for e in self.log:
            if e[0] == "open":
                stack.append(e[1])
            else:
                assert stack and stack.pop() == e[1], self.log
        assert not stack, stack


def test_span_keeps_exclusive_phase_contract():
    from tpuvsr.obs import spans
    obs = RunObserver(annotation=lambda: None)
    obs.start(time.time(), backend="host")
    with obs.span(spans.DISPATCH, depth=1):
        time.sleep(0.02)
        with obs.span(spans.HOST_SYNC):
            time.sleep(0.02)
    with pytest.raises(RuntimeError):
        with obs.span(spans.CHECKPOINT, depth=2):
            raise RuntimeError("inside a span")
    # the frame of the failed span was closed on the way out: only the
    # root is open, and drain() (finish/close) closes that
    assert [f[0] for f in obs.metrics._stack] == ["check"]
    with pytest.raises(KeyError):
        obs.span("level 7 dispatch")        # not in the vocabulary
    span = obs.span(spans.INFLIGHT)
    span.__enter__()
    obs.close()                             # drains the open frame
    span.__exit__(None, None, None)         # and this is then a no-op
    ph = obs.metrics.phases
    assert set(ph) == {"check", "dispatch", "host_sync", "checkpoint",
                       "inflight"}
    assert ph["dispatch"] >= 0.015 and ph["host_sync"] >= 0.015
    assert ph["dispatch"] < ph["host_sync"] + 0.05      # exclusive
    assert not obs.metrics._stack


@pytest.mark.parametrize("kind", ["spans", "parts"])
def test_span_annotations_follow_the_fixed_vocabulary(tmp_path, kind):
    from tpuvsr.obs import spans
    rec = _Recorder()
    asked = []

    def factory():
        asked.append(1)
        return rec
    with trace_scope("feedc0de00000001"):
        obs = RunObserver(journal_path=str(tmp_path / "j.jsonl"),
                          annotation=factory)
        res = _stub_device_engine(pipeline=2).run(
            obs=obs, checkpoint_path=str(tmp_path / "ck"))
    assert res.ok and res.levels == [1, 2, 3, 4, 3, 2, 1]
    assert asked == [1]         # decided once, at start()
    rec.assert_nested()
    names = rec.opened()
    assert set(names) <= set(spans.ENGINE_SPANS) | set(spans.ENGINE_PARTS)
    assert not any(re.search(r"\d", n) for n in names)
    doc = res.metrics
    if kind == "parts":
        # a part lies directly inside its phase's span, and the
        # document's `phase_parts` names the parts that were opened (a
        # read-back's lie where JAX read the cache, under any phase)
        stack, seen = [], set()
        for e in rec.log:
            if e[0] == "close":
                stack.pop()
                continue
            if e[1] in spans.ENGINE_PARTS \
                    and e[1] not in spans.READ_BACK_PARTS:
                phase, part = spans.ENGINE_PARTS[e[1]]
                assert spans.ENGINE_SPANS[stack[-1]] == phase, rec.log
                seen.add((phase, part))
            stack.append(e[1])
        assert seen == {(phase, part)
                        for phase, parts in doc["phase_parts"].items()
                        for part in parts}
        assert {phase for phase, _ in seen} == {"init", "checkpoint"}
        return
    names = [n for n in names if n in spans.ENGINE_SPANS]
    # the root comes first and closes last, and carries the run's ids
    first, last = rec.log[0], rec.log[-1]
    assert first[:2] == ("open", spans.CHECK)
    assert first[2] == {"run_id": obs.run_id,
                        "trace_id": "feedc0de00000001"}
    assert last == ("close", spans.CHECK)
    # one annotation per phase entry: every dispatch, every snapshot
    n_launch = names.count(spans.BUILD) + names.count(spans.DISPATCH)
    assert n_launch == doc["counters"]["dispatches"]
    assert names.count(spans.BUILD) == 1
    assert names.count(spans.INIT) == 1
    assert names.count(spans.CHECKPOINT) == doc["counters"]["checkpoints"]
    assert names.count(spans.CHECKPOINT) >= 1
    assert names.count(spans.INFLIGHT) >= 1
    # a boundary before the first level and after each, closed by the
    # next launch or by the run's end; one finish
    assert names.count(spans.BOUNDARY) == len(res.levels) + 1
    assert names.count(spans.FINISH) == 1
    assert rec.log[-3:-1] == [("open", spans.FINISH, {}),
                              ("close", spans.FINISH)]
    assert [e[2] for e in rec.log
            if e[0] == "open" and e[1] == spans.BOUNDARY] \
        == [{"depth": d} for d in range(len(res.levels) + 1)]
    assert {spans.ENGINE_SPANS[n] for n in names} == set(doc["phases"])
    # numbers ride as attributes
    depths = [e[2]["depth"] for e in rec.log
              if e[0] == "open" and e[1] == spans.DISPATCH]
    assert depths and all(isinstance(d, int) for d in depths)


def test_span_with_profiling_off_never_makes_an_annotation(monkeypatch):
    import jax.profiler
    monkeypatch.delenv("TPUVSR_PROFILE", raising=False)

    def boom(*a, **kw):
        raise AssertionError("TraceAnnotation made with profiling off")
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
    reads = []
    real = os.environ.get
    res = _stub_device_engine().run()
    assert res.ok and res.metrics["phases"]["dispatch"] > 0
    # the spans held open across statements are phase frames too
    assert res.metrics["phases"]["boundary"] > 0
    assert "finish" in res.metrics["phases"]
    # and no span reads the environment: a run's reads of
    # TPUVSR_PROFILE do not grow with its dispatches
    monkeypatch.setattr(
        "tpuvsr.obs.profiler.profile_dir",
        lambda: reads.append(1) or real("TPUVSR_PROFILE") or None)
    res = _stub_device_engine().run()
    assert res.metrics["counters"]["dispatches"] >= 7
    assert len(reads) <= 2      # start(): the session and the factory


def test_profile_trace_opens_without_python_tracer(tmp_path, monkeypatch):
    from tpuvsr.obs.profiler import annotation_factory, profile_trace
    monkeypatch.delenv("TPUVSR_PROFILE", raising=False)
    assert annotation_factory() is None
    import jax.profiler
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda *a, **kw: calls.append("start"))
    with profile_trace() as on:
        assert on is False and not calls           # off: a no-op
    monkeypatch.setenv("TPUVSR_PROFILE", str(tmp_path / "prof"))
    assert annotation_factory() is jax.profiler.TraceAnnotation

    def fake_start_trace(directory, profiler_options=None):
        calls.append((directory, profiler_options.python_tracer_level,
                      profiler_options.host_tracer_level))
    monkeypatch.setattr(jax.profiler, "start_trace", fake_start_trace)
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append("stop"))
    with profile_trace() as on:
        assert on is True
    assert calls == [(str(tmp_path / "prof"), 0, 2), "stop"]

    # a session is already active: the run carries on untraced-by-us
    def refused(directory, profiler_options=None):
        raise RuntimeError("Only one profile may be run at a time.")
    monkeypatch.setattr(jax.profiler, "start_trace", refused)
    said = []
    with profile_trace(log=said.append) as on:
        assert on is False
    assert said and "continuing" in said[0]


def _small_jit(salt):
    import jax
    import jax.numpy as jnp

    def fresh_program(x):
        return (x * salt + 1).sum()
    fresh_program.__name__ = f"fresh_program_{salt}"
    return jax.jit(fresh_program)(jnp.arange(8.0)).block_until_ready()


def test_build_counters_follow_the_running_observer(tmp_path,
                                                    monkeypatch):
    import threading

    from tpuvsr.obs import builds
    monkeypatch.setattr(builds, "JOURNAL_BUILD_S", 0.0)
    jp = str(tmp_path / "j.jsonl")
    _small_jit(101)                     # outside any observer: nobody's
    a = RunObserver(journal_path=jp)
    a.engine = "device"
    a.start(time.time(), backend="cpu")
    _small_jit(102)
    other = []
    t = threading.Thread(target=lambda: other.append(_small_jit(103)))
    t.start()
    t.join()                            # another thread: not a's
    n_a = a.builds.programs
    assert n_a >= 1 and other
    res = a.finish(_Res())
    doc = validate_metrics(res.metrics)
    assert doc["counters"]["build_programs"] == n_a
    g = doc["gauges"]
    assert g["build_trace_s"] > 0 and g["build_lower_s"] > 0
    assert g["build_backend_s"] > 0 and g["build_cache_load_s"] >= 0
    assert {"build_cache_hits", "build_cache_misses"} <= set(
        doc["counters"])
    _small_jit(104)                     # after finish(): nobody's
    assert a.builds.programs == n_a
    # a second observer in sequence starts from nothing
    b = RunObserver()
    b.engine = "device"
    b.start(time.time(), backend="cpu")
    assert b.builds.programs == 0
    _small_jit(105)
    seen = []

    def in_thread():
        c = RunObserver()
        c.engine = "device"
        c.start(time.time(), backend="cpu")
        _small_jit(106)
        seen.append(c.builds.programs)
        c.finish(_Res())
    t = threading.Thread(target=in_thread)
    t.start()
    t.join()
    assert seen and seen[0] >= 1
    n_b = b.builds.programs
    assert 1 <= n_b < n_a + 2 and a.builds.programs == n_a
    b.finish(_Res())
    # the journal's build events validate and name their program
    evs = [e for e in read_journal(jp) if e["event"] == "build"]
    assert evs and len(evs) == n_a
    assert any("fresh_program_102" in e["fun_name"] for e in evs)
    assert not any("fresh_program_103" in e["fun_name"] for e in evs)
    for e in evs:
        assert e["cache"] in ("hit", "miss", "none")
        assert e["trace_s"] >= 0 and e["lower_s"] >= 0
        assert e["backend_s"] >= 0


def test_build_meter_counts_nested_traces_once():
    from tpuvsr.obs import builds
    now = [100.0]
    reported = []
    m = builds.BuildMeter(report=reported.append, clock=lambda: now[0])
    # an inner jit's trace (1 s, ending at t=102) lies inside the
    # outer's (3 s, ending at t=103): the union is 3 s, not 4
    now[0] = 102.0
    m.duration(builds.TRACE_EVENT, 1.0, "inner")
    now[0] = 103.0
    m.duration(builds.TRACE_EVENT, 3.0, "level")
    m.duration(builds.LOWER_EVENT, 2.0, "jit_level")
    m.event(builds.CACHE_HIT_EVENT)
    m.duration(builds.CACHE_LOAD_EVENT, 0.5)
    m.duration(builds.BACKEND_EVENT, 0.75, "jit_level")
    assert (m.trace_s, m.lower_s, m.backend_s) == (3.0, 2.0, 0.75)
    assert (m.programs, m.cache_hits, m.cache_load_s) == (1, 1, 0.5)
    assert reported == [{"fun_name": "jit_level", "trace_s": 3.0,
                         "lower_s": 2.0, "backend_s": 0.75,
                         "cache": "hit", "export": "none"}]
    # a millisecond jit is counted and not journaled
    now[0] = 104.0
    m.duration(builds.TRACE_EVENT, 0.001, "tiny")
    m.duration(builds.BACKEND_EVENT, 0.002, "jit_tiny")
    assert m.programs == 2 and len(reported) == 1


class _Res:
    """The least a result needs for RunObserver.finish."""
    ok = True
    elapsed = 0.0


def _lowered_stages(jitted, *args):
    text = jitted.lower(*args).as_text(debug_info=True)
    return set(re.findall(r"tpuvsr\.(?:level|shard)\.[a-z_]+", text))


def _level_args(eng):
    import jax.numpy as jnp
    from tpuvsr.engine.fpset import empty_table
    bufs = eng._alloc_bufs(eng.next_cap)
    i32 = jnp.zeros((), jnp.int32)
    return ({"slots": empty_table(eng.fpset_capacity)["slots"]},
            bufs[0], i32, i32, *bufs, i32, jnp.zeros((), bool),
            None, None, i32)


def test_level_program_stages_are_named():
    from tpuvsr.obs import spans
    from tpuvsr.testing import STUB_LEVELS, stub_sym_engine
    common = {spans.GUARD_MATRIX, spans.COMPACT, spans.EXPAND,
              spans.FINGERPRINT, spans.FPSET_INSERT, spans.PACK_SCATTER,
              spans.INVARIANTS}
    fused = _stub_device_engine()
    assert fused.commit == "fused" and fused._pk is not None
    assert _lowered_stages(fused._level, *_level_args(fused)) == common
    per_action = _stub_device_engine(commit="per-action")
    assert _lowered_stages(per_action._level,
                           *_level_args(per_action)) == common
    sym = stub_sym_engine()
    assert sym._canon is not None
    assert _lowered_stages(sym._level, *_level_args(sym)) \
        == common | {spans.CANON}
    assert set(spans.LEVEL_STAGES) >= common | {spans.CANON}
    # metadata only: the pinned stub run is what it was
    res = fused.run()
    assert res.levels == list(STUB_LEVELS) and res.distinct_states == 16
    assert per_action.run().levels == list(STUB_LEVELS)


@pytest.mark.skipif(len(__import__("jax").devices()) < 2,
                    reason="needs 2 virtual devices")
def test_sharded_step_stages_are_named():
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from tpuvsr.obs import spans
    from tpuvsr.testing import STUB_LEVELS, stub_sharded_engine
    eng = stub_sharded_engine(n_devices=2)
    D, N = eng.D, eng.N
    sh = NamedSharding(eng.mesh, P("d"))

    def put(x):
        return jax.device_put(x, sh)
    rows = put(jnp.zeros((D * N, eng._pk.words), jnp.uint32))
    col = put(jnp.zeros((D * N,), jnp.int32))
    per_dev = put(jnp.zeros((D,), jnp.int32))
    args = ({"slots": put(jnp.zeros((D, eng.fp_cap, 5), jnp.uint32))},
            rows, per_dev, per_dev, rows, col, col, col, per_dev,
            per_dev)
    got = _lowered_stages(eng._step, *args)
    assert got == {spans.GUARD_MATRIX, spans.COMPACT, spans.EXPAND,
                   spans.FINGERPRINT, spans.FPSET_INSERT,
                   spans.PACK_SCATTER, spans.INVARIANTS,
                   spans.SHARD_BUCKET, spans.SHARD_ALL_TO_ALL}
    assert eng.run().levels == list(STUB_LEVELS)
