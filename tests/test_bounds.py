"""Speclint pass 6 "bounds" (ISSUE 13): the symbolic interval
pre-pass and every engine seam that consumes it.

Groups:

* the analysis itself — exact intervals on the counter fixture, dead
  actions proven by constant folding AND by interval unsatisfiability,
  tightening REFUSED on a nonlinear guard, fanout/state-bound facts;
* consumption oracles — a `-bounds on` run must be bit-identical in
  verdict, counts, level sizes and violation traces to `-bounds off`
  across the device/paged/sharded engines, while packing strictly
  fewer bits, pruning the dead action, and (on the exact-fanout
  fixture) running ZERO expansion-growth redraws;
* the checkpoint seam — snapshots record the facts digest; resuming
  under a flipped `-bounds` is a policy error; the disk-spill
  streaming checkpoint writer (the PR 11 residual) keeps page-sized
  peak residency and resumes bit-identically;
* the service admission gate — a submission whose static state bound
  exceeds its requested tier is rejected before ever running.
"""

import os

import numpy as np
import pytest

from tpuvsr.analysis import run_lint
from tpuvsr.analysis.passes.bounds import analyze
from tpuvsr.core.values import TLAError
from tpuvsr.testing import (STUB_DISTINCT, STUB_LEVELS,
                            SYMPAIR_DISTINCT, counter_spec,
                            stub_device_engine, stub_model_factory,
                            stub_sym_engine, sym_pair_spec)


# ---------------------------------------------------------------------
# the analysis
# ---------------------------------------------------------------------
def test_counter_intervals_exact():
    f = analyze(counter_spec())
    assert f.tightened
    assert f.intervals == {"x": (0, 3), "y": (0, 3)}
    assert f.state_bound == STUB_DISTINCT          # 4 * 4 — exact
    assert f.fanout == {"IncX": 1, "IncY": 1}
    assert f.fanout_exact["IncX"] and f.fanout_exact["IncY"]
    assert not f.dead_actions


def test_dead_action_proven_by_folding():
    f = analyze(counter_spec(dead_action=True))
    assert f.dead_actions == ["Jump"]
    assert "FALSE" in f.dead_reasons["Jump"]
    assert f.tightened and f.state_bound == STUB_DISTINCT


def test_dead_action_proven_by_intervals():
    # Limit = 0: both guards are x < 0 against x in [0, 0] — dead by
    # interval refinement, not by pure folding (x is not an
    # aux-counter the vacuity fold knows about)
    f = analyze(counter_spec(limit=0))
    assert sorted(f.dead_actions) == ["IncX", "IncY"]
    assert f.state_bound == 1


def test_nonlinear_guard_refuses_tightening():
    f = analyze(counter_spec(nonlinear_guard=True))
    assert not f.tightened
    assert "interval domain" in f.refused
    assert f.intervals == {} and f.state_bound is None
    # dead-by-folding facts would still be sound; none exist here
    assert not f.dead_actions


def test_range_membership_guard_refines_not_refuses():
    # `x \in 0..K` is a common guard idiom: it must REFINE through the
    # same _domain_value logic Init/binder chains use, not trigger the
    # whole-spec refusal (code-review follow-up)
    from tpuvsr.engine.spec import SpecModel
    from tpuvsr.frontend.cfg import parse_cfg_text
    from tpuvsr.frontend.parser import parse_module_text
    from tpuvsr.testing import COUNTER, COUNTER_CFG
    src = COUNTER.replace("/\\ x < Limit", "/\\ x \\in 0..2")
    spec = SpecModel(parse_module_text(src),
                     parse_cfg_text(COUNTER_CFG))
    f = analyze(spec)
    assert f.tightened
    assert f.intervals["x"] == (0, 3)      # 0..2 guard, then +1
    assert f.state_bound == 16


def test_sympair_fanout_and_state_bound():
    f = analyze(sym_pair_spec())
    assert f.fanout == {"WriteA": 3, "WriteB": 3}
    assert f.fanout_exact["WriteA"]
    # {0, v1, v2, v3} per register: 4 * 4 = 16 — exact off-symmetry
    assert f.state_bound == SYMPAIR_DISTINCT


def test_digest_tracks_cfg_and_facts():
    a = analyze(counter_spec())
    b = analyze(counter_spec(dead_action=True))
    c = analyze(counter_spec())
    assert a.digest == c.digest
    assert a.digest != b.digest


def test_lint_report_has_bounds_section():
    r = run_lint(counter_spec())
    assert "bounds" in r.passes_run
    doc = r.to_dict()["bounds"]
    assert doc["tightened"] and doc["state_bound"] == STUB_DISTINCT
    # the refusal is a WARN finding + tightened:false in the section
    r2 = run_lint(counter_spec(nonlinear_guard=True))
    assert r2.ok                                  # refusal is not an error
    assert r2.to_dict()["bounds"]["tightened"] is False
    assert any(f.passname == "bounds" for f in r2.warnings)


# ---------------------------------------------------------------------
# pack tightening
# ---------------------------------------------------------------------
def test_tightened_pack_spec_fewer_bits_exact_roundtrip():
    from tpuvsr.engine.pack import build_pack_spec
    codec, _kern = stub_model_factory()(counter_spec())
    facts = analyze(counter_spec())
    decl = build_pack_spec(codec)
    tight = build_pack_spec(codec, tighten=facts.plane_tighten())
    assert tight.total_bits < decl.total_bits
    assert tight.version != decl.version
    # every reachable row round-trips the tightened format exactly
    rows = {"status": np.zeros(16, np.int32),
            "x": np.repeat(np.arange(4, dtype=np.int32), 4),
            "y": np.tile(np.arange(4, dtype=np.int32), 4),
            "err": np.zeros(16, np.int32)}
    rt = tight.unpack_np(tight.pack_np(rows))
    for k in rows:
        assert np.array_equal(rows[k], rt[k])


def test_engine_builds_tightened_and_declared_specs():
    e = stub_device_engine()
    assert e._pk.total_bits < e._pk_decl.total_bits
    off = stub_device_engine(bounds=False)
    assert off._pk.total_bits == e._pk_decl.total_bits


def test_bounds_on_requires_live_lint_gate(monkeypatch):
    monkeypatch.setenv("TPUVSR_LINT", "off")
    with pytest.raises(TLAError):
        stub_device_engine(bounds=True)
    # auto silently stands down — engines run untightened
    e = stub_device_engine()
    assert e._facts is None and e._pk.total_bits == 8


def test_drift_pass_checks_tightened_roundtrip():
    # a codec whose layout stores values OUTSIDE the reachable
    # intervals the bounds pass derived (stale width edit) must fail
    # the extended drift cross-check at lint time (ISSUE 13 satellite
    # extending the PR 9 pack-drift fixture)
    from tpuvsr.analysis.passes.drift import check_bounds_drift
    from tpuvsr.analysis.report import LintReport
    spec = counter_spec()
    codec, _ = stub_model_factory()(spec)
    report = LintReport(module="stub")
    check_bounds_drift(spec, codec, report)
    assert report.ok                    # honest codec: clean

    class Stale(type(codec)):
        # encodes x shifted by +4: outside the reachable [0, 3]
        def encode(self, st):
            d = super().encode(st)
            d["x"] = np.int32(int(d["x"]) + 4)
            return d
    report2 = LintReport(module="stub")
    check_bounds_drift(spec, Stale(), report2)
    assert not report2.ok
    assert any("TIGHTENED" in f.message for f in report2.errors)


# ---------------------------------------------------------------------
# engine consumption oracles
# ---------------------------------------------------------------------
def _counts(res):
    return (res.ok, res.distinct_states, res.states_generated,
            res.levels, res.violated_invariant)


def test_device_bit_identity_and_dead_prune():
    on = stub_device_engine(dead_action=True)
    off = stub_device_engine(dead_action=True, bounds=False)
    assert on.kern.action_names == ["IncX", "IncY"]
    assert off.kern.action_names == ["IncX", "IncY", "Jump"]
    r_on, r_off = on.run(), off.run()
    assert _counts(r_on) == _counts(r_off)
    assert r_on.distinct_states == STUB_DISTINCT
    assert r_on.levels == STUB_LEVELS


def test_device_violation_trace_bit_identity():
    from tpuvsr.engine.device_bfs import DeviceBFS

    def trace_tuple(res):
        return [(t.action_name, tuple(sorted(t.state.items())))
                for t in res.trace]

    runs = []
    for b in ("auto", False):
        e = DeviceBFS(counter_spec(inv_bound=3, dead_action=True),
                      model_factory=stub_model_factory(
                          inv_bound=3, dead_action=True),
                      hash_mode="full", tile_size=4,
                      fpset_capacity=1 << 8, next_capacity=1 << 6,
                      bounds=b)
        runs.append(e.run())
    r_on, r_off = runs
    assert not r_on.ok and not r_off.ok
    assert r_on.violated_invariant == r_off.violated_invariant
    assert trace_tuple(r_on) == trace_tuple(r_off)


def test_paged_and_sharded_bit_identity():
    from tpuvsr.engine.paged_bfs import PagedBFS
    from tpuvsr.testing import stub_sharded_engine
    p_on = stub_device_engine(cls=PagedBFS, chunk_tiles=1).run()
    p_off = stub_device_engine(cls=PagedBFS, chunk_tiles=1,
                               bounds=False).run()
    assert _counts(p_on) == _counts(p_off)
    assert p_on.distinct_states == STUB_DISTINCT
    s_on = stub_sharded_engine(n_devices=2).run()
    s_off = stub_sharded_engine(n_devices=2, bounds=False).run()
    assert _counts(s_on) == _counts(s_off)
    assert s_on.distinct_states == STUB_DISTINCT


def test_fanout_caps_zero_growth_redraws():
    # SymPair, symmetry off, tile 8: one tile holds states with three
    # simultaneously enabled lanes per action.  The fanout-seeded caps
    # are the proven per-state maximum and never redraw (the ISSUE 13
    # zero-redraw acceptance); caps forced below it do (growth redraws
    # + recompiles) with identical results
    e_on = stub_sym_engine(symmetry=False, tile_size=8)
    assert e_on.expand_caps == [8 * e_on._facts.fanout[n]
                                for n in e_on.kern.action_names]
    r_on = e_on.run()
    e_off = stub_sym_engine(symmetry=False, tile_size=8, bounds=False)
    e_off.expand_caps = [8] * len(e_off.expand_caps)
    e_off._build(None)          # re-jit the level program at the caps
    r_off = e_off.run()
    assert r_on.distinct_states == r_off.distinct_states \
        == SYMPAIR_DISTINCT
    assert r_on.metrics["counters"].get("grow_expand_buffer", 0) == 0
    assert r_off.metrics["counters"].get("grow_expand_buffer", 0) > 0


def test_run_start_journal_bounds_key(tmp_path):
    from tpuvsr.obs import RunObserver, read_journal
    jp = tmp_path / "j.jsonl"
    stub_device_engine(dead_action=True).run(
        obs=RunObserver(journal_path=str(jp)))
    start = [e for e in read_journal(str(jp))
             if e["event"] == "run_start"][0]
    assert start["bounds"] == {"tightened": True,
                               "dead_actions": ["Jump"],
                               "state_bound": STUB_DISTINCT}
    # bounds off journals null (key-set parity preserved)
    jp2 = tmp_path / "j2.jsonl"
    stub_device_engine(bounds=False).run(
        obs=RunObserver(journal_path=str(jp2)))
    start2 = [e for e in read_journal(str(jp2))
              if e["event"] == "run_start"][0]
    assert start2["bounds"] is None
    assert set(start) == set(start2)


def test_refused_tightening_journaled_and_runs_declared(tmp_path):
    from tpuvsr.obs import RunObserver, read_journal
    spec = counter_spec(nonlinear_guard=True)
    e = stub_device_engine(spec=spec)
    assert e._facts is not None and not e._facts.tightened
    assert e._pk.total_bits == e._pk_decl.total_bits   # declared widths
    jp = tmp_path / "j.jsonl"
    r = e.run(obs=RunObserver(journal_path=str(jp)))
    assert r.ok
    start = [ev for ev in read_journal(str(jp))
             if ev["event"] == "run_start"][0]
    assert start["bounds"]["tightened"] is False
    assert r.metrics["gauges"]["bound_tightening_ratio"] == 1.0


def test_bounds_gauges():
    r = stub_device_engine(dead_action=True).run()
    g = r.metrics["gauges"]
    assert g["state_bound"] == STUB_DISTINCT
    assert g["dead_actions"] == 1
    assert g["bound_tightening_ratio"] > 1.0


# ---------------------------------------------------------------------
# checkpoint seams
# ---------------------------------------------------------------------
@pytest.mark.parametrize("engine", ["device", "paged", "sharded"])
def test_checkpoint_records_digest_and_refuses_flip(tmp_path, engine):
    # every engine refuses a snapshot it wrote itself, letter for letter
    import json
    from tpuvsr.testing import stub_bfs_engine
    ck = str(tmp_path / "ck")
    e = stub_bfs_engine(engine)
    e.run(checkpoint_path=ck, max_depth=4)
    with open(os.path.join(ck, "manifest.json")) as f:
        mf = json.load(f)
    assert mf["bounds"]["digest"] == e._facts.digest
    assert mf["bounds"]["tightened"] is True
    with pytest.raises(TLAError, match=(
            r"was written under bounds facts \S+ but this engine "
            r"consumes off; the tightened packing and pruned action "
            r"ids are not comparable — resume with the matching "
            r"-bounds setting \(and the same cfg constants\)$")):
        stub_bfs_engine(engine, bounds=False).run(resume_from=ck)
    # matched resume completes the exact fixpoint
    r = stub_bfs_engine(engine).run(resume_from=ck)
    assert r.distinct_states == STUB_DISTINCT
    assert r.levels == STUB_LEVELS


def test_off_checkpoint_refuses_on_resume(tmp_path):
    ck = str(tmp_path / "ck")
    stub_device_engine(bounds=False).run(checkpoint_path=ck,
                                         max_depth=4)
    with pytest.raises(TLAError, match="bounds"):
        stub_device_engine().run(resume_from=ck)
    r = stub_device_engine(bounds=False).run(resume_from=ck)
    assert r.distinct_states == STUB_DISTINCT


def test_spill_checkpoint_streams_and_resumes(tmp_path):
    # the PR 11 residual (ISSUE 13 satellite): a disk-spilled frontier
    # checkpoints through the chunked payload writer — peak resident
    # rows stay page-sized (tiny spill_ram_rows budget), and the
    # resumed run is bit-identical
    from tpuvsr.engine.paged_bfs import PagedBFS
    ck = str(tmp_path / "ck")
    sd = str(tmp_path / "spill")
    e = stub_device_engine(cls=PagedBFS, spill_dir=sd,
                           spill_ram_rows=1, chunk_tiles=1,
                           tile_size=2)
    r = e.run(checkpoint_path=ck)
    assert r.distinct_states == STUB_DISTINCT
    assert r.levels == STUB_LEVELS
    # streamed: checkpoints were fed page-sized blocks, and no block
    # ever held the whole widest frontier (peak-resident-rows
    # assertion — the old writer materialized all n_front rows)
    assert e._ckpt_blocks >= 2
    assert 0 < e._ckpt_peak_rows < max(STUB_LEVELS)
    e2 = stub_device_engine(cls=PagedBFS, spill_dir=sd,
                            spill_ram_rows=1, chunk_tiles=1,
                            tile_size=2)
    r2 = e2.run(resume_from=ck)
    assert r2.distinct_states == STUB_DISTINCT
    assert r2.levels == STUB_LEVELS


def test_chunked_frontier_roundtrip(tmp_path):
    # the writer/reader pair in isolation: chunked members reassemble
    # to the exact plane arrays
    from tpuvsr.engine.checkpoint import load_checkpoint, save_checkpoint
    ck = str(tmp_path / "ck")
    rows = {"x": np.arange(7, dtype=np.int32),
            "y": (np.arange(7, dtype=np.int32) * 3) % 5}

    def blocks():
        for lo, hi in ((0, 3), (3, 5), (5, 7)):
            yield {k: v[lo:hi] for k, v in rows.items()}

    save_checkpoint(
        ck, slots=np.zeros((4, 4), np.uint32), n_front=7,
        frontier_blocks=blocks(),
        h_parent=np.full(1, -1, np.int64),
        h_action=np.full(1, -1, np.int32),
        h_param=np.zeros(1, np.int32),
        init_dense=[{"x": np.int32(0), "y": np.int32(0)}],
        level_sizes=[1], depth=0, fp_count=1, states_generated=1,
        max_msgs=4, expand_mults=[2], elapsed=0.0)
    ckd = load_checkpoint(ck)
    assert np.array_equal(ckd["frontier"]["x"], rows["x"])
    assert np.array_equal(ckd["frontier"]["y"], rows["y"])


# ---------------------------------------------------------------------
# corpus (reference-gated): dead-action pruning on a real model
# ---------------------------------------------------------------------
from tests.conftest import requires_reference, vsr_spec  # noqa: E402


@requires_reference
def test_corpus_dead_action_pruned_and_bit_identical():
    """ISSUE 13 acceptance on a corpus model: the config-gating idiom
    (NoProgressChangeLimit = 0) makes NoProgressChange statically dead
    — the bounds pass proves it, the engine prunes it from the real
    VSR kernel's lane tables, and a bounded run is bit-identical to
    bounds off.  (Interval tightening is REFUSED on the corpus's
    function-valued guards — journaled tightened:false — so the
    consumable facts here are the dead action + declared packing.)"""
    from tpuvsr.engine.device_bfs import DeviceBFS
    spec = vsr_spec(timer=1)
    spec.cfg.constants["NoProgressChangeLimit"] = 0
    spec.ev.constants["NoProgressChangeLimit"] = 0
    facts = analyze(spec)
    assert "NoProgressChange" in facts.dead_actions
    assert not facts.tightened          # function-valued guards refuse
    on = DeviceBFS(spec, tile_size=32, fpset_capacity=1 << 14,
                   next_capacity=1 << 12)
    assert "NoProgressChange" not in on.kern.action_names
    spec2 = vsr_spec(timer=1)
    spec2.cfg.constants["NoProgressChangeLimit"] = 0
    spec2.ev.constants["NoProgressChangeLimit"] = 0
    off = DeviceBFS(spec2, tile_size=32, fpset_capacity=1 << 14,
                    next_capacity=1 << 12, bounds=False)
    assert "NoProgressChange" in off.kern.action_names
    r_on = on.run(max_states=400)
    r_off = off.run(max_states=400)
    assert (r_on.distinct_states, r_on.states_generated,
            r_on.levels) == (r_off.distinct_states,
                             r_off.states_generated, r_off.levels)


# ---------------------------------------------------------------------
# service admission
# ---------------------------------------------------------------------
def test_service_rejects_oversized_submission(tmp_path):
    from tpuvsr.service.queue import JobQueue
    from tpuvsr.service.worker import Worker
    q = JobQueue(str(tmp_path / "spool"))
    # the counter spec's static bound is 16 states; a tier priced at 8
    # provably cannot hold it -> rejected at admission, never runs
    too_small = q.submit("stub", flags={"stub": True,
                                        "tier_states": 8})
    fits = q.submit("stub", flags={"stub": True, "tier_states": 100})
    w = Worker(q, devices=1)
    w.drain(max_jobs=4)
    jr = q.get(too_small.job_id)
    assert jr.state == "failed"
    assert jr.reason == "bounds-admission"
    assert jr.result["state_bound"] == STUB_DISTINCT
    assert jr.result["advised_devices"] >= 1
    # the rejected job never reached running (no job_started event)
    from tpuvsr.obs import read_journal
    events = [e["event"] for e in
              read_journal(q.journal_path(too_small.job_id))]
    assert "job_started" not in events
    assert q.get(fits.job_id).state == "done"
    assert q.get(fits.job_id).result["distinct"] == STUB_DISTINCT
