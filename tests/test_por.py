"""Speclint pass 7 "independence" + ample-set partial-order reduction
(ISSUE 16): the static analysis, the engine-side resolve/filter seam,
and every consumption oracle.

Groups:

* the analysis itself — access sets, the independence matrix,
  invariant visibility, monotone witnesses, per-action poisoning,
  the digest, and the lint-report surface;
* resolve_por / PORFilter — the policy switch (gate-off, temporal,
  -edges, non-fused commit blockers) and the eligibility tables;
* consumption oracles — POR on/off must be verdict- and
  deadlock-identical on every engine while the reduced run's counts
  only SHRINK: the ``inv_free`` counter fixture (live on device,
  paged, fused, chained AND sharded — both actions carry monotone
  witnesses) and the SymPair fixture (live single-device, inert
  sharded — no witness), plus inertness oracles (visible invariant,
  eligible-free filter) where counts must be bit-identical;
* trace honesty — a violation is preserved under POR even when the
  first-found witness trace differs;
* the journal/metrics surface — run_start ``por`` object with key-set
  parity, por_cut_ratio/ample_states gauges;
* the checkpoint seam — manifests record the facts digest; resuming
  under a flipped ``-por`` is a policy error in both directions;
* the host-interpreter cross-check — the unreduced device run matches
  the interpreter fixpoint exactly and the reduced run never exceeds
  it.
"""

import json
import os

import pytest

from tpuvsr.analysis import run_lint
from tpuvsr.analysis.passes.independence import analyze
from tpuvsr.core.values import TLAError
from tpuvsr.engine.por import PORFilter, resolve_por
from tpuvsr.engine.spec import SpecModel
from tpuvsr.frontend.cfg import parse_cfg_text
from tpuvsr.frontend.parser import parse_module_text
from tpuvsr.testing import (COUNTER, COUNTER_CFG, POR_STUB_DISTINCT,
                            POR_STUB_FULL, POR_STUB_KEPT,
                            POR_STUB_LEVELS, STUB_DISTINCT,
                            STUB_LEVELS, SYMPAIR_DISTINCT,
                            counter_spec, stub_device_engine,
                            stub_model_factory, stub_sharded_engine,
                            stub_sym_engine, stub_sym_factory,
                            sym_pair_spec)

#: the SymPair fixture's single-device reduction oracle (symmetry
#: off): WriteA/WriteB are independent and invisible, so 3 of the 16
#: states collapse — the one state where both registers still hold 0
#: after level 1 takes the ample shortcut
SYM_POR_DISTINCT = 13
SYM_POR_LEVELS = [1, 3, 9]
SYM_OFF_LEVELS = [1, 6, 9]


# ---------------------------------------------------------------------
# the analysis
# ---------------------------------------------------------------------
def test_counter_access_sets_matrix_and_visibility():
    f = analyze(counter_spec())
    assert f.action_names == ["IncX", "IncY"]
    assert f.reads == {"IncX": ["x"], "IncY": ["y"]}
    assert f.writes == {"IncX": ["x"], "IncY": ["y"]}
    # disjoint frames: independent...
    assert f.matrix == [[True, True], [True, True]]
    assert f.independent_pairs == 1
    # ...but the default Bound reads BOTH counters: visible (C2 fails)
    assert f.visible == {"IncX": True, "IncY": True}
    assert not f.poisoned and f.inv_refused is None


def test_inv_free_fixture_is_invisible_with_witnesses():
    f = analyze(counter_spec(inv_free=True))
    assert f.visible == {"IncX": False, "IncY": False}
    # x' = x + 1 under a finite bounds interval: strict-progress
    # witnesses on both actions (the sharded engine's static proviso)
    assert f.monotone == {"IncX": "x", "IncY": "y"}


def test_partial_visibility_tracks_invariant_reads():
    # Bound == x <= 2 reads only x: IncX visible, IncY invisible
    f = analyze(counter_spec(inv_x_bound=2))
    assert f.visible == {"IncX": True, "IncY": False}


def test_sympair_independent_invisible_no_witness():
    f = analyze(sym_pair_spec())
    assert f.independent_pairs == 1
    assert f.visible == {"WriteA": False, "WriteB": False}
    # assignment updates (r' = v), not increments: no static witness
    assert f.monotone == {"WriteA": None, "WriteB": None}


def test_unattributable_prime_poisons_one_action():
    # (y + 0)' is a prime over a compound expression: IncY's planes
    # cannot be attributed, so it alone goes dependent-with-all
    src = COUNTER.replace("/\\ y' = y + 1", "/\\ (y + 0)' = y + 1")
    spec = SpecModel(parse_module_text(src), parse_cfg_text(COUNTER_CFG))
    f = analyze(spec)
    assert list(f.poisoned) == ["IncY"]
    assert "prime" in f.poisoned["IncY"]
    assert f.matrix[0][1] is False and f.matrix[1][0] is False
    assert f.independent_pairs == 0
    # poisoning is per-action: IncX's sets are still attributed
    assert f.writes["IncX"] == ["x"]


def test_dead_actions_excluded_from_matrix():
    f = analyze(counter_spec(dead_action=True))
    assert f.pruned_dead == ["Jump"]
    assert f.action_names == ["IncX", "IncY"]


def test_digest_tracks_facts():
    a = analyze(counter_spec(inv_free=True))
    b = analyze(counter_spec())
    c = analyze(counter_spec(inv_free=True))
    assert a.digest == c.digest
    assert a.digest != b.digest          # visibility flips the facts


def test_lint_report_has_independence_extra():
    r = run_lint(counter_spec(inv_free=True))
    assert "independence" in r.passes_run
    doc = r.to_dict()["independence"]
    assert doc["independent_pairs"] == 1
    assert doc["matrix"] == [[True, True], [True, True]]
    assert doc["digest"] == analyze(counter_spec(inv_free=True)).digest
    # a poisoned action is a WARN finding, not an error
    src = COUNTER.replace("/\\ y' = y + 1", "/\\ (y + 0)' = y + 1")
    spec = SpecModel(parse_module_text(src), parse_cfg_text(COUNTER_CFG))
    r2 = run_lint(spec)
    assert r2.ok
    assert any(f.passname == "independence" for f in r2.warnings)


# ---------------------------------------------------------------------
# resolve_por / PORFilter
# ---------------------------------------------------------------------
def test_resolve_por_off_and_auto():
    spec = counter_spec(inv_free=True)
    assert resolve_por(spec, "off") is None
    assert resolve_por(spec, False) is None
    assert resolve_por(spec, None) is None
    assert resolve_por(spec, "auto") is analyze(spec)
    assert resolve_por(spec, "on") is analyze(spec)
    with pytest.raises(TLAError, match="por"):
        resolve_por(spec, "maybe")


def test_resolve_por_requires_live_lint_gate(monkeypatch):
    monkeypatch.setenv("TPUVSR_LINT", "off")
    spec = counter_spec(inv_free=True)
    assert resolve_por(spec, "auto") is None
    with pytest.raises(TLAError, match="speclint gate"):
        resolve_por(spec, "on")


@pytest.mark.parametrize("blocker,match", [
    ({"temporal": True}, "temporal"),
    ({"edges": True}, "edges"),
    ({"commit": "per-action"}, "fused"),
], ids=["temporal", "edges", "per-action"])
def test_resolve_por_blockers(blocker, match):
    spec = counter_spec(inv_free=True)
    # auto silently stands down; forced is a loud policy error
    assert resolve_por(spec, "auto", **blocker) is None
    with pytest.raises(TLAError, match=match):
        resolve_por(spec, "on", **blocker)


def test_filter_eligibility_tables():
    spec = counter_spec(inv_free=True)
    _, kern = stub_model_factory()(spec)
    filt = PORFilter(analyze(spec), kern)
    assert filt.n_eligible == 2 and filt.any_eligible
    assert filt.amat.tolist() == [[True, True], [True, True]]
    # both actions carry witnesses: the sharded proviso keeps both
    assert PORFilter(analyze(spec), kern, sharded=True).n_eligible == 2
    # the default counter's invariant reads both planes: C2 rejects
    # everything and the ineligible rows are all-False (self-veto)
    fv = PORFilter(analyze(counter_spec()), kern)
    assert fv.n_eligible == 0 and not fv.any_eligible
    assert not fv.amat.any()


def test_filter_sharded_proviso_needs_witness():
    spec = sym_pair_spec()
    _, kern = stub_sym_factory()(spec)
    assert PORFilter(analyze(spec), kern).n_eligible == 2
    # no monotone witness: the sharded static proviso keeps nothing
    sh = PORFilter(analyze(spec), kern, sharded=True)
    assert sh.n_eligible == 0
    assert sh.journal_doc()["sharded_proviso"] is True


# ---------------------------------------------------------------------
# engine consumption oracles
# ---------------------------------------------------------------------
def _verdict(res):
    return (res.ok, res.violated_invariant, res.error == "deadlock")


def test_device_reduction_verdict_and_deadlock_identity():
    on = stub_device_engine(spec=counter_spec(inv_free=True), por="on")
    r_on = on.run(check_deadlock=True)
    r_off = stub_device_engine(spec=counter_spec(inv_free=True),
                               por="off").run(check_deadlock=True)
    assert _verdict(r_on) == _verdict(r_off)
    assert r_on.error == "deadlock"        # (3, 3) survives reduction
    assert r_off.distinct_states == STUB_DISTINCT
    assert r_off.levels == STUB_LEVELS
    assert r_on.distinct_states == POR_STUB_DISTINCT
    assert r_on.levels == POR_STUB_LEVELS
    assert on._por_kept == POR_STUB_KEPT
    assert on._por_full == POR_STUB_FULL


def test_paged_reduction_parity():
    from tpuvsr.engine.paged_bfs import PagedBFS
    e = stub_device_engine(cls=PagedBFS, chunk_tiles=1,
                           spec=counter_spec(inv_free=True), por="on")
    r = e.run(check_deadlock=True)
    assert r.error == "deadlock"
    assert r.distinct_states == POR_STUB_DISTINCT
    assert r.levels == POR_STUB_LEVELS
    assert e._por_kept == POR_STUB_KEPT
    assert e._por_full == POR_STUB_FULL


def test_sharded_reduction_parity():
    # both actions carry monotone witnesses: the static proviso keeps
    # the reduction live on the owner-partitioned engine, with the
    # SAME fixpoint as the single-device C3 on this fixture
    e_on = stub_sharded_engine(n_devices=2,
                               spec=counter_spec(inv_free=True),
                               por="on", check_deadlock=True)
    r_on = e_on.run()
    r_off = stub_sharded_engine(n_devices=2,
                                spec=counter_spec(inv_free=True),
                                check_deadlock=True).run()
    assert _verdict(r_on) == _verdict(r_off)
    assert r_on.error == "deadlock"
    assert r_off.distinct_states == STUB_DISTINCT
    assert r_on.distinct_states == POR_STUB_DISTINCT
    assert r_on.levels == POR_STUB_LEVELS
    assert e_on._por_kept == POR_STUB_KEPT
    assert e_on._por_full == POR_STUB_FULL


def test_sympair_single_device_reduction():
    on = stub_sym_engine(symmetry=False, por="on")
    r_on = on.run()
    r_off = stub_sym_engine(symmetry=False, por="off").run()
    assert r_on.ok and r_off.ok
    assert r_off.distinct_states == SYMPAIR_DISTINCT
    assert r_off.levels == SYM_OFF_LEVELS
    assert r_on.distinct_states == SYM_POR_DISTINCT
    assert r_on.levels == SYM_POR_LEVELS
    from tpuvsr.engine.paged_bfs import PagedBFS
    r_p = stub_sym_engine(PagedBFS, symmetry=False, por="on").run()
    assert r_p.distinct_states == SYM_POR_DISTINCT
    assert r_p.levels == SYM_POR_LEVELS


def test_sympair_sharded_inert_without_witness():
    # no monotone witness -> the sharded filter keeps nothing: POR-on
    # must be bit-identical to off (inert, never silently unsound)
    from tpuvsr.testing import stub_sym_sharded
    e = stub_sym_sharded(n_devices=2, symmetry=False, por="on")
    assert not e._por_active
    r = e.run()
    assert r.ok and r.distinct_states == SYMPAIR_DISTINCT


def test_visible_invariant_keeps_por_inert():
    # the default Bound reads both counters: nothing is eligible and
    # POR-on is bit-identical to off — including generated counts
    on = stub_device_engine(por="on")
    r_on = on.run()
    r_off = stub_device_engine(por="off").run()
    assert r_on.distinct_states == r_off.distinct_states == STUB_DISTINCT
    assert r_on.levels == r_off.levels == STUB_LEVELS
    assert r_on.states_generated == r_off.states_generated
    assert r_on.metrics["gauges"]["por_cut_ratio"] == 1.0
    assert r_on.metrics["gauges"]["ample_states"] == 0


def test_reduction_bit_identical_across_bounds_modes():
    # POR composes with the bounds pre-pass: flipping -bounds must not
    # change the reduced fixpoint (facts prune dead actions first, so
    # the action universes agree either way on this fixture)
    a = stub_device_engine(spec=counter_spec(inv_free=True),
                           por="on").run()
    b = stub_device_engine(spec=counter_spec(inv_free=True),
                           por="on", bounds=False).run()
    assert (a.distinct_states, a.states_generated, a.levels) == \
        (b.distinct_states, b.states_generated, b.levels)


def test_violation_preserved_with_trace_honesty():
    # Bound == x <= 2: IncX is visible (never ample) but IncY is
    # eligible — the reduced run defers IncX behind ample IncY moves
    # and must still surface the violation; the first-found witness
    # trace may differ (trace honesty), the verdict cannot
    from tpuvsr.engine.device_bfs import DeviceBFS

    def mk(por):
        return DeviceBFS(counter_spec(inv_x_bound=2),
                         model_factory=stub_model_factory(inv_x_bound=2),
                         hash_mode="full", tile_size=4,
                         fpset_capacity=1 << 8, next_capacity=1 << 6,
                         por=por)
    r_on, r_off = mk("on").run(), mk("off").run()
    assert not r_on.ok and not r_off.ok
    assert r_on.violated_invariant == r_off.violated_invariant == "Bound"
    assert r_on.trace and r_off.trace
    assert r_on.trace[-1].state["x"] == r_off.trace[-1].state["x"] == 3


def test_engine_constructor_refuses_forced_on_under_blockers():
    with pytest.raises(TLAError, match="fused"):
        stub_device_engine(spec=counter_spec(inv_free=True),
                           por="on", commit="per-action")
    # auto stands down instead
    e = stub_device_engine(spec=counter_spec(inv_free=True),
                           por="auto", commit="per-action")
    assert e._por_facts is None
    r = e.run()
    assert r.distinct_states == STUB_DISTINCT


# ---------------------------------------------------------------------
# journal / metrics surface
# ---------------------------------------------------------------------
def test_run_start_journal_por_key(tmp_path):
    from tpuvsr.obs import RunObserver, read_journal
    jp = tmp_path / "j.jsonl"
    e = stub_device_engine(spec=counter_spec(inv_free=True), por="on")
    e.run(obs=RunObserver(journal_path=str(jp)))
    start = [ev for ev in read_journal(str(jp))
             if ev["event"] == "run_start"][0]
    assert start["por"] == {
        "digest": e._por.digest,
        "actions": 2,
        "eligible_actions": 2,
        "sharded_proviso": False,
        "independence": {"independent_pairs": 1, "poisoned": [],
                         "digest": e._por.digest}}
    # por off journals null (key-set parity preserved)
    jp2 = tmp_path / "j2.jsonl"
    stub_device_engine(spec=counter_spec(inv_free=True)).run(
        obs=RunObserver(journal_path=str(jp2)))
    start2 = [ev for ev in read_journal(str(jp2))
              if ev["event"] == "run_start"][0]
    assert start2["por"] is None
    assert set(start) == set(start2)


def test_sharded_journal_marks_proviso(tmp_path):
    from tpuvsr.obs import RunObserver, read_journal
    jp = tmp_path / "j.jsonl"
    stub_sharded_engine(n_devices=2, spec=counter_spec(inv_free=True),
                        por="on").run(
        obs=RunObserver(journal_path=str(jp)))
    start = [ev for ev in read_journal(str(jp))
             if ev["event"] == "run_start"][0]
    assert start["por"]["sharded_proviso"] is True


def test_cut_ratio_gauges():
    r = stub_device_engine(spec=counter_spec(inv_free=True),
                           por="on").run()
    g = r.metrics["gauges"]
    assert g["por_cut_ratio"] == round(POR_STUB_KEPT / POR_STUB_FULL, 4)
    assert g["por_cut_ratio"] < 1.0        # the acceptance floor
    assert g["ample_states"] == 3
    assert g["por_eligible_actions"] == 2
    # off runs emit NO por gauges (the observer only sees real knobs)
    r_off = stub_device_engine(spec=counter_spec(inv_free=True)).run()
    assert "por_cut_ratio" not in r_off.metrics["gauges"]


# ---------------------------------------------------------------------
# checkpoint seam
# ---------------------------------------------------------------------
@pytest.mark.parametrize("engine", ["device", "paged", "sharded"])
def test_checkpoint_records_digest_and_refuses_flip(tmp_path, engine):
    # every engine refuses a snapshot it wrote itself, letter for letter
    from tpuvsr.testing import stub_bfs_engine
    ck = str(tmp_path / "ck")
    e = stub_bfs_engine(engine, spec=counter_spec(inv_free=True),
                        por="on")
    e.run(checkpoint_path=ck, max_depth=3)
    with open(os.path.join(ck, "manifest.json")) as f:
        mf = json.load(f)
    assert mf["por"] == {"digest": e._por.digest,
                         "eligible_actions": 2,
                         "sharded_proviso": engine == "sharded"}
    with pytest.raises(TLAError, match=(
            r"was written under POR facts \S+ but this engine consumes "
            r"off; the explored state sets are not comparable — resume "
            r"with the matching -por setting \(and the same "
            r"spec/cfg\)$")):
        stub_bfs_engine(engine, spec=counter_spec(inv_free=True)).run(
            resume_from=ck)
    # matched resume completes the exact reduced fixpoint
    r = stub_bfs_engine(engine, spec=counter_spec(inv_free=True),
                        por="on").run(resume_from=ck)
    assert r.distinct_states == POR_STUB_DISTINCT
    assert r.levels == POR_STUB_LEVELS


# the resume variants below are slow-tier: tier-1 already covers the
# seam via test_checkpoint_records_digest_and_refuses_flip plus the
# fault matrix's kill-por-resume scenario (tests/test_resilience.py)
@pytest.mark.slow
def test_off_checkpoint_refuses_on_resume(tmp_path):
    ck = str(tmp_path / "ck")
    stub_device_engine(spec=counter_spec(inv_free=True)).run(
        checkpoint_path=ck, max_depth=3)
    with pytest.raises(TLAError, match="POR"):
        stub_device_engine(spec=counter_spec(inv_free=True),
                           por="on").run(resume_from=ck)
    r = stub_device_engine(spec=counter_spec(inv_free=True)).run(
        resume_from=ck)
    assert r.distinct_states == STUB_DISTINCT


@pytest.mark.slow
def test_paged_checkpoint_resume_bit_identical(tmp_path):
    from tpuvsr.engine.paged_bfs import PagedBFS
    ck = str(tmp_path / "ck")
    stub_device_engine(cls=PagedBFS, chunk_tiles=1,
                       spec=counter_spec(inv_free=True),
                       por="on").run(checkpoint_path=ck, max_depth=3)
    r = stub_device_engine(cls=PagedBFS, chunk_tiles=1,
                           spec=counter_spec(inv_free=True),
                           por="on").run(resume_from=ck)
    assert r.distinct_states == POR_STUB_DISTINCT
    assert r.levels == POR_STUB_LEVELS


@pytest.mark.slow
def test_sharded_checkpoint_resume_bit_identical(tmp_path):
    ck = str(tmp_path / "ck")
    stub_sharded_engine(n_devices=2, spec=counter_spec(inv_free=True),
                        por="on").run(checkpoint_path=ck, max_depth=3)
    with pytest.raises(TLAError, match="POR"):
        stub_sharded_engine(n_devices=2,
                            spec=counter_spec(inv_free=True)).run(
            resume_from=ck)
    r = stub_sharded_engine(n_devices=2,
                            spec=counter_spec(inv_free=True),
                            por="on").run(resume_from=ck)
    assert r.distinct_states == POR_STUB_DISTINCT
    assert r.levels == POR_STUB_LEVELS


@pytest.mark.slow
def test_convert_sharded_snapshot_keeps_por_manifest(tmp_path):
    # the supervisor's sharded -> paged degrade rung rewrites the
    # snapshot to single-device format; the POR identity must ride
    # the conversion or the resuming engine's flip check goes blind
    from tpuvsr.parallel.sharded_bfs import convert_sharded_snapshot
    ck = str(tmp_path / "ck")
    spec = counter_spec(inv_free=True)
    stub_sharded_engine(n_devices=2, spec=spec, por="on").run(
        checkpoint_path=ck, max_depth=3)
    assert convert_sharded_snapshot(ck, spec) is True
    with open(os.path.join(ck, "manifest.json")) as f:
        mf = json.load(f)
    assert mf["por"]["eligible_actions"] == 2
    assert mf["por"]["sharded_proviso"] is True
    # a POR-off single-device engine still refuses the converted
    # reduced snapshot
    from tpuvsr.engine.paged_bfs import PagedBFS
    with pytest.raises(TLAError, match="POR"):
        stub_device_engine(cls=PagedBFS, chunk_tiles=1,
                           spec=counter_spec(inv_free=True)).run(
            resume_from=ck)


# ---------------------------------------------------------------------
# host-interpreter cross-check
# ---------------------------------------------------------------------
def test_interp_cross_check():
    from tpuvsr.engine.bfs import bfs_check
    full = bfs_check(counter_spec(inv_free=True), check_deadlock=True)
    assert full.distinct_states == STUB_DISTINCT
    r_off = stub_device_engine(spec=counter_spec(inv_free=True),
                               por="off").run(check_deadlock=True)
    r_on = stub_device_engine(spec=counter_spec(inv_free=True),
                              por="on").run(check_deadlock=True)
    # the unreduced device run IS the interpreter fixpoint; the
    # reduced run shrinks (never grows) and keeps the verdict
    assert r_off.distinct_states == full.distinct_states
    assert r_on.distinct_states <= full.distinct_states
    assert (full.error == "deadlock") == (r_on.error == "deadlock")
    assert full.ok == r_on.ok == r_off.ok
