"""Pipelined dispatch window tests (ISSUE 4).

Acceptance: for every window depth K the engines must explore the
IDENTICAL space — same distinct/generated counts, level sizes, and
violation traces — as the synchronous (-pipeline 1) path, across the
device, paged, and sharded engines, including with faults (oom, kill)
landing while a window is in flight and across a SIGTERM rescue /
resume seam.  Everything runs tier-1 on the stub harness
(tpuvsr/testing.py): no reference mount, no TPU.

Plus the new observability surface: the ``inflight`` phase keeps the
phase timers summing to wall-clock, the ``pipeline_depth`` /
``unfed_s`` gauges land in the metrics document.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from tpuvsr.obs import RunObserver, read_journal, validate_metrics
from tpuvsr.resilience import faults
from tpuvsr.resilience.supervisor import (Preempted, PreemptionGuard,
                                          Supervisor, clear_preemption)
from tpuvsr.testing import (STUB_DISTINCT, STUB_LEVELS, counter_spec,
                            stub_device_engine, stub_engine_factory)

WINDOWS = (1, 2, 4)


@pytest.fixture(autouse=True)
def _clean_fault_state():
    yield
    faults.clear()
    clear_preemption()


def _sig(res):
    """The equivalence signature the ISSUE pins across window depths."""
    return (res.distinct_states, res.states_generated, res.levels,
            res.metrics["gauges"].get("action_expansions"))


def _trace_sig(res):
    return (res.violated_invariant,
            [(e.action_name, e.state) for e in res.trace])


# ---------------------------------------------------------------------
# clean-run equivalence: device / paged / sharded x K in {1, 2, 4}
# ---------------------------------------------------------------------
def test_device_equivalence_across_windows():
    sigs = {}
    for K in WINDOWS:
        res = stub_device_engine(pipeline=K).run()
        assert res.ok and res.distinct_states == STUB_DISTINCT
        assert res.levels == STUB_LEVELS
        sigs[K] = _sig(res)
        assert res.metrics["gauges"]["pipeline_depth"] == K
    assert sigs[2] == sigs[1] and sigs[4] == sigs[1]
    # per-action counters sum to generated minus the one init state
    acts = sigs[1][3]
    assert sum(acts.values()) == sigs[1][1] - 1


def test_paged_equivalence_across_windows():
    from tpuvsr.engine.paged_bfs import PagedBFS
    sigs, spills = {}, {}
    for K in WINDOWS:
        eng = stub_device_engine(cls=PagedBFS, chunk_tiles=1,
                                 pipeline=K)
        res = eng.run()
        assert res.ok and res.levels == STUB_LEVELS
        sigs[K] = _sig(res)
        spills[K] = (eng.spill_count, eng.spill_rows)
    assert sigs[2] == sigs[1] and sigs[4] == sigs[1]
    # the spill schedule is part of the paged engine's semantics
    assert spills[2] == spills[1] and spills[4] == spills[1]


@pytest.mark.skipif(len(__import__("jax").devices()) < 2,
                    reason="needs 2 virtual devices")
def test_sharded_equivalence_across_windows():
    import jax
    from jax.sharding import Mesh
    from tpuvsr.parallel.sharded_bfs import ShardedBFS
    from tpuvsr.testing import stub_model_factory
    sigs = {}
    for K in WINDOWS:
        mesh = Mesh(np.array(jax.devices()[:2]), ("d",))
        eng = ShardedBFS(counter_spec(), mesh, tile=4, bucket_cap=64,
                         next_capacity=1 << 6, fpset_capacity=1 << 8,
                         model_factory=stub_model_factory(),
                         pipeline=K)
        res = eng.run()
        assert res.ok and res.distinct_states == STUB_DISTINCT
        assert res.levels == STUB_LEVELS
        sigs[K] = _sig(res) + (res.exchange["useful_rows"],)
    assert sigs[2] == sigs[1] and sigs[4] == sigs[1]


def test_violation_trace_equivalence_across_windows():
    from tpuvsr.engine.paged_bfs import PagedBFS
    oracle = None
    for K in WINDOWS:
        for cls, kw in ((None, {}), (PagedBFS, {"chunk_tiles": 1})):
            res = stub_device_engine(cls=cls, inv_bound=4,
                                     pipeline=K, **kw).run()
            assert not res.ok and res.violated_invariant == "Bound"
            sig = _trace_sig(res)
            if oracle is None:
                oracle = sig
            assert sig == oracle, (K, cls)


# ---------------------------------------------------------------------
# faults landing mid-window
# ---------------------------------------------------------------------
@pytest.mark.parametrize("K", [2, 4])
def test_oom_mid_window_supervised_exact_fixpoint(tmp_path, K):
    spec = counter_spec()
    faults.install("oom@level=3")
    sup = Supervisor(spec, checkpoint_path=str(tmp_path / "ck"),
                     engine_factory=stub_engine_factory(
                         spec, pipeline=K),
                     tile_size=4, min_tile=2, backoff_base=0.0,
                     sleep=lambda s: None)
    res = sup.run()
    assert res.ok and res.distinct_states == STUB_DISTINCT
    assert res.levels == STUB_LEVELS
    assert sup.attempts == 2 and ("tile", 4, 2) in sup.degrades


@pytest.mark.parametrize("K", [2, 4])
def test_kill_mid_window_rescue_resume_equivalence(tmp_path, K):
    ck = str(tmp_path / "ck")
    jp = str(tmp_path / "j.jsonl")
    faults.install("kill@level=3")
    preempted = None
    with PreemptionGuard():
        try:
            stub_device_engine(pipeline=K).run(
                checkpoint_path=ck, obs=RunObserver(journal_path=jp))
        except Preempted as p:
            preempted = p
    faults.clear()
    assert preempted is not None and preempted.depth == 3
    res2 = stub_device_engine(pipeline=K).run(resume_from=ck)
    assert res2.ok and res2.distinct_states == STUB_DISTINCT
    assert res2.levels == STUB_LEVELS
    ev = [e["event"] for e in read_journal(jp)]
    assert "rescue_checkpoint" in ev and "fault" in ev


# ---------------------------------------------------------------------
# phase accounting + journal/metrics surface
# ---------------------------------------------------------------------
def test_pipelined_phases_sum_to_elapsed(tmp_path):
    mp = str(tmp_path / "m.json")
    res = stub_device_engine(pipeline=4).run(
        obs=RunObserver(metrics_path=mp))
    assert res.ok
    doc = validate_metrics(json.load(open(mp)))
    ph = doc["phases"]
    core = sum(ph.get(k, 0.0) for k in ("compile", "dispatch",
                                        "host_sync", "inflight",
                                        "check", "init",
                                        "boundary", "finish"))
    assert core >= 0.90 * res.elapsed, (ph, res.elapsed)
    assert sum(ph.values()) <= 1.05 * res.elapsed, (ph, res.elapsed)
    g = doc["gauges"]
    assert g["pipeline_depth"] == 4
    # the unfed clock (ISSUE 35): by phase, never under inflight, no
    # more than each phase's own seconds, summing to the gauge
    unfed = doc["phases_unfed"]
    assert "inflight" not in unfed and "boundary" in unfed
    assert all(0.0 <= v <= ph[k] + 1e-6 for k, v in unfed.items())
    assert abs(sum(unfed.values()) - g["unfed_s"]) < 1e-4
    assert 0.0 < g["unfed_s"] <= doc["elapsed_s"]
    assert sum(g["action_expansions"].values()) \
        == res.states_generated - 1


def test_run_start_journals_pipeline_depth(tmp_path):
    from tpuvsr.engine.bfs import bfs_check
    jp = str(tmp_path / "j.jsonl")
    stub_device_engine(pipeline=3).run(obs=RunObserver(journal_path=jp))
    ji = str(tmp_path / "i.jsonl")
    bfs_check(counter_spec(), obs=RunObserver(journal_path=ji))
    dev = [e for e in read_journal(jp) if e["event"] == "run_start"][0]
    interp = [e for e in read_journal(ji)
              if e["event"] == "run_start"][0]
    # the key exists on EVERY engine (key-set parity); only the depth
    # differs
    assert dev["pipeline"] == 3
    assert interp["pipeline"] == 1


# ---------------------------------------------------------------------
# CLI flag surface
# ---------------------------------------------------------------------
def _cli(*argv):
    return subprocess.run(
        [sys.executable, "-m", "tpuvsr", *argv],
        capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "PYTHONPATH": os.path.dirname(os.path.dirname(
                 os.path.abspath(__file__))),
             "HOME": os.path.expanduser("~")})


def test_cli_pipeline_flag_validation():
    r = _cli("spec.tla", "-pipeline", "0")
    assert r.returncode == 2
    # a valid depth parses (fails later on the missing spec file, a
    # non-usage error)
    r = _cli("/nonexistent/spec.tla", "-pipeline", "2")
    assert r.returncode != 2


def test_cli_pipeline_runs_interp(tmp_path):
    from tpuvsr.testing import COUNTER, COUNTER_CFG
    (tmp_path / "ObsCounter.tla").write_text(COUNTER)
    (tmp_path / "ObsCounter.cfg").write_text(COUNTER_CFG)
    jp = tmp_path / "j.jsonl"
    r = _cli(str(tmp_path / "ObsCounter.tla"), "-engine", "interp",
             "-pipeline", "3", "-json", "-journal", str(jp))
    assert r.returncode == 0, r.stderr
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["ok"] is True
    start = [e for e in read_journal(str(jp))
             if e["event"] == "run_start"][0]
    assert start["pipeline"] == 1      # interp has no dispatch window


# ---------------------------------------------------------------------
# what the budget stop drops has its own counter (ISSUE 25)
# ---------------------------------------------------------------------
def test_drain_files_tickets_by_reason():
    from tpuvsr.engine.pipeline import DispatchPipeline

    class _Ready:
        def block_until_ready(self):
            return self

    obs = RunObserver(annotation=lambda: None)
    pipe = DispatchPipeline(3, obs, ready=lambda out: _Ready())
    for depth in (1, 1, 1):
        pipe.launch(lambda: object(), depth=depth)
    assert pipe.in_flight == 3
    assert pipe.drain() == 3                    # a pause: replays
    pipe.launch(lambda: object(), depth=2)
    pipe.launch(lambda: object(), fresh=True, depth=2)
    assert pipe.drain(reason="budget") == 2     # real chunks, dropped
    assert pipe.drain(reason="budget") == 0
    c = obs.metrics.counters
    assert c["pipeline_replays"] == 3
    assert c["budget_dropped_dispatches"] == 2
    assert c["dispatches"] == 5
    assert set(obs.metrics.phases) == {"dispatch", "compile"}


@pytest.mark.parametrize("K", WINDOWS)
def test_budget_stop_counts_the_dispatches_it_drops(K):
    # the budget is spent at the first collect: what is still in the
    # window (K - 1 tickets) is real work the run throws away
    res = stub_device_engine(pipeline=K).run(max_seconds=1e-9)
    assert res.error and res.error.startswith("time budget")
    c = res.metrics["counters"]
    assert c.get("budget_dropped_dispatches", 0) == K - 1
    assert c.get("pipeline_replays", 0) == 0
    # a run that ends by itself drops replays only
    c = stub_device_engine(pipeline=K).run().metrics["counters"]
    assert "budget_dropped_dispatches" not in c
    assert (c.get("pipeline_replays", 0) > 0) == (K > 1)
