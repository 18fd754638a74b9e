"""A traced slice ends at a depth, not at a second (ISSUE 42): the
configuration's `assumed.trace_depth` ends a --trace 1 run with whole
levels and nothing dropped, `check` holds it to the oracle cut there,
and a configuration without the key falls back to the traffic file's
`trace_seconds`.  Control flow on the CPU, at the rehearsal's
vsr-small; and the configurations' files as they are committed."""

import json
import os

import pytest

import cells

DEPTH = 6           # past the traffic files' warmup_depth of 3


@pytest.fixture
def small_with_depth(monkeypatch):
    """vsr-small has no `trace_depth`: give the rehearsal one."""
    real = cells.Cell.__init__

    def init(self, *args, **kw):
        real(self, *args, **kw)
        self.config["assumed"]["trace_depth"] = DEPTH
    monkeypatch.setattr(cells.Cell, "__init__", init)


def _record(rows):
    return next(r for r in rows if "workload" in r)


def _compared(rows):
    return {r["compared"]: r for r in rows if "compared" in r}


@pytest.mark.parametrize("workload", ["defect-bfs-timed",
                                      "defect-bfs-timed-paged",
                                      "defect-bfs-timed-4chip"])
def test_traced_window_ends_at_the_depth_with_nothing_dropped(
        run_cell, small_with_depth, workload):
    line, rows = run_cell(workload, seconds=600, trace=1)
    assert line["correct"] is True and line["failed"] == 0
    record, compared = _record(rows), _compared(rows)
    oracle = cells.Cell(workload, rehearse=True).oracle_levels()
    assert record["trace_depth"] == DEPTH
    assert record["levels"] == oracle[:DEPTH + 1]
    assert record["distinct"] == sum(oracle[:DEPTH + 1])
    assert compared["stopped_at_trace_depth"]["got"][1] == DEPTH
    assert f"levels.complete[0..{DEPTH}]" in compared
    assert "stopped_by_budget_or_at_last_pinned_depth" not in compared
    # the depth ended it: no dispatch was in flight for a stop to drop
    import run
    with open(os.path.join(run.OUT, workload, "seed7-trace1",
                           "window.metrics.json")) as f:
        counters = json.load(f)["counters"]
    assert counters.get("budget_dropped_dispatches", 0) == 0


def test_a_timed_run_reads_no_trace_depth(run_cell, small_with_depth):
    line, rows = run_cell("defect-bfs-timed", seconds=4, trace=0)
    assert line["correct"] is True
    assert "trace_depth" not in _record(rows)
    assert "stopped_by_budget_or_at_last_pinned_depth" in _compared(rows)


def test_check_compares_the_cut_oracle(run_cell, small_with_depth,
                                       monkeypatch):
    """A state lost inside the slice fails the cut comparison."""
    from tpuvsr.engine.device_bfs import DeviceBFS
    real = DeviceBFS.run

    def lossy(self, *args, **kw):
        res = real(self, *args, **kw)
        if kw.get("max_seconds"):
            res.levels[DEPTH] -= 1
            res.distinct_states -= 1
        return res
    monkeypatch.setattr(DeviceBFS, "run", lossy)
    line, rows = run_cell("defect-bfs-timed", seconds=600, trace=1)
    assert line["correct"] is False and line["failed"] == 1
    bad = [name for name, r in _compared(rows).items() if not r["ok"]]
    assert bad == [f"levels.complete[0..{DEPTH}]"]


def test_a_traced_run_the_budget_ended_is_not_correct(
        run_cell, small_with_depth, monkeypatch):
    from tpuvsr.engine.device_bfs import DeviceBFS
    real = DeviceBFS.run

    def hurried(self, *args, **kw):
        if kw.get("max_seconds"):
            kw = dict(kw, max_seconds=1e-3)
        return real(self, *args, **kw)
    monkeypatch.setattr(DeviceBFS, "run", hurried)
    line, rows = run_cell("defect-bfs-timed", seconds=600, trace=1)
    assert line["correct"] is False
    stop = _compared(rows)["stopped_at_trace_depth"]
    assert not stop["ok"] and stop["got"][0].startswith("time budget")


def test_without_the_key_the_slice_is_cut_by_trace_seconds(run_cell):
    line, rows = run_cell("defect-bfs-timed", seconds=600, trace=1)
    assert line["correct"] is True
    assert "trace_depth" not in _record(rows)
    stop = _compared(rows)["stopped_by_budget_or_at_last_pinned_depth"]
    seconds = cells.load_json("traffic", "bfs-timed.json")["trace_seconds"]
    assert stop["want"][0] == f"time budget after >= {seconds:g}s"


# ---- the configurations' files ------------------------------------

def _bfs_cells():
    doc = cells.benchmark_doc()
    return [w["name"] for w in doc["workloads"]
            if cells.load_json("traffic", w["traffic"] + ".json")["kind"]
            .startswith("bfs-timed")]


def test_six_bfs_cells():
    assert len(_bfs_cells()) >= 6


@pytest.mark.parametrize("workload", _bfs_cells())
def test_trace_depth_lies_between_warmup_and_pin(workload):
    cell = cells.Cell(workload)
    assumed = cell.config["assumed"]
    depth = assumed["trace_depth"]
    assert isinstance(depth, int)
    assert cell.traffic["warmup_depth"] < depth \
        <= cell.config["oracle"]["levels"]["complete_through_depth"]
    # one line of why, with the seconds and the states it gave
    why = assumed["trace_depth_why"]
    assert "\n" not in why and f"{sum(cell.oracle_levels()[:depth + 1]):,}" \
        in why
    # the engine's arguments stay the constructor's own
    for kw in assumed["engine"].values():
        assert "trace_depth" not in kw


def test_vsr_small_has_no_trace_depth():
    """The fallback's one user: the rehearsal's configuration."""
    assert "trace_depth" not in cells.load_json(
        "configs", "vsr-small.json")["assumed"]


def _receive_matching_svc_cap(config):
    from tpuvsr.engine.device_bfs import DeviceBFS
    from tpuvsr.engine.spec import load_spec
    doc = cells.load_json("configs", config + ".json")
    spec = load_spec(doc["module"], os.path.join(cells.BENCH, doc["cfg"]))
    kw = dict(doc["assumed"]["engine"]["device"],
              next_capacity=1 << 12, fpset_capacity=1 << 14)
    eng = DeviceBFS(spec, **kw)
    action = eng.kern.action_names.index("ReceiveMatchingSVC")
    return eng._expand_caps()[action], eng.tile


@pytest.mark.parametrize("config", ["vsr-shipped", "vsr-shipped-restart",
                                    "vr-state-transfer"])
def test_receive_matching_svc_cap_is_five_tiles(config):
    """ISSUE 42 (1): vsr-shipped carries the cap the two configurations
    beside it carry, so a faster engine grows nothing inside level 15."""
    cap, tile = _receive_matching_svc_cap(config)
    assert (cap, tile) == (640, 128)


def test_shipped_differs_from_restart_only_where_it_must():
    shipped, restart = (cells.load_json("configs", n + ".json")
                        ["assumed"]["engine"]["device"]
                        for n in ("vsr-shipped", "vsr-shipped-restart"))
    assert shipped["expand_mults"] == restart["expand_mults"]
    assert {k for k in shipped if shipped[k] != restart[k]} == \
        {"next_capacity"}
    assert (shipped["next_capacity"], shipped["fpset_capacity"]) == \
        (1 << 20, 1 << 24)
