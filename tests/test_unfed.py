"""What the host costs the chip, measured inside the program (ISSUE 35).

* ``Metrics`` on an injected clock: the unfed clock's seconds land on
  the phase that is current, split exactly where a phase begins or
  ends, and sum to the gauge ``unfed_s``; level rows say what a level
  cost.
* ``DispatchPipeline`` at window 1, 2 and 4 on a scripted clock: the
  unfed clock runs from the window's making, stops when an enqueue
  returns, never runs through a blocked wait, runs again after the
  collect that empties the queue and after a drain.
* ``DeviceBFS``, ``PagedBFS`` and ``ShardedBFS`` on the stub kernel:
  the ``boundary`` and ``finish`` phases exist, the phases still sum to
  the run's elapsed, every level row carries its cost, the rows add up
  to the document, and the instrumentation moved no state.
"""

import json

import pytest

from tpuvsr.engine.pipeline import DispatchPipeline
from tpuvsr.obs import (Metrics, RunObserver, read_journal, spans,
                        validate_metrics)
from tpuvsr.testing import (STUB_DISTINCT, STUB_LEVELS,
                            stub_device_engine, stub_sharded_engine)


class _Clock:
    """A clock that moves only when the test says so."""

    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def tick(self, dt):
        self.t += dt


# ---------------------------------------------------------------------
# the collector
# ---------------------------------------------------------------------
def test_unfed_seconds_land_on_the_current_phase():
    clk = _Clock()
    m = Metrics(clock=clk)
    m.begin("check")
    clk.tick(1.0)               # fed: nothing is charged
    m.unfed_start()
    clk.tick(2.0)               # check: 2 unfed
    m.begin("boundary")
    clk.tick(3.0)               # boundary: 3 unfed
    m.begin("checkpoint")
    clk.tick(4.0)               # checkpoint: 4 unfed
    m.end()
    clk.tick(5.0)               # boundary again: 3 + 5
    m.end()
    m.begin("dispatch")
    clk.tick(0.5)               # the enqueue: 0.5 unfed under dispatch
    m.unfed_stop()
    clk.tick(0.25)              # fed from here on
    m.end()
    m.begin("inflight")
    clk.tick(7.0)
    m.end()
    m.unfed_start()
    m.unfed_start()             # already running: no second start
    clk.tick(0.125)             # check: 2 + 0.125
    m.drain()
    assert m.unfed == {"check": 2.125, "boundary": 8.0,
                       "checkpoint": 4.0, "dispatch": 0.5}
    assert m.phases == {"check": 3.125, "boundary": 8.0,
                        "checkpoint": 4.0, "dispatch": 0.75,
                        "inflight": 7.0}
    doc = m.to_dict(run_id="r", engine="e", elapsed_s=22.875)
    assert doc["phases_unfed"] == m.unfed
    assert "inflight" not in doc["phases_unfed"]
    assert doc["gauges"]["unfed_s"] == sum(m.unfed.values()) == 14.625
    assert sum(doc["phases"].values()) == 22.875
    # drain() stopped the clock: a later frame starts fed
    m.begin("check")
    clk.tick(1.0)
    m.end()
    assert m.unfed["check"] == 2.125


def test_unfed_clock_outside_every_frame_charges_nothing():
    clk = _Clock()
    m = Metrics(clock=clk)
    m.unfed_start()
    clk.tick(5.0)               # no frame is open: nothing is timed
    m.begin("init")
    clk.tick(1.0)
    m.unfed_stop()
    m.end()
    assert m.unfed == {"init": 1.0} and m.phases == {"init": 1.0}
    # a run that never drove a window has no such section
    plain = Metrics(clock=clk)
    with plain.timer("check"):
        clk.tick(1.0)
    doc = plain.to_dict(run_id="r", engine="e", elapsed_s=1.0)
    assert "phases_unfed" not in doc and "unfed_s" not in doc["gauges"]
    validate_metrics(dict(doc, levels=[]))


def test_level_rows_say_what_a_level_cost():
    clk = _Clock()
    m = Metrics(clock=clk)
    m.begin("check")
    m.unfed_start()
    with m.timer("init"):
        clk.tick(2.0)
    m.begin("boundary")
    clk.tick(0.5)
    m.end()
    with m.timer("dispatch"):
        clk.tick(0.25)
        m.unfed_stop()
    m.count("dispatches")
    with m.timer("inflight"):
        clk.tick(4.0)
    m.unfed_start()
    m.begin("boundary")         # open through the row, as in the engines
    clk.tick(0.125)
    row1 = m.level(1, frontier=1, distinct=3, generated=3, elapsed_s=6.875)
    clk.tick(1.0)
    m.end()
    for _ in range(2):
        with m.timer("dispatch"):
            clk.tick(0.25)
            m.unfed_stop()
        m.count("dispatches")
    with m.timer("inflight"):
        clk.tick(8.0)
    m.unfed_start()
    clk.tick(0.5)               # under the root frame
    row2 = m.level(2, frontier=2, distinct=6, generated=7,
                   elapsed_s=16.875)
    with m.timer("finish"):
        clk.tick(0.0625)
    m.drain()
    assert row1 == {
        "depth": 1, "frontier": 1, "distinct": 3, "generated": 3,
        "elapsed_s": 6.875, "wall_s": 6.875, "unfed_s": 2.875,
        "dispatches": 1,
        "phases": {"init": 2.0, "boundary": 0.625, "dispatch": 0.25,
                   "inflight": 4.0}}
    assert row2 == {
        "depth": 2, "frontier": 2, "distinct": 6, "generated": 7,
        "elapsed_s": 16.875, "wall_s": 10.0, "unfed_s": 1.75,
        "dispatches": 2,
        "phases": {"boundary": 1.0, "dispatch": 0.5, "inflight": 8.0,
                   "check": 0.5}}
    # the rows and what `finish` adds after the last are the document
    total = {"finish": 0.0625}
    for row in (row1, row2):
        for k, v in row["phases"].items():
            total[k] = total.get(k, 0.0) + v
    assert total == m.phases
    assert row1["unfed_s"] + row2["unfed_s"] + 0.0625 \
        == sum(m.unfed.values())
    validate_metrics(m.to_dict(run_id="r", engine="e", elapsed_s=17.0))


def test_validate_metrics_accepts_documents_without_the_new_keys():
    old = {"schema": "tpuvsr-metrics/1", "run_id": "r", "engine": "e",
           "elapsed_s": 1.0, "phases": {"check": 1.0}, "counters": {},
           "gauges": {"overlap_saved_s": 0.1, "inserts_per_tile": 1},
           "levels": [{"depth": 1, "frontier": 1, "distinct": 3,
                       "generated": 3, "elapsed_s": 0.5}]}
    assert validate_metrics(old, strict=True) is old
    with pytest.raises(ValueError):
        validate_metrics(dict(old, phases_unfed={"check": -1.0}))
    with pytest.raises(ValueError):
        validate_metrics(dict(old, phases_unfed=[1.0]))


# ---------------------------------------------------------------------
# the dispatch window on a scripted clock
# ---------------------------------------------------------------------
ENQUEUE, DEVICE, PULL, HOST = 0.25, 8.0, 0.5, 2.0


def _scripted_window(K):
    """A window of depth K whose every step moves the clock by a known
    amount: an enqueue ENQUEUE, a blocked wait DEVICE, a scalar pull
    PULL; the test moves it HOST between calls."""
    clk = _Clock()
    obs = RunObserver(annotation=lambda: None)
    obs.metrics = Metrics(clock=clk)
    obs.start(0.0, backend="host")

    class _Ready:
        def block_until_ready(self):
            clk.tick(DEVICE)

    def fn():
        clk.tick(ENQUEUE)
        return object()

    def pull(out):
        clk.tick(PULL)
        return ()
    clk.tick(HOST)              # before the window exists: not counted
    pipe = DispatchPipeline(K, obs, ready=lambda out: _Ready())
    return clk, obs, pipe, fn, pull


@pytest.mark.parametrize("K", (1, 2, 4))
def test_unfed_clock_follows_the_window(K):
    clk, obs, pipe, fn, pull = _scripted_window(K)
    m = obs.metrics
    with obs.span(spans.INIT):
        clk.tick(HOST)          # from the window's making: init counts
    obs.boundary(depth=0)
    clk.tick(HOST)
    # fill the window: the first enqueue ends the unfed time (and the
    # boundary span), the others find the device fed
    pipe.launch(fn, fresh=True, depth=1)
    assert m.unfed == {"init": HOST, "boundary": HOST,
                       "compile": ENQUEUE}
    assert obs._boundary is None and m.phases["boundary"] == HOST
    while pipe.has_room():
        pipe.launch(fn, depth=1)
    clk.tick(HOST)              # host work behind a full window
    fed = dict(m.unfed)
    if K == 1:
        # the launch blocked to completion: the device has been idle
        # since it returned, and waits through the pull too
        pipe.collect(pull)
        clk.tick(HOST)
        pipe.launch(fn, depth=1)
        assert m.unfed == {"init": HOST, "boundary": HOST,
                           "compile": ENQUEUE, "check": 2 * HOST,
                           "host_sync": PULL, "dispatch": ENQUEUE}
        assert "inflight" not in m.phases
    else:
        # collects that leave work in flight: the device stays fed
        for _ in range(K - 1):
            pipe.collect(pull)
            clk.tick(HOST)
        assert m.unfed == fed
        # the collect of the last ticket: unfed from the end of the
        # blocked wait, so through the pull and the host work after it
        pipe.collect(pull)
        clk.tick(HOST)
        pipe.launch(fn, depth=1)
        assert m.unfed == dict(fed, host_sync=PULL, check=HOST,
                               dispatch=ENQUEUE)
        assert m.phases["inflight"] == K * DEVICE
    # a drain: the tickets are dropped, the device counts as unfed
    before = dict(m.unfed)
    if pipe.has_room():
        pipe.launch(fn, depth=1)
    assert pipe.drain() >= 1
    obs.boundary(depth=1)
    clk.tick(HOST)
    # a wait on the chain tip is the device's work, not unfed time
    pipe.wait(pipe._ready(None))
    clk.tick(HOST)
    pipe.launch(fn, depth=2)
    grown = {k: m.unfed[k] - before.get(k, 0.0) for k in m.unfed
             if m.unfed[k] != before.get(k, 0.0)}
    assert grown == {"boundary": 2 * HOST, "dispatch": ENQUEUE}, grown
    assert "inflight" not in m.unfed
    assert all(v <= m._phases_at(clk.t)[k] for k, v in m.unfed.items())
    # a budget stop drops REAL chunks: they keep the device fed
    before = dict(m.unfed)
    if K > 1:
        assert pipe.drain(reason="budget") == 1
        clk.tick(HOST)
        assert m.unfed == before and m._unfed_since is None
    res = type("R", (), {"ok": True, "elapsed": 0.0})()
    doc = obs.finish(res).metrics
    assert abs(sum(doc["phases_unfed"].values())
               - doc["gauges"]["unfed_s"]) < 1e-9
    assert "overlap_saved_s" not in doc["gauges"]


# ---------------------------------------------------------------------
# the three BFS loops on the stub kernel
# ---------------------------------------------------------------------
def _paged(**kw):
    from tpuvsr.engine.paged_bfs import PagedBFS
    return stub_device_engine(cls=PagedBFS, chunk_tiles=1, **kw)


ENGINES = {
    "device": lambda: stub_device_engine(pipeline=2),
    "device-k1": lambda: stub_device_engine(pipeline=1),
    "paged": lambda: _paged(pipeline=2),
    "sharded": lambda: stub_sharded_engine(n_devices=2),
}
ROW_KEYS = {"wall_s", "phases", "unfed_s", "dispatches"}


@pytest.mark.parametrize("name", sorted(ENGINES))
def test_loops_carry_boundary_finish_and_level_costs(name, tmp_path):
    jp = str(tmp_path / "j.jsonl")
    mp = str(tmp_path / "m.json")
    res = ENGINES[name]().run(
        obs=RunObserver(journal_path=jp, metrics_path=mp),
        checkpoint_path=str(tmp_path / "ck"))
    # instrumentation moved no state
    assert res.ok and res.distinct_states == STUB_DISTINCT
    assert res.levels == STUB_LEVELS
    doc = validate_metrics(json.load(open(mp)), strict=True)
    ph = doc["phases"]
    assert ph["boundary"] > 0 and "finish" in ph
    assert ph["checkpoint"] > 0         # nested in boundary, its own
    core = sum(ph.get(k, 0.0) for k in (
        "compile", "dispatch", "host_sync", "inflight", "check", "init",
        "boundary", "finish", "checkpoint", "page_in", "page_out"))
    assert core >= 0.90 * res.elapsed, (ph, res.elapsed)
    assert sum(ph.values()) <= 1.05 * res.elapsed, (ph, res.elapsed)
    # the root frame keeps only what no span names
    assert ph["check"] <= 0.10 * res.elapsed, ph
    unfed = doc["phases_unfed"]
    assert "inflight" not in unfed
    assert all(0.0 <= v <= ph[k] + 1e-6 for k, v in unfed.items())
    assert abs(sum(unfed.values()) - doc["gauges"]["unfed_s"]) < 1e-4
    assert doc["gauges"]["unfed_s"] <= doc["elapsed_s"]
    # nothing is launched before init ends
    assert abs(unfed["init"] - ph["init"]) < 1e-5
    assert "overlap_saved_s" not in doc["gauges"]
    assert "inserts_per_tile" not in doc["gauges"]
    # every level row carries its cost; the rows and what the run does
    # after the last row (the end of the last boundary, `finish`) are
    # the document
    rows = doc["levels"]
    assert len(rows) == len(STUB_LEVELS)
    total = {}
    for row in rows:
        assert ROW_KEYS <= set(row)
        assert row["wall_s"] > 0 and row["dispatches"] >= 1
        assert abs(sum(row["phases"].values()) - row["wall_s"]) < 1e-4
        assert 0.0 <= row["unfed_s"] <= row["wall_s"] + 1e-6
        assert "finish" not in row["phases"]
        for k, v in row["phases"].items():
            total[k] = total.get(k, 0.0) + v
    assert sum(r["dispatches"] for r in rows) \
        == doc["counters"]["dispatches"]
    tail = {k: ph[k] - total.get(k, 0.0) for k in ph}
    assert abs(tail.pop("finish") - ph["finish"]) < 1e-9
    assert all(v > -1e-4 for v in tail.values()), tail
    # after the last row the run is in its last boundary, then finish
    assert sum(abs(v) for k, v in tail.items()
               if k not in ("boundary", "check", "host_sync")) < 1e-4, tail
    assert sum(r["unfed_s"] for r in rows) \
        <= doc["gauges"]["unfed_s"] + 1e-4
    # the journal's level_done carries the same cost (it outlives a
    # killed run)
    done = [e for e in read_journal(jp) if e["event"] == "level_done"]
    assert [(e["wall_s"], e["phases"], e["unfed_s"], e["dispatches"])
            for e in done] == [(r["wall_s"], r["phases"], r["unfed_s"],
                                r["dispatches"]) for r in rows]
    c = doc["counters"]
    # bytes at the boundary, counted where they move: the resident
    # engine's pointer slices, the sharded engine's zero buffers and
    # pointer planes; pages keep their own counters
    assert (c.get("boundary_pull_bytes", 0) > 0) == (name != "paged")
    assert ("boundary_put_bytes" in c) == (name == "sharded")


def test_sharded_boundary_bytes_are_the_buffers_it_moves():
    eng = stub_sharded_engine(n_devices=2)
    res = eng.run()
    c = res.metrics["counters"]
    levels = len(res.metrics["levels"])
    D, N, words = eng.D, eng.N, eng._pk.words
    # a level starts with three control vectors put from the host ...
    assert c["boundary_put_bytes"] == levels * 3 * D * 4
    # ... and the zero next buffer and three pointer planes filled on
    # the device, as the FPSet shards were at the run's start
    assert c["boundary_fill_bytes"] == levels * (
        D * N * words * 4 + 3 * D * N * 4) + D * eng.fp_cap * 5 * 4
    # ... and ends, while it found states, with the three pointer
    # planes pulled whole
    assert c["boundary_pull_bytes"] == (levels - 1) * 3 * D * N * 4


def test_stats_table_prints_unfed_beside_each_phase():
    lines = []
    res = stub_device_engine().run(
        obs=RunObserver(log=lines.append, table=True))
    assert res.ok
    table = [x for x in lines if x.startswith("phase seconds:")]
    assert len(table) == 1
    for phase in res.metrics["phases"]:
        assert f"{phase} " in table[0]
    assert table[0].count("unfed ") == len(res.metrics["phases"])
