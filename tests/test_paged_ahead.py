"""The paged engine's dispatch window runs over pages (ISSUE 38).

While a page runs, the next one is cut, put and launched behind it; a
dispatch launched past a page's end, which ran the guard stage over a
whole page and committed nothing, is gone.  What is queued behind a
PAUSE runs no tile (`_page_start` decides on the device), is dropped
under `pipeline_replays` and counted under `pages_ahead_void`, and its
page is launched again from the array the host holds.

On the native small check, pages of 256 rows (levels 6-8 take 2-4):
one engine whose next buffer never fills and one whose buffer sits at
the floor the level program allows (it pauses inside a page with the
next one queued), both against the resident engine and the pin, row
for row; on the stub kernel, a counterexample at every window.
"""

import time

import numpy as np
import pytest

from tpuvsr.engine import paged_bfs
from tpuvsr.engine.device_bfs import DeviceBFS
from tpuvsr.engine.paged_bfs import PagedBFS
from tpuvsr.testing import stub_device_engine

from tests.test_native_paged_pages import _ClockJump

DEPTH = 9
CHUNK_TILES = 2


def _pointers(eng):
    eng._flush_pointers()       # the resident engine's lie on the device
    return tuple(np.concatenate(x) for x in (
        eng._h_parent, eng._h_action, eng._h_param))


@pytest.fixture(scope="module")
def resident(small_native):
    eng = DeviceBFS(small_native)
    res = eng.run(max_depth=DEPTH)
    return res, _pointers(eng)


@pytest.fixture(scope="module")
def roomy(small_native):
    """The next buffer holds a whole level: no pause, pages only."""
    eng = PagedBFS(small_native, chunk_tiles=CHUNK_TILES,
                   retain_levels=True)
    res = eng.run(max_depth=DEPTH)
    return eng, res, list(eng.level_blocks)


@pytest.fixture(scope="module")
def floored(small_native):
    """The next buffer at its floor: it fills inside a page."""
    eng = PagedBFS(small_native, chunk_tiles=CHUNK_TILES,
                   next_capacity=1, retain_levels=True)
    res = eng.run(max_depth=DEPTH)
    return eng, res, list(eng.level_blocks)


def _pages(pin, cc):
    return [-(-n // cc) for n in pin[:DEPTH]]


def _same_as_resident(eng, res, resident, pin):
    want, pointers = resident
    assert res.ok and res.levels == want.levels == pin[:DEPTH + 1]
    assert res.distinct_states == want.distinct_states == sum(res.levels)
    assert res.states_generated == want.states_generated
    for got, ref in zip(_pointers(eng), pointers):
        np.testing.assert_array_equal(got, ref)
    assert eng.spill_rows == sum(pin[1:DEPTH + 1])


def test_pages_queue_behind_pages_and_nothing_is_replayed(
        roomy, resident, small_pin):
    eng, res, _ = roomy
    _same_as_resident(eng, res, resident, small_pin)
    c = res.metrics["counters"]
    pages = _pages(small_pin, CHUNK_TILES * eng.tile)
    assert max(pages) >= 3
    # a dispatch a page, the first of each level with nothing before it
    assert c["dispatches"] == c["page_ins"] == sum(pages)
    assert c["pages_ahead"] == sum(n - 1 for n in pages)
    assert "pipeline_replays" not in c and "pages_ahead_void" not in c
    assert eng.spill_count == 0


def test_a_one_page_level_is_one_dispatch(roomy, small_pin):
    eng, res, _ = roomy
    pages = _pages(small_pin, CHUNK_TILES * eng.tile)
    rows = res.metrics["levels"]
    assert [r["dispatches"] for r in rows] == pages
    assert pages[:4] == [1, 1, 1, 1]


def test_a_pause_with_the_next_page_queued(floored, roomy, resident,
                                           small_pin):
    eng, res, blocks = floored
    _same_as_resident(eng, res, resident, small_pin)
    c = res.metrics["counters"]
    assert eng.spill_count >= 1
    # what stood behind a pause ran no tile, was dropped, and went in
    # once all the same
    assert c["pages_ahead_void"] >= 1
    assert c["pipeline_replays"] == c["pages_ahead_void"]
    assert c["page_ins"] == sum(_pages(small_pin,
                                       CHUNK_TILES * eng.tile))
    assert c["page_in_rows"] == sum(small_pin[:DEPTH])
    assert c["spill_rows"] == sum(small_pin[1:DEPTH + 1])
    assert c["page_shapes"] == 2
    # the frontier of every level, row for row, whatever the buffer
    other = roomy[2]
    assert len(blocks) == len(other) == DEPTH
    for a, b in zip(blocks, other):
        assert a.keys() == b.keys()
        for k in a:
            np.testing.assert_array_equal(a[k], b[k])


@pytest.mark.parametrize("K", [1, 3])
def test_other_windows_same_rows(floored, small_pin, K):
    eng, first, blocks = floored
    eng.pipe_window = K
    try:
        res = eng.run(max_depth=DEPTH)
    finally:
        eng.pipe_window = 2
    assert res.levels == first.levels
    assert res.states_generated == first.states_generated
    for a, b in zip(eng.level_blocks, blocks):
        for k in a:
            np.testing.assert_array_equal(a[k], b[k])
    c, c2 = res.metrics["counters"], first.metrics["counters"]
    # the page and spill schedule is the same at every window
    assert [c[k] for k in ("page_ins", "spills", "spill_rows")] \
        == [c2[k] for k in ("page_ins", "spills", "spill_rows")]
    if K == 1:
        assert "pages_ahead" not in c and "pipeline_replays" not in c
    else:
        assert c["pipeline_replays"] == c["pages_ahead_void"] >= 1


def test_a_budget_stop_counts_the_page_in_flight(roomy, small_pin,
                                                 monkeypatch):
    eng, _, _ = roomy
    cut = 8                     # level 7 goes in as three pages
    offset = [0.0]
    real = time.time

    class clock:
        time = staticmethod(lambda: real() + offset[0])
    monkeypatch.setattr(paged_bfs, "time", clock)
    res = eng.run(max_seconds=600.0, obs=_ClockJump(cut, offset))
    assert res.error == "time budget 600.0s reached"
    levels = res.levels
    assert levels[:cut] == small_pin[:cut]
    assert 0 < levels[cut] < small_pin[cut]
    assert res.distinct_states == sum(levels)
    assert eng.spill_rows == sum(levels[1:])
    # the stop came at the first page's collect with the second in
    # flight: that one was collected too, and no third went in
    c = res.metrics["counters"]
    cc = CHUNK_TILES * eng.tile
    assert c["page_in_rows"] == sum(small_pin[:cut - 1]) + 2 * cc
    assert c["dispatches"] == c["page_ins"]
    assert "pipeline_replays" not in c
    assert "budget_dropped_dispatches" not in c
    # at window 1 nothing is in flight at the stop: one page fewer
    eng.pipe_window, offset[0] = 1, 0.0
    try:
        one = eng.run(max_seconds=600.0, obs=_ClockJump(cut, offset))
    finally:
        eng.pipe_window = 2
    assert one.distinct_states == sum(one.levels)
    assert 0 < one.levels[cut] < levels[cut]


def test_a_state_limit_stops_between_levels(roomy, small_pin):
    eng, _, _ = roomy
    res = eng.run(max_states=1000)
    assert res.error == "state limit 1000 reached"
    assert res.levels == small_pin[:len(res.levels)]
    assert res.distinct_states == sum(res.levels) >= 1000
    assert eng.spill_rows == sum(res.levels[1:])
    assert "pipeline_replays" not in res.metrics["counters"]


def test_no_program_is_built_past_the_one_page_levels(roomy):
    """The benchmark warms the engine up to depth 3, levels of one
    page: a window then launches pages behind pages, and `_page_start`
    takes a dispatch's outputs where it took the host's scalars."""
    eng, _, _ = roomy
    paged_bfs._page_start.clear_cache()
    warm = eng.run(max_depth=3)
    assert warm.metrics["counters"]["build_programs"] == 1
    res = eng.run(max_depth=DEPTH)
    assert res.metrics["counters"]["pages_ahead"] > 0
    assert res.metrics["counters"].get("build_programs", 0) == 0
    assert "compile" not in res.metrics["phases"]


@pytest.mark.parametrize("K", [1, 2, 3])
def test_counterexample_over_pages(K):
    # tile 2, a page a tile: levels of 3 and 4 states are two pages,
    # and the violation is found with a page queued behind it
    want = stub_device_engine(inv_bound=4, tile_size=2).run()
    res = stub_device_engine(cls=PagedBFS, inv_bound=4, tile_size=2,
                             chunk_tiles=1, pipeline=K).run()
    assert not res.ok and res.violated_invariant == "Bound"
    assert [(e.action_name, e.state) for e in res.trace] \
        == [(e.action_name, e.state) for e in want.trace]


def test_page_start():
    from tpuvsr.engine.device_bfs import R_NEXT_GROW, RUNNING
    i32 = np.int32
    start = paged_bfs._page_start
    # nothing before it, or a page that ran to its end: it runs
    assert int(start(i32(0), i32(RUNNING), i32(0), i32(5), i32(8))) == 5
    assert int(start(i32(8), i32(RUNNING), i32(8), i32(0), i32(3))) == 0
    # behind a pause: past its own last tile
    assert int(start(i32(2), i32(R_NEXT_GROW), i32(8), i32(0), i32(3))) \
        == 4
    # and behind such a one: its `t` is not its page's tile count
    assert int(start(i32(4), i32(RUNNING), i32(3), i32(0), i32(8))) == 9
