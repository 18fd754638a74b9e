"""The host-paged engine's pages have one shape (ISSUE 31).

One `PagedBFS` on the native small check, its device chunk 512 rows
and its next buffer at the floor the level program allows, so that
levels 7-10 take several chunks and the buffer fills inside a chunk
(`R_NEXT_GROW`: a page-out that a full buffer forced, not the end of
a chunk).  Whatever a page holds, it goes in as one block of 512 rows
and comes out as blocks of 512: a run builds no program per page.
"""

import time

import pytest

from tpuvsr.core.values import TLAError
from tpuvsr.engine import paged_bfs
from tpuvsr.engine.paged_bfs import PagedBFS
from tpuvsr.obs import RunObserver, read_journal

DEPTH = 10
CHUNK_TILES = 4


@pytest.fixture(scope="module")
def paged(small_native, tmp_path_factory):
    """The one built engine of this module, and its first run (with a
    journal, as every later one: the end of such a run reduces the
    FPSet's statistics on the device, one program more)."""
    eng = PagedBFS(small_native, chunk_tiles=CHUNK_TILES,
                   next_capacity=1, requires=sorted(PagedBFS.PROVIDES))
    jp = str(tmp_path_factory.mktemp("first") / "j.jsonl")
    res = eng.run(max_depth=DEPTH, obs=RunObserver(journal_path=jp))
    return eng, res


def test_levels_equal_the_pin_with_spills_inside_chunks(paged, small_pin):
    eng, res = paged
    pin = small_pin[:DEPTH + 1]
    assert res.ok and res.levels == pin
    assert res.distinct_states == sum(pin)
    # the buffer sat at its floor, in whole pages
    cc = CHUNK_TILES * eng.tile
    assert eng.next_cap % cc == 0
    assert eng.next_cap - cc < eng._total_E() + eng.tile <= eng.next_cap
    assert eng.spill_count >= 1         # a full buffer paged out
    assert eng.spill_rows == sum(pin[1:])
    c = res.metrics["counters"]
    assert c["page_in_rows"] == sum(pin[:DEPTH])
    assert c["page_ins"] == sum(-(-n // cc) for n in pin[:DEPTH])
    assert c["spills"] >= c["page_ins"]
    assert res.metrics["phases"]["page_in"] > 0
    assert res.metrics["phases"]["page_out"] > 0


def test_one_page_shape_in_and_one_out(paged):
    eng, res = paged
    cc = CHUNK_TILES * eng.tile
    assert eng.page_shapes["in"] == {((cc, eng._pk.words),)}
    assert eng.page_shapes["out"] == {((cc, eng._pk.words), (3, cc))}
    assert res.metrics["counters"]["page_shapes"] == 2


def test_second_run_same_levels_no_new_shape_no_build(paged, small_pin,
                                                      tmp_path):
    eng, first = paged
    shapes = (set(eng.page_shapes["in"]), set(eng.page_shapes["out"]))
    jp = str(tmp_path / "j.jsonl")
    res = eng.run(max_depth=DEPTH, obs=RunObserver(journal_path=jp))
    assert res.levels == first.levels == small_pin[:DEPTH + 1]
    assert (eng.page_shapes["in"], eng.page_shapes["out"]) == shapes
    assert res.metrics["counters"]["page_shapes"] == 2
    # nothing was built for a page: not one program of the run's
    assert res.metrics["counters"].get("build_programs", 0) == 0
    assert "compile" not in res.metrics["phases"]
    events = read_journal(jp)
    ins = [e for e in events if e["event"] == "page_in"]
    outs = [e for e in events if e["event"] == "spill"]
    assert sum(e["rows"] for e in ins) == sum(first.levels[:DEPTH])
    assert sum(e["rows"] for e in outs) == sum(first.levels[1:])
    assert max(e["rows"] for e in ins + outs) <= CHUNK_TILES * eng.tile


class _ClockJump(RunObserver):
    """Puts the engine's clock an hour on at the first progress report
    of level `at`: the budget test that follows it stops the run."""

    def __init__(self, at, offset, **kw):
        super().__init__(**kw)
        self._at, self._offset = at, offset

    def progress(self, depth=None, **kw):
        if depth == self._at:
            self._offset[0] = 3600.0
        return super().progress(depth=depth, **kw)


def test_budget_that_cuts_a_level(paged, small_pin, monkeypatch):
    eng, _ = paged
    cut = 9
    offset = [0.0]
    real = time.time

    class clock:
        time = staticmethod(lambda: real() + offset[0])
    monkeypatch.setattr(paged_bfs, "time", clock)
    res = eng.run(max_seconds=600.0, obs=_ClockJump(cut, offset))
    assert res.error == "time budget 600.0s reached"
    levels = res.levels
    assert len(levels) == cut + 1
    assert levels[:cut] == small_pin[:cut]
    assert 0 < levels[cut] < small_pin[cut]     # several chunks, cut
    assert res.distinct_states == sum(levels)
    assert eng.spill_rows == sum(levels[1:])
    assert res.metrics["counters"]["page_shapes"] == 2


def test_requires_refuses_before_any_build(small_native, monkeypatch):
    def boom(*a, **kw):
        raise AssertionError("built before `requires` was checked")
    monkeypatch.setattr(PagedBFS, "_build", boom)
    with pytest.raises(TLAError, match="does not provide .'nope'."):
        PagedBFS(small_native, requires=["nope"])
