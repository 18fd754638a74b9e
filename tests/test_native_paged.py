"""The host-paged engine on the real VSR kernel, from committed files.

`tests/test_paged.py` compares `PagedBFS` with the interpreter and
needs the reference mount; tier-1 otherwise sees the engine on the
stub kernel only.  Here it runs the small check against the pinned
level sizes with a device chunk small enough that the deeper levels
page through it in several chunks, each drained to the host.
"""

import pytest

from tpuvsr.engine.paged_bfs import PagedBFS
from tpuvsr.obs import RunObserver, read_journal

DEPTH = 10
CHUNK_TILES = 4        # 512 states a chunk: levels 7-10 take 2-4


@pytest.mark.parametrize("pack", ["auto", False],
                         ids=["packed", "dense"])
def test_paged_native_exact_levels(small_native, small_pin, tmp_path,
                                   pack):
    jp = str(tmp_path / "j.jsonl")
    eng = PagedBFS(small_native, chunk_tiles=CHUNK_TILES, pack=pack)
    assert (eng._pk is not None) == (pack == "auto")
    res = eng.run(max_depth=DEPTH, obs=RunObserver(journal_path=jp))
    pin = small_pin[:DEPTH + 1]
    assert res.ok and res.levels == list(eng.level_sizes) == pin
    assert res.distinct_states == sum(pin)
    # every committed row went out to the host in a journaled page,
    # and the levels of several chunks in several pages
    spills = [e for e in read_journal(jp) if e["event"] == "spill"]
    assert len(spills) > DEPTH
    assert sum(e["rows"] for e in spills) == eng.spill_rows \
        == sum(pin[1:])
    row = eng._state_row_bytes()
    assert all(e["bytes"] == e["rows"] * row for e in spills)
    c = res.metrics["counters"]
    assert c["spill_rows"] == eng.spill_rows
    assert c["spills"] == len(spills)
    # and every parent came in once, in a journaled page of at most a
    # chunk's rows: a page a chunk, each the one shape (one block of
    # 512 rows in; out, the rows and their pointers)
    cc = CHUNK_TILES * eng.tile
    ins = [e for e in read_journal(jp) if e["event"] == "page_in"]
    assert [e["rows"] for e in ins] == [
        min(cc, n - at) for n in pin[:DEPTH] for at in range(0, n, cc)]
    assert all(e["bytes"] == e["rows"] * row for e in ins)
    assert c["page_ins"] == len(ins)
    assert c["page_in_rows"] == sum(pin[:DEPTH])
    assert c["page_in_bytes"] == sum(pin[:DEPTH]) * row
    assert max(e["rows"] for e in spills) <= cc
    assert c["page_shapes"] == 2
    assert len(eng.page_shapes["in"]) == len(eng.page_shapes["out"]) == 1
