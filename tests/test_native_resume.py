"""Snapshot and resume on the real VSR kernel, from committed files.

Every served job snapshots at every level boundary
(`service/worker.py` hands the supervisor a checkpoint path and no
cadence), and a requeued job resumes from the newest one.  The stub
harness pins the seam on a 16-state counter; these cases hold the two
engines a job can run on to the pinned level sizes of the small check
across it.
"""

import pytest

from tpuvsr.engine.device_bfs import DeviceBFS
from tpuvsr.engine.paged_bfs import PagedBFS
from tpuvsr.obs import RunObserver, read_journal

SNAPSHOT_DEPTH, END_DEPTH = 6, 10


@pytest.mark.parametrize("cls,kw", [
    (DeviceBFS, {"pipeline": 1}),
    (DeviceBFS, {"pipeline": 2}),
    (PagedBFS, {}),
], ids=["device-K1", "device-K2", "paged"])
def test_native_checkpoint_resume_exact_levels(small_native, small_pin,
                                               tmp_path, cls, kw):
    ck, jp = str(tmp_path / "ck"), str(tmp_path / "j.jsonl")
    first = cls(small_native, **kw).run(
        max_depth=SNAPSHOT_DEPTH, checkpoint_path=ck,
        obs=RunObserver(journal_path=jp))
    assert first.ok and first.levels == small_pin[:SNAPSHOT_DEPTH + 1]
    # a fresh engine: nothing of the writer but the snapshot
    eng = cls(small_native, **kw)
    res = eng.run(max_depth=END_DEPTH, resume_from=ck,
                  obs=RunObserver(journal_path=jp))
    pin = small_pin[:END_DEPTH + 1]
    assert res.ok and res.error == f"depth limit {END_DEPTH} reached"
    assert res.levels == list(eng.level_sizes) == pin
    assert res.distinct_states == sum(pin)
    assert res.diameter == END_DEPTH
    # one journal, one continuous run: the second segment says it is
    # resumed, counts on from the snapshot's depth and keeps the clock
    events = read_journal(jp)
    starts = [e for e in events if e["event"] == "run_start"]
    assert [e["resumed"] for e in starts] == [False, True]
    levels = [e for e in events if e["event"] == "level_done"]
    assert [e["depth"] for e in levels] == list(range(1, END_DEPTH + 1))
    assert [e["frontier"] for e in levels] == pin[:END_DEPTH]
    elapsed = [e["elapsed_s"] for e in levels]
    assert elapsed == sorted(elapsed)
    snaps = [e["depth"] for e in events if e["event"] == "checkpoint"]
    assert snaps == list(range(1, SNAPSHOT_DEPTH + 1))
