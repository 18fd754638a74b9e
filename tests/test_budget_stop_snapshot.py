"""A run that its time budget stops inside a level leaves no snapshot
of that level (ISSUE 45): whatever is on disk after the stop is a whole
level, and resuming it ends at the full run's levels and count.

The stub counter spec, one state a tile, one tile a dispatch, nothing
in flight (`pipeline=1`): the j-th collect of the level at `depth`
moves the engine modules' clock past the budget, so the stop falls
after exactly j of that level's states."""

import time

import pytest

from tpuvsr.engine import device_bfs, paged_bfs
from tpuvsr.engine.device_bfs import DeviceBFS
from tpuvsr.engine.paged_bfs import PagedBFS
from tpuvsr.obs import RunObserver
from tpuvsr.testing import stub_device_engine

FULL = [1, 2, 3, 4, 3, 2, 1]
BUDGET = 600.0

# (depth, collects of that depth before the clock jumps): the level
# being expanded holds FULL[depth - 1] states, more than the collects
STOPS = [(3, 1), (3, 2), (4, 1), (4, 2), (5, 1)]


class _JumpAtCollect(RunObserver):
    """The clock jumps an hour at the `nth` progress report of level
    `depth`: the budget test that follows it stops the run."""

    def __init__(self, depth, nth, offset, **kw):
        super().__init__(**kw)
        self._depth, self._left, self._offset = depth, nth, offset

    def progress(self, depth=None, **kw):
        if depth == self._depth:
            self._left -= 1
            if self._left == 0:
                self._offset[0] = 3600.0
        return super().progress(depth=depth, **kw)


def _engine(cls):
    return stub_device_engine(cls=cls, tile_size=1, chunk_tiles=1,
                              pipeline=1)


@pytest.mark.parametrize("depth,nth", STOPS)
@pytest.mark.parametrize("cls", [DeviceBFS, PagedBFS])
def test_a_budget_stop_inside_a_level_resumes_whole(cls, depth, nth,
                                                    tmp_path, monkeypatch):
    assert nth < FULL[depth - 1]
    offset = [0.0]
    real = time.time

    class clock:
        time = staticmethod(lambda: real() + offset[0])
    for module in (device_bfs, paged_bfs):
        monkeypatch.setattr(module, "time", clock)
    path = str(tmp_path / "ck")
    res = _engine(cls).run(
        max_seconds=BUDGET, checkpoint_path=path, checkpoint_every=None,
        obs=_JumpAtCollect(depth, nth, offset))
    assert res.error == f"time budget {BUDGET}s reached"
    # the stop fell inside the level: its last entry is what `nth` of
    # its states gave (all of the next level already, at some stops)
    assert res.levels[:depth] == FULL[:depth]
    assert len(res.levels) == depth + 1
    assert 0 < res.levels[depth] <= FULL[depth]
    offset[0] = 0.0
    again = _engine(cls).run(resume_from=path)
    assert again.error is None and again.ok
    assert again.levels == FULL
    assert again.distinct_states == 16
