"""`correct` comes out true on a sound run, and false on the control
and on a timed path that is broken underneath."""

import pytest


def test_sound_run_is_correct(run_cell):
    line, rows = run_cell("defect-bfs-timed")
    assert line["correct"] is True and line["failed"] == 0
    assert line["rehearsal"] is True and line["metrics"] == {}
    assert all(r["ok"] for r in rows if "compared" in r)
    # each number compared beside its limit, last in the last line
    assert list(line)[-1] == "compared"
    assert set(line["compared"]) == {r["compared"] for r in rows
                                     if "compared" in r}
    assert all(c["ok"] and c["limit"] == 0
               for c in line["compared"].values())


@pytest.mark.parametrize("workload,bits", [("defect-bfs-timed", 16),
                                           ("defect-bfs-timed", 20),
                                           ("small-verdict", 24)])
def test_control_narrow_fingerprints_is_not_correct(run_cell, workload,
                                                    bits):
    """The control: exact dedup broken by keeping `bits` of the 128
    fingerprint bits.  Colliding states merge, levels fall short."""
    import control
    with control.narrow_fingerprints(bits):
        line, rows = run_cell(workload, seconds=6)
    assert line["correct"] is False
    bad = [r for r in rows if "compared" in r and not r["ok"]]
    assert bad and line["failed"] > 0


def test_dropped_state_is_not_correct(run_cell, monkeypatch):
    """The timed path broken underneath: the engine loses one state of
    its last complete level on the way out."""
    from tpuvsr.engine.device_bfs import DeviceBFS
    real = DeviceBFS.run

    def lossy(self, *args, **kw):
        res = real(self, *args, **kw)
        if kw.get("max_seconds") and len(res.levels) > 2:
            res.levels[-2] -= 1
            res.distinct_states -= 1
        return res
    monkeypatch.setattr(DeviceBFS, "run", lossy)
    line, rows = run_cell("defect-bfs-timed")
    assert line["correct"] is False and line["failed"] == 1
    bad = [r["compared"] for r in rows if "compared" in r and not r["ok"]]
    assert bad and all(b.startswith("levels.complete") for b in bad)


def test_unchanged_state_is_not_correct(run_cell, monkeypatch):
    """A step that returns its state unchanged: the window's run stops
    where the warm-up left it instead of exploring."""
    from tpuvsr.engine.device_bfs import DeviceBFS
    real = DeviceBFS.run

    def stuck(self, *args, **kw):
        if kw.get("max_seconds"):
            kw = dict(kw, max_depth=2, max_seconds=None)
        return real(self, *args, **kw)
    monkeypatch.setattr(DeviceBFS, "run", stuck)
    line, rows = run_cell("defect-bfs-timed")
    assert line["correct"] is False
