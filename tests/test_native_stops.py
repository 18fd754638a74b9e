"""Where `DeviceBFS.run` stops when it is told to, and the served path
over it, on the real VSR kernel from committed files.

The benchmark's `correct` reads the stop off the result ("budget used
or pinned depth reached") and holds a served job to `done` and the
pinned level sizes; the stub harness pins the same seams on a 16-state
counter.  One engine is built for the three stops: a second `run()` on
it starts from Init again and builds nothing.
"""

import json
import os
import re

import pytest

from tests.conftest import REPO, SMALL_CFG
from tpuvsr.engine.device_bfs import DeviceBFS
from tpuvsr.obs import read_journal


@pytest.fixture(scope="module")
def engine(small_native):
    return DeviceBFS(small_native)


@pytest.mark.parametrize("stop", ["max_depth", "max_states",
                                  "max_seconds"])
def test_native_run_stops_where_asked(engine, small_pin, stop):
    kw, said, levels = {
        # the limit the two defect cells end by
        "max_depth": ({"max_depth": 10}, "depth limit 10 reached",
                      small_pin[:11]),
        # tested between levels: the first level whose total passes it
        "max_states": ({"max_states": 500}, "state limit 500 reached",
                       small_pin[:7]),
        # spent at the first collect: level 1 is in, and what the
        # window still held is dropped, counted, and in no level
        "max_seconds": ({"max_seconds": 1e-9},
                        "time budget 1e-09s reached", small_pin[:2]),
    }[stop]
    res = engine.run(**kw)
    assert res.ok and res.error == said
    assert res.levels == list(engine.level_sizes) == levels
    assert res.distinct_states == sum(res.levels)
    assert res.diameter == len(levels) - 1
    dropped = res.metrics["counters"].get("budget_dropped_dispatches", 0)
    assert dropped == (engine.pipe_window - 1
                       if stop == "max_seconds" else 0)


def test_served_native_job_exact_levels(small_pin, tmp_path, capsys):
    """`submit` -> `serve --drain` -> `status`, the three calls of the
    `small-verdict` cell, to depth 8: `done`, the pinned sizes, and
    one snapshot per level boundary but the last, where the depth
    limit ends the run (S4's cost, held here as a count)."""
    from tpuvsr.service.api import main as api_main
    depth = 8
    spool = str(tmp_path / "spool")
    assert api_main(["submit", "VSR", "-config", SMALL_CFG, "--spool",
                     spool, "--flag", f"maxdepth={depth}",
                     "--json"]) == 0
    job_id = json.loads(capsys.readouterr().out)["job_id"]
    assert api_main(["serve", "--drain", "--spool", spool,
                     "--quiet"]) == 0
    capsys.readouterr()
    assert api_main(["status", job_id, "--spool", spool, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    pin = small_pin[:depth + 1]
    result = doc["result"]
    assert doc["state"] == "done" and result["ok"]
    assert result["levels"] == pin
    assert (result["distinct"], result["diameter"]) == (sum(pin), depth)
    assert result["error"] == f"depth limit {depth} reached"
    assert result["supervisor"]["attempts"] == 1
    events = read_journal(doc["journal"])
    kinds = [e["event"] for e in events]
    assert kinds.count("checkpoint") == len(pin) - 1
    started = [e for e in events if e["event"] == "job_started"]
    assert [e["backend"] for e in started] == ["cpu"]
    with open(doc["metrics"]) as f:
        assert json.load(f)["counters"]["checkpoints"] == len(pin) - 1


def test_schema_engine_values_are_the_engines_labels():
    """The `engine` values obs/SCHEMA.md lists are exactly the labels
    the engines hand to `RunObserver.ensure`."""
    with open(os.path.join(REPO, "tpuvsr", "obs", "SCHEMA.md")) as f:
        listed = re.search(r"^`engine` values: (.*?)\.\s", f.read(),
                           re.S | re.M).group(1)
    listed = re.findall(r"`([a-z-]+)`", listed)
    used = []
    for root, _dirs, files in os.walk(os.path.join(REPO, "tpuvsr")):
        for name in files:
            if name.endswith(".py") and name != "observer.py":
                with open(os.path.join(root, name)) as f:
                    used += re.findall(
                        r"RunObserver\.ensure\(\s*obs,\s*\"([a-z-]+)\"",
                        f.read())
    assert len(listed) == len(set(listed)) and len(used) == len(set(used))
    assert sorted(listed) == sorted(used)
    assert "device" in used
