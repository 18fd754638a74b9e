"""The readers of the nine metrics that read the program's own account
of the host's cost to the chip (ISSUE 35), on a small recorded metrics
document (ShardedBFS on the stub kernel, two virtual devices; a CPU
run, so only the arithmetic means anything here), and `None` where the
program has no such phase, clock, row key or counter: the parent's."""

import copy

import pytest

import cells

DOC = {"elapsed_s": 8.0,
       "phases": {"check": 0.0625, "init": 0.5, "compile": 1.0,
                  "dispatch": 0.25, "inflight": 4.0, "host_sync": 0.5,
                  "boundary": 1.5, "checkpoint": 0.125,
                  "finish": 0.0625},
       "phases_unfed": {"check": 0.0625, "init": 0.5, "compile": 1.0,
                        "dispatch": 0.125, "host_sync": 0.25,
                        "boundary": 1.5, "checkpoint": 0.125,
                        "finish": 0.0625},
       "counters": {"dispatches": 8, "boundary_put_bytes": 6_000_000,
                    "boundary_pull_bytes": 2_000_000},
       "gauges": {"unfed_s": 3.625, "pipeline_depth": 2},
       "levels": [
           {"depth": 1, "frontier": 1, "distinct": 3, "generated": 3,
            "elapsed_s": 2.0, "wall_s": 2.0, "unfed_s": 1.75,
            "dispatches": 2, "phases": {"init": 0.5, "compile": 1.0}},
           {"depth": 2, "frontier": 100, "distinct": 300,
            "generated": 400, "elapsed_s": 2.5, "wall_s": 0.5,
            "unfed_s": 0.25, "dispatches": 2,
            "phases": {"boundary": 0.25, "inflight": 0.25}},
           {"depth": 3, "frontier": 128, "distinct": 900,
            "generated": 1300, "elapsed_s": 3.25, "wall_s": 0.75,
            "unfed_s": 0.5, "dispatches": 2,
            "phases": {"boundary": 0.5, "inflight": 0.25}},
           {"depth": 4, "frontier": 129, "distinct": 2000,
            "generated": 3000, "elapsed_s": 8.0, "wall_s": 4.75,
            "unfed_s": 1.0, "dispatches": 2,
            "phases": {"boundary": 0.75, "inflight": 3.5}}]}
# the same run on the parent's program: the boundary under the root
# frame, no unfed clock, bare level rows, no boundary counters
OLD_DOC = {"elapsed_s": 8.0,
           "phases": {"check": 1.625, "init": 0.5, "compile": 1.0,
                      "dispatch": 0.25, "inflight": 4.0,
                      "host_sync": 0.5, "checkpoint": 0.125},
           "counters": {"dispatches": 8},
           "gauges": {"overlap_saved_s": 0.5, "pipeline_depth": 2},
           "levels": [{k: row[k] for k in ("depth", "frontier",
                                           "distinct", "generated",
                                           "elapsed_s")}
                      for row in DOC["levels"]]}
TWINS = {"engine.boundary_s.bfs": "engine.boundary_s",
         "engine.unspanned_share.bfs": "engine.unspanned_share",
         "device.unfed_share.bfs": "device.unfed_share.verdict",
         "engine.level_floor_s.bfs": "engine.level_floor_s"}
WANT = {"engine.boundary_s": 1.5,
        "engine.unspanned_share": 100.0 * 0.0625 / 8.0,
        "device.unfed_share.verdict": 100.0 * 3.625 / 8.0,
        "engine.level_floor_s": 0.75,     # median of 2.0, 0.5, 0.75
        "engine.boundary_mb_per_level.bfs": 8.0 / 4}
# what each reads on the parent's document: only the root frame's share
# is there to read, and it is what this PR shrinks
WANT_OLD = {"engine.unspanned_share": 100.0 * 1.625 / 8.0}


def reader(name):
    return cells.load_plugin("layer_metrics", name).read


@pytest.mark.parametrize("name", sorted(WANT) + sorted(TWINS))
def test_boundary_readers(name):
    read = reader(name)
    base = TWINS.get(name, name)
    assert read({"metrics_doc": DOC}, None, None) == WANT[base]
    # the parent's program, no metrics document, nothing at all
    assert read({"metrics_doc": OLD_DOC}, None, None) \
        == WANT_OLD.get(base)
    assert read({"metrics_doc": None}, None, None) is None
    assert read({}, None, None) is None


def test_every_new_metric_has_its_reader_and_its_cells():
    doc = cells.benchmark_doc()
    # every cell that reports distinct_per_s
    rate, = (m for m in doc["end_to_end"] if m["name"] == "distinct_per_s")
    bfs = [w["name"] for w in doc["workloads"]
           if w["name"] in rate["workloads"]]
    assert len(bfs) >= 6 and "small-verdict" not in bfs
    entries = {m["name"]: m for m in doc["per_layer"]}
    for name in list(WANT) + list(TWINS):
        m = entries[name]
        assert m["better"] == "lower"
        if name == "engine.boundary_mb_per_level.bfs":
            want = (["defect-bfs-timed-4chip"], "distinct_per_s")
        elif name.endswith(".bfs"):
            want = (bfs, "distinct_per_s")
        else:
            want = (["small-verdict"], "verdict_s")
        assert (sorted(m["workloads"]), m["moves"]) == \
            (sorted(want[0]), want[1]), name


def test_level_floor_needs_a_small_level():
    read = reader("engine.level_floor_s")
    doc = copy.deepcopy(DOC)
    doc["levels"] = [row for row in doc["levels"]
                     if row["frontier"] > 128]
    assert read({"metrics_doc": doc}, None, None) is None
    doc["levels"] = []
    assert read({"metrics_doc": doc}, None, None) is None
    assert reader("engine.boundary_mb_per_level.bfs")(
        {"metrics_doc": doc}, None, None) is None


def test_no_elapsed_no_share():
    doc = dict(DOC, elapsed_s=0.0)
    for name in ("engine.unspanned_share", "device.unfed_share.verdict"):
        assert reader(name)({"metrics_doc": doc}, None, None) is None


def test_one_counter_is_enough_for_the_megabytes():
    doc = copy.deepcopy(DOC)
    del doc["counters"]["boundary_put_bytes"]    # the resident engine
    assert reader("engine.boundary_mb_per_level.bfs")(
        {"metrics_doc": doc}, None, None) == 2.0 / 4
