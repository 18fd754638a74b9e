"""The readers of `shipped-bfs-timed`'s two `canon.*` metrics on a
made-up metrics document, `None` where the program has no such
counters (the parent's engine, a symmetry-off run), and the cell's
control flow rehearsed on the CPU."""

import pytest

import cells

LANES, MOVED, DISTINCT = 4000, 1400, 1600
DOC = {"elapsed_s": 4.0,
       "counters": {"dispatches": 60, "canon_lanes": LANES,
                    "canon_relabelled": MOVED},
       "gauges": {"symmetry_perms": 2}}
# a symmetry-off run, or the parent's program: no canon counters
OFF_DOC = {"elapsed_s": 4.0, "counters": {"dispatches": 60},
           "gauges": {"symmetry_perms": 1}}


def reader(name):
    return cells.load_plugin("layer_metrics", name).read


@pytest.mark.parametrize("name, want", [
    ("canon.images_per_state", 2 * LANES / DISTINCT),
    ("canon.relabel_share", 100.0 * MOVED / LANES)])
def test_canon_readers(name, want):
    read = reader(name)
    assert read({"metrics_doc": DOC, "distinct": DISTINCT},
                None, None) == want
    assert read({"metrics_doc": OFF_DOC, "distinct": DISTINCT},
                None, None) is None
    assert read({"metrics_doc": None, "distinct": DISTINCT},
                None, None) is None
    assert read({}, None, None) is None


def test_no_lanes_no_share():
    """A run that canonicalized nothing (stopped in its first
    dispatch): no share of zero lanes, no images per no state."""
    doc = dict(DOC, counters={"canon_lanes": 0, "canon_relabelled": 0})
    assert reader("canon.relabel_share")(
        {"metrics_doc": doc, "distinct": 1}, None, None) is None
    assert reader("canon.images_per_state")(
        {"metrics_doc": doc, "distinct": 1}, None, None) == 0.0
    assert reader("canon.images_per_state")(
        {"metrics_doc": DOC, "distinct": 0}, None, None) is None


def test_both_are_the_cells_metrics():
    cell = cells.Cell("shipped-bfs-timed")
    names = [m["name"] for m in cell.metrics_for("per_layer")]
    assert {"canon.images_per_state", "canon.relabel_share"} <= set(names)
    assert cell.config["name"] == "vsr-shipped"
    assert cell.oracle_levels()[-1] == 838162
    assert sum(cell.oracle_levels()) == 1946857


def test_rehearsal_reaches_its_last_line(run_cell):
    line, rows = run_cell("shipped-bfs-timed")
    assert line["correct"] is True and line["failed"] == 0
    assert line["rehearsal"] is True and line["metrics"] == {}
    assert all(r["ok"] for r in rows if "compared" in r)
