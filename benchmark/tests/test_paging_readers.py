"""The readers of the paged cell's three `paging.*` metrics on a
recorded metrics document (PagedBFS on vsr-small to depth 10, chunks
of 512 rows; a CPU run, so only the counters mean anything here), and
`None` where the program has no such counters: the parent's engine,
or a cell of another engine."""

import pytest

import cells

ROW = 84            # bytes of a packed vsr-small row
IN_ROWS, OUT_ROWS = 4189, 5645
DOC = {"elapsed_s": 4.0,
       "phases": {"check": 0.5, "dispatch": 0.25, "inflight": 2.75,
                  "host_sync": 0.125, "init": 0.125,
                  "page_in": 0.0625, "page_out": 0.1875},
       "counters": {"dispatches": 60, "page_ins": 16,
                    "page_in_rows": IN_ROWS,
                    "page_in_bytes": IN_ROWS * ROW, "spills": 21,
                    "spill_rows": OUT_ROWS,
                    "spill_bytes": OUT_ROWS * ROW, "page_shapes": 2},
       "gauges": {"fpset_capacity": 1 << 20}}
# the same run on the parent's engine: drains under host_sync, no
# page-in span, no page counters
OLD_DOC = {"elapsed_s": 4.0,
           "phases": {"check": 0.5, "dispatch": 0.25, "inflight": 2.75,
                      "host_sync": 0.375, "init": 0.125},
           "counters": {"dispatches": 60, "spills": 21,
                        "spill_rows": OUT_ROWS,
                        "spill_bytes": OUT_ROWS * ROW},
           "gauges": {"fpset_capacity": 1 << 20}}
BUILDS = {"compiles": 3, "slow_builds": 0, "cache_hits": 0,
          "cache_writes": 0, "compile_s": 0.04}


def reader(name):
    return cells.load_plugin("layer_metrics", name).read


@pytest.mark.parametrize("name, want", [
    ("paging.share", 100.0 * 0.25 / 4.0),
    ("paging.gb_per_s", (IN_ROWS + OUT_ROWS) * ROW / 0.25 / 1e9),
    ("paging.jits_in_window", 3)])
def test_paging_readers(name, want):
    read = reader(name)
    obs = {"metrics_doc": DOC, "window_builds": BUILDS}
    assert read(obs, None, None) == want
    # the parent's program, no metrics document, nothing at all
    assert read({"metrics_doc": OLD_DOC, "window_builds": BUILDS},
                None, None) is None
    assert read({"metrics_doc": None, "window_builds": BUILDS},
                None, None) is None
    assert read({}, None, None) is None


def test_no_seconds_no_rate():
    doc = dict(DOC, phases={"check": 4.0})
    assert reader("paging.gb_per_s")({"metrics_doc": doc}, None,
                                     None) is None
    assert reader("paging.share")({"metrics_doc": doc}, None,
                                  None) is None
