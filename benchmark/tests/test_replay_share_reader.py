"""The reader of `paging.replay_share` on recorded metrics documents:
an engine that replays every page (the counters of PagedBFS before
ISSUE 38: a second dispatch on each of 16 pages, dropped), one whose
window runs over pages (what is dropped is what stood behind a pause),
and `None` where nothing is paged or nothing was dispatched."""

import pytest

import cells

REPLAYING = {"dispatches": 32, "pipeline_replays": 16, "page_ins": 16,
             "page_shapes": 2}
OVER_PAGES = {"dispatches": 20, "pipeline_replays": 2, "page_ins": 16,
              "page_shapes": 2, "pages_ahead": 9, "pages_ahead_void": 2}
NO_REPLAY = {"dispatches": 16, "page_ins": 16, "page_shapes": 2}
RESIDENT = {"dispatches": 37, "pipeline_replays": 11}


def read(counters):
    reader = cells.load_plugin("layer_metrics", "paging.replay_share").read
    return reader({"metrics_doc": {"elapsed_s": 4.0, "phases": {},
                                   "counters": counters, "gauges": {}}},
                  None, None)


@pytest.mark.parametrize("counters, want", [
    (REPLAYING, 50.0), (OVER_PAGES, 10.0), (NO_REPLAY, 0.0),
    (RESIDENT, None), ({"page_shapes": 2}, None)])
def test_replay_share(counters, want):
    assert read(counters) == want


def test_no_document_no_number():
    reader = cells.load_plugin("layer_metrics", "paging.replay_share").read
    assert reader({"metrics_doc": None}, None, None) is None
    assert reader({}, None, None) is None


def test_it_is_the_paged_cells_metric():
    entry = [m for m in cells.benchmark_doc()["per_layer"]
             if m["name"] == "paging.replay_share"]
    assert entry == [{"name": "paging.replay_share", "unit": "%",
                      "better": "lower", "source": "program_counter",
                      "layer": "paging", "moves": "distinct_per_s",
                      "workloads": ["defect-bfs-timed-paged"]}]
