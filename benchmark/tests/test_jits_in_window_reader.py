"""The reader of `engine.jits_in_window.bfs` on recorded runs: a
window that compiled a slice a level, one that compiled nothing, and
`None` where the run's record has no `window_builds`."""

import pytest

import cells

DOC = {"elapsed_s": 8.6, "phases": {}, "counters": {}, "gauges": {}}


def read(obs):
    reader = cells.load_plugin("layer_metrics",
                               "engine.jits_in_window.bfs").read
    return reader(obs, None, None)


@pytest.mark.parametrize("builds, want", [
    ({"compiles": 7, "slow_builds": 0, "cache_hits": 0, "cache_writes": 0,
      "compile_s": 0.451}, 7),
    ({"compiles": 0, "slow_builds": 0, "cache_hits": 0, "cache_writes": 0,
      "compile_s": 0.0}, 0)])
def test_it_counts_every_compile_of_the_window(builds, want):
    assert read({"metrics_doc": DOC, "window_builds": builds}) == want
    # not the paged cell's test: no counter of the document is asked for
    assert read({"metrics_doc": None, "window_builds": builds}) == want


def test_no_record_no_number():
    assert read({"metrics_doc": DOC}) is None
    assert read({}) is None


def test_it_is_the_resident_and_sharded_cells_metric():
    doc = cells.benchmark_doc()
    entry = [m for m in doc["per_layer"]
             if m["name"] == "engine.jits_in_window.bfs"]
    assert entry == [{"name": "engine.jits_in_window.bfs", "unit": "count",
                      "better": "lower", "source": "program_counter",
                      "layer": "engine host loop",
                      "moves": "distinct_per_s",
                      "workloads": ["defect-bfs-timed", "shipped-bfs-timed",
                                    "restart-bfs-timed", "st03-bfs-timed",
                                    "defect-bfs-timed-4chip"]}]
    assert doc["per_layer"][-1] == entry[0]     # appended, nothing moved
