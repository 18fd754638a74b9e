"""The twelve readers of ISSUE 51: the three parts of a read-back from
the persistent cache (gauges), and the parts of `init` and of a
snapshot (`phase_parts` of the metrics document); `None` on a document
of the parent's program, which has neither; each entry present in
`per_layer` with its cells."""

import pytest

import cells

SERVED = ["small-verdict"]
#: the two symmetry-on cells whose idle is `init`, and the symmetry-off
#: control with its twin at five replicas (the two list the same metrics)
BFS = ["shipped-bfs-timed", "restart-bfs-timed", "st03-bfs-timed",
       "st03-r5-bfs-timed"]

#: a second served job's document, cut to what the readers read
#: (my chip run, PR 51: seed 5100000101, the window's second job)
RECORDED = {
    "phases": {"init": 0.575384, "checkpoint": 1.032696,
               "compile": 6.457},
    "gauges": {"build_cache_load_s": 4.241711,
               "build_cache_read_s": 0.009294,
               "build_cache_decompress_s": 0.086484,
               "build_executable_load_s": 4.145933},
    "counters": {"build_cache_read_bytes": 17_140_237,
                 "build_cache_decompressed_bytes": 247_466_885},
    "phase_parts": {
        "init": {"states": 0.070442, "fingerprint": 0.494122,
                 "device": 0.010699},
        "checkpoint": {"pull": 0.239489, "write": 0.531657,
                       "durable": 0.238395}},
}
#: the parent's: the phases and the whole load, no part of either
PARENT = {"phases": dict(RECORDED["phases"]),
          "gauges": {"build_cache_load_s": 4.241711},
          "counters": {}}

READERS = {
    "engine.cache_read_s": (0.009294, "verdict_s", SERVED),
    "engine.cache_decompress_s": (0.086484, "verdict_s", SERVED),
    "engine.executable_load_s": (4.145933, "verdict_s", SERVED),
    "engine.init_states_s": (0.070442, "verdict_s", SERVED),
    "engine.init_fingerprint_s": (0.494122, "verdict_s", SERVED),
    "engine.init_device_s": (0.010699, "verdict_s", SERVED),
    "engine.init_states_s.bfs": (0.070442, "distinct_per_s", BFS),
    "engine.init_fingerprint_s.bfs": (0.494122, "distinct_per_s", BFS),
    "engine.init_device_s.bfs": (0.010699, "distinct_per_s", BFS),
    "engine.snapshot_pull_s": (0.239489, "verdict_s", SERVED),
    "engine.snapshot_write_s": (0.531657, "verdict_s", SERVED),
    "engine.snapshot_durable_s": (0.238395, "verdict_s", SERVED),
}


def read(name, doc):
    return cells.load_plugin("layer_metrics", name).read(
        {"metrics_doc": doc}, None, None)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reads_its_part(name):
    assert read(name, RECORDED) == READERS[name][0]


@pytest.mark.parametrize("name", sorted(READERS))
def test_nothing_to_read_on_the_parents_document(name):
    assert read(name, PARENT) is None
    assert read(name, None) is None
    # a phase that opened no part (a resume's `init` has no `states`)
    assert read(name, dict(PARENT, phase_parts={"init": {},
                                                "checkpoint": {}})) is None


def test_the_parts_of_a_read_back_sum_to_the_load():
    g = RECORDED["gauges"]
    assert sum(read(n, RECORDED) for n in (
        "engine.cache_read_s", "engine.cache_decompress_s",
        "engine.executable_load_s")) == pytest.approx(
            g["build_cache_load_s"])


@pytest.mark.parametrize("name", sorted(READERS))
def test_the_entry_is_present_with_its_cells(name):
    _, moves, workloads = READERS[name]
    entries = [m for m in cells.benchmark_doc()["per_layer"]
               if m["name"] == name]
    assert entries == [{"name": name, "unit": "s", "better": "lower",
                        "source": "program_span",
                        "layer": "engine host loop", "moves": moves,
                        "workloads": workloads}]
    # every listed cell reports the end-to-end metric the part moves
    e2e = next(m for m in cells.benchmark_doc()["end_to_end"]
               if m["name"] == moves)
    assert set(workloads) <= set(e2e["workloads"])
