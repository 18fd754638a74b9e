"""The reader of `engine.snapshot_mb` (ISSUE 36): counter
`checkpoint_bytes` over `checkpoints`, and `None` where the program has
no such counter (the parent's) or the job wrote no snapshot."""

import cells


def read(doc):
    return cells.load_plugin("layer_metrics", "engine.snapshot_mb").read(
        {"metrics_doc": doc}, None, None)


def test_megabytes_a_snapshot():
    assert read({"counters": {"checkpoints": 23,
                              "checkpoint_bytes": 11_500_000}}) == 0.5


def test_nothing_to_read():
    # the parent's program counts snapshots and not their bytes
    assert read({"counters": {"checkpoints": 23}}) is None
    assert read({"counters": {"checkpoints": 0,
                              "checkpoint_bytes": 0}}) is None
    assert read({"counters": {}}) is None
    assert read(None) is None


def test_the_entry_lists_the_served_cell():
    m = next(m for m in cells.benchmark_doc()["per_layer"]
             if m["name"] == "engine.snapshot_mb")
    assert m == {"name": "engine.snapshot_mb", "unit": "MB",
                 "better": "lower", "source": "program_counter",
                 "layer": "engine host loop", "moves": "verdict_s",
                 "workloads": ["small-verdict"]}
