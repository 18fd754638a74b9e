"""The readers of `al05-bfs-timed`'s two new metrics on a recorded
metrics document (the gauges and counters of a CPU run of
configs/vr-replica-recovery-async-log.cfg through depth 10), `None`
where the program has no such counter (the parent's engine; every cell
of another module), and that the cell and its metrics are IN
`BENCHMARK.json`: membership, never "last" or an exact list, which the
next configuration's PR would break."""

import cells

# DeviceBFS at the cell's capacities .run(max_depth=10), CPU, PR 53; the
# plain reference (tools/async_log_reference.py) counts the same
DISTINCT, RECOVERING, SURVIVORS, REPLIES = 995401, 747013, 254878, 320
EXPANSIONS = {
    "TimerSendSVC": 169027, "ReceiveHigherSVC": 375328,
    "ReceiveMatchingSVC": 502467, "SendDVC": 359449,
    "ReceiveHigherDVC": 11255, "ReceiveMatchingDVC": 69616,
    "SendSV": 2400, "ReceiveSV": 1230, "ReceiveClientRequest": 19468,
    "ReceivePrepareMsg": 31816, "ReceivePrepareOkMsg": 32956,
    "PrimaryExecuteOp": 13428, "SendGetState": 4, "ReceiveGetState": 0,
    "ReceiveNewState": 0, "Crash": 456599, "ReceiveRecoveryMsg": 40191,
    "ReceiveRecoveryResponseMsg": 95162, "CompleteRecovery": 888,
    "NoProgressChange": 0}
DOC = {"elapsed_s": 70.9,
       "counters": {"dispatches": 80, "state_transfer_states": 4,
                    "bag_slots": 14141680, "bag_tombstones": 4965828,
                    "recovering_states": RECOVERING,
                    "prefix_survivor_states": SURVIVORS,
                    "suffix_reply_states": REPLIES},
       "gauges": {"action_expansions": EXPANSIONS, "bag_peak": 19,
                  "rec_set_peak": 2, "dvc_set_peak": 3}}
# a program without the kernel's commit_stats, or another module's
OFF_DOC = {"elapsed_s": 4.0, "counters": {"dispatches": 12,
                                          "recovering_states": 7},
           "gauges": {"action_expansions": EXPANSIONS}}
NEW = ("async_log.prefix_survivor_share", "async_log.suffix_reply_share")


def reader(name):
    return cells.load_plugin("layer_metrics", name).read


def test_prefix_survivor_share():
    read = reader("async_log.prefix_survivor_share")
    want = 100.0 * SURVIVORS / DISTINCT
    assert read({"metrics_doc": DOC, "distinct": DISTINCT},
                None, None) == want
    assert 25.60 < want < 25.61
    assert read({"metrics_doc": OFF_DOC, "distinct": DISTINCT},
                None, None) is None
    assert read({"metrics_doc": DOC, "distinct": 0}, None, None) is None
    assert read({"metrics_doc": None, "distinct": DISTINCT},
                None, None) is None
    assert read({}, None, None) is None


def test_suffix_reply_share():
    read = reader("async_log.suffix_reply_share")
    want = 100.0 * REPLIES / DISTINCT
    assert read({"metrics_doc": DOC, "distinct": DISTINCT},
                None, None) == want
    assert 0.032 < want < 0.033
    # the counter at 0 is a reading, not an absence
    zero = dict(DOC, counters=dict(DOC["counters"], suffix_reply_states=0))
    assert read({"metrics_doc": zero, "distinct": DISTINCT},
                None, None) == 0.0
    assert read({"metrics_doc": OFF_DOC, "distinct": DISTINCT},
                None, None) is None
    assert read({"metrics_doc": DOC, "distinct": 0}, None, None) is None
    assert read({}, None, None) is None


def test_the_accepted_readers_on_this_modules_document():
    """PR 46's reader of the crash / checkpoint / recovery actions sums
    this module's four (the two checkpoint messages fire 0 times), and
    PR 37's counter of Recovering states is this program's too."""
    obs = {"metrics_doc": DOC, "distinct": DISTINCT}
    four = 456599 + 40191 + 95162 + 888
    assert reader("checkpoint_recovery.expansion_share")(
        obs, None, None) == 100.0 * four / sum(EXPANSIONS.values())
    assert reader("recovering.state_share")(obs, None, None) \
        == 100.0 * RECOVERING / DISTINCT
    assert reader("log_gc.state_share")(obs, None, None) is None
    assert 0 < reader("state_transfer.expansion_share")(obs, None, None) \
        < 0.001
    assert 35.1 < reader("bag.tombstone_share")(obs, None, None) < 35.2


def test_the_cell_and_its_metrics_are_in_the_benchmark():
    doc = cells.benchmark_doc()
    by_name = {m["name"]: m for m in doc["per_layer"]}
    for name in NEW:
        entry = by_name[name]
        assert "al05-bfs-timed" in entry["workloads"]
        assert (entry["moves"], entry["layer"], entry["unit"],
                entry["better"], entry["source"]) == (
            "distinct_per_s", "kernels and tables", "%", "higher",
            "program_counter")
    assert "al05-bfs-timed" in {w["name"] for w in doc["workloads"]}
    assert "vr-replica-recovery-async-log" in {
        c["name"] for c in doc["configs"]}
    cell = cells.Cell("al05-bfs-timed")
    assert cell.chips == 1 and cell.entry["traffic"] == "bfs-timed"
    assert cell.config["name"] == "vr-replica-recovery-async-log"
    assert cell.config["module"] == "VR_REPLICA_RECOVERY_ASYNC_LOG"
    assert cell.config["reduced"] == ["depth"]
    assert cell.config["constants"]["CrashLimit"] == 1
    assert set(cell.config["assumed"]["cfg"]) >= {
        "ReplicaCount", "Values", "StartViewOnTimerLimit",
        "NoProgressChangeLimit", "CrashLimit", "view", "symmetry",
        "invariants", "init"}
    levels = cell.oracle_levels()
    assert levels[:9] == [1, 7, 37, 171, 697, 2604, 9039, 29217, 87485]
    pin = cell.config["oracle"]["levels"]["complete_through_depth"]
    assert len(levels) == pin + 1
    oracle = cells.load_json("oracles", "async_log_levels.json")
    assert len(oracle["level_sizes"]) == pin + 2    # one level past it
    assert sum(levels) == oracle["through"][pin - 1]["distinct"]
    assert oracle["through"][pin - 1]["committed"][
        "prefix_survivor_states"] > 0
    # every per-layer metric cp06-bfs-timed reports but the garbage
    # collector's, and the two new
    control = {m["name"] for m in
               cells.Cell("cp06-bfs-timed").metrics_for("per_layer")}
    mine = {m["name"] for m in cell.metrics_for("per_layer")}
    assert mine == control - {"log_gc.state_share"} | set(NEW)
    assert {m["name"] for m in cell.metrics_for("end_to_end")} == {
        "distinct_per_s", "setup_s"}
    # the names and the lines the driver's contract bounds
    entry = next(w for w in doc["workloads"] if w["name"] == cell.name)
    config = next(c for c in doc["configs"]
                  if c["name"] == cell.config["name"])
    assert max(len(entry["why"]), len(config["why"]),
               len(config["source"])) <= 200
    assert config["source"] == cell.config["source"]
    assert "VR_REPLICA_RECOVERY_ASYNC_LOG.tla:992-1017" in config["source"]
