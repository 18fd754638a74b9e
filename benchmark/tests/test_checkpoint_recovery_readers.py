"""The readers of `cp06-bfs-timed`'s two new metrics, and
`recovering.state_share` on a document of this module, on a recorded
metrics document (the gauges and counters of a CPU run of
configs/vr-replica-recovery-cp.cfg through depth 12), and `None` where
the program has no such gauge or counter (the parent's engine; every
cell of another module)."""

import cells

# DeviceBFS(tile_size=128, max_msgs=24).run(max_depth=12), CPU, PR 46;
# the plain reference (tools/checkpoint_recovery_reference.py) counts
# the same twenty-two numbers and the same four counters
EXPANSIONS = {
    "TimerSendSVC": 240597, "ReceiveHigherSVC": 639228,
    "ReceiveMatchingSVC": 1027980, "SendDVC": 51122,
    "ReceiveHigherDVC": 3680, "ReceiveMatchingDVC": 13640, "SendSV": 0,
    "ReceiveSV": 0, "ReceiveClientRequest": 24726,
    "ReceivePrepareMsg": 48218, "ReceivePrepareOkMsg": 65780,
    "PrimaryExecuteOp": 29098, "SendGetState": 0, "ReceiveGetState": 0,
    "ReceiveNewState": 0, "Crash": 376738,
    "ReceiveGetCheckpointMsg": 400316, "ReceiveNewCheckpointMsg": 191890,
    "ReceiveRecoveryMsg": 38364, "ReceiveRecoveryResponseMsg": 105168,
    "CompleteRecovery": 1812, "NoProgressChange": 0}
DISTINCT, RECOVERING, COLLECTED = 1413265, 1204980, 90156
DOC = {"elapsed_s": 180.3,
       "counters": {"dispatches": 107, "state_transfer_states": 0,
                    "bag_slots": 21437962, "bag_tombstones": 9274058,
                    "recovering_states": RECOVERING,
                    "gc_states": COLLECTED},
       "gauges": {"action_expansions": EXPANSIONS, "bag_peak": 21,
                  "rec_set_peak": 2, "dvc_set_peak": 2}}
# a program without the kernel's commit_stats: the action gauge is
# there (it always was), the counters are not
OFF_DOC = {"elapsed_s": 4.0, "counters": {"dispatches": 12},
           "gauges": {"action_expansions": EXPANSIONS}}
# another module's program: the gauge is there, the six actions are not
ST03_ACTIONS = {"TimerSendSVC": 187302, "ReceiveMatchingSVC": 3294917,
                "SendGetState": 116, "ExecuteOp": 11200}


def reader(name):
    return cells.load_plugin("layer_metrics", name).read


def test_expansion_share():
    read = reader("checkpoint_recovery.expansion_share")
    assert sum(EXPANSIONS.values()) == 3258357
    six = 376738 + 400316 + 191890 + 38364 + 105168 + 1812
    want = 100.0 * six / 3258357
    assert read({"metrics_doc": DOC, "distinct": DISTINCT},
                None, None) == want
    assert 34.19 < want < 34.20
    assert read({"metrics_doc": {"gauges": {
        "action_expansions": ST03_ACTIONS}}}, None, None) == 0.0
    assert read({"metrics_doc": {"gauges": {"action_expansions": dict.
                                            fromkeys(EXPANSIONS, 0)}}},
                None, None) is None
    assert read({"metrics_doc": {"counters": {}, "gauges": {}}},
                None, None) is None
    assert read({"metrics_doc": None}, None, None) is None
    assert read({}, None, None) is None


def test_gc_state_share():
    read = reader("log_gc.state_share")
    want = 100.0 * COLLECTED / DISTINCT
    assert read({"metrics_doc": DOC, "distinct": DISTINCT},
                None, None) == want
    assert 6.37 < want < 6.38
    assert read({"metrics_doc": OFF_DOC, "distinct": DISTINCT},
                None, None) is None
    assert read({"metrics_doc": DOC, "distinct": 0}, None, None) is None
    assert read({"metrics_doc": None, "distinct": DISTINCT},
                None, None) is None
    assert read({}, None, None) is None


def test_recovering_share_reads_this_modules_counter():
    read = reader("recovering.state_share")
    want = 100.0 * RECOVERING / DISTINCT
    assert read({"metrics_doc": DOC, "distinct": DISTINCT},
                None, None) == want
    assert 85.26 < want < 85.27
    assert read({"metrics_doc": OFF_DOC, "distinct": DISTINCT},
                None, None) is None


def test_the_inherited_readers_read_zero_not_none():
    """The trio does not fire through the pin and no replica waits in
    StateTransfer: the cell prints 0, since the gauge and the counter
    are there."""
    obs = {"metrics_doc": DOC, "distinct": DISTINCT}
    assert reader("state_transfer.expansion_share")(obs, None, None) == 0.0
    assert reader("state_transfer.state_share")(obs, None, None) == 0.0
    assert 43.2 < reader("bag.tombstone_share")(obs, None, None) < 43.3


def test_the_two_are_the_cells_metrics_and_only_its():
    doc = cells.benchmark_doc()
    new = ("checkpoint_recovery.expansion_share", "log_gc.state_share")
    for name in new:
        (entry,) = [m for m in doc["per_layer"] if m["name"] == name]
        assert entry["workloads"] == ["cp06-bfs-timed"]
        assert (entry["moves"], entry["layer"], entry["unit"]) == (
            "distinct_per_s", "kernels and tables", "%")
    assert [m["name"] for m in doc["per_layer"][-2:]] == list(new)
    assert doc["workloads"][-1]["name"] == "cp06-bfs-timed"
    assert doc["configs"][-1]["name"] == "vr-replica-recovery-cp"
    cell = cells.Cell("cp06-bfs-timed")
    assert cell.chips == 1 and cell.entry["traffic"] == "bfs-timed"
    assert cell.config["name"] == "vr-replica-recovery-cp"
    assert cell.config["module"] == "VR_REPLICA_RECOVERY_CP"
    assert cell.config["reduced"] == ["depth"]
    assert cell.config["constants"]["CrashLimit"] == 1
    assert set(cell.config["assumed"]["cfg"]) >= {
        "ReplicaCount", "Values", "StartViewOnTimerLimit",
        "NoProgressChangeLimit", "CrashLimit", "view", "symmetry",
        "invariants"}
    levels = cell.oracle_levels()
    assert levels[:9] == [1, 7, 35, 140, 510, 1693, 5157, 14270, 36042]
    assert len(levels) == 13 and sum(levels) == DISTINCT
    oracle = cells.load_json("oracles", "checkpoint_recovery_levels.json")
    assert oracle["action_expansions"] == EXPANSIONS
    # every per-layer metric st03-bfs-timed reports, the restart cell's
    # recovering.state_share, and the two new
    control = {m["name"] for m in
               cells.Cell("st03-bfs-timed").metrics_for("per_layer")}
    mine = {m["name"] for m in cell.metrics_for("per_layer")}
    assert mine == control | {"recovering.state_share"} | set(new)
    assert [m["name"] for m in cell.metrics_for("end_to_end")] == [
        "distinct_per_s", "setup_s"]
    # the names and the lines the driver's contract bounds
    entry, config = doc["workloads"][-1], doc["configs"][-1]
    assert max(len(entry["why"]), len(config["why"]),
               len(config["source"])) <= 200
    assert config["source"] == cell.config["source"]
    assert "VR_REPLICA_RECOVERY_CP.tla:1186-1213" in config["source"]
