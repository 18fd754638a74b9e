"""The readers of `restart-bfs-timed`'s two metrics on a recorded
metrics document (the gauges and counters of a CPU run of
configs/vsr-shipped-restart.cfg to depth 6), and `None` where the
program has no such counter (the parent's engine; every cfg that binds
RestartEmptyLimit = 0)."""

import cells

# DeviceBFS(tile_size=128, max_msgs=32).run(max_depth=6), CPU, PR 37
EXPANSIONS = {
    "TimerSendSVC": 2544, "ReceiveHigherSVC": 4107,
    "ReceiveMatchingSVC": 3419, "SendDVC": 1965, "ReceiveHigherDVC": 89,
    "ReceiveMatchingDVC": 237, "SendSV": 0, "ReceiveSV": 0,
    "ReceiveClientRequest": 698, "ReceivePrepareMsg": 307,
    "ReceivePrepareOkMsg": 182, "ExecuteOp": 51, "SendGetState": 0,
    "ReceiveGetState": 0, "ReceiveNewState": 0, "RestartEmpty": 1728,
    "ReceivesRecoveryMsg": 836, "ReceivesRecoveryResponseMsg": 396,
    "CompleteRecovery": 2}
DISTINCT, RECOVERING = 8318, 3020
DOC = {"elapsed_s": 4.0,
       "counters": {"dispatches": 12, "canon_lanes": 16561,
                    "recovering_states": RECOVERING},
       "gauges": {"action_expansions": EXPANSIONS, "dvc_set_peak": 1}}
# the parent's program, or a cfg that cannot restart: the action
# gauge is there (it always was), the counter is not
OFF_DOC = {"elapsed_s": 4.0, "counters": {"dispatches": 12},
           "gauges": {"action_expansions": dict(
               EXPANSIONS, RestartEmpty=0, ReceivesRecoveryMsg=0,
               ReceivesRecoveryResponseMsg=0, CompleteRecovery=0)}}


def reader(name):
    return cells.load_plugin("layer_metrics", name).read


def test_expansion_share():
    read = reader("recovery.expansion_share")
    assert sum(EXPANSIONS.values()) == 16561     # = canon_lanes
    want = 100.0 * (1728 + 836 + 396 + 2) / 16561
    assert read({"metrics_doc": DOC, "distinct": DISTINCT},
                None, None) == want
    assert 17.8 < want < 17.9
    assert read({"metrics_doc": OFF_DOC, "distinct": DISTINCT},
                None, None) == 0.0
    assert read({"metrics_doc": {"counters": {}, "gauges": {}}},
                None, None) is None
    assert read({"metrics_doc": None}, None, None) is None
    assert read({}, None, None) is None


def test_state_share():
    read = reader("recovering.state_share")
    assert read({"metrics_doc": DOC, "distinct": DISTINCT},
                None, None) == 100.0 * RECOVERING / DISTINCT
    assert read({"metrics_doc": OFF_DOC, "distinct": DISTINCT},
                None, None) is None
    assert read({"metrics_doc": DOC, "distinct": 0}, None, None) is None
    assert read({"metrics_doc": None, "distinct": DISTINCT},
                None, None) is None
    assert read({}, None, None) is None


def test_both_are_the_cells_metrics_and_only_its():
    doc = cells.benchmark_doc()
    for name in ("recovery.expansion_share", "recovering.state_share"):
        (entry,) = [m for m in doc["per_layer"] if m["name"] == name]
        assert entry["workloads"] == ["restart-bfs-timed"]
        assert entry["moves"] == "distinct_per_s"
    cell = cells.Cell("restart-bfs-timed")
    assert cell.config["name"] == "vsr-shipped-restart"
    assert cell.config["constants"]["RestartEmptyLimit"] == 1
    levels = cell.oracle_levels()
    assert levels[:9] == [1, 6, 27, 113, 446, 1695, 6030, 19894, 60799]
    # every per-layer metric the control cell reports, and the two new
    control = {m["name"] for m in
               cells.Cell("shipped-bfs-timed").metrics_for("per_layer")}
    mine = {m["name"] for m in cell.metrics_for("per_layer")}
    assert mine == control | {"recovery.expansion_share",
                              "recovering.state_share"}
