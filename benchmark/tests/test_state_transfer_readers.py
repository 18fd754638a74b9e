"""The readers of `st03-bfs-timed`'s three metrics on a recorded
metrics document (the gauges and counters of a CPU run of
configs/vr-state-transfer.cfg through depth 13), and `None` where the
program has no such counter (the parent's engine; every `VSR` cell)."""

import cells

# DeviceBFS(tile_size=128, max_msgs=24).run(max_depth=13), CPU, PR 41;
# the plain reference (tools/state_transfer_reference.py) counts the
# same sixteen numbers
EXPANSIONS = {
    "TimerSendSVC": 187302, "ReceiveHigherSVC": 881888,
    "ReceiveMatchingSVC": 3294917, "SendDVC": 1748350,
    "ReceiveHigherDVC": 44679, "ReceiveMatchingDVC": 487657,
    "SendSV": 45807, "ReceiveSV": 58511, "ReceiveClientRequest": 48374,
    "ReceivePrepareMsg": 18504, "ReceivePrepareOkMsg": 28100,
    "ExecuteOp": 11200, "SendGetState": 116, "ReceiveGetState": 190,
    "ReceiveNewState": 20, "NoProgressChange": 0}
DISTINCT, WAITING = 2651054, 180
SLOTS, TOMBSTONES = 42992449, 19549026
DOC = {"elapsed_s": 126.6,
       "counters": {"dispatches": 181, "state_transfer_states": WAITING,
                    "bag_slots": SLOTS, "bag_tombstones": TOMBSTONES},
       "gauges": {"action_expansions": EXPANSIONS, "bag_peak": 21}}
# a program without the kernel's commit_stats: the action gauge is
# there (it always was), the counters are not
OFF_DOC = {"elapsed_s": 4.0, "counters": {"dispatches": 12},
           "gauges": {"action_expansions": EXPANSIONS}}


def reader(name):
    return cells.load_plugin("layer_metrics", name).read


def test_expansion_share():
    read = reader("state_transfer.expansion_share")
    assert sum(EXPANSIONS.values()) == 6855615
    want = 100.0 * (116 + 190 + 20) / 6855615
    assert read({"metrics_doc": DOC, "distinct": DISTINCT},
                None, None) == want
    assert 0.0047 < want < 0.0048
    quiet = dict(EXPANSIONS, SendGetState=0, ReceiveGetState=0,
                 ReceiveNewState=0)
    assert read({"metrics_doc": {"gauges": {"action_expansions": quiet}}},
                None, None) == 0.0
    assert read({"metrics_doc": {"counters": {}, "gauges": {}}},
                None, None) is None
    assert read({"metrics_doc": None}, None, None) is None
    assert read({}, None, None) is None


def test_state_share():
    read = reader("state_transfer.state_share")
    assert read({"metrics_doc": DOC, "distinct": DISTINCT},
                None, None) == 100.0 * WAITING / DISTINCT
    assert read({"metrics_doc": OFF_DOC, "distinct": DISTINCT},
                None, None) is None
    assert read({"metrics_doc": DOC, "distinct": 0}, None, None) is None
    assert read({"metrics_doc": None, "distinct": DISTINCT},
                None, None) is None
    assert read({}, None, None) is None


def test_tombstone_share():
    read = reader("bag.tombstone_share")
    want = 100.0 * TOMBSTONES / SLOTS
    assert read({"metrics_doc": DOC}, None, None) == want
    assert 45.4 < want < 45.5
    assert read({"metrics_doc": OFF_DOC}, None, None) is None
    assert read({"metrics_doc": {"counters": {"bag_slots": 0,
                                              "bag_tombstones": 0}}},
                None, None) is None
    assert read({"metrics_doc": None}, None, None) is None
    assert read({}, None, None) is None


def test_the_three_are_the_cells_metrics_and_only_its():
    doc = cells.benchmark_doc()
    new = ("state_transfer.expansion_share", "state_transfer.state_share",
           "bag.tombstone_share")
    for name in new:
        (entry,) = [m for m in doc["per_layer"] if m["name"] == name]
        assert entry["workloads"] == ["st03-bfs-timed"]
        assert entry["moves"] == "distinct_per_s"
        assert entry["layer"] == "kernels and tables"
    cell = cells.Cell("st03-bfs-timed")
    assert cell.chips == 1 and cell.entry["traffic"] == "bfs-timed"
    assert cell.config["name"] == "vr-state-transfer"
    assert cell.config["module"] == "VR_STATE_TRANSFER"
    assert cell.config["reduced"] == ["depth"]
    levels = cell.oracle_levels()
    assert levels[:9] == [1, 4, 17, 63, 238, 851, 2814, 8564, 24012]
    # every per-layer metric defect-bfs-timed reports, engine.init_s,
    # and the three new
    control = {m["name"] for m in
               cells.Cell("defect-bfs-timed").metrics_for("per_layer")}
    mine = {m["name"] for m in cell.metrics_for("per_layer")}
    assert mine == control | {"engine.init_s"} | set(new)
    assert [m["name"] for m in cell.metrics_for("end_to_end")] == [
        "distinct_per_s", "setup_s"]
