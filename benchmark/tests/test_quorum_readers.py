"""The readers of the two quorum metrics on a recorded metrics document
(the counters of a CPU run of configs/vr-state-transfer-r5.cfg through
depth 8, equal to the plain reference's: tools/quorum_counts.py,
oracles/state_transfer_r5_levels.json), `None` where the program has no
such counter (the parent's engine; every `VSR` cell), and which cells
report them."""

import pytest

import cells

# DeviceBFS at the cell's capacities, run(max_depth=8), CPU, PR 49
DISTINCT = 2660824
WAITING, SVC_WAITING = 2620474, 2617492
DOC = {"elapsed_s": 92.1,
       "counters": {"dispatches": 331, "bag_slots": 67495187,
                    "bag_tombstones": 14148486,
                    "quorum_waiting_states": WAITING,
                    "svc_quorum_waiting_states": SVC_WAITING},
       "gauges": {"bag_peak": 32}}
# three replicas: one record is the StartViewChange quorum, nobody
# waits on one, and the engine writes the counter at 0
R3_DOC = {"counters": {"quorum_waiting_states": 12345,
                       "svc_quorum_waiting_states": 0}}
# a program without the two entries of commit_stats: the older
# counters are there, these are not
OFF_DOC = {"elapsed_s": 4.0, "counters": {"dispatches": 12,
                                          "bag_slots": 5,
                                          "bag_tombstones": 1}}
NEW = ("quorum.waiting_state_share", "quorum.svc_waiting_state_share")
CELLS = ("st03-r5-bfs-timed", "st03-bfs-timed")


def reader(name):
    return cells.load_plugin("layer_metrics", name).read


# (metric, its counter's value at five replicas, the share there in
# per cent to a tenth, what three replicas read: one record is the
# StartViewChange quorum, so the second reads 0 and not None)
@pytest.mark.parametrize("name, counted, share, at_three", [
    ("quorum.waiting_state_share", WAITING, 98.5, 50.0),
    ("quorum.svc_waiting_state_share", SVC_WAITING, 98.4, 0.0)])
def test_share_of_the_committed_states(name, counted, share, at_three):
    read = reader(name)
    want = 100.0 * counted / DISTINCT
    assert read({"metrics_doc": DOC, "distinct": DISTINCT},
                None, None) == want
    assert round(want, 1) == share
    assert read({"metrics_doc": R3_DOC, "distinct": 24690},
                None, None) == at_three
    assert read({"metrics_doc": OFF_DOC, "distinct": DISTINCT},
                None, None) is None
    assert read({"metrics_doc": DOC, "distinct": 0}, None, None) is None
    assert read({"metrics_doc": None, "distinct": DISTINCT},
                None, None) is None
    assert read({}, None, None) is None


def test_the_two_cells_report_both():
    """Membership, never a list's exact content or its place: a later
    PR appends cells and metrics (PERF.md 7)."""
    doc = cells.benchmark_doc()
    for name in NEW:
        (entry,) = [m for m in doc["per_layer"] if m["name"] == name]
        for cell in CELLS:
            assert cell in entry["workloads"]
        assert "defect-bfs-timed" not in entry["workloads"]
        assert entry["moves"] == "distinct_per_s"
        assert entry["layer"] == "kernels and tables"
        assert entry["source"] == "program_counter"
        assert (entry["unit"], entry["better"]) == ("%", "higher")


def test_the_new_cell_is_its_controls_twin():
    cell = cells.Cell("st03-r5-bfs-timed")
    control = cells.Cell("st03-bfs-timed")
    assert cell.chips == 1 and cell.entry["traffic"] == "bfs-timed"
    assert cell.config["name"] == "vr-state-transfer-r5"
    assert cell.config["module"] == control.config["module"] \
        == "VR_STATE_TRANSFER"
    assert cell.config["reduced"] == ["depth"]
    # the same cfg but one constant
    assert dict(control.config["constants"], ReplicaCount=5) \
        == cell.config["constants"]
    for key in ("invariants", "view", "symmetry"):
        assert cell.config[key] == control.config[key]
    with open(cell.path(cell.config["cfg"])) as f:
        mine = f.read().split("CONSTANTS")[1]
    with open(control.path(control.config["cfg"])) as f:
        theirs = f.read().split("CONSTANTS")[1]
    assert mine == theirs.replace("ReplicaCount = 3", "ReplicaCount = 5")
    levels = cell.oracle_levels()
    assert levels == [1, 6, 44, 286, 1834, 11514, 69580, 399810]
    pin = cell.config["oracle"]["levels"]["complete_through_depth"]
    warm = cell.traffic["warmup_depth"]
    assert warm < cell.config["assumed"]["trace_depth"] < pin == 7
    assert max(levels) <= cell.config["assumed"]["engine"]["device"][
        "next_capacity"]
    # every per-layer and end-to-end metric the control reports
    mine = {m["name"] for m in cell.metrics_for("per_layer")}
    assert mine == {m["name"] for m in control.metrics_for("per_layer")}
    assert set(NEW) <= mine
    assert [m["name"] for m in cell.metrics_for("end_to_end")] == [
        m["name"] for m in control.metrics_for("end_to_end")]


def test_the_oracle_holds_counts_and_counters():
    cell = cells.Cell("st03-r5-bfs-timed")
    doc = cells.load_json(cell.config["oracle"]["levels"]["file"])
    # the oracle goes a level past the cell's pin: what the chip was
    # held to, whole levels through depth 8
    assert doc["complete_through_depth"] == 8 > cell.config["oracle"][
        "levels"]["complete_through_depth"]
    assert doc["distinct"] == sum(doc["level_sizes"]) == DISTINCT
    assert sum(doc["action_expansions"].values()) + 1 == doc["generated"]
    assert len(doc["action_expansions"]) == 16
    assert doc["committed"]["quorum_waiting_states"] == WAITING
    assert doc["committed"]["svc_quorum_waiting_states"] == SVC_WAITING
    assert doc["bag_peak"] <= cell.config["widths"]["max_msgs"]
    # what a window does not reach: no StartView and no state transfer
    for action in ("SendSV", "ReceiveSV", "SendGetState",
                   "ReceiveGetState", "ReceiveNewState"):
        assert doc["action_expansions"][action] == 0
