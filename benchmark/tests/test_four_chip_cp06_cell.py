"""Where BENCHMARK.json lists the four-chip CP06 cell: in every list a
four-chip cell or a CP06 cell is read by, in none of a reader of
one-chip engines only, and under no entry of its own."""

import cells

CELL_NAME = "cp06-bfs-timed-4chip"
# what a sharded engine's cell is read by: the engine, device, level
# and exchange readers of `defect-bfs-timed-4chip` ...
FOUR_CHIP = "defect-bfs-timed-4chip"
# ... and what reads the kernel's counters behind the exchange
KERNEL = ("recovering.state_share", "state_transfer.expansion_share",
          "state_transfer.state_share", "bag.tombstone_share",
          "checkpoint_recovery.expansion_share", "log_gc.state_share")


def test_where_the_cell_is_listed():
    doc = cells.benchmark_doc()
    entry = {w["name"]: w for w in doc["workloads"]}[CELL_NAME]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "vr-replica-recovery-cp-4chip", "bfs-timed-sharded", 4)
    listed = {m["name"] for m in doc["per_layer"] + doc["end_to_end"]
              if CELL_NAME in m.get("workloads", ())}
    with_d4 = {m["name"] for m in doc["per_layer"] + doc["end_to_end"]
               if FOUR_CHIP in m.get("workloads", ())}
    assert "distinct_per_s" in listed
    assert with_d4 <= listed
    assert {"exchange.useful_share", "exchange.collective_share",
            "exchange.ici_roofline", "shard.skew", "engine.init_s",
            "engine.boundary_mb_per_level.bfs",
            "engine.jits_in_window.bfs"} <= with_d4
    assert set(KERNEL) <= listed
    assert listed == with_d4 | set(KERNEL)
    # nothing that reads a one-chip engine only
    for name in listed:
        assert not name.startswith(("paging.", "canon.", "fpset.",
                                    "quorum.", "async_log."))
    # every reader that lists the cell has its file
    for name in listed - {"distinct_per_s"}:
        cells.load_plugin("layer_metrics", name)
    cell = cells.Cell(CELL_NAME)
    assert cell.trace_depth is None
    assert cell.config["assumed"]["trace_depth"] in (7, 8)
    # an engine that counts nothing behind the exchange refuses it
    assert "commit_stats_at_owner" in \
        cell.config["assumed"]["engine"]["sharded"]["requires"]
    assert len(cell.oracle_levels()) in (13, 14)
