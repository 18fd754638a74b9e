"""The readers of the four-chip cell's exchange metrics, each on a
made-up metrics document and reduced trace, and `None` where its source
is absent: the parent's program has no `exchange_offchip_bytes`,
`shard_skew` or `init` of its own, a one-chip trace no collective."""

import types

import pytest

import cells

# 100 committed tiles on 4 chips, 128-row buckets, 505-byte rows
ROW_BYTES, TILES, CHIPS, CAP = 505, 100, 4, 128
WIRE_ROWS = TILES * CHIPS * CHIPS * CAP
DOC = {"phases": {"check": 0.1, "init": 0.25, "inflight": 2.0},
       "gauges": {"shard_skew": 1.0124, "shard_distinct": [1, 2, 3, 4],
                  "exchange_row_bytes": ROW_BYTES,
                  "exchange_useful_rows": WIRE_ROWS // 4,
                  "exchange_wire_rows": WIRE_ROWS,
                  "exchange_wire_bytes": WIRE_ROWS * ROW_BYTES,
                  "exchange_offchip_bytes":
                      WIRE_ROWS // CHIPS * (CHIPS - 1) * ROW_BYTES},
       "counters": {"init_packed_rows": 1}}
# the parent's program: the same run without what ISSUE 27 added
OLD_DOC = {"phases": {"check": 0.1, "inflight": 2.0},
           "gauges": {k: v for k, v in DOC["gauges"].items()
                      if k not in ("shard_skew",
                                   "exchange_offchip_bytes")},
           "counters": {}}
TRACE = {"busy_s": 2.0, "devices": 4,
         "device_opcodes": [["fusion kLoop", 1.2], ["all-to-all", 0.25],
                            ["all-reduce", 0.125], ["copy", 0.1],
                            ["all-reduce-start", 0.0625],
                            ["all-reduce-done", 0.0625]]}
ONE_CHIP_TRACE = {"busy_s": 2.0, "devices": 1,
                  "device_opcodes": [["fusion kLoop", 1.9], ["copy", 0.1]]}
CELL = types.SimpleNamespace(peaks={"ici_gbit_per_s": 1600},
                             devices=[None] * CHIPS)


def reader(name):
    return cells.load_plugin("layer_metrics", name).read


@pytest.mark.parametrize("name, want", [
    ("engine.init_s", 0.25), ("shard.skew", 1.0124),
    ("exchange.useful_share", 25.0)])
def test_counter_readers(name, want):
    read = reader(name)
    assert read({"metrics_doc": DOC}, None, CELL) == want
    assert read({"metrics_doc": None}, TRACE, CELL) is None
    assert read({}, None, CELL) is None
    if name != "exchange.useful_share":     # the parent has its gauges
        assert read({"metrics_doc": OLD_DOC}, TRACE, CELL) is None


def test_collective_share():
    read = reader("exchange.collective_share")
    assert read({}, TRACE, CELL) == 100.0 * 0.5 / 2.0
    assert read({}, ONE_CHIP_TRACE, CELL) is None
    assert read({"metrics_doc": DOC}, None, CELL) is None
    assert read({}, {"busy_s": 1.0}, CELL) is None


def test_ici_roofline():
    mod = cells.load_plugin("layer_metrics", "exchange.ici_roofline")
    offchip = mod.all_to_all_offchip_bytes(TILES, CHIPS, CAP, ROW_BYTES)
    # the program's gauge is this function's count
    assert offchip == DOC["gauges"]["exchange_offchip_bytes"]
    assert offchip * CHIPS == \
        DOC["gauges"]["exchange_wire_bytes"] * (CHIPS - 1)
    want = 100.0 * (offchip / CHIPS) / 0.5 / 200e9
    got = mod.read({"metrics_doc": DOC}, TRACE, CELL)
    assert abs(got - want) < 1e-12 and 0 < got < 100
    # no gauge (the parent), no collective, no trace, no peaks
    assert mod.read({"metrics_doc": OLD_DOC}, TRACE, CELL) is None
    assert mod.read({"metrics_doc": DOC}, ONE_CHIP_TRACE, CELL) is None
    assert mod.read({"metrics_doc": DOC}, None, CELL) is None
    assert mod.read({}, TRACE, CELL) is None
    rehearsal = types.SimpleNamespace(peaks=None, devices=[None] * 4)
    assert mod.read({"metrics_doc": DOC}, TRACE, rehearsal) is None
