"""The reduction from a trace extract to busy seconds, self times and
idle gaps: by hand on a small made-up extract, and on a slice recorded
on the chip (data/defect_trace_slice.json.gz)."""

import gzip
import json
import os

import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

MS = 1_000_000


def hand_made():
    ops = [["while.1", 0, 10 * MS],             # encloses the next two
           ["fusion.a", 1 * MS, 3 * MS],
           ["fusion.b", 5 * MS, 4 * MS],
           ["copy.2", 30 * MS, 2 * MS],          # after a 20 ms gap
           ["fusion.a", 33 * MS, 1 * MS]]        # after a 1 ms gap
    host = [["level 7 dispatch", 9 * MS, 3 * MS],
            ["PjitFunction(level)", 0, 40 * MS],
            ["block_until_ready", 12 * MS, 17 * MS],
            ["tiny", 20 * MS, 1 * MS]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [["jit_level", 0, 34 * MS]]},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [
            {"name": "main", "events": host},
            {"name": "python", "events": [["noise", 0, 40 * MS]]}]}]}


def test_hand_made_extract():
    r = trace_reduce.reduce(hand_made())
    assert r["devices"] == 1 and r["device_events"] == 5
    # busy: [0,10) + [30,32) + [33,34) ms; the Modules line is not read
    assert abs(r["busy_s"] - 0.013) < 1e-12
    assert abs(r["span_s"] - 0.034) < 1e-12
    ops = dict(r["device_ops"])
    # the while is charged only what its children leave: 10 - 3 - 4
    assert abs(ops["while.1"] - 0.003) < 1e-12
    assert abs(ops["fusion.a"] - 0.004) < 1e-12
    assert abs(ops["fusion.b"] - 0.004) < 1e-12
    assert r["device_ops"][0][1] >= r["device_ops"][-1][1]
    gaps = dict(r["idle_gaps"])
    # the 20 ms gap [10, 30) lies whole only in the enclosing call
    # (block_until_ready ends at 29; `tiny` covers just its middle);
    # so does the 1 ms gap
    assert abs(gaps["PjitFunction(level)"] - 0.021) < 1e-12
    assert "tiny" not in gaps


def test_two_devices_average():
    doc = hand_made()
    second = json.loads(json.dumps(doc["planes"][0]))
    second["name"] = "/device:TPU:1"
    second["lines"][1]["events"] = [["fusion.a", 0, 1 * MS]]
    doc["planes"].append(second)
    r = trace_reduce.reduce(doc)
    assert r["devices"] == 2
    assert abs(r["busy_s"] - (0.013 + 0.001) / 2) < 1e-12
    assert r["busy_s_per_device"] == [0.013, 0.001]


def test_no_device_plane_reads_nothing():
    doc = {"planes": [p for p in hand_made()["planes"]
                      if p["name"].startswith("/host")]}
    assert trace_reduce.reduce(doc) is None


def test_digits_collapse_in_gap_names():
    doc = hand_made()
    doc["planes"][1]["lines"][0]["events"] = [
        ["level 7 dispatch", 12 * MS, 10 * MS]]   # the middle only
    gaps = dict(trace_reduce.reduce(doc)["idle_gaps"])
    assert "level N dispatch" in gaps


def recorded():
    with gzip.open(os.path.join(DATA, "defect_trace_slice.json.gz"),
                   "rt") as f:
        return json.load(f)


def test_recorded_slice_from_the_chip():
    """400 device operations from the middle of a defect-bfs-timed
    trace (TPU v5 lite, PR 24), every line cut to the same interval.
    Busy is checked against a second way of taking the union."""
    doc = recorded()
    (plane,) = [p for p in doc["planes"] if p["name"] == "/device:TPU:0"]
    assert {ln["name"] for ln in plane["lines"]} >= {"XLA Ops"}
    r = trace_reduce.reduce(doc)
    assert r["devices"] == 1 and r["device_events"] == 400
    # the union again, by sweeping the sorted end points
    (ops,) = [ln["events"] for ln in plane["lines"]
              if ln["name"] == "XLA Ops"]
    points = sorted([(s, 1) for _n, s, d in ops]
                    + [(s + d, -1) for _n, s, d in ops],
                    key=lambda p: (p[0], -p[1]))
    depth, busy_ns, since = 0, 0, None
    for t, step in points:
        if depth == 0 and step == 1:
            since = t
        depth += step
        if depth == 0:
            busy_ns += t - since
    assert abs(r["busy_s"] - busy_ns / 1e9) < 1e-12
    assert r["busy_s"] <= r["span_s"]
    # self times never exceed busy time, and cover it where nothing nests
    total = sum(sec for _name, sec in trace_reduce.reduce(
        doc, top=10_000)["device_ops"])
    assert total <= r["busy_s"] + 1e-12
    assert total > 0.99 * r["busy_s"]
    name, seconds = r["device_ops"][0]
    assert name == "copy.25923 s32[8960,32,3,4]"
    assert abs(seconds - 5.2763e-05) < 1e-12
    assert r["device_opcodes"][0][0] == "copy"


def test_short_names_and_opcodes():
    hlo = ("%fusion.13949 = u32[2097152,5]{0,1:T(8,128)S(1)} fusion("
           "u32[2097152,5]{0,1:T(8,128)S(1)} %get-tuple-element.11630), "
           "kind=kCustom, calls=%fused_computation.1883")
    assert trace_reduce.short_name(hlo) == "fusion.13949 u32[2097152,5]"
    assert trace_reduce.opcode(hlo) == "fusion kCustom"
    tup = ("%dus_fusion.3 = (s32[8960,32,9]{1,2,0:T(8,128)S(1)}, "
           "s32[512,32,9]{1,2,0}) fusion(s32[8960,32,9]{1,2,0} %g.1), "
           "kind=kLoop, calls=%c")
    assert trace_reduce.short_name(tup) == "dus_fusion.3 s32[8960,32,9]"
    assert trace_reduce.opcode(tup) == "fusion kLoop"
    assert trace_reduce.opcode("jit_level") == "other"


def test_coverage_is_span_over_window():
    """What the device's events span of the traced window: the hand-made
    extract spans 34 ms.  A line like the ledger's PR 34
    defect-bfs-timed (0.8 of 3.8 s held) reads far under 0.5."""
    r = trace_reduce.reduce(hand_made())
    assert abs(trace_reduce.coverage(r, 0.034) - 1.0) < 1e-9
    assert abs(trace_reduce.coverage(r, 0.17) - 0.2) < 1e-9
    assert trace_reduce.coverage(None, 3.0) is None
    assert trace_reduce.coverage(r, 0) is None
