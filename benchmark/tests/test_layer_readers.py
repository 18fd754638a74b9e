"""The per-layer readers that read the program's build counters,
snapshot phase and unattributed idle seconds: each on a made-up
metrics document or reduced trace, and `None` where its source is
absent (a program without the counter, an untraced run)."""

import pytest

import cells
import trace_reduce

MS = 1_000_000

DOC = {"phases": {"check": 1.0, "compile": 60.0, "checkpoint": 6.5},
       "gauges": {"build_trace_s": 31.5, "build_lower_s": 22.25,
                  "build_backend_s": 9.0, "occupancy": 0.1},
       "counters": {"build_programs": 61}}
# a program from before the build counters: same document, no gauges
OLD_DOC = {"phases": {"check": 1.0, "compile": 60.0},
           "gauges": {"occupancy": 0.1}, "counters": {}}
TRACE = {"busy_s": 1.0,
         "idle_gaps": [["PjitFunction(level)", 64.6],
                       ["no host span", 0.25],
                       ["tpuvsr.engine.checkpoint", 0.125]]}


def reader(name):
    return cells.load_plugin("layer_metrics", name).read


@pytest.mark.parametrize("name, want", [
    ("engine.build_trace_s", 31.5),
    ("engine.build_lower_s", 22.25),
    ("engine.build_backend_s", 9.0),
    ("engine.snapshot_s", 6.5),
    ("engine.build_trace_s.bfs", 31.5),
    ("engine.build_lower_s.bfs", 22.25),
    ("engine.build_backend_s.bfs", 9.0),
])
def test_counter_readers(name, want):
    read = reader(name)
    assert read({"metrics_doc": DOC}, None, None) == want
    assert read({"metrics_doc": DOC}, TRACE, None) == want
    # the parent's program has no such counter; a job may leave no
    # metrics document at all
    assert read({"metrics_doc": OLD_DOC}, TRACE, None) is None
    assert read({"metrics_doc": None}, TRACE, None) is None
    assert read({}, None, None) is None


@pytest.mark.parametrize("name", ["device.idle_unattributed_s",
                                  "device.idle_unattributed_s.bfs"])
def test_idle_unattributed_reader(name):
    read = reader(name)
    assert read({}, TRACE, None) == 0.25
    # every long gap lies under a span: below the tenth name, reads 0
    covered = dict(TRACE, idle_gaps=[["tpuvsr.engine.inflight", 2.0]])
    assert read({}, covered, None) == 0.0
    assert read({"metrics_doc": DOC}, None, None) is None
    assert read({}, {"busy_s": 1.0}, None) is None


def test_idle_unattributed_on_a_reduced_extract():
    """Through the reducer: a gap under one of the program's spans is
    attributed to it, a gap under none is `no host span`."""
    ops = [["fusion.a", 0, 1 * MS], ["fusion.a", 11 * MS, 1 * MS],
           ["fusion.a", 32 * MS, 1 * MS]]
    host = [["tpuvsr.engine.checkpoint", 1 * MS, 10 * MS]]
    doc = {"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU",
         "lines": [{"name": "main", "events": host}]}]}
    trace = trace_reduce.reduce(doc)
    gaps = dict(trace["idle_gaps"])
    assert abs(gaps["tpuvsr.engine.checkpoint"] - 0.010) < 1e-12
    assert abs(reader("device.idle_unattributed_s")({}, trace, None)
               - 0.020) < 1e-12


def test_every_declared_metric_has_a_reader():
    for m in cells.benchmark_doc()["per_layer"]:
        assert callable(reader(m["name"])), m["name"]


def test_stage_table_arithmetic():
    """tools/stage_table.py: the innermost scope on an operation's name
    stack names its stage, and an enclosing event is charged only what
    its children leave uncovered."""
    import importlib.util
    import os
    path = os.path.join(cells.BENCH, "tools", "stage_table.py")
    spec = importlib.util.spec_from_file_location("stage_table", path)
    st = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(st)
    stack = ("jit(level)/while/body/tpuvsr.level.pack_scatter/"
             "tpuvsr.level.fpset_insert/scatter")
    assert st.stage_of([("flops", 3), ("tf_op", stack)]) \
        == ("tf_op", "tpuvsr.level.fpset_insert")
    assert st.stage_of([("tf_op", "jit(level)/while/cond/lt")]) \
        == (None, st.UNSCOPED)
    events = [(0, 10 * MS, st.UNSCOPED),                    # the while
              (1 * MS, 3 * MS, "tpuvsr.level.expand"),
              (5 * MS, 4 * MS, "tpuvsr.level.fpset_insert"),
              (20 * MS, 2 * MS, "tpuvsr.level.expand")]
    total = {}
    for stage, ns in st.self_seconds(events):
        total[stage] = total.get(stage, 0) + ns
    assert total == {st.UNSCOPED: 3 * MS,
                     "tpuvsr.level.expand": 5 * MS,
                     "tpuvsr.level.fpset_insert": 4 * MS}

    # end to end on a made-up XSpace: the name stack is a stat of the
    # operation's METADATA, by value or by reference
    space = st.xplane_pb2().XSpace()
    plane = space.planes.add(name="/device:TPU:0")
    plane.stat_metadata[1].name = "tf_op"
    plane.stat_metadata[2].name = stack
    plane.stat_metadata[3].name = "flops"
    for mid, name in ((1, "%while.1"), (2, "%fusion.a"), (3, "%fusion.b")):
        plane.event_metadata[mid].name = name
    plane.event_metadata[2].stats.add(metadata_id=1, ref_value=2)
    plane.event_metadata[2].stats.add(metadata_id=3, uint64_value=7)
    plane.event_metadata[3].stats.add(
        metadata_id=1, str_value="jit(level)/tpuvsr.level.expand/mul")
    plane.lines.add(name="Steps").events.add(metadata_id=1,
                                             duration_ps=99)
    ops = plane.lines.add(name="XLA Ops", timestamp_ns=5)
    for mid, off, dur in ((1, 0, 10_000), (2, 1_000, 3_000),
                          (3, 5_000, 4_000), (3, 20_000, 2_000)):
        ops.events.add(metadata_id=mid, offset_ps=off, duration_ps=dur)
    space.planes.add(name="/host:CPU").lines.add(name="main")
    import tempfile
    with tempfile.TemporaryDirectory() as tmp:
        full = os.path.join(tmp, "t.xplane.pb")
        with open(full, "wb") as f:
            f.write(space.SerializeToString())
        slim = os.path.join(tmp, "slim.xplane.pb")
        doc = st.table(full, slim=slim)
        again = st.table(slim)
    assert doc["scope_stat"] == {"tf_op": 3} and doc["devices"] == 1
    assert doc["device_events"] == 4
    got = {r["stage"]: round(r["device_s"] * 1e12) for r in doc["stages"]}
    assert got == {st.UNSCOPED: 3_000, "tpuvsr.level.fpset_insert": 3_000,
                   "tpuvsr.level.expand": 6_000}
    assert again["stages"] == doc["stages"]
