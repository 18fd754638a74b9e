"""Traffic kind `bfs-timed-paged`: the `bfs-timed` check on the
host-paged engine — one exhaustive breadth-first check from Init under
`PagedBFS.run(max_seconds)`, on the engine object that set-up built
and warmed.

What it adds to `bfs-timed`, and why it is a kind of its own: that
kind's `build_engine` knows `device` and `sharded` and its `check`
treats only `device` as an engine that tests its budget at chunk
collects (the level a budget cuts is partial); neither may be edited.
This kind builds `PagedBFS(spec, **assumed.engine.paged)` (the FPSet
alone on the device, the frontier paged through it from host RAM),
takes `bfs-timed`'s set-up, window, comparisons and end-to-end
metric as they are, and holds the run to what the paging promises:
every committed row left the device once (`spill_rows` = the states
past Init), pages of one shape in and one out, no growth, and the
FPSet at the configuration's size.  A configuration without an
`assumed.engine.paged` entry (the rehearsal's vsr-small) runs the
engine's defaults.
"""

import copy

import cells
import oracle


def _capacities(cell):
    return dict(cell.config["assumed"]["engine"].get("paged", {}),
                **(cell.traffic.get("engine_flags") or {}))


def _build_engine(cell, spec):
    # an engine that lacks what `requires` names refuses here, before
    # it builds anything (one from before ISSUE 31 by a TypeError)
    from tpuvsr.engine.paged_bfs import PagedBFS
    return PagedBFS(spec, **_capacities(cell))


# this kind's own instance of bfs-timed (load_plugin makes a module
# per call), with the one function that names the engines replaced:
# set-up (trace walk, build, warm-up), window and end-to-end metric
# are bfs-timed's, line for line
_base = cells.load_plugin("traffic_kinds", "bfs-timed")
_base.build_engine = _build_engine
setup = _base.setup
window = _base.window
end_to_end = _base.end_to_end


def check(cell, state, obs):
    # bfs-timed's comparisons, read as for its other engine that tests
    # the budget at chunk collects: the level a budget cut is partial
    as_device = copy.copy(cell)
    as_device.traffic = dict(cell.traffic, engine="device")
    checked = _base.check(as_device, state, obs)
    out = checked["comparisons"]
    doc = obs["metrics_doc"]
    counters, gauges = doc["counters"], doc["gauges"]
    out.append(oracle.compare("paging.spill_rows_equal_states_past_init",
                              counters.get("spill_rows", 0),
                              sum(obs["levels"][1:])))
    shapes = counters.get("page_shapes")
    out.append(oracle.compare("paging.page_shapes", shapes, "1 or 2",
                              ok=shapes in (1, 2)))
    out.append(oracle.compare("paging.grows", obs["grows"], 0))
    out.append(oracle.compare(
        "paging.fpset_capacity", gauges.get("fpset_capacity"),
        int(_capacities(cell).get("fpset_capacity",
                                  state["engine"].fpset_capacity))))
    return checked
