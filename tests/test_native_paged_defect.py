"""The host-paged engine on the defect constants, from committed
files: the deployment `benchmark/configs/vsr-defect-paged.json` names,
at a depth the CPU reaches.  `PagedBFS` against the pinned level sizes
(`scripts/defect_window.json`) and against the resident engine, which
runs the same level program on a frontier that never leaves the
device: equal levels, equal distinct states, equal generated states.
"""

import json
import os

import pytest

from tpuvsr.engine.device_bfs import DeviceBFS
from tpuvsr.engine.paged_bfs import PagedBFS

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEPTH = 6
# one set of capacities for both engines: the level program of the
# second is then the first's, read back from the compile cache
CAPS = dict(max_msgs=32, tile_size=128, next_capacity=1 << 14,
            fpset_capacity=1 << 16)
# the paged configuration's pre-calibrated caps (depth 11 needs 534
# lanes of ReceiveHigherSVC in one tile of 128, the static start has
# 512): tile multipliers, as `expand_mults` has always taken them
MULTS = {"ReceiveHigherSVC": 17, "ReceiveMatchingSVC": 16}


@pytest.fixture(scope="module")
def defect_native():
    from tpuvsr.engine.spec import load_spec
    return load_spec("VSR", os.path.join(REPO, "examples",
                                         "VSR_defect.cfg"))


@pytest.fixture(scope="module")
def defect_pin():
    with open(os.path.join(REPO, "scripts", "defect_window.json")) as f:
        return json.load(f)["level_sizes"][:DEPTH + 1]


@pytest.fixture(scope="module")
def paged_run(defect_native):
    eng = PagedBFS(defect_native, chunk_tiles=2, expand_mults=MULTS,
                   **CAPS)
    start = dict(zip(eng.kern.action_names, eng._expand_caps()))
    return eng, eng.run(max_depth=DEPTH), start


def test_given_expand_mults_floor_the_fused_caps(paged_run):
    """A caller's `expand_mults` is the fused caps' start and the
    floor no calibration goes under; an action it does not name keeps
    the static start."""
    from tpuvsr.engine.device_bfs import static_cap
    eng, res, start = paged_run
    assert eng.commit == "fused"
    for name, full in ((n, eng.tile * eng.kern._lane_count(n))
                       for n in eng.kern.action_names):
        want = static_cap(eng.tile, full)
        if name in MULTS:
            want = max(want, min(full, eng.tile * MULTS[name]))
        assert start[name] == want, name
    assert start["ReceiveHigherSVC"] == 17 * 128
    # 4,095 states later (calibration looks at every level's end)
    assert dict(zip(eng.kern.action_names, eng._expand_caps())) == start
    assert res.metrics["counters"].get("grows", 0) == 0


def test_paged_defect_equals_the_pin(paged_run, defect_pin):
    eng, res, _ = paged_run
    assert res.ok and res.error == f"depth limit {DEPTH} reached"
    assert res.levels == defect_pin
    assert res.distinct_states == sum(defect_pin)
    assert eng.spill_rows == sum(defect_pin[1:])
    assert eng.codec.shape.MAX_MSGS == 32       # never grown
    c = res.metrics["counters"]
    assert c.get("grows", 0) == 0
    assert c["page_shapes"] == 2
    assert c["page_in_rows"] == sum(defect_pin[:DEPTH])


def test_paged_defect_equals_the_resident_engine(paged_run,
                                                 defect_native):
    _, paged, _ = paged_run
    res = DeviceBFS(defect_native, **CAPS).run(max_depth=DEPTH)
    assert res.levels == paged.levels
    assert res.distinct_states == paged.distinct_states
    assert res.states_generated == paged.states_generated
