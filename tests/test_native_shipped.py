"""The shipped VSR.cfg (benchmark/configs/vsr-shipped.cfg: |Values|=2,
timer 2, SYMMETRY symmValues) on the kernel-native spec: every engine
with symmetry on against the plain reference of orbit reduction
(benchmark/tools/orbit_reference.py), level for level.

The reference decodes the states a symmetry-OFF run committed and
counts, on the host, the distinct least images of their VIEW
projections under Permutations(Values); it calls nothing of
engine/canon.py, of the kernel's `_permuted` or of its hash.  So it
tells a canonicalization that picks two images for one orbit (the
count is then the kernel's, not the spec's) from one that does not.
"""

import os
import sys

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from tpuvsr.engine.spec import load_spec

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark", "tools"))
import orbit_reference  # noqa: E402

CFG = os.path.join(REPO, "benchmark", "configs", "vsr-shipped.cfg")
DEPTH = 6       # 1,776 orbits of 2,506 states; every build is ~30 s
MAX_MSGS = 32
STATES = [1, 4, 14, 48, 168, 558, 1713]
ORBITS = [1, 3, 10, 35, 124, 403, 1200]


@pytest.fixture(scope="module")
def shipped():
    return load_spec("VSR", CFG)


@pytest.fixture(scope="module")
def symmetry_off(shipped):
    """(engine, result) of the symmetry-off run the reference reads."""
    return orbit_reference.symmetry_off_run(shipped, DEPTH,
                                            max_msgs=MAX_MSGS)


@pytest.fixture(scope="module")
def reference(shipped, symmetry_off):
    """Per level: the set of least images of the symmetry-off run."""
    eng, _res = symmetry_off
    return [set(orbit_reference.level_images(
        eng.codec, shipped.symmetry_perms, b)) for b in eng.level_blocks]


def _device(spec):
    from tpuvsr.engine.device_bfs import DeviceBFS
    return DeviceBFS(spec, max_msgs=MAX_MSGS)


def _paged(spec):
    from tpuvsr.engine.paged_bfs import PagedBFS
    return PagedBFS(spec, max_msgs=MAX_MSGS, retain_levels=True)


def _sharded(spec):
    from tpuvsr.parallel.sharded_bfs import ShardedBFS
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    return ShardedBFS(spec, Mesh(np.array(jax.devices()[:4]), ("d",)),
                      max_msgs=MAX_MSGS, tile=32, bucket_cap=128,
                      next_capacity=1 << 11, fpset_capacity=1 << 13)


def test_reference_counts_orbits(reference):
    assert [len(s) for s in reference] == ORBITS


@pytest.mark.parametrize("build", [_device, _paged, _sharded],
                         ids=["device", "paged", "sharded"])
def test_symmetry_on_levels_equal_the_reference(build, shipped,
                                                reference):
    eng = build(shipped)
    res = eng.run(max_depth=DEPTH)
    assert res.ok and res.error == f"depth limit {DEPTH} reached"
    assert list(eng.level_sizes) == [len(s) for s in reference]
    assert res.distinct_states == sum(ORBITS)
    assert res.metrics["gauges"]["symmetry_perms"] == 2
    assert res.metrics["counters"].get("grows", 0) == 0
    if build is _sharded:
        return
    # what canon did, counted on the device: it ran for every
    # generated state; some kept the swapped image
    counters = res.metrics["counters"]
    assert counters["canon_lanes"] == res.states_generated - 1
    assert 0 < counters["canon_relabelled"] < counters["canon_lanes"]
    if build is _paged:
        # the representatives the run committed (it keeps the levels
        # it expanded): no two of one orbit, and level for level the
        # reference's orbits
        assert len(eng.level_blocks) == DEPTH
        for block, want in zip(eng.level_blocks, reference):
            images = orbit_reference.level_images(
                eng.codec, shipped.symmetry_perms, block)
            assert len(set(images)) == len(images) == len(want)
            assert set(images) == want


def test_symmetry_off_is_the_ab_leg(symmetry_off):
    """`symmetry=False` on the same cfg: every orbit member is stored,
    and the level program carries no canon counter."""
    eng, res = symmetry_off
    assert list(eng.level_sizes[:DEPTH + 1]) == STATES
    assert res.metrics["gauges"]["symmetry_perms"] == 1
    assert not [k for k in res.metrics["counters"] if "canon" in k]
