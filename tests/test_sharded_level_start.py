"""What a sharded level starts with (ISSUE 44): every zero-filled
global array — the next buffer, the three pointer planes, the FPSet
shards — is filled on the device by a program the engine owns, and
the one pull of a dispatch's control scalars runs a program the
engine owns too.  So the host puts three control vectors a level and
a second `run()` of an engine compiles nothing.

The oracle of the arrays is what the engine did before:
`put_sharded(np.zeros(...))`.
"""

import inspect
import os
import subprocess
import sys

import numpy as np
import pytest

from tpuvsr.obs import RunObserver
from tpuvsr.parallel.multihost import launch, put_sharded
from tpuvsr.parallel.sharded_bfs import ShardedBFS
from tpuvsr.testing import stub_sharded_engine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
D = 2
PACK = {"packed": "auto", "dense": False}


@pytest.fixture(scope="module", params=sorted(PACK))
def twice(request):
    """One engine object: a warm-up to depth 3 (what a benchmark
    window's set-up runs), then the run to the fixpoint."""
    eng = stub_sharded_engine(n_devices=D, pack=PACK[request.param])
    return {"eng": eng, "warm": eng.run(max_depth=3), "res": eng.run()}


def _buffer_shapes(eng):
    """Shape and dtype of every zero array a level starts with."""
    rows = eng.D * eng.N
    if eng._pk is not None:
        nb = [((rows, eng._pk.words), np.uint32)]
    else:
        nb = [((rows,) + np.shape(v), np.int32)
              for v in eng.codec.zero_state().values()]
    return nb + [((rows,), np.int32)] * 3


def _nbytes(shapes):
    return sum(int(np.prod(s)) * np.dtype(t).itemsize for s, t in shapes)


def test_a_second_run_compiles_nothing(twice):
    """The parent made `pack_scalars` anew in every `run()`: one
    compile a run, with every chip idle."""
    warm, res = twice["warm"], twice["res"]
    assert warm.levels == [1, 2, 3, 4]
    assert res.ok and res.levels == [1, 2, 3, 4, 3, 2, 1]
    assert warm.metrics["counters"]["build_programs"] > 0
    assert res.metrics["counters"]["build_programs"] == 0
    assert res.metrics["gauges"]["build_backend_s"] == 0


def test_the_host_puts_the_control_vectors_and_no_more(twice):
    eng = twice["eng"]
    table = ((eng.D, eng.fp_cap, 5), np.uint32)
    for res in (twice["warm"], twice["res"]):
        c = res.metrics["counters"]
        levels = len(res.metrics["levels"])
        # nn, start_t, base_gid
        assert c["boundary_put_bytes"] == levels * 3 * eng.D * 4
        assert c["boundary_fill_bytes"] == \
            levels * _nbytes(_buffer_shapes(eng)) + _nbytes([table])


@pytest.mark.parametrize("what", ["next buffer", "pointer plane",
                                  "table shards"])
def test_zeros_equal_the_host_put_they_replace(twice, what):
    eng = twice["eng"]
    shapes = {"next buffer": _buffer_shapes(eng)[:-3],
              "pointer plane": _buffer_shapes(eng)[-1:],
              "table shards": [((eng.D, eng.fp_cap, 5), np.uint32)]}[what]
    obs = RunObserver.ensure(None, "sharded", eng.spec)
    for shape, dtype in shapes:
        new = eng._zeros(shape, dtype, obs)
        old = put_sharded(np.zeros(shape, dtype), eng._sh)
        assert (new.shape, new.dtype) == (old.shape, old.dtype)
        assert new.sharding.is_equivalent_to(old.sharding, new.ndim)
        assert [(s.device, s.index) for s in new.addressable_shards] \
            == [(s.device, s.index) for s in old.addressable_shards]
        assert np.asarray(new).tobytes() == np.asarray(old).tobytes()
    assert obs.metrics.counters["boundary_fill_bytes"] == _nbytes(shapes)


def test_alloc_frontier_is_the_buffers_of_the_formula(twice):
    eng = twice["eng"]
    obs = RunObserver.ensure(None, "sharded", eng.spec)
    nb, *pointers = eng._alloc_frontier(eng.N, obs)
    planes = [nb] if eng._pk is not None else list(nb.values())
    assert [(a.shape, a.dtype) for a in planes + pointers] \
        == [(s, np.dtype(t)) for s, t in _buffer_shapes(eng)]
    assert not any(np.asarray(a).any() for a in planes + pointers)
    assert "boundary_put_bytes" not in obs.metrics.counters


def test_no_jit_is_created_inside_run():
    assert "jax.jit(" not in inspect.getsource(ShardedBFS.run)


WORKER = """
from tpuvsr.parallel.multihost import init_from_env
pid = init_from_env()
import jax
from tpuvsr.testing import stub_sharded_engine
for pack in ("auto", False):
    eng = stub_sharded_engine(n_devices=len(jax.devices()), pack=pack)
    eng.run(max_depth=3)
    res = eng.run()
    c = res.metrics["counters"]
    print("RANK", pid, jax.process_count(), res.levels,
          c["build_programs"], c["boundary_put_bytes"], flush=True)
"""


def test_every_process_of_a_mesh_fills_its_own_shards():
    """Two processes of two devices each (gloo): no host array is made
    and none is put, so the maker is the same call on every rank."""
    probe = subprocess.run(
        [sys.executable, "-c", "import jax; jax.config.update("
         "'jax_cpu_collectives_implementation', 'gloo')"],
        capture_output=True, timeout=180)
    if probe.returncode:
        pytest.skip("gloo CPU collectives unavailable")
    rcs, outs = launch([sys.executable, "-c", WORKER], nproc=2,
                       local_devices=2, timeout=600,
                       extra_env={"PYTHONPATH": os.pathsep.join(
                           [REPO, os.environ.get("PYTHONPATH", "")])})
    assert rcs == [0, 0], outs
    for pid, out in enumerate(outs):
        rows = [x for x in out.splitlines() if x.startswith("RANK")]
        assert rows == [f"RANK {pid} 2 [1, 2, 3, 4, 3, 2, 1] 0 "
                        f"{7 * 3 * 4 * 4}"] * 2, out
