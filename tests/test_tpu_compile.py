"""AOT compiles of the main path for a described v5e:2x2 — the chip's
compiler asked before the chip (on-chip-measurement guide §2.3).

Nothing runs and no device is attached: each test lowers one program
of the VSR checker at the defect widths (examples/VSR_defect.cfg:
R=3, |Values|=3, MAX_MSGS=32 — the bound the defect window ends at)
and the CLI's default tile, for `topo.devices[0]` (or a 4-device mesh
of `topo.devices`), and requires the TPU compiler to accept it inside
the chip's 16 GB.  A compile that passes is not a chip run:
chip_smoke.py is.

The topology is described inside a module-scoped fixture (never at
import: only one process may load the TPU library, and xdist workers
all import this file), the persistent compilation cache is off around
the compiles (an AOT entry cannot be read back without a chip), and
everything compiles in the test's own process.
"""

import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFECT_CFG = os.path.join(REPO, "examples", "VSR_defect.cfg")
HBM_BYTES = 16 << 30
MAX_MSGS = 32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "skip"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_cache():
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def spec():
    from tpuvsr.engine.spec import load_spec
    return load_spec("VSR", DEFECT_CFG)


@pytest.fixture(scope="module")
def engine(spec):
    from tpuvsr.engine.device_bfs import DeviceBFS
    return DeviceBFS(spec, max_msgs=MAX_MSGS)    # CLI defaults otherwise


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                       sharding=sharding), tree)


def _compile(lowered, what):
    """Compile, print seconds + memory analysis (CHANGES.md records
    them), and require the program to fit one chip."""
    t0 = time.time()
    compiled = lowered.compile()
    secs = time.time() - t0
    ma = compiled.memory_analysis()
    print(f"\n[tpu-compile] {what}: {secs:.1f}s  "
          f"args={ma.argument_size_in_bytes} "
          f"out={ma.output_size_in_bytes} "
          f"temp={ma.temp_size_in_bytes} "
          f"code={ma.generated_code_size_in_bytes}")
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert total < HBM_BYTES, f"{what} needs {total} bytes of HBM"
    return compiled


def test_fused_level_program(engine, one_chip, no_cache):
    """DeviceBFS's level program (`_fused_body_factory` inside
    `_make_level`) at the CLI's default tile/chunk/capacities."""
    from tpuvsr.engine.fpset import empty_table
    assert engine.commit == "fused" and engine._pk is not None
    bufs = engine._alloc_bufs(engine.next_cap)
    i32 = jnp.zeros((), jnp.int32)
    args = _shapes(
        ({"slots": empty_table(engine.fpset_capacity)["slots"]},
         bufs[0], i32, i32, *bufs, i32, jnp.zeros((), bool)),
        one_chip)
    lowered = engine._level.lower(*args, None, None,
                                  _shapes(i32, one_chip))
    _compile(lowered, f"fused level program tile={engine.tile} "
                      f"lanes={engine.L}")


def test_fpset_insert(one_chip, no_cache):
    """`fpset.insert_core` at the default capacity, one tile-sized
    batch of fingerprints."""
    from tpuvsr.engine.fpset import empty_table, insert_core
    cap = 1 << 20
    args = _shapes((empty_table(cap), jnp.zeros((4096, 4), jnp.uint32),
                    jnp.zeros((4096,), bool)), one_chip)
    _compile(jax.jit(insert_core).lower(*args),
             f"fpset.insert_core cap={cap}")


def test_pack_unpack_pair(engine, one_chip, no_cache):
    """The packed-frontier round trip at the defect layout."""
    pk = engine._pk
    zero = engine.codec.zero_state()
    n = engine.tile
    dense = _shapes({k: jnp.zeros((n,) + np.shape(v), jnp.int32)
                     for k, v in zero.items()}, one_chip)
    rows = _shapes(jnp.zeros((n, pk.words), jnp.uint32), one_chip)
    _compile(jax.jit(jax.vmap(pk.pack)).lower(dense),
             f"pack rows={n} words={pk.words}")
    _compile(jax.jit(jax.vmap(pk.unpack)).lower(rows), f"unpack rows={n}")


def test_sharded_step(spec, topo, no_cache):
    """`step_shard` of ShardedBFS over a 4-device mesh of the
    described chips: the all_to_all exchange must partition."""
    from tpuvsr.parallel.sharded_bfs import ShardedBFS
    mesh = Mesh(np.array(topo.devices), ("d",))
    eng = ShardedBFS(spec, mesh, max_msgs=MAX_MSGS)
    D, N = eng.D, eng.N
    sh = NamedSharding(mesh, P("d"))
    rows = jnp.zeros((D * N, eng._pk.words), jnp.uint32)
    col = jnp.zeros((D * N,), jnp.int32)
    per_dev = jnp.zeros((D,), jnp.int32)
    args = _shapes(
        ({"slots": jnp.zeros((D, eng.fp_cap, 5), jnp.uint32)},
         rows, per_dev, per_dev, rows, col, col, col, per_dev,
         per_dev), sh)
    compiled = _compile(eng._step.lower(*args),
                        f"sharded step D={D} tile={eng.tile}")
    assert "all-to-all" in compiled.as_text()


@pytest.fixture(scope="module")
def four_chip_engine(spec, topo):
    """ShardedBFS at the four-chip configuration's capacities over a
    mesh of the described chips (nothing is compiled to make it)."""
    import json

    from tpuvsr.parallel.sharded_bfs import ShardedBFS
    with open(os.path.join(REPO, "benchmark", "configs",
                           "vsr-defect-4chip.json")) as f:
        caps = json.load(f)["assumed"]["engine"]["sharded"]
    eng = ShardedBFS(spec, Mesh(np.array(topo.devices), ("d",)), **caps)
    assert (eng.N, eng.fp_cap) == (1 << 18, 1 << 21)
    return eng


def test_sharded_start_programs(four_chip_engine, no_cache):
    """The two programs of a sharded run's start (ISSUE 27) at the
    four-chip configuration's per-shard capacities: the packed first
    frontier padded on each shard, and the Init insert."""
    eng = four_chip_engine
    D, N, words = eng.D, eng.N, eng._pk.words
    sh, rep = eng._sh, eng._rep_sh
    u32 = jnp.uint32
    _compile(eng._fill_packed.lower(
        jax.ShapeDtypeStruct((words,), u32, sharding=rep),
        jax.ShapeDtypeStruct((D, words), u32, sharding=sh), N),
        f"sharded start fill D={D} N={N}")
    _compile(eng._sharded_ins.lower(
        {"slots": jax.ShapeDtypeStruct((D, eng.fp_cap, 5), u32,
                                       sharding=sh)},
        jax.ShapeDtypeStruct((1, 4), u32, sharding=rep),
        jax.ShapeDtypeStruct((1,), jnp.bool_, sharding=rep)),
        f"sharded Init insert D={D} slots={eng.fp_cap}")


@pytest.mark.parametrize("what", ["next buffer", "pointer plane",
                                  "table shards"])
def test_sharded_zero_fill(four_chip_engine, no_cache, what):
    """The zero arrays of a sharded level's start and of `init`
    (ISSUE 44) at the same capacities: each chip fills its own
    quarter, and the program takes nothing from the host."""
    eng = four_chip_engine
    D, N = eng.D, eng.N
    shape, dtype = {"next buffer": ((D * N, eng._pk.words), np.uint32),
                    "pointer plane": ((D * N,), np.int32),
                    "table shards": ((D, eng.fp_cap, 5), np.uint32)}[what]
    compiled = _compile(eng._zero_fill.lower(shape, np.dtype(dtype)),
                        f"sharded zero fill {what} {shape}")
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes == 0
    # a quarter of the array, as the chip tiles it (119 words lie in 120)
    quarter = int(np.prod(shape)) * 4 // D
    assert quarter <= ma.output_size_in_bytes < 1.02 * quarter


def test_sharded_control_scalars(four_chip_engine, no_cache):
    """The one pull of a dispatch's control scalars (`pack_scalars`,
    the engine's since ISSUE 44)."""
    eng = four_chip_engine
    D, A = eng.D, len(eng.kern.action_names)
    vec = jax.ShapeDtypeStruct((D,), jnp.int32, sharding=eng._sh)
    counts = jax.ShapeDtypeStruct((D, A), jnp.uint32, sharding=eng._sh)
    _compile(eng._pack_scalars.lower(vec, vec, vec, vec, vec, counts,
                                     counts),
             f"sharded control scalars D={D} A={A}")


def test_sharded_step_with_commit_stats(topo, no_cache):
    """`step_shard` of the four-chip CP06 configuration at its real
    capacities (ISSUE 55): a second kernel class, 812 lanes a state, and
    the kernel's ten `commit_stats` words riding each bucket row to its
    owner: seven all_to_alls where the defect step has six, and the
    control scalars' pull with the stat vectors in it."""
    import json

    from tpuvsr.engine.spec import load_spec
    from tpuvsr.parallel.sharded_bfs import ShardedBFS
    bench = os.path.join(REPO, "benchmark")
    with open(os.path.join(bench, "configs",
                           "vr-replica-recovery-cp-4chip.json")) as f:
        doc = json.load(f)
    eng = ShardedBFS(
        load_spec(doc["module"], os.path.join(bench, doc["cfg"])),
        Mesh(np.array(topo.devices), ("d",)),
        **doc["assumed"]["engine"]["sharded"])
    D, N, A = eng.D, eng.N, len(eng.kern.action_names)
    assert (N, eng.fp_cap, A) == (1 << 19, 1 << 22, 22)
    n_stat = len(eng.kern.COMMIT_STATS)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=eng._sh)
    per_dev = arg((D,), jnp.int32)
    rows, col = arg((D * N, eng._pk.words), jnp.uint32), arg((D * N,),
                                                             jnp.int32)
    compiled = _compile(
        eng._step.lower({"slots": arg((D, eng.fp_cap, 5), jnp.uint32)},
                        rows, per_dev, per_dev, rows, col, col, col,
                        per_dev, per_dev),
        f"sharded step with commit stats D={D} tile={eng.tile}")
    assert compiled.as_text().count("all-to-all(") == 7
    counts = arg((D, A), jnp.uint32)
    _compile(eng._pack_scalars.lower(per_dev, per_dev, per_dev, per_dev,
                                     per_dev, counts, counts,
                                     arg((D, n_stat), jnp.uint32)),
             f"sharded control scalars D={D} A={A} stats={n_stat}")


@pytest.mark.parametrize("what", ["insert", "stats", "page-out"])
def test_paged_deployment_programs(one_chip, no_cache, what):
    """The programs beside the level program that touch the paged
    configuration's FPSet and pages (ISSUE 31), at its capacities:
    each must fit the chip beside a table of 1<<28 slots — 8.59 GB
    there, not 5.37: the compiler pads a slot's 5 words to 8."""
    import json

    from tpuvsr.engine import fpset
    from tpuvsr.engine.paged_bfs import _drain_page
    with open(os.path.join(REPO, "benchmark", "configs",
                           "vsr-defect-paged.json")) as f:
        caps = json.load(f)["assumed"]["engine"]["paged"]
    cap, nc = caps["fpset_capacity"], caps["next_capacity"]
    rows = caps["chunk_tiles"] * caps["tile_size"]
    assert cap == 1 << 28

    def s(shape, dtype=jnp.uint32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    if what == "insert":
        compiled = _compile(fpset.insert_batch.lower(
            {"slots": s((cap, 5))}, s((2048, 4)), s((2048,), jnp.bool_)),
            f"fpset.insert_batch cap={cap}")
        table = compiled.memory_analysis().alias_size_in_bytes
        assert table == cap * 32            # donated, in place, padded
    elif what == "stats":
        compiled = _compile(fpset._occupied_displaced.lower(s((cap, 5))),
                            f"fpset._occupied_displaced cap={cap}")
        # a pass in pieces: no temporary the size of a table column
        assert compiled.memory_analysis().temp_size_in_bytes < 1 << 26
    else:
        col = s((nc,), jnp.int32)
        _compile(_drain_page.lower((s((nc, 119)), col, col, col),
                                   s((), jnp.int32), rows=rows),
                 f"paged page-out rows={rows} of {nc}")
