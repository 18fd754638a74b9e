"""Rows leave the resident engine's buffers in pages of one shape
(ISSUE 43): no device-to-host pull of `DeviceBFS` runs a program whose
shape depends on a level's size.

The parent sliced the three trace-pointer planes, and a snapshot's
frontier, to the level's size eagerly: `n_next` is a Python int, so
every level of a new size was a new XLA program, compiled at the
level's end with the dispatch window drained.  `RowPages` cuts pages
of a chunk's rows with one jitted program whose start is a scalar and
trims the tail on the host; what reaches the host (trace pointers,
counterexamples, checkpoint payloads) is bit for bit what it was.
"""

import hashlib
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tpuvsr.engine import paged_bfs
from tpuvsr.engine.checkpoint import load_checkpoint
from tpuvsr.engine.device_bfs import (DeviceBFS, RowPages, _cut_pointers,
                                      _cut_rows)
from tpuvsr.engine.paged_bfs import PagedBFS
from tpuvsr.testing import stub_device_engine

ROWS = 8            # a page of the helper's own cases
CHUNK_TILES = 2     # the real kernel's: pages of 2 x 128 = 256 rows
DEPTH = 12          # levels of 1 to 3,289 states: 1 to 13 pages


# ---------------------------------------------------------------------
# (a) the helper against the plain slice
# ---------------------------------------------------------------------
def _buffer(kind, cap):
    """(cut, device buffer, row axis, the rows as the host would slice
    them from the whole buffer)."""
    rng = np.random.default_rng(cap)
    if kind == "pointers":
        planes = [rng.integers(-1, 1 << 20, cap).astype(np.int32)
                  for _ in range(3)]
        return (_cut_pointers, tuple(jnp.asarray(p) for p in planes), 1,
                lambda n: np.stack(planes)[:, :n])
    if kind == "packed":
        buf = rng.integers(0, 1 << 32, (cap, 5), dtype=np.uint32)
        return _cut_rows, jnp.asarray(buf), 0, lambda n: buf[:n]
    dense = {"view": rng.integers(0, 9, cap).astype(np.int32),
             "log": rng.integers(0, 9, (cap, 3, 2)).astype(np.int32)}
    return (_cut_rows, {k: jnp.asarray(v) for k, v in dense.items()}, 0,
            lambda n: {k: v[:n] for k, v in dense.items()})


# (capacity, n): a buffer of whole pages, one shorter than a page, and
# one whose last page would pass its end
SIZES = [(4 * ROWS, n) for n in (1, ROWS - 1, ROWS, ROWS + 1, 3 * ROWS,
                                 4 * ROWS)] \
    + [(5, 1), (5, 5), (30, 25), (30, 30)]


@pytest.mark.parametrize("cap, n", SIZES)
@pytest.mark.parametrize("kind", ["pointers", "packed", "dense"])
def test_pages_give_the_rows_a_slice_gives(kind, cap, n):
    cut, buf, axis, plain = _buffer(kind, cap)
    pages = RowPages(cut, buf, n, ROWS, axis)
    rows = min(ROWS, cap)
    assert len(pages.pages) == -(-n // rows)
    assert {np.shape(v)[axis] for v in jax.tree.leaves(pages.pages)} \
        == {rows}
    got, want = pages.host(), plain(n)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (g.dtype, g.shape) == (w.dtype, w.shape)
        assert g.tobytes() == w.tobytes()
        assert g.base is None           # arrays of their own


@pytest.mark.parametrize("kind", ["pointers", "packed", "dense"])
def test_a_buffer_is_one_program_whatever_it_holds(kind):
    cap = 6 * ROWS + 3                  # no other test's shape
    cut, buf, axis, _ = _buffer(kind, cap)
    RowPages(cut, buf, 1, ROWS, axis).host()
    built = cut._cache_size()
    for n in (2, ROWS + 1, 5 * ROWS, cap):
        RowPages(cut, buf, n, ROWS, axis).host()
    assert cut._cache_size() == built


# ---------------------------------------------------------------------
# (b)-(e) the engine, on the small check's real kernel
# ---------------------------------------------------------------------
def _pointers(eng):
    eng._flush_pointers()
    return tuple(np.concatenate(h) for h in (
        eng._h_parent, eng._h_action, eng._h_param))


def _pull_pages(levels, rows):
    return sum(-(-n // rows) for n in levels[1:])


@pytest.fixture(scope="module")
def resident(small_native, tmp_path_factory):
    """One engine object: a warm-up to depth 3 (what a benchmark
    window's set-up runs), the run to DEPTH, and a third run that
    snapshots every level and stops in the middle, every frontier it
    snapshot held beside the plain slice of the same buffer."""
    eng = DeviceBFS(small_native, chunk_tiles=CHUNK_TILES)
    out = {"eng": eng, "warm": eng.run(max_depth=3),
           "res": eng.run(max_depth=DEPTH)}
    out["pointers"] = _pointers(eng)

    out["ckpt"] = ckpt = str(tmp_path_factory.mktemp("pulls") / "ckpt")
    out["snapshots"] = taken = []
    paged_cut = eng._snapshot_frontier

    def both(buf, n):
        kw = paged_cut(buf, n)
        taken.append((kw, np.asarray(buf)[:n]))
        return kw
    eng._snapshot_frontier = both
    try:
        out["half"] = eng.run(max_depth=DEPTH // 2, checkpoint_path=ckpt)
    finally:
        del eng._snapshot_frontier
    out["half_pointers"] = _pointers(eng)
    return out


def test_a_second_run_compiles_nothing(resident, small_pin):
    """The parent built one `jit(dynamic_slice)` a level past the
    warm-up's depth (9 here), each with the device idle."""
    warm, res = resident["warm"], resident["res"]
    assert warm.levels == small_pin[:4]
    assert res.levels == small_pin[:DEPTH + 1]
    assert warm.metrics["counters"]["build_programs"] > 0
    assert res.metrics["counters"]["build_programs"] == 0
    assert res.metrics["gauges"]["build_backend_s"] == 0


def test_the_counter_is_the_pages_of_every_level(resident):
    rows = CHUNK_TILES * resident["eng"].tile
    for res in (resident["warm"], resident["res"], resident["half"]):
        c = res.metrics["counters"]
        assert c["boundary_pull_pages"] == _pull_pages(res.levels, rows)
        assert c["boundary_pull_bytes"] \
            == c["boundary_pull_pages"] * 3 * 4 * rows
    assert resident["res"].metrics["counters"]["boundary_pull_pages"] > DEPTH


def test_pointers_equal_the_paged_engines(resident, small_native):
    paged = PagedBFS(small_native, chunk_tiles=CHUNK_TILES)
    res = paged.run(max_depth=DEPTH)
    assert res.levels == resident["res"].levels
    for got, want in zip(resident["pointers"], _pointers(paged)):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(got, want)
    parent, action, param = resident["pointers"]
    assert parent.dtype == np.int64 and action.dtype == np.int32 \
        and param.dtype == np.int32
    assert len(parent) == res.distinct_states


def test_a_snapshot_holds_the_rows_a_slice_gives(resident):
    """Every level's frontier as `save_checkpoint` got it against
    `np.asarray(front)[:n]`, and the file of the last against the
    same; the pointers in the snapshot are the run's."""
    half = resident["half"]
    assert [len(plain) for _, plain in resident["snapshots"]] \
        == half.levels[1:]
    for kw, plain in resident["snapshots"]:
        (key, rows), = kw.items()
        assert key == "frontier_packed"
        assert (rows.dtype, rows.shape) == (plain.dtype, plain.shape)
        assert rows.tobytes() == plain.tobytes()
    with np.load(os.path.join(resident["ckpt"], "frontier.npz")) as f:
        assert f["packed"].tobytes() \
            == resident["snapshots"][-1][1].tobytes()
    ck = load_checkpoint(resident["ckpt"])
    for key, want in zip(("h_parent", "h_action", "h_param"),
                         resident["half_pointers"]):
        assert ck[key].dtype == want.dtype
        np.testing.assert_array_equal(ck[key], want)
    n = sum(half.levels)
    for got, want in zip(resident["half_pointers"], resident["pointers"]):
        np.testing.assert_array_equal(got, want[:n])


def test_a_resume_ends_where_the_whole_run_does(resident):
    eng, whole = resident["eng"], resident["res"]
    res = eng.run(resume_from=resident["ckpt"], max_depth=DEPTH)
    assert (res.levels, res.distinct_states, res.states_generated) \
        == (whole.levels, whole.distinct_states, whole.states_generated)
    for got, want in zip(_pointers(eng), resident["pointers"]):
        np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------
# the stub harness: dense planes, a counterexample, pages of 2 rows
# ---------------------------------------------------------------------
STUB = dict(tile_size=2, chunk_tiles=1)     # levels 1 2 3 4 3 2 1


@pytest.mark.parametrize("pack", [True, False], ids=["packed", "dense"])
def test_stub_snapshot_and_resume(pack, tmp_path):
    ckpt = str(tmp_path / "ckpt")
    eng = stub_device_engine(pack=pack, **STUB)
    half = eng.run(max_depth=3, checkpoint_path=ckpt)
    assert half.levels == [1, 2, 3, 4]
    assert half.metrics["counters"]["boundary_pull_pages"] == 1 + 2 + 2
    ck = load_checkpoint(ckpt)
    # the level of 4 states, two pages: (3,0) (2,1) (1,2) (0,3)
    assert sorted(zip(ck["frontier"]["x"].tolist(),
                      ck["frontier"]["y"].tolist())) \
        == [(0, 3), (1, 2), (2, 1), (3, 0)]
    whole = stub_device_engine(pack=pack, **STUB).run()
    res = stub_device_engine(pack=pack, **STUB).run(resume_from=ckpt)
    assert (res.levels, res.distinct_states) \
        == (whole.levels, whole.distinct_states) == ([1, 2, 3, 4, 3, 2, 1],
                                                     16)


@pytest.mark.parametrize("cls", [DeviceBFS, PagedBFS])
def test_a_known_counterexample_is_reproduced(cls):
    res = stub_device_engine(cls=cls, inv_bound=3, **STUB).run()
    assert not res.ok and res.violated_invariant == "Bound"
    assert [(t.action_name, t.state["x"], t.state["y"])
            for t in res.trace] == [
        (None, 0, 0), ("IncY", 0, 1), ("IncX", 1, 1), ("IncX", 2, 1),
        ("IncX", 3, 1)]


# ---------------------------------------------------------------------
# the paged engine's own page program is the parent's
# ---------------------------------------------------------------------
def test_the_paged_engines_drain_is_untouched():
    """`paged_bfs._drain_page` lowered at the paged cell's shapes
    (`benchmark/configs/vsr-defect-paged.json`: a next buffer of
    131,072 packed rows of 119 words, pages of 8,192): the digest was
    taken on the parent's tree (d6bd4e0)."""
    S = jax.ShapeDtypeStruct
    bufs = (S((131072, 119), jnp.uint32),) + (S((131072,), jnp.int32),) * 3
    text = paged_bfs._drain_page.lower(
        bufs, S((), jnp.int32), rows=8192).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "727a75a8e463f3b295809a4905b23ad1e7d65a3ca5da7341f43c6ee6a05220f0")
