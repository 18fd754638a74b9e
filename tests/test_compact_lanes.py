"""Stage 2's selection of an action's enabled lanes (`enabled_lanes`,
ISSUE 56) against `jnp.nonzero`, which made it until then.

`Stage2.tile_pass` compacts, per action, the enabled (state, lane)
items of a tile's guard bits into `E_a` slots.  The helper makes no
scatter and no sort; it has to give `nonzero`'s three arrays element
for element (state-major, then lane; a slot at or past the count reads
``(T - 1, 0, False)``; the first `E_a` set bits where an action passes
its cap), at every shape a cell runs, under `jit` and under the
`fori_loop` the level program runs it in.  And one real tile of the
defect cfg goes through `tile_pass` with either selection: the queue,
plane by plane, the blocks run and the per-slot verdicts are the same.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tpuvsr.engine import device_bfs
from tpuvsr.engine.device_bfs import DeviceBFS, I32, enabled_lanes
from tpuvsr.engine.spec import load_spec

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# (T, L_a, E_a): the widest and the narrowest actions of the one-chip
# cells (tile 128) and of the sharded step (tile 32), a cap a
# configuration's `expand_mults` raised, and one no cell fills
SHAPES = [(128, 32, 512), (128, 96, 512), (128, 216, 768), (128, 3, 384),
          (32, 96, 128), (32, 3, 96), (128, 32, 4096)]
# set bits: none, one, sparse, dense, all, and more than the cap holds
FILLS = ["none", "one", 0.03, 0.2, "all", "over"]
TILES = 4


def nonzero_lanes(en, slots):
    """The selection as `Stage2.tile_pass` made it until ISSUE 56."""
    T, L = en.shape
    (sel,) = jnp.nonzero(en.reshape(T * L), size=slots, fill_value=T * L)
    return (jnp.clip(sel // L, 0, T - 1).astype(I32),
            (sel % L).astype(I32), sel < T * L)


def _bits(rng, shape, fill, slots):
    rows, L = shape
    if fill == "none":
        return np.zeros(shape, bool)
    if fill == "all":
        return np.ones(shape, bool)
    if fill == "one":
        en = np.zeros(shape, bool)
        en[rng.integers(rows), rng.integers(L)] = True
        return en
    if fill == "over":
        # a tile's worth holds more set bits than the cap, where the
        # shape has that many lanes at all
        return rng.random(shape) < min(1.0, 1.5 * slots * TILES / (rows * L))
    return rng.random(shape) < fill


def _under_loop(select, T, slots):
    """`select` over the tiles of a chunk, as the level program's tile
    loop runs it."""
    def run(bits):
        def tile(t, out):
            en = jax.lax.dynamic_slice_in_dim(bits, t * T, T)
            return tuple(
                jax.lax.dynamic_update_slice_in_dim(buf, v[None], t, 0)
                for buf, v in zip(out, select(en, slots)))
        return jax.lax.fori_loop(0, TILES, tile, (
            jnp.zeros((TILES, slots), I32), jnp.zeros((TILES, slots), I32),
            jnp.zeros((TILES, slots), bool)))
    return jax.jit(run)


@pytest.mark.parametrize("fill", FILLS, ids=str)
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_enabled_lanes_is_nonzero(shape, fill):
    T, L, slots = shape
    rng = np.random.default_rng([T, L, slots, FILLS.index(fill)])
    chunk = _bits(rng, (TILES * T, L), fill, slots)
    if fill == "over" and T * L > slots:
        assert chunk[:T].sum() > slots
    want = jax.jit(nonzero_lanes, static_argnums=1)(
        jnp.asarray(chunk[:T]), slots)
    got = jax.jit(enabled_lanes, static_argnums=1)(
        jnp.asarray(chunk[:T]), slots)
    for name, g, w in zip(("pidx", "lane", "ok"), got, want):
        assert g.dtype == w.dtype and g.shape == w.shape == (slots,), name
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), name)
    n = min(int(chunk[:T].sum()), slots)
    assert int(got[2].sum()) == n and bool(got[2][:n].all())
    # under the tile loop: every tile of the chunk, each with bits of
    # its own
    want = _under_loop(nonzero_lanes, T, slots)(jnp.asarray(chunk))
    got = _under_loop(enabled_lanes, T, slots)(jnp.asarray(chunk))
    for name, g, w in zip(("pidx", "lane", "ok"), got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), name)


# --- one real tile through `tile_pass` --------------------------------------

TILE = 32           # the sharded cell's tile: caps of 128 and 96 slots
WALK_LEVELS = 8


def _walked(spec, codec, kern, rows):
    """`rows` states reached breadth first from the init state through
    the kernel's own actions (a level wider than `rows` is expanded
    from a random `rows` of its states), drawn at random from the
    levels past the third: dense planes ``[rows, ...]``."""
    rng = np.random.default_rng(56)
    (init,) = spec.init_states()
    front, seen, deep = [codec.encode(init)], set(), []
    for level in range(WALK_LEVELS):
        head = [front[i] for i in rng.permutation(len(front))[:rows]]
        batch = [head[i % len(head)] for i in range(rows)]
        succs, en = kern.step_batch(
            {k: jnp.asarray(np.stack([s[k] for s in batch]))
             for k in batch[0]})
        en = np.asarray(en)[:len(head)]
        succs = {k: np.asarray(v) for k, v in succs.items()}
        front = []
        for n, lane in zip(*np.nonzero(en)):
            st = {k: v[n, lane] for k, v in succs.items()
                  if not k.startswith("_")}
            key = b"".join(st[k].tobytes() for k in sorted(st))
            if key not in seen and not st["err"]:
                seen.add(key)
                front.append(st)
        if level >= 3:
            deep += front
    assert len(deep) >= rows
    states = [deep[i] for i in rng.permutation(len(deep))[:rows]]
    return {k: np.stack([s[k] for s in states]).astype(np.int32)
            for k in states[0]}


def _tile_queue(eng, tile):
    """`tile` through stage 1's guards and `Stage2.tile_pass` at the
    engine's caps: (queue, q_end, blocks, per-action segments)."""
    caps = eng._expand_caps()
    guards = eng._guard_matrix(eng.kern)

    def run(tile):
        en_segs = guards(tile)
        cnts = jnp.stack([e.sum(dtype=I32) for e in en_segs])
        segs = []
        queue, q_end, blocks = eng._stage2.tile_pass(caps, sum(caps))(
            tile, en_segs, cnts, lambda aid, seg: segs.append(seg))
        return queue, q_end, blocks, segs, cnts

    return jax.device_get(jax.jit(run)(tile))


def test_tile_pass_queue_is_the_parents(monkeypatch):
    """A real tile of the defect cfg (32 states walked from Init, the
    sharded cell's tile and caps): the queue `tile_pass` builds with
    `enabled_lanes` is, plane by plane, the one it builds with
    `jnp.nonzero` in its place."""
    spec = load_spec("VSR", os.path.join(
        REPO, "benchmark", "configs", "vsr-defect.cfg"))
    eng = DeviceBFS(spec, max_msgs=32, tile_size=TILE)
    tile = _walked(spec, eng.codec, eng.kern, TILE)
    got = _tile_queue(eng, tile)
    monkeypatch.setattr(device_bfs, "enabled_lanes", nonzero_lanes)
    want = _tile_queue(eng, tile)
    cnts = got[4]
    # the tile is a real one: several actions have enabled lanes, some
    # fill more than one block, and nothing passes its cap
    assert (cnts > 0).sum() >= 6 and cnts.max() > device_bfs.block_rows(96)
    assert (cnts <= np.asarray(eng._expand_caps())).all()
    assert int(got[1]) == int(want[1]) > 0
    flat_got, tree = jax.tree_util.tree_flatten(got)
    flat_want, tree_want = jax.tree_util.tree_flatten(want)
    assert tree == tree_want
    for g, w in zip(flat_got, flat_want):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(g, w)
    assert set(got[0]) >= {"rows", "fp", "en", "aid", "pidx", "lane"}
    assert int(got[0]["en"].sum()) == int(cnts.sum())
