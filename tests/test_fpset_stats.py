"""`fpset.table_stats` reduces a table where it lies and returns two
counts; the walk over a host copy that it replaced (ISSUE 31: 5.4 GB
and 268 M rows of fancy indexing at 1<<28 slots) stays here as the
reference."""

import numpy as np
import pytest

import jax.numpy as jnp

from tpuvsr.engine import fpset


def table_stats_np(slots):
    """The numpy walk `table_stats` was until PR 31."""
    s = np.asarray(slots)
    cap = int(s.shape[0])
    occ = s[:, 0] != 0
    n = int(occ.sum())
    out = {"capacity": cap, "occupied": n,
           "occupancy": n / cap if cap else 0.0,
           "displaced": 0, "collision_rate": 0.0}
    if n == 0:
        return out
    keyed = s[occ, :4].astype(np.uint32)
    with np.errstate(over="ignore"):
        # numpy replica of _slot_hash (stored words are already keyed)
        h = keyed[:, 0] ^ (keyed[:, 1] * np.uint32(0x9E3779B1))
        h = h ^ (keyed[:, 2] * np.uint32(0x85EBCA6B)) ^ (keyed[:, 3] >> 5)
        h = h ^ (h >> 15)
        home = (h * np.uint32(0x27D4EB2F)) & np.uint32(cap - 1)
    idx = np.nonzero(occ)[0].astype(np.uint32)
    displaced = int((home != idx).sum())
    out["displaced"] = displaced
    out["collision_rate"] = displaced / n
    return out


def seeded_table(cap, n, seed):
    fps = np.random.default_rng(seed).integers(
        0, 1 << 32, size=(n, 4), dtype=np.uint32)
    fps[0, 0] = 0           # a tag the table remaps to 1
    table, fresh, overflow = fpset.insert_batch(
        fpset.empty_table(cap), jnp.asarray(fps), jnp.ones((n,), bool))
    assert int(fresh.sum()) == n and not bool(overflow)
    return table["slots"]


# each case a capacity of its own: the reduction is one jitted program
# per table shape, and the last case traces it with a smaller piece
@pytest.mark.parametrize("cap,n,piece", [
    (1 << 7, 0, None), (1 << 8, 150, None), (1 << 9, 300, 64)],
    ids=["empty", "displaced", "in-8-pieces"])
def test_device_stats_equal_numpy_walk(monkeypatch, cap, n, piece):
    if piece:
        monkeypatch.setattr(fpset, "STATS_PIECE", piece)
    slots = seeded_table(cap, n, seed=cap) if n \
        else fpset.empty_table(cap)["slots"]
    want = table_stats_np(slots)
    assert want["occupied"] == n
    assert (want["displaced"] > 0) == (n > 0)
    assert fpset.table_stats(slots) == want
    # a host copy reads the same
    assert fpset.table_stats(np.asarray(slots)) == want
