"""Device-resident fingerprint set (the TLC FPSet rebuilt for HBM).

The reference workload drove TLC's disk-spilling FPSet to 500 GB
(README:20); the TPU engine instead keeps 128-bit fingerprints in an
HBM-resident open-addressing hash table and batch-inserts an entire
frontier expansion per call (SURVEY.md §2.5).

Layout: one ``slots[CAP, 5]`` uint32 array per table; columns are
(tag, row0, row1, row2, claim) where tag is word 0 of the fingerprint
(0 = empty slot; fingerprints with word 0 == 0 are remapped to 1),
row0..2 are words 1..3, and claim transiently holds the batch lane id
that claimed the slot.  Insertion is claim-then-verify linear probing,
fully vectorized over the batch:

  1. gather each lane's probe slot;
  2. lanes seeing their own (tag, row) are duplicates (resolved);
  3. lanes seeing empty scatter their full (tag, row, lane-id) payload
     in ONE scatter, then re-read; the lane that reads back its own
     payload — including the lane id — won (resolved, fresh); losers
     probe on.

Because the claim column disambiguates same-fingerprint writers within
one scatter, batches may contain duplicate fingerprints: exactly one
lane per distinct new fingerprint resolves fresh, and its duplicates
resolve as duplicates on the next probe iteration.  (This is what lets
the BFS level kernel skip sort-based intra-batch dedup entirely.)

Like TLC's 64-bit fingerprinting, set membership is probabilistic: a
128-bit collision silently merges two states — vanishingly unlikely at
reachable-set sizes.
"""

from __future__ import annotations

import os
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from ..obs import spans

U32 = jnp.uint32
MAX_PROBES = 64

# Experimental hedge for the tile-1024 TPU mis-exploration (ROADMAP
# S4): if the claim-then-verify scatter->gather
# pair is being fused/reordered by the TPU lowering, an optimization
# barrier between the claim write and the verify read forces the
# ordering.  Off by default; scripts/tpu_miscompile_repro.py flips it
# in a subprocess to test the hypothesis on hardware.
_CLAIM_BARRIER = os.environ.get("TPUVSR_FPSET_BARRIER", "0") == "1"


def empty_table(capacity: int):
    """capacity must be a power of two."""
    assert capacity & (capacity - 1) == 0
    return {"slots": jnp.zeros((capacity, 5), U32)}


def _slot_hash(fps):
    """[B, 4] -> [B] uint32 probe-start; decorrelated from the claim tag
    (word 0) so clustered tags don't cluster slots."""
    h = fps[:, 0] ^ (fps[:, 1] * jnp.uint32(0x9E3779B1))
    h = h ^ (fps[:, 2] * jnp.uint32(0x85EBCA6B)) ^ (fps[:, 3] >> 5)
    h = h ^ (h >> 15)
    return h * jnp.uint32(0x27D4EB2F)


@jax.named_scope(spans.FPSET_INSERT)
def dedup_batch(fps, mask, tie=None):
    """Keep the first occurrence of each distinct fingerprint.

    Returns (perm, keep): `perm` sorts the batch so equal fingerprints
    are adjacent (masked-out lanes sort to the end), `keep[i]` marks
    lanes of fps[perm] that are valid first occurrences.  With `tie`
    (an int array, one priority per lane) the winner among equal
    fingerprints is the lane with the SMALLEST tie value instead of
    the smallest batch position — the sharded fused-commit step passes
    the canonical state-major flat index so a compacted (reordered)
    batch picks the same winner the dense batch would (ISSUE 10).
    (The single-device BFS engine's fused commit relies on the default
    batch-position tie; the sharded exchange uses both forms.)

    With symmetry canonicalization on (ISSUE 11, engine/canon.py) the
    fps in a batch are orbit-least images, so ORBIT-MATES carry equal
    keys here: the stable first-occurrence winner is what decides
    which generated representative a whole orbit commits to the
    frontier — the same earliest-queue-item rule, now doing the
    orbit-level dedup too.
    """
    key = [jnp.where(mask, fps[:, i], jnp.uint32(0xFFFFFFFF))
           for i in range(4)]
    minor = (key[3],) if tie is None else (tie, key[3])
    perm = jnp.lexsort(minor + (key[2], key[1], key[0]))
    sfps = fps[perm]
    smask = mask[perm]
    neq = (sfps[1:] != sfps[:-1]).any(axis=1)
    first = jnp.concatenate([jnp.ones((1,), bool), neq])
    return perm, first & smask


def _keyed(fps):
    """Canonical (tag, row) encoding: word 0 remapped 0 -> 1 so 0 can
    mark empty slots; the probe chain hashes the canonical key so a
    table rebuilt by grow() probes identically to future lookups."""
    tag = jnp.where(fps[:, 0] == 0, jnp.uint32(1), fps[:, 0])
    keyed = jnp.concatenate([tag[:, None], fps[:, 1:]], axis=1)
    return keyed, _slot_hash(keyed)


@jax.named_scope(spans.FPSET_INSERT)
def insert_core(table, fps, mask):
    """Insert fps[mask] into the table.  Duplicate fingerprints within
    the batch are allowed: exactly one lane per distinct new fingerprint
    returns fresh.  Returns (table, fresh, overflow); overflow means
    some lanes were still unresolved after MAX_PROBES (their inserts
    did not happen — grow the table and retry).  Plain traceable
    function — compose inside a jit (insert_batch is the standalone
    jitted form)."""
    slots = table["slots"]
    cap = slots.shape[0]
    capm = jnp.uint32(cap - 1)
    keyed, h0 = _keyed(fps)
    n = fps.shape[0]
    lane_id = jnp.arange(n, dtype=U32)
    payload = jnp.concatenate([keyed, lane_id[:, None]], axis=1)  # [n, 5]

    def cond(carry):
        t, _slots, unresolved, _fresh = carry
        return (t < MAX_PROBES) & unresolved.any()

    def body(carry):
        t, slots, unresolved, fresh = carry
        idx = (h0 + jnp.uint32(t)) & capm
        cur = slots[idx]
        mine = (cur[:, :4] == keyed).all(axis=1)
        dup = unresolved & mine
        empty = unresolved & (cur[:, 0] == 0)
        # claim: one scatter writes tag+row+lane-id atomically, so the
        # read-back names a single winner even among equal fingerprints
        cidx = jnp.where(empty, idx, jnp.uint32(cap))  # OOB drops the write
        slots = slots.at[cidx].set(payload, mode="drop")
        if _CLAIM_BARRIER:
            slots = jax.lax.optimization_barrier(slots)
        post = slots[idx]
        won = empty & (post == payload).all(axis=1)
        # a lane that saw empty but reads back its own (tag, row) under
        # someone else's claim lost the race to an EQUAL fingerprint —
        # resolve it as a duplicate now; advancing the probe would
        # wrongly insert the fingerprint a second time at the next slot
        lost_dup = empty & ~won & (post[:, :4] == keyed).all(axis=1)
        fresh = fresh | won
        unresolved = unresolved & ~dup & ~won & ~lost_dup
        return t + 1, slots, unresolved, fresh

    _, slots, unresolved, fresh = jax.lax.while_loop(
        cond, body,
        (jnp.int32(0), slots, mask, jnp.zeros_like(mask)))
    return {**table, "slots": slots}, fresh, unresolved.any()


insert_batch = partial(jax.jit, donate_argnums=(0,))(insert_core)


STATS_PIECE = 1 << 22


@jax.jit
def _occupied_displaced(slots):
    """(occupied, displaced) of a ``slots`` array, reduced where it
    lies, `STATS_PIECE` slots at a time (one pass over 1<<28 slots
    keeps 1.3 GB of temporaries).  Stored words are already keyed, so
    `_slot_hash` of a slot's first four is its probe-chain start.
    uint32 sums: exact to 2**32 slots."""
    cap = slots.shape[0]
    piece = min(cap, STATS_PIECE)        # both powers of two

    def body(i, acc):
        at = i * piece
        part = jax.lax.dynamic_slice_in_dim(slots, at, piece)
        occ = part[:, 0] != 0
        home = _slot_hash(part[:, :4]) & jnp.uint32(cap - 1)
        idx = at.astype(U32) + jnp.arange(piece, dtype=U32)
        return (acc[0] + occ.sum(dtype=U32),
                acc[1] + (occ & (home != idx)).sum(dtype=U32))
    zero = jnp.zeros((), U32)
    return jax.lax.fori_loop(0, cap // piece, body, (zero, zero))


def table_stats(slots):
    """Occupancy/collision stats of a table's ``slots`` array (device
    or numpy).  "Displaced" slots are occupied slots not sitting at
    their probe-chain start — the linear-probing collision measure the
    obs layer reports as ``fpset_collision_rate``.  Reduced on the
    device the table lies on: two scalars come back, never the table
    (`tests/test_fpset_stats.py` keeps the numpy walk as the
    reference)."""
    cap = int(slots.shape[0])
    n, displaced = (int(x) for x in jax.device_get(
        _occupied_displaced(slots)))
    return {"capacity": cap, "occupied": n, "occupancy": n / cap,
            "displaced": displaced,
            "collision_rate": displaced / n if n else 0.0}


def query_core(table, fps, mask):
    """Read-only membership probe: returns (fresh, overflow).  `fresh`
    marks masked lanes whose fingerprint is NOT in the table (duplicate
    lanes within the batch all read fresh — callers using the count for
    capacity checks get a conservative overcount); lanes unresolved
    after MAX_PROBES raise `overflow` and are not fresh."""
    slots = table["slots"]
    cap = slots.shape[0]
    capm = jnp.uint32(cap - 1)
    keyed, h0 = _keyed(fps)

    def cond(carry):
        t, unresolved, _fresh = carry
        return (t < MAX_PROBES) & unresolved.any()

    def body(carry):
        t, unresolved, fresh = carry
        idx = (h0 + jnp.uint32(t)) & capm
        cur = slots[idx]
        mine = (cur[:, :4] == keyed).all(axis=1)
        empty = unresolved & (cur[:, 0] == 0)
        fresh = fresh | empty
        unresolved = unresolved & ~mine & ~empty
        return t + 1, unresolved, fresh

    _, unresolved, fresh = jax.lax.while_loop(
        cond, body, (jnp.int32(0), mask, jnp.zeros_like(mask)))
    return fresh, unresolved.any()


def store_gids(slots, vals, fps, gids, mask):
    """Write ``gids[mask]`` into the parallel ``vals[CAP]`` array at
    each masked lane's resolved probe slot.  Every masked fingerprint
    must already be PRESENT in ``slots`` (insert first, then store) —
    the lane re-probes its chain to find the slot it resolved to.
    Plain traceable function; the streamed edge-emission commit
    (ISSUE 15) composes it with ``insert_core`` inside the level
    kernel so every fresh state's graph node id lands next to its
    fingerprint, and ``lookup_gids`` then resolves successor
    fingerprints — fresh AND duplicate — to gids on device."""
    cap = slots.shape[0]
    capm = jnp.uint32(cap - 1)
    keyed, h0 = _keyed(fps)

    def cond(carry):
        t, unresolved, _v = carry
        return (t < MAX_PROBES) & unresolved.any()

    def body(carry):
        t, unresolved, vals = carry
        idx = (h0 + jnp.uint32(t)) & capm
        cur = slots[idx]
        mine = unresolved & (cur[:, :4] == keyed).all(axis=1)
        vidx = jnp.where(mine, idx, jnp.uint32(cap))
        vals = vals.at[vidx].set(gids, mode="drop")
        unresolved = unresolved & ~mine
        return t + 1, unresolved, vals

    _, _, vals = jax.lax.while_loop(
        cond, body, (jnp.int32(0), mask, vals))
    return vals


def insert_gids(table, vals, fps, gids, mask):
    """insert_core that also records a 32-bit value (a graph node id)
    per fingerprint in the parallel ``vals[CAP]`` array — the device
    side of the liveness graph's fingerprint->gid index
    (engine/device_liveness.py).  Batches must not contain duplicate
    fingerprints (graph nodes are distinct by construction).  Returns
    (table, vals, overflow, fresh_count)."""
    table, fresh, ovf = insert_core(table, fps, mask)
    # each fresh lane re-probes its own chain to find the slot it won
    # and writes its gid there
    vals = store_gids(table["slots"], vals, fps, gids, mask & fresh)
    return table, vals, ovf, fresh.sum(dtype=jnp.int32)


def lookup_gids(table, vals, fps, mask):
    """fps -> stored gid (or -1 when absent/unresolved).  Read-only."""
    slots = table["slots"]
    cap = slots.shape[0]
    capm = jnp.uint32(cap - 1)
    keyed, h0 = _keyed(fps)
    n = fps.shape[0]

    def cond(carry):
        t, unresolved, _o = carry
        return (t < MAX_PROBES) & unresolved.any()

    def body(carry):
        t, unresolved, out = carry
        idx = (h0 + jnp.uint32(t)) & capm
        cur = slots[idx]
        mine = unresolved & (cur[:, :4] == keyed).all(axis=1)
        out = jnp.where(mine, vals[idx].astype(jnp.int32), out)
        empty = cur[:, 0] == 0
        unresolved = unresolved & ~mine & ~empty
        return t + 1, unresolved, out

    _, _, out = jax.lax.while_loop(
        cond, body, (jnp.int32(0), mask,
                     jnp.full((n,), -1, jnp.int32)))
    return out


def grow(table, factor=4):
    """Host-side rebuild into a larger table (on probe overflow or high
    load).  Rare; chunked re-insertion of all occupied slots.  A table
    carrying a ``gids`` value column (the streamed edge-emission mode,
    ISSUE 15) is rebuilt WITH it: each occupied slot's stored gid
    follows its fingerprint to the new probe chain."""
    slots = np.asarray(table["slots"])
    occ = slots[:, 0] != 0
    fps = slots[occ, :4]
    cap = int(slots.shape[0])
    old_gids = (np.asarray(table["gids"])[occ]
                if "gids" in table else None)
    new = empty_table(cap * factor)
    new_gids = (jnp.full((cap * factor,), -1, jnp.int32)
                if old_gids is not None else None)
    chunk = 1 << 16
    ins_g = jax.jit(insert_gids, donate_argnums=(0, 1)) \
        if old_gids is not None else None
    for off in range(0, fps.shape[0], chunk):
        part = fps[off:off + chunk]
        pad = np.zeros((chunk - part.shape[0], 4), np.uint32)
        batch = jnp.asarray(np.concatenate([part, pad]))
        m = jnp.asarray(np.arange(chunk) < part.shape[0])
        if old_gids is not None:
            gpart = old_gids[off:off + chunk].astype(np.int32)
            gpad = np.zeros((chunk - gpart.shape[0],), np.int32)
            new, new_gids, ovf, _ = ins_g(
                new, new_gids, batch,
                jnp.asarray(np.concatenate([gpart, gpad])), m)
        else:
            new, _, ovf = insert_batch(new, batch, m)
        if bool(ovf):
            return grow(table, factor * 2)
    if new_gids is not None:
        new["gids"] = new_gids
    return new
