"""Device-resident (TPU) breadth-first model checking engine.

This is the reference's hot loop — TLC's BFS worker (SURVEY.md §3.1) —
restructured so an entire BFS level runs ON DEVICE inside one jitted
``lax.while_loop``, with a single host synchronization per chunk of
tiles (round 1 synced ~5x per 32-state tile, which was the whole
runtime).  The tile body is the occupancy-packed
THREE-STAGE pass (ISSUE 10, ``commit="fused"``, the default):

  chunk --guard matrix--> every action's guard over every lane of the
                          whole chunk of tiles, one vmapped pass:
                          EXACT per-action enabled counts (generated /
                          per-action counters, deadlock detection,
                          exact cap-overflow `need` so growth hits the
                          true count and level boundaries calibrate
                          the caps back down onto observed maxima)
  tile  --work queue  --> enabled (state, lane) items compacted per
                          action; ONLY the blocks of them that hold a
                          real item are expanded (vsr_kernel),
                          fingerprinted (VIEW + symmetry; the full
                          128-bit hash of the whole successor,
                          ``hash_mode="full"``, the default since
                          ISSUE 52), invariant-checked and packed, then
                          appended to one dense tile-local queue —
                          expand FLOPs scale with `generated`, not sum
                          of static caps
  tile  --commit      --> the queue's written prefix, COMMIT_PIECE
                          lanes at a time (ISSUE 30): per piece a
                          stable first-occurrence dedup mask picks the
                          earliest queue item among duplicate
                          fingerprints, ONE FPSet insert_core batch
                          whose claim column arbitrates distinct
                          fingerprints racing for a slot, ONE scatter
                          set; a later piece finds an earlier one's
                          fingerprints in the table, so the winners
                          are the per-action commit order's, and the
                          headroom check at tile entry keeps inserts
                          and scatters atomic

``commit="per-action"`` preserves the historical body — n_actions
serial guard/compact/expand/insert/scatter phases per tile — and the
two modes are BIT-IDENTICAL in counts, level sizes and traces
(tests/test_commit.py; the failure-cause priority and the
committed-action-prefix rule on a failing tile are replicated
verbatim).  One documented edge: an FPSet PROBE-OVERFLOW pause
(R_FPSET_GROW mid-tile, rare — the proactive between-level growth
keeps chains short) commits the resolvable subset of every piece
where per-action committed an action prefix, so after re-entry that
tile's next-frontier gids may be ORDERED differently between the
modes; the committed sets, counts, level sizes and trace CONTENT
still agree (both orders dedup to the same exploration).

Full states never leave the device.  The host keeps only the compact
(parent gid, action id, lane param) pointer table, and counterexamples
are reconstructed by REPLAYING the recorded action chain from the
initial state (exactly how the recorded choices determine the states),
then emitted in the reference's trace format (TRACE:3-7).

Pause/resume protocol: growth events (message-table too small, FPSet
load, next-buffer capacity), invariant violations, in-action slot
errors and deadlocks surface as a `reason` code; the level kernel
commits NOTHING for the action that failed, so the host can grow the
relevant structure and re-enter the level at the paused tile — lanes
already committed simply dedup against the FPSet on re-run.

Dispatch pipelining (ISSUE 4): the chunked loop keeps a bounded
window of K level-kernel dispatches in flight (``pipeline=K``,
default 2), chained on device-side (start_t, nn) scalars and blocking
only on the oldest — host-side work overlaps device compute, and the
pause protocol above is exactly what makes speculation safe
(engine/pipeline.py has the drain-and-replay argument).  Results are
bit-identical for every K.

Scale note: fingerprints live in HBM at 16 B/state; the frontier and
next-frontier buffers hold dense states in HBM (~state_size x capacity);
the host holds 10 B/state of trace pointers.  Multi-host sharding is
the next tier (SURVEY.md §5 distributed backend, parallel/sharded_bfs).
"""

from __future__ import annotations

import functools
import os
import sys
import time
from dataclasses import dataclass

import numpy as np

import jax
import jax.numpy as jnp

from ..core.values import TLAError
from ..models import registry
from ..models.vsr import ERR_BAG_OVERFLOW
from ..obs import RunObserver, builds, closes_observer, spans
from ..resilience.faults import fault_point
from ..resilience.supervisor import Preempted, preempt_signal
from . import program_store
from .bfs import CheckResult
from .checked import CheckedModel, of_model
from .fpset import (dedup_batch, empty_table, grow, insert_batch,
                    insert_core, lookup_gids, store_gids)
from .spec import SpecModel

I32 = jnp.int32


def trace_once(fn, name):
    """`fn` as a stage that a program's trace runs once: ``jax.jit`` of
    it, so a second use on tracers of equal types reuses the jaxpr of
    the first (and its batched form, and one function of the lowered
    module) instead of running the Python body again.  The level body
    uses such a stage once per action.  Made once per built kernel,
    which `fn` closes over, and never kept past it."""
    def body(*args):
        builds.shared_stage(traced=True)
        return fn(*args)
    body.__name__ = name
    jitted = jax.jit(body, inline=True)

    @functools.wraps(fn)
    def stage(*args):
        builds.shared_stage(traced=False)
        return jitted(*args)
    return stage


# level-kernel stop reasons
RUNNING = 0
R_VIOLATION = 2      # an invariant failed on a generated state
R_BAG_GROW = 3       # a successor needs more message-table slots
R_FPSET_GROW = 4     # fingerprint probing exhausted (table too full)
R_NEXT_GROW = 5      # next-frontier buffer out of capacity
R_SLOT_ERR = 6       # dense-layout slot collision (config limitation)
R_DEADLOCK = 7       # a frontier state has no enabled successor
R_EXPAND_GROW = 8    # per-action enabled-lane compaction buffer too small
# 9 is reserved (the sharded step's rank-agreed R_EXPAND_GROW vote)
R_EDGE_FLUSH = 10    # edge append buffer out of headroom (ISSUE 15):
#                      the host drains the committed (src, action, dst)
#                      triples into the CSR builder and re-enters —
#                      the paused tile committed nothing, exactly like
#                      the paged engine's R_NEXT_GROW spill

# Back-compat alias: the perm-table builder lives in the registry now.
_value_perm_table = registry.value_perm_table


def _align8(n):
    """Round an expansion-cap target up to a lane multiple of 8 (keeps
    the compaction shapes TPU-register friendly without inflating the
    occupancy denominator)."""
    return ((int(n) + 7) // 8) * 8


# Cap headroom over the exact per-tile need the guard matrix observed
# (growth and calibration both): the needs creep up level by level as
# frontier states grow richer, and 2x was breached five times in the
# 24-level flagship run — six compiles of the level program.  What the
# padding costs a level program since ISSUE 30: the headroom gate of
# the one-chip body (a tile commits only while the next buffer has room
# for the sum of the caps) and the selection of each action's segment
# (`enabled_lanes`: its compares go by the cap's slots).
# Stage 2 expands only the blocks that hold enabled lanes (EXPAND_BLOCK,
# `Stage2`: the one-chip body's and, since ISSUE 50, the sharded
# step's) and appends them to a dense queue.  The one-chip stage 3
# walks what was appended (COMMIT_PIECE); the sharded step's dedup,
# buckets and exchange still read a queue as wide as the sum of the
# caps.
CAP_HEADROOM = 4


# Enabled lanes per frontier state an action's cap allows before
# anything was observed.  The flagship run peaks at 3.2 (408 lanes of
# ReceiveMatchingSVC in a 128-state tile) and the defect window at 3.8
# by depth 8; a start of 1 bought three growth rebuilds on the way
# there, at about 110 s each on the v5e's host, while the wider program
# compiles only ~1.5x slower (AOT for the v5e, small config: 98 s at
# 2.4k lanes per tile, 143 s at 8.8k, 154 s at 15k).  Not 8: a tile
# commits only while the next-frontier buffer has room for every cap
# lane, so caps near the buffer's 16k rows force it to grow instead.
# The start sizes the headroom gate only (see CAP_HEADROOM): the
# defect window filled 9.5 % of these lanes; until ISSUE 28 the action
# functions ran over all of them, and until ISSUE 30 stage 3 did.
CAP_START = 4


# Slots of an action's segment that stage 2 of the fused body expands
# in one call: the segment is walked in blocks of this many, and only
# the blocks that hold an enabled lane run (the guard matrix counted
# them).  Two readings on one v5e chip, the defect window to depth 10
# (PERF.md, PR 28): at 128 the run commits 33,431 and 33,234 states/s
# (12,279 and 12,287 with one call over every cap lane), at 64 33,874
# and 33,960 on the same seeds.  1.3-2.2 % does not pay for twice the
# device events a second, so 128 for every segment wider than that:
# every cap of a tile of 128 states (384 and up).
EXPAND_BLOCK = 128


def block_rows(cap):
    """Slots in one block of a segment of `cap`.  A segment no wider
    than EXPAND_BLOCK would be one block with nothing to skip: the
    sharded step's caps at its tile of 32 states are 128 and 96, of
    which 9 % hold a lane (PERF.md, PR 50).  Such a segment is walked
    in quarters of EXPAND_BLOCK (that step on one v5e chip, the defect
    window to depth 9: 29,608 states/s in blocks of 16, 36,040 of 32,
    35,672 of 64; 18,840 with every cap slot expanded); a block never
    exceeds its action's cap."""
    if cap > EXPAND_BLOCK:
        return EXPAND_BLOCK
    return min(cap, max(1, EXPAND_BLOCK // 4))


# Lanes of the tile-local commit queue that stage 3 of the fused body
# (batch dedup, FPSet insert, scatter) takes in one trip: the queue's
# written prefix is walked in pieces of this many, one after the other.
# Two readings on one v5e chip, the defect window to depth 10 (PERF.md,
# PR 30; the parent, one batch of 8,960 lanes a tile: 33,261 and 33,408
# states/s): at 1,024 the run commits 43,518 and 43,271, in 2,205
# pieces over its 1,170 tiles; at 2,048 45,372 and 45,310 on the same
# seeds, one piece a tile.  A trip costs more than its lanes (every
# probe round of the insert moves the whole table), so 2,048: the
# window's fullest tile fits in one.  A queue no wider than one piece
# (every small tile) is one piece of the queue's own width, with no
# loop.
COMMIT_PIECE = 2048


def piece_lanes(total):
    """Lanes in one piece of a commit queue that holds `total`."""
    return min(COMMIT_PIECE, total)


def enabled_lanes(en, slots):
    """The first `slots` enabled (state, lane) items of one action's
    guard bits `en` [T, L], state-major and then by lane: what
    ``jnp.nonzero(en.reshape(T * L), size=slots, fill_value=T * L)``
    finds, as ``(pidx, lane, ok)`` of `slots` entries each, int32,
    int32 and bool.  A slot at or past the count reads ``(T - 1, 0,
    False)``.

    `nonzero` lowers to a scatter-add of all T * L lanes, an element at
    a time on the chip, to find the few that are enabled: 8-18 % of a
    one-chip slice's busy seconds (PERF.md, PR 56).  Here a slot finds
    its state by comparing its number with the running counts of the
    states, and its lane by comparing its rank in that state with the
    running count along the state's own bits: `slots` x T and `slots`
    x L compares, no scatter, no sort, and no `cumsum` (a
    `reduce_window` on the chip: the running counts are sums under a
    triangle, which fuse with the compares).  Exact: the two products
    multiply 0/1 by 0/1 (bfloat16 holds both) and accumulate in
    float32, so each entry is an integer of at most L, far under
    2**24; everything else is int32."""
    T, L = en.shape
    states = jnp.arange(T, dtype=I32)
    lanes = jnp.arange(L, dtype=I32)
    slot = jnp.arange(slots, dtype=I32)
    row = en.sum(1, dtype=I32)
    # enabled lanes in the states up to and with each one
    end = ((states[None, :] <= states[:, None]) * row[None, :]).sum(
        1, dtype=I32)
    ok = slot < end[-1]
    # a slot's state: the states whose running count it has passed
    state = jnp.minimum(
        (end[None, :] <= slot[:, None]).sum(1, dtype=I32), T - 1)
    rank = slot - ((states[None, :] < state[:, None]) * row[None, :]).sum(
        1, dtype=I32)
    # the state's own bits, then their running count along the lanes
    bits = jnp.dot((states[None, :] == state[:, None]).astype(jnp.bfloat16),
                   en.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)
    upto = jnp.dot(bits.astype(jnp.bfloat16),
                   (lanes[:, None] <= lanes[None, :]).astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)
    # the (rank + 1)-th set bit lies behind the lanes whose running
    # count has not passed `rank`
    lane = (upto <= rank[:, None].astype(jnp.float32)).sum(1, dtype=I32)
    return jnp.where(ok, state, T - 1), jnp.where(ok, lane, 0), ok


def static_cap(tile, full):
    """An action's expansion cap before the guard matrix has observed
    anything (and the floor calibration never shrinks below)."""
    return min(full, max(8, _align8(CAP_START * tile)))


def grown_caps(caps, need_seen, full):
    """The fused commit's cap growth policy (shared with the sharded
    engine): per-action caps after a growth event.

    Every growth re-jits the whole level program — a minute on the chip
    — so one event buys headroom for EVERY action, not only the one
    that overflowed: CAP_HEADROOM times the exact per-tile need the
    guard matrix has observed so far, at least double for an action
    that did overflow, never above the action's full lane count.  The exact needs creep up
    level by level; growing to them exactly cost one recompile per
    level (41 in the 24-level flagship run)."""
    out = []
    for cap, need, top in zip(caps, need_seen, full):
        want = _align8(CAP_HEADROOM * int(need))
        if need > cap:
            want = max(want, 2 * int(cap))
        out.append(int(min(top, max(cap, want))))
    return out


def slot_error(codec):
    """What R_SLOT_ERR says to the user, for all three BFS engines: a
    successor holds a receive-set the codec's dense layout cannot, and
    the run stops rather than drop a record (models/vsr.py, layout)."""
    slots = getattr(codec.shape, "DVC_SLOTS", 1)
    return (f"dense-layout slot overflow: a successor's rep_dvc_recv "
            f"would hold more than {slots} different DoViewChange "
            f"record(s) of one source in one view (K = {slots} at "
            f"RestartEmptyLimit = "
            f"{getattr(codec.shape, 'restart_limit', 0)}), or a second "
            f"recovery response of one source to one nonce; the run "
            f"stops here and drops nothing (models/vsr.py, layout)")


class Stage2:
    """Stage 2 of the fused tile body for one built kernel, the same
    for every engine that runs it (ISSUE 50): the one-chip level
    program (`DeviceBFS._fused_body_factory`, `PagedBFS` through it)
    and the sharded step (`parallel/sharded_bfs.make_sharded_level`).

    `tile_pass(caps, width)` gives the function a tile body calls: per
    action the selection of its enabled segment (`enabled_lanes`), then
    ONLY the blocks of `block_rows(cap)` slots that hold an enabled
    lane (stage 1 counted them exactly) are gathered, expanded,
    fingerprinted, invariant-checked and packed, and appended at the
    running end of one dense tile-local queue.  What commits the queue
    (stage 3: a local insert and scatter, or ownership buckets and an
    exchange) is the engine's own.

    The per-successor stages (`fp_stage`, `inv_stage`, and the block
    stages made on demand) are traced once for THIS kernel: a grown
    message table is a new kernel and a new `Stage2`, a grown cap
    finds its stages traced.

    Every engine builds it with `incremental` False at its defaults
    (ISSUE 52): a block hashes its whole successors
    (`kern.fingerprint`), and the trace holds no parent parts, no
    parts gather and no touch bookkeeping in the action functions.
    `DeviceBFS(hash_mode="incremental")` is what still asks for the
    other hash, the same values from the parent's parts."""

    def __init__(self, model, incremental, stat_fn=None,
                 count_moved=False):
        self.kern, self.pk, self.canon = model.kern, model.pk, model.canon
        # the incremental hash reconstitutes a fingerprint from the
        # parent's per-row parts (one replica row and R + 1 slot rows
        # at dynamic indices a successor, against the full hash's R +
        # max_msgs dense rows); the orbit-least image of a canon run
        # cannot be, and forces the full hash.  No engine asks for it
        # at its defaults: in blocks the full hash is the cheaper one
        # on the chip (PERF.md, PRs 50 and 52)
        self.incremental = incremental and self.canon is None
        # the kernel's counts over committed states (``commit_stats``)
        # and whether a slot records that its least image is not the
        # identity's: a queue plane each, for the engine that reads it
        self.stat_fn, self.count_moved = stat_fn, count_moved
        kern = self.kern
        self.fp_stage = trace_once(
            kern.fingerprint_incremental if self.incremental
            else self._fingerprint_least
            if self.canon is not None else kern.fingerprint,
            "fingerprint")
        self.inv_stage = trace_once(model.inv, "invariants")
        self._expand_stages = {}    # (action, block rows) -> stage
        self._pack_stages = {}      # block rows -> stage
        # the types of one unpacked state row, of one queue row and of
        # a row's hash parts
        if self.pk is not None:
            self.row = jax.eval_shape(self.pk.unpack, jax.ShapeDtypeStruct(
                (self.pk.words,), jnp.uint32))
            self.qrow = jax.ShapeDtypeStruct((self.pk.words,), jnp.uint32)
        else:
            self.row = {k: jax.ShapeDtypeStruct(np.shape(v), np.int32)
                        for k, v in model.codec.zero_state().items()}
            self.qrow = self.row
        self.parts_row = (jax.eval_shape(kern.parent_parts, self.row)
                          if self.incremental else None)

    def _fingerprint_least(self, st):
        """``CanonSpec.fingerprint_fn`` with what the level program
        counts as ``canon_relabelled``: (fingerprint of `st`'s least
        orbit image, whether that image is not the identity's)."""
        with jax.named_scope(spans.CANON):
            image, moved = self.canon.least(st)
        return self.kern.fingerprint(image), moved

    def successor_fn(self, name, fn):
        """Stage 2 of every tile body for one enabled (state, lane)
        item of action `name`: expand it with `fn`, fingerprint the
        successor, check the invariants — each under its stage scope.
        Returns ``one(st, parts, lane) -> (successor, fingerprint,
        enabled, invariants ok, err, relabelled)`` for the body to vmap
        over its compacted lanes; `parts` is the parent's hash parts
        (``kern.parent_parts``) under the incremental hash and None
        under the full one; `relabelled` is None unless the run
        canonicalizes.  With a kernel that counts over committed
        states (``commit_stats``) the tuple ends with the successor's
        stat vector."""
        kern = self.kern
        fp_stage, inv_stage = self.fp_stage, self.inv_stage
        incremental = self.incremental
        canon = self.canon is not None
        stat_fn = self.stat_fn

        def one(st, parts, lane):
            with jax.named_scope(spans.EXPAND):
                succ, en = fn(kern.seed_touch(st) if incremental else st,
                              lane)
            clean = {k: v for k, v in succ.items()
                     if not k.startswith("_")}
            # ISSUE 11 commit stage: under canon the fingerprint is
            # taken on the canonical orbit image while the staged queue
            # keeps the generated state — orbit-mates dedup to one
            # committed representative
            moved = None
            with jax.named_scope(spans.FINGERPRINT):
                if incremental:
                    fp = fp_stage(succ, kern.lane_replica(name, st, lane),
                                  parts, st)
                elif canon:
                    fp, moved = fp_stage(clean)
                else:
                    fp = fp_stage(clean)
            with jax.named_scope(spans.INVARIANTS):
                iok = inv_stage(clean)
            out = (clean, fp, en, iok, clean["err"], moved)
            return out + (stat_fn(clean),) if stat_fn else out

        return one

    def expand_stage(self, aid, rows, sharding=None):
        """One block of `rows` compacted items of action `aid`:
        `successor_fn` under `vmap`, traced HERE, outside the loops,
        and inlined where the tile pass's block loop uses it.  The loop
        is a `while` inside the tile loop's `while` inside `jit`, and
        the 19 action functions cost twice the Python seconds when
        they are traced from in there (v5e host, the small config:
        10.3 s against 6.1 s with one `vmap` in the tile loop; 6.3 s
        so).  `sharding` is what the types of the loop's values carry
        (`tile_pass`)."""
        key = (aid, rows)
        if key not in self._expand_stages:
            kern = self.kern

            def batch(s):
                return jax.ShapeDtypeStruct((rows,) + s.shape, s.dtype,
                                            sharding=sharding)

            stage = jax.jit(jax.vmap(self.successor_fn(
                kern.action_names[aid], kern._action_fns()[aid])),
                inline=True)
            stage.trace(jax.tree_util.tree_map(batch, self.row),
                        jax.tree_util.tree_map(batch, self.parts_row),
                        jax.ShapeDtypeStruct((rows,), I32, sharding=sharding))
            self._expand_stages[key] = stage
        return self._expand_stages[key]

    def pack_stage(self, rows, sharding=None):
        """`pk.pack` over one block of `rows` successors, traced HERE
        once per kernel and block size like `expand_stage`, so the 19
        block loops share one pack instead of each tracing theirs.
        Without a pack spec a queue row is the plane dict itself."""
        if self.pk is None:
            return lambda succ: succ
        if rows not in self._pack_stages:
            stage = jax.jit(jax.vmap(self.pk.pack), inline=True)
            stage.trace(jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct((rows,) + s.shape, s.dtype,
                                               sharding=sharding),
                self.row))
            self._pack_stages[rows] = stage
        return self._pack_stages[rows]

    def tile_pass(self, caps, width, like=None):
        """``run(tile, en_segs, cnts, each) -> (queue, q_end, blocks)``
        for per-action caps `caps` (slots) and a queue of `width` slots
        (at least their sum).  The block stages are traced here: call
        it outside the tile loop.  Under `shard_map` call it inside
        the mapped function and pass a value of that trace as `like`:
        the types of values there name the mesh, a stage traced on
        types that do not is not found again by the loops (they would
        each trace their action function anew, and every action its
        own fingerprint and invariant stage).

        `tile` holds the unpacked states of one tile, `en_segs[a]` the
        [T, L_a] lanes of action a that are to be expanded (the guard
        matrix, masked by whatever the engine masks it by) and `cnts`
        their counts.  The queue is action-major and, within an
        action, in `enabled_lanes` order, and holds no slot of a block
        that did not run: planes `rows` (packed where the run packs), `fp`,
        `en`, `aid`, `pidx` (the parent's row in the tile) and `lane`,
        and `moved` / `stat` where the engine asked for them; a slot no
        block wrote keeps its zeros, with `en` False, and `q_end` is
        the end of what was written.  `blocks[a]` is the number of
        blocks action a ran.  After an action's blocks `each(aid, seg)`
        gets its per-slot verdicts in segment order, ``seg = (pidx,
        lane, sel_ok, en, iok, err)`` of `caps[aid]` slots each, for
        the engine to fold into its own failure vote."""
        kern, row, qrow = self.kern, self.row, self.qrow
        incremental = self.incremental
        moved_plane, stats = self.count_moved, self.stat_fn is not None
        n_stat = len(kern.COMMIT_STATS) if stats else 0
        sharding = None if like is None else jax.typeof(like).sharding
        expand_of = [self.expand_stage(aid, block_rows(cap), sharding)
                     for aid, cap in enumerate(caps)]
        pack_of = [self.pack_stage(block_rows(cap), sharding)
                   for cap in caps]

        def put(bufs, vals, at):
            """`vals` written into `bufs` from row `at` on.  The
            primitive, bound bare: `at` needs no wrap-around
            arithmetic, and 41 planes an action would each trace
            theirs."""
            zero = jnp.asarray(0, I32)
            return jax.tree_util.tree_map(
                lambda buf, v: jax.lax.dynamic_update_slice_p.bind(
                    buf, v.astype(buf.dtype), at,
                    *[zero] * (buf.ndim - 1)),
                bufs, vals)

        def lanes(dtype, *shape):
            return jax.lax.full((width,) + shape, 0, dtype)

        def run(tile, en_segs, cnts, each):
            if incremental:
                with jax.named_scope(spans.FINGERPRINT):
                    parts = jax.vmap(kern.parent_parts)(tile)
            else:
                parts = None
            blk_segs = []
            # the queue: a slot no block wrote keeps its zeros, with
            # `en` False
            queue = {
                "rows": jax.tree_util.tree_map(
                    lambda s: lanes(s.dtype, *s.shape), qrow),
                "fp": lanes(jnp.uint32, 4), "en": lanes(bool),
                "aid": lanes(I32), "pidx": lanes(I32),
                "lane": lanes(I32)}
            if moved_plane:
                queue["moved"] = lanes(bool)
            if stats:
                queue["stat"] = lanes(jnp.uint32, n_stat)
            q_end = jnp.asarray(0, I32)
            for aid, E_a in enumerate(caps):
                with jax.named_scope(spans.COMPACT):
                    pidx, lane_sel, sel_ok = enabled_lanes(en_segs[aid], E_a)
                # only the blocks of the segment that hold an enabled
                # lane are expanded: stage 1 counted them exactly.  A
                # slot at or past cnt in a block that ran has sel_ok
                # False, so `en` is False there and every later read is
                # masked by it
                B = block_rows(E_a)
                n_blk = (jnp.minimum(cnts[aid], E_a) + B - 1) // B
                blk_segs.append(n_blk)
                expand, pack = expand_of[aid], pack_of[aid]
                aid_b = jax.lax.full((B,), aid, I32)

                def block(b, out):
                    # the last block of a cap that is no multiple of B
                    # is clamped onto the one before it: the rows they
                    # share are written twice, the same
                    queue, seg = out
                    with jax.named_scope(spans.COMPACT):
                        lo = jnp.minimum(b * B, E_a - B)
                        pidx_b = jax.lax.dynamic_slice_in_dim(pidx, lo, B)
                        lane_b = jax.lax.dynamic_slice_in_dim(
                            lane_sel, lo, B)
                        ok_b = jax.lax.dynamic_slice_in_dim(sel_ok, lo, B)
                        st_b = {k: v[pidx_b] for k, v in tile.items()}
                        parts_b = jax.tree_util.tree_map(
                            lambda v: v[pidx_b], parts)
                    succ, fp, en2, iok, errv, moved, *stat = expand(
                        st_b, parts_b, lane_b)
                    # a successor is a state row: packed here, a block
                    # at a time, it never exists unpacked at the
                    # queue's width
                    rows_b = pack({k: succ[k].astype(s.dtype)
                                   for k, s in row.items()})
                    with jax.named_scope(spans.COMPACT):
                        item = {"rows": rows_b, "fp": fp,
                                "en": en2 & ok_b, "aid": aid_b,
                                "pidx": pidx_b, "lane": lane_b}
                        if moved_plane:
                            item["moved"] = moved
                        if stats:
                            item["stat"] = stat[0]
                        queue = put(queue, item, q_end + lo)
                        return queue, put(seg, (en2, iok, errv), lo)

                no = jax.lax.full((E_a,), False, bool)
                queue, (en2, iok, errv) = jax.lax.fori_loop(
                    0, n_blk, block,
                    (queue, (no, no, jax.lax.full(
                        (E_a,), 0, row["err"].dtype))))
                q_end = q_end + jnp.minimum(n_blk * B, E_a)
                each(aid, (pidx, lane_sel, sel_ok, en2, iok, errv))
            return queue, q_end, blk_segs

        return run


def _cut(v, start, rows):
    return jax.lax.dynamic_slice_in_dim(v, start, rows, axis=0)


@functools.partial(jax.jit, static_argnames=("rows",))
def _cut_rows(buf, start, rows):
    """Rows [start, start + rows) of every plane of a buffer.  `start`
    is a traced scalar, so every page of one buffer shape is this one
    program, whatever a level holds."""
    return jax.tree.map(lambda v: _cut(v, start, rows), buf)


@functools.partial(jax.jit, static_argnames=("rows",))
def _cut_pointers(planes, start, rows):
    """The same rows of the three trace-pointer planes as one
    [3, rows] array: one copy to the host a page."""
    return jnp.stack([_cut(v, start, rows) for v in planes])


class RowPages:
    """The first `n` rows of a device buffer on their way to the host
    in pages of `rows` rows (the whole buffer where it is shorter).

    No program here depends on `n`: a slice of `n` rows is a new
    program for every new `n`, and a level's end compiled one with the
    chip idle (ISSUE 43).  `cut` (`_cut_rows`, or `_cut_pointers`,
    whose rows lie along `axis` 1) is launched once a page and each
    page's copy to the host started; `host()` joins them and cuts the
    tail.  A last page that would pass the buffer's end starts early
    enough to end there instead (the device would clamp it the same
    way, silently), and gives up its head on the host."""

    def __init__(self, cut, buf, n, rows, axis=0):
        cap = jax.tree.leaves(buf)[0].shape[0]
        self.n, self.axis = n, axis
        self.rows = rows = min(rows, cap)
        self.starts = [min(at, cap - rows)
                       for at in range(0, max(n, 1), rows)]
        self.pages = [cut(buf, np.int32(s), rows=rows)
                      for s in self.starts]
        for leaf in jax.tree.leaves(self.pages):
            leaf.copy_to_host_async()

    @property
    def nbytes(self):
        return sum(v.nbytes for v in jax.tree.leaves(self.pages))

    def host(self):
        """The rows as host arrays of their own; waits for the
        copies."""
        head = (slice(None),) * self.axis
        parts = []
        for i, page in enumerate(jax.device_get(self.pages)):
            at, start = i * self.rows, self.starts[i]
            keep = slice(at - start, min(at + self.rows, self.n) - start)
            parts.append(jax.tree.map(lambda v: v[head + (keep,)], page))
        return jax.tree.map(
            lambda *vs: np.concatenate(vs, axis=self.axis), *parts)


# Largest tile width validated against the pinned fixpoint counts on
# a real TPU: tile=1024 once mis-explored the flagship config
# (58,957 distinct vs pinned 43,941), an unresolved TPU-lowering
# correctness failure (ROADMAP S4; scripts/tpu_miscompile_repro.py is
# the repro ladder).  Until a re-run sweep validates wider tiles, the
# engine refuses them on accelerator backends (CPU lowering is
# validated at all widths).
MAX_VALIDATED_TPU_TILE = 512


@dataclass
class _Run:
    """One `run()` of a one-chip engine: what the caller asked for and
    what the host loop carries from level to level.  The steps an
    engine overrides are methods that take it (`DeviceBFS.run`); an
    engine with more to carry subclasses it (`_run_record`)."""
    res: CheckResult
    obs: RunObserver
    pipe: object
    t0: float
    max_states: int
    max_depth: int
    max_seconds: float
    check_deadlock: bool
    checkpoint_path: str
    checkpoint_every: float
    resume_from: str
    table: dict = None      # the FPSet (and its gid column)
    fp_cap: int = 0
    fp_count: int = 0
    n_front: int = 0
    level_base: int = 0     # gid of the frontier's first row
    depth: int = 0
    last_checkpoint: float = 0.0
    stop: str = None        # why the run ends with the level it is in
    n_next: int = 0         # rows the next buffer holds
    # the resident frontier, its pointer planes, and the next buffers
    front: object = None
    fpar: object = None
    fact: object = None
    fprm: object = None
    bufs: tuple = None

    def out_of_time(self, now):
        """Whether `now` (the calling engine module's clock) is past
        the run's budget; `stop` then says so."""
        if self.max_seconds and now - self.t0 > self.max_seconds:
            self.stop = f"time budget {self.max_seconds}s reached"
        return self.stop is not None


class DeviceBFS:
    _run_record = _Run

    # what is checked lives on `self.model` (engine/checked.py); the
    # names this file, its subclasses and the tests read it by
    codec, kern = of_model("codec"), of_model("kern")
    commit, inv_names = of_model("commit"), of_model("inv_names")
    _inv, _facts, _pruned = (of_model("inv"), of_model("facts"),
                             of_model("pruned"))
    _pk, _pk_decl = of_model("pk"), of_model("pk_decl")
    _canon, _sym_fold = of_model("canon"), of_model("sym_fold")
    _por, _por_facts, _por_active = (of_model("por"), of_model("por_facts"),
                                     of_model("por_active"))
    _symmetry_on = of_model("symmetry_on")
    _pack_manifest = of_model("pack_manifest")

    def __init__(self, spec: SpecModel, max_msgs=None, tile_size=128,
                 fpset_capacity=1 << 20, hash_mode="full",
                 next_capacity=1 << 14, chunk_tiles=64, expand_mult=2,
                 expand_mults=None, model_factory=None, pipeline=2,
                 pack="auto", commit="fused", symmetry="auto",
                 bounds="auto", edges=False, por="off"):
        if edges and not getattr(self, "_edges_on", False):
            # the tile bodies support emission on any engine, but the
            # drain seam (R_EDGE_FLUSH -> host CSR builder) lives in
            # the host-paged level loop
            raise TLAError(
                "edge emission needs the host-paged drain loop; "
                "construct PagedBFS(edges=True) (or run the CLI "
                "temporal path, which does)")
        if (tile_size > MAX_VALIDATED_TPU_TILE
                and os.environ.get("TPUVSR_UNSAFE_TILE") != "1"
                and jax.default_backend() != "cpu"):
            raise TLAError(
                f"tile_size={tile_size} exceeds the largest width "
                f"validated against pinned counts on a TPU backend "
                f"({MAX_VALIDATED_TPU_TILE}; tile=1024 mis-explored "
                f"there — ROADMAP S4).  Set "
                f"TPUVSR_UNSAFE_TILE=1 to override for diagnosis runs.")
        self.spec = spec
        # streamed edge emission (ISSUE 15): set by PagedBFS before
        # this constructor runs (the host-paged engine owns the drain
        # seam); when on, the tile bodies resolve every enabled lane's
        # successor fingerprint to a gid on device and append
        # (src gid, action, dst gid) triples to the edge buffer
        self._edges_on = getattr(self, "_edges_on", False)
        # the spec and the five levers as a codec, a kernel and the
        # specs bound to them (engine/checked.py)
        self.model = CheckedModel(
            spec, model_factory, pack=pack, commit=commit,
            symmetry=symmetry, bounds=bounds, por=por,
            edges=self._edges_on)
        self.tile = tile_size
        self.fpset_capacity = fpset_capacity
        self.hash_mode = hash_mode
        self.next_cap = next_capacity
        self.chunk_tiles = chunk_tiles
        # dispatch-window depth: keep up to `pipeline` level-kernel
        # dispatches in flight, blocking only on the oldest (ISSUE 4;
        # 1 = the fully synchronous pre-pipeline behavior)
        self.pipe_window = max(1, int(pipeline))
        # per-action enabled-lane compaction capacity = tile * mult
        # (each action's cap auto-doubles on its own R_EXPAND_GROW;
        # pass a pre-calibrated per-action vector to skip the growth
        # recompiles); dict forms are resolved against the kernel's
        # action names once the kernel exists (_build)
        self.expand_mults = expand_mults
        self._mults_given = expand_mults is not None
        self._expand_mult_default = expand_mult
        # fused-mode per-action expansion caps (absolute lane counts,
        # exact-count grown/calibrated; run-scoped — snapshots keep the
        # per-action expand_mults format and a resumed fused run simply
        # re-calibrates).  The fused commit (the default) sizes them by
        # EXACT enabled counts instead of tile-multiple guesses
        self.expand_caps = None
        self._need_seen = None
        self._por_kept = 0
        self._por_full = 0
        self._por_amp = 0
        registry.ensure_compile_cache()
        self.debug_checks = registry.ensure_debug_flags()
        self._build(max_msgs)

    # ------------------------------------------------------------------
    # kernel + jitted level construction
    # ------------------------------------------------------------------
    def _build(self, max_msgs):
        """(Re)build the checked model (codec, kernel, lever specs) and
        what this engine makes of it — expansion caps, traced stages,
        the level pass — for a message-table bound; called again on
        bag growth."""
        self.model.build(max_msgs)
        names = self.kern.action_names
        if self.expand_mults is None:
            self.expand_mults = [self._expand_mult_default] * len(names)
        elif isinstance(self.expand_mults, dict):
            base = [self._expand_mult_default] * len(names)
            for n, m in self.expand_mults.items():
                base[names.index(n)] = m
            self.expand_mults = base
        else:
            self.expand_mults = list(self.expand_mults)
        if self.commit == "fused":
            tl = [self.tile * self.kern._lane_count(n) for n in names]
            if self.expand_caps is None:
                # static start; growth events (and the level-boundary
                # calibration) re-cap from the observed per-tile maxima
                self.expand_caps = [self._cap_floor(a, t)
                                    for a, t in enumerate(tl)]
                # static fanout bounds (ISSUE 13): the bounds pass
                # proves at most `fanout` lanes of an action enable
                # per state, so tile*fanout is a sound initial cap —
                # on exact-bounds fixtures the growth redraw count is
                # ZERO (the cap already covers the true maximum)
                if self._facts is not None:
                    for a, n in enumerate(names):
                        fo = self._facts.fanout.get(n)
                        if fo:
                            self.expand_caps[a] = min(
                                tl[a],
                                max(8, _align8(self.tile * fo)))
            else:
                # re-clamp after a MAX_MSGS rebuild (lane counts grow)
                self.expand_caps = [min(t, max(8, int(c)))
                                    for t, c in zip(tl, self.expand_caps)]
            if self._need_seen is None or \
                    len(self._need_seen) != len(names):
                self._need_seen = np.zeros(len(names), np.int64)
        self.L = self.kern.n_lanes
        # what canon did, counted on the device beside the action
        # counts (the fused body; ISSUE 33): only a program that
        # canonicalizes carries the counter
        self._canon_counts = (self._canon is not None
                              and self.commit == "fused")
        # what the kernel asks to have counted over the states a run
        # commits (``commit_stats``: VSR's recovering_states and
        # dvc_set_peak, ISSUE 37), beside them on the device; a kernel
        # without the hook, or one that returns None for its shape,
        # gets the program it always had
        self._stat_fn = (getattr(self.kern, "commit_stats", None)
                         if self.commit == "fused" else None)
        # which entries of the stat vector add up (the others: maxima)
        self._stat_sums = np.array(
            [how == "sum" for _n, how in self.kern.COMMIT_STATS]
            if self._stat_fn else [], bool)
        # stage 2 and the per-successor stages that no action changes,
        # traced once for THIS kernel (trace_once): the level body
        # uses each once per action
        self._stage2 = Stage2(self.model, self.hash_mode == "incremental",
                              self._stat_fn, self._canon_counts)
        self._fp_incremental = self._stage2.incremental
        self._fp_stage = self._stage2.fp_stage
        self._inv_stage = self._stage2.inv_stage
        self._level_jit = None  # the level pass, built lazily (_level)
        # obs accounting: the first dispatch after a (re)jit is charged
        # to the "compile" phase (jit traces+compiles at first call)
        self._fresh_jit = True

    @property
    def _level(self):
        """The level pass, built at its first use: in a run, so the
        run's observer meters the build.  It goes through the store of
        traced programs (engine/program_store.py): a process that finds
        this engine's program there does not trace it again."""
        if self._level_jit is None:
            self._level_jit = program_store.StoredProgram(
                self._make_level, "level", (0, 4, 5, 6, 7, 10),
                self._level_key_doc())
        return self._level_jit

    def _level_key_doc(self):
        """Everything the trace of `_make_level` reads of this engine,
        for the store's key (the arguments' types, where the
        capacities live, come with the call), or None where a class
        the package's source does not determine has a hand in it: a
        kernel, a codec or an engine defined elsewhere, or inside a
        function."""
        from .checkpoint import spec_digest
        from . import fpset
        spec = self.spec
        try:
            return program_store.describe({
                "engine": type(self),
                "spec": spec_digest(spec), "module": spec.module,
                "kernel": self.kern, "codec": self.codec,
                "pruned": self._pruned,
                "tile": self.tile, "chunk_tiles": self.chunk_tiles,
                "commit": self.commit, "hash_mode": self.hash_mode,
                "expand_caps": self.expand_caps,
                "expand_mults": self.expand_mults,
                "constants": program_store.module_constants(
                    sys.modules[__name__], fpset),
                "inv_names": self.inv_names,
                "pack": self.model.pack_manifest(),
                "canon": self.model.canon_manifest(),
                "bounds": self.model.bounds_manifest(),
                "por": [self.model.por_manifest(), self._por_active],
                "edges": self._edges_on,
                "debug_checks": self.debug_checks})
        except program_store.Uncovered:
            return None

    def _run_level(self, *args):
        """One dispatch of the level pass.  The loops hand the pipeline
        this, not `_level`: a program made here is made inside the
        dispatch's span (``tpuvsr.engine.build`` when fresh), its
        stages' traces included."""
        return self._level(*args)

    def _cap_floor(self, a, full):
        """Action `a`'s fused cap before the guard matrix has observed
        anything, and the floor calibration keeps: the static start,
        or the caller's multiplier of the tile where that is more (a
        pre-calibrated `expand_mults` skips the growth rebuild of a
        depth the caller knows, here as under the per-action
        commit)."""
        cap = static_cap(self.tile, full)
        if self._mults_given:
            cap = max(cap, min(full, _align8(
                self.tile * self.expand_mults[a])))
        return cap

    def _expand_caps(self):
        """Per-action enabled-lane compaction capacities, in lanes.
        Fused commit: the absolute exact-count caps (grown to observed
        need, calibrated down at level boundaries).  Per-action commit:
        the historical tile-multiple formula.  PagedBFS sizes its
        next-buffer headroom floor from the same list."""
        kern, T = self.kern, self.tile
        if self.commit == "fused":
            return [min(T * kern._lane_count(n), max(8, int(c)))
                    for n, c in zip(kern.action_names, self.expand_caps)]
        return [min(T * kern._lane_count(nm),
                    max(64, T * self.expand_mults[a]))
                for a, nm in enumerate(kern.action_names)]

    def _guard_matrix(self, kern):
        """Stage 1 of the fused pass: a closure evaluating EVERY
        action's guard over a dense state batch in one vmapped sweep —
        returns the per-action [B, L_a] enabled matrices.  Applied
        chunk-wide by _make_level (exact per-action counts for the
        whole chunk of tiles)."""
        guards = kern._guard_fns()

        def mat(batch):
            segs = []
            with jax.named_scope(spans.GUARD_MATRIX):
                for name, guard in zip(kern.action_names, guards):
                    lanes = jnp.arange(kern._lane_count(name), dtype=I32)
                    segs.append(jax.vmap(lambda st: jax.vmap(
                        lambda ln, g=guard: g(st, ln))(lanes))(batch))
            return segs

        return mat

    def _tile_body_factory(self):
        """Build the one-tile expansion body of the level pass
        (_make_level, its one caller).  Returns (caps, total_E,
        make_body) where make_body(frontier, n_front, want_deadlock,
        chunk_ctx, edge_bases, pdepth) closes over the traced frontier
        and count; ``chunk_ctx`` is the chunk-wide precomputed (dense
        states, guard matrix, start tile) of the hoisted stage-1 pass
        that the fused body slices its tile from (None under
        per-action commit, whose body derives its own).

        Packed frontier (ISSUE 9): with a pack spec bound, the at-rest
        frontier and next buffers are ``[cap, words]`` uint32 planes —
        the body unpacks a tile on entry and packs successors on exit,
        so the expansion/fingerprint/invariant pipeline in between is
        UNCHANGED and results stay bit-identical with packing on/off
        (the pack/unpack round trip is exact for in-range values)."""
        if self.commit == "fused":
            return self._fused_body_factory()
        kern = self.kern
        pk = self._pk
        T = self.tile
        incremental = self._fp_incremental

        # per-action compaction capacities (adaptive; R_EXPAND_GROW
        # carries the overflowing action so only it grows)
        caps = self._expand_caps()
        total_E = sum(caps)
        edges_on = self._edges_on
        aid_q_pa = jnp.asarray(np.repeat(
            np.arange(len(caps), dtype=np.int32), caps))

        def make_body(frontier, n_front, want_deadlock, chunk_ctx,
                      edge_bases, pdepth):
            # chunk_ctx and pdepth (the fused commit's POR level
            # marker; POR is a resolve_por blocker under per-action
            # commit) are accepted here only for the signature the two
            # bodies share
            F_cap = (frontier.shape[0] if pk is not None
                     else frontier["status"].shape[0])

            def body(c):
                t = c["t"]
                base = t * T
                sidx = base + jnp.arange(T, dtype=I32)
                valid = sidx < n_front
                with jax.named_scope(spans.PACK_SCATTER):
                    if pk is not None:
                        # packed at-rest frontier: gather [T, words]
                        # rows, unpack to the dense tile the kernel
                        # consumes
                        tile = jax.vmap(pk.unpack)(
                            frontier[jnp.clip(sidx, 0, F_cap - 1)])
                    else:
                        tile = {k: v[jnp.clip(sidx, 0, F_cap - 1)]
                                for k, v in frontier.items()}
                if incremental:
                    with jax.named_scope(spans.FINGERPRINT):
                        parts = jax.vmap(kern.parent_parts)(tile)

                slots = c["slots"]
                nb, nbp, nba, nbprm = c["nb"], c["nbp"], c["nba"], c["nbprm"]
                N_cap = nbp.shape[0]
                nn, dist = c["nn"], c["dist"]
                reason, viol = c["reason"], c["viol"]
                en_any = jnp.zeros((T,), bool)
                gen_local = jnp.asarray(0, I32)
                act_local = []      # per-action enabled-lane counts
                grow_aid = c["grow_aid"]

                # headroom check up front: with N_cap - nn >= total_E no
                # scatter can overrun the buffer, so an insert is never
                # committed without its successors landing — which keeps
                # the pause/resume protocol idempotent with no membership
                # query pass.  Edge emission adds the parallel gate on
                # the edge append buffer (full = drain to host, not
                # grow in HBM)
                room_next = (N_cap - nn) >= total_E
                if edges_on:
                    E_cap_e = c["eb_src"].shape[0]
                    room_edge = (E_cap_e - c["edge_n"]) >= total_E
                    gids_v = c["gids"]
                    fp_segs, en_segs_e, pidx_segs_e = [], [], []
                else:
                    room_edge = jnp.asarray(True)
                commit = room_next & room_edge
                reason = jnp.where((reason == RUNNING) & ~room_next,
                                   R_NEXT_GROW, reason)
                reason = jnp.where((reason == RUNNING) & ~room_edge,
                                   R_EDGE_FLUSH, reason)
                viol_any = jnp.asarray(False)
                bag_err = jnp.asarray(False)
                slot_err = jnp.asarray(False)
                ovf_e = jnp.asarray(False)
                ovf_i = jnp.asarray(False)

                for aid, (name, fn, guard) in enumerate(
                        zip(kern.action_names, kern._action_fns(),
                            kern._guard_fns())):
                    L_a = kern._lane_count(name)
                    TL = T * L_a
                    lanes = jnp.arange(L_a, dtype=I32)
                    E_a = caps[aid]

                    # -- phase 1: cheap guard pass over every lane -----
                    with jax.named_scope(spans.GUARD_MATRIX):
                        en = jax.vmap(lambda st: jax.vmap(
                            lambda ln: guard(st, ln))(lanes))(tile)
                        en = en & valid[:, None]
                        en_any = en_any | en.any(axis=1)
                        en_f = en.reshape(TL)
                        n_en = en_f.sum()
                        gen_local = gen_local + n_en
                        act_local.append(n_en)
                        ovf_a = n_en > E_a
                        grow_aid = jnp.where(ovf_a & ~ovf_e, aid,
                                             grow_aid)
                        ovf_e = ovf_e | ovf_a

                    # -- phase 2: expand only the enabled lanes --------
                    with jax.named_scope(spans.COMPACT):
                        (sel,) = jnp.nonzero(en_f, size=E_a,
                                             fill_value=TL)
                        sel_ok = sel < TL
                        pidx = jnp.clip(sel // L_a, 0, T - 1).astype(I32)
                        lane_sel = (sel % L_a).astype(I32)
                        st_sel = {k: v[pidx] for k, v in tile.items()}

                    parts_sel = None
                    if incremental:
                        with jax.named_scope(spans.COMPACT):
                            parts_sel = jax.tree_util.tree_map(
                                lambda v: v[pidx], parts)
                    succ_f, fp, en2, iok, errv, *_ = jax.vmap(
                        self._stage2.successor_fn(name, fn))(
                            st_sel, parts_sel, lane_sel)

                    with jax.named_scope(spans.INVARIANTS):
                        en_s = en2 & sel_ok
                        errv = jnp.where(en_s, errv, 0)
                        viol_l = en_s & ~iok & (errv == 0)
                        a_bag = ((errv & ERR_BAG_OVERFLOW) != 0).any()
                        a_slot = ((errv & ~ERR_BAG_OVERFLOW) != 0).any()
                        have_v = viol_l.any()
                        vidx = jnp.argmax(viol_l)
                        vinfo = jnp.stack(
                            [(base + pidx[vidx]).astype(I32),
                             jnp.asarray(aid, I32), lane_sel[vidx]])
                        viol = jnp.where(have_v & (viol[0] < 0), vinfo,
                                         viol)
                        viol_any = viol_any | have_v
                        bag_err = bag_err | a_bag
                        slot_err = slot_err | a_slot

                    # -- phase 3: insert + scatter, consumed in place --
                    commit_a = (commit & ~have_v & ~a_slot & ~a_bag
                                & ~ovf_a)
                    tbl, fresh, a_ovf_i = insert_core(
                        {"slots": slots}, fp, en_s & commit_a)
                    slots = tbl["slots"]
                    with jax.named_scope(spans.PACK_SCATTER):
                        dest = jnp.where(
                            fresh, nn + jnp.cumsum(fresh) - 1,
                            N_cap).astype(I32)
                        if pk is not None:
                            # pack successors on exit: the next buffer
                            # holds [words] uint32 rows, not dense
                            # planes
                            nb = nb.at[dest].set(
                                jax.vmap(pk.pack)(succ_f), mode="drop")
                        else:
                            for k in nb:
                                nb[k] = nb[k].at[dest].set(succ_f[k],
                                                           mode="drop")
                        nbp = nbp.at[dest].set(base + pidx, mode="drop")
                        nba = nba.at[dest].set(aid, mode="drop")
                        nbprm = nbprm.at[dest].set(lane_sel, mode="drop")
                    nfi = fresh.sum()
                    nn = nn + nfi
                    dist = dist + nfi
                    ovf_i = ovf_i | a_ovf_i
                    commit = commit_a & ~a_ovf_i
                    if edges_on:
                        # fresh gids stored UNGATED (mirrors insert
                        # persistence across a pause); triples are
                        # staged and appended once at tile end, gated
                        # on the whole tile committing — the same
                        # exactly-once discipline as `gen`
                        with jax.named_scope(spans.EDGE_EMIT):
                            gids_v = store_gids(
                                slots, gids_v, fp,
                                (edge_bases[1] + dest).astype(I32),
                                fresh)
                        fp_segs.append(fp)
                        en_segs_e.append(en_s)
                        pidx_segs_e.append(pidx)

                # failure cause priority: violation > slot error > bag
                # growth > expand-capacity > fpset growth (next-capacity
                # was folded in up front)
                new_reason = jnp.where(
                    viol_any, R_VIOLATION,
                    jnp.where(slot_err, R_SLOT_ERR,
                              jnp.where(bag_err, R_BAG_GROW,
                                        jnp.where(ovf_e, R_EXPAND_GROW,
                                                  jnp.where(ovf_i,
                                                            R_FPSET_GROW,
                                                            RUNNING)))))
                reason = jnp.where(reason == RUNNING, new_reason, reason)

                dead = valid & ~en_any
                dl = want_deadlock & commit & dead.any()
                reason = jnp.where(dl & (reason == RUNNING),
                                   R_DEADLOCK, reason)
                dead_i = jnp.where(dl, base + jnp.argmax(dead), c["dead"])
                # per-action expansion counters ride the carry as an
                # on-device accumulator (ISSUE 4 satellite) — same
                # commit gating as `gen`, so sum(act) == gen always
                act_vec = jnp.stack(act_local).astype(jnp.uint32)
                ret = {
                    "t": jnp.where(commit & (reason == RUNNING),
                                   t + 1, t),
                    "reason": reason, "viol": viol, "dead": dead_i,
                    "grow_aid": grow_aid,
                    # per-action mode sizes growth by doubling; the
                    # need vector only carries data in fused commit,
                    # and so does the count of expand blocks
                    "need": c["need"], "blk": c["blk"],
                    "slots": slots,
                    "nb": nb, "nbp": nbp, "nba": nba, "nbprm": nbprm,
                    "nn": nn, "dist": dist,
                    "gen": c["gen"] + jnp.where(commit, gen_local, 0),
                    "act": c["act"] + jnp.where(commit, act_vec,
                                                jnp.uint32(0)),
                }
                if edges_on:
                    # one staged emission at tile end (action-major
                    # queue order = the fused body's), gated on the
                    # final commit flag — a tile that paused or failed
                    # emits nothing and re-emits whole on re-entry
                    with jax.named_scope(spans.EDGE_EMIT):
                        fp_q = jnp.concatenate(fp_segs)
                        emit = jnp.concatenate(en_segs_e) & commit
                        pidx_q = jnp.concatenate(pidx_segs_e)
                        dst_g = lookup_gids({"slots": slots}, gids_v,
                                            fp_q, emit)
                        edst = jnp.where(
                            emit, c["edge_n"] + jnp.cumsum(emit) - 1,
                            E_cap_e)
                        ret["gids"] = gids_v
                        ret["eb_src"] = c["eb_src"].at[edst].set(
                            (edge_bases[0] + base + pidx_q).astype(I32),
                            mode="drop")
                        ret["eb_aid"] = c["eb_aid"].at[edst].set(
                            aid_q_pa, mode="drop")
                        ret["eb_dst"] = c["eb_dst"].at[edst].set(
                            dst_g, mode="drop")
                        ret["edge_n"] = c["edge_n"] + emit.sum()
                return ret

            return body

        return caps, total_E, make_body

    def _fused_body_factory(self):
        """The ISSUE 10 tentpole body: one frontier tile flows through
        three stages —

        (1) **guard matrix**: every action's guard over every lane of
            the tile in one sweep (no expansion interleaved), yielding
            EXACT per-action enabled counts: they drive the generated/
            per-action counters, deadlock detection, and exact
            cap-overflow events (the ``need`` vector carries the
            observed per-action maxima so growth is sized to the real
            count, not a doubling guess);
        (2) **work-queue compaction**: each action's enabled
            (state, lane) items are compacted, and ONLY the blocks of
            them that hold an enabled lane are expanded, fingerprinted,
            invariant-checked and packed.  A block is appended, packed,
            at the running end of ONE tile-local commit queue
            (ISSUE 30) with its action, parent index and lane per
            slot: action-major and, within an action, state-major and
            then by lane, so queue order == the per-action commit
            order, and the queue holds no lane of a block that did not
            run;
        (3) **commit in pieces**: the queue's written prefix is
            committed COMMIT_PIECE lanes at a time — batch dedup,
            FPSet ``insert_core``, scatter of rows and pointers — one
            piece after the other (one piece, no loop, where the whole
            queue is no wider).  A stable first-occurrence dedup mask
            makes the winner among a piece's duplicate fingerprints
            the earliest queue item, a later piece finds an earlier
            piece's fingerprints in the table, and ``dest`` carries on
            from piece to piece: every next-buffer row and pointer is
            what ONE batch over the whole queue gives, which is the
            action order the per-action body commits in.  The
            failure-cause priority (violation > slot > bag >
            expand-grow > fpset-grow) plus the committed-action-prefix
            rule on a failing tile are preserved verbatim, so results
            are bit-identical to commit="per-action"."""
        kern = self.kern
        T = self.tile
        n_act = len(kern.action_names)
        caps = self._expand_caps()
        total_E = sum(caps)
        caps_v = jnp.asarray(caps, I32)
        # the queue is a whole number of pieces, so no piece's slice is
        # clamped onto the one before it
        P = piece_lanes(total_E)
        Q = -(-total_E // P) * P
        edges_on = self._edges_on
        canon_counts = self._canon_counts
        stats = self._stat_fn is not None
        stat_sums = self._stat_sums
        stage2 = self._stage2.tile_pass(caps, Q)
        # ample-set POR (ISSUE 16): amat[a, b] says "expanding only a
        # is safe given an enabled b" (por.PORFilter).  POR and edge
        # emission are mutually exclusive (resolve_por blocker), so
        # the FPSet gids column has exactly one meaning per run: graph
        # node ids under -edges, C3 level markers under -por
        por_active = self._por_active
        if por_active:
            assert not edges_on
            amat_dev = jnp.asarray(self._por.amat)

        def make_body(frontier, n_front, want_deadlock, chunk_ctx,
                      edge_bases, pdepth):
            # the tile's rows come out of the chunk's unpacked states:
            # this body never reads `frontier` itself
            cstates, csegs, c_start = chunk_ctx

            def body(c):
                t = c["t"]
                base = t * T
                sidx = base + jnp.arange(T, dtype=I32)
                valid = sidx < n_front
                off = (t - c_start) * T
                with jax.named_scope(spans.GUARD_MATRIX):
                    tile = {k: jax.lax.dynamic_slice_in_dim(v, off, T)
                            for k, v in cstates.items()}
                    en_segs = [
                        jax.lax.dynamic_slice_in_dim(s, off, T)
                        for s in csegs]
                # -- stage 1: guard matrix -> exact per-action counts --
                with jax.named_scope(spans.GUARD_MATRIX):
                    en_segs = [e & valid[:, None] for e in en_segs]
                    cnts = jnp.stack([e.sum(dtype=I32) for e in en_segs])
                    en_any = jnp.zeros((T,), bool)
                    for e in en_segs:
                        en_any = en_any | e.any(axis=1)
                    gen_local = cnts.sum()
                    ovf_vec = cnts > caps_v
                    ovf_e = ovf_vec.any()
                    grow_aid = jnp.where(ovf_e,
                                         jnp.argmax(ovf_vec).astype(I32),
                                         c["grow_aid"])
                    need = jnp.maximum(c["need"],
                                       cnts.astype(jnp.uint32))
                if por_active:
                    # ample candidate per frontier row: one gather of
                    # the enabled bitmask against the independence
                    # matrix — row r may shortcut iff some enabled
                    # action conflicts with NO enabled action
                    # (ineligible rows of amat are all-False, so they
                    # self-veto).  Computed on the UNMASKED guard
                    # matrix, like en_any/deadlock and need/caps —
                    # the reduction only ever touches the commit
                    en_act = jnp.stack([e.any(axis=1) for e in en_segs],
                                       axis=1)               # [T, n_act]
                    conflict = (en_act.astype(I32)
                                @ (~amat_dev).astype(I32).T) > 0
                    cand = en_act & ~conflict
                    has_cand = cand.any(axis=1)
                    aid_star = jnp.argmax(cand, axis=1).astype(I32)

                nbp = c["nbp"]
                N_cap = nbp.shape[0]
                nn = c["nn"]
                reason, viol = c["reason"], c["viol"]
                # same headroom gate as the per-action body: with
                # N_cap - nn >= total_E no scatter can overrun, so an
                # insert is never committed without its successors.
                # Edge emission adds the parallel gate on the edge
                # append buffer (a full one means "drain to the host
                # CSR builder", not "grow in HBM")
                room_next = (N_cap - nn) >= total_E
                if edges_on:
                    E_cap_e = c["eb_src"].shape[0]
                    room_edge = (E_cap_e - c["edge_n"]) >= total_E
                else:
                    room_edge = jnp.asarray(True)
                commit0 = room_next & room_edge
                reason = jnp.where((reason == RUNNING) & ~room_next,
                                   R_NEXT_GROW, reason)
                reason = jnp.where((reason == RUNNING) & ~room_edge,
                                   R_EDGE_FLUSH, reason)

                # -- stage 2: work-queue compaction + expansion, the
                # blocks that hold enabled lanes (Stage2.tile_pass);
                # each action's verdicts are folded as its blocks end
                viol_any = jnp.asarray(False)
                bag_err = jnp.asarray(False)
                slot_err = jnp.asarray(False)
                first_bad = jnp.asarray(n_act, I32)

                def fold(aid, seg):
                    nonlocal viol, viol_any, bag_err, slot_err, first_bad
                    pidx, lane_sel, sel_ok, en2, iok, errv = seg
                    with jax.named_scope(spans.INVARIANTS):
                        en_s = en2 & sel_ok
                        errv = jnp.where(en_s, errv, 0)
                        viol_l = en_s & ~iok & (errv == 0)
                        a_bag = ((errv & ERR_BAG_OVERFLOW) != 0).any()
                        a_slot = ((errv & ~ERR_BAG_OVERFLOW) != 0).any()
                        have_v = viol_l.any()
                        vidx = jnp.argmax(viol_l)
                        vinfo = jnp.stack(
                            [(base + pidx[vidx]).astype(I32),
                             jnp.asarray(aid, I32), lane_sel[vidx]])
                        viol = jnp.where(have_v & (viol[0] < 0), vinfo,
                                         viol)
                        viol_any = viol_any | have_v
                        bag_err = bag_err | a_bag
                        slot_err = slot_err | a_slot
                        # committed-prefix rule: every queue item of an
                        # action at or past the FIRST failing one
                        # commits nothing (identical to the per-action
                        # body's carried commit flag going false there)
                        bad_a = have_v | a_slot | a_bag | ovf_vec[aid]
                        first_bad = jnp.minimum(
                            first_bad, jnp.where(bad_a, aid, n_act))

                queue, q_end, blk_segs = stage2(tile, en_segs, cnts, fold)

                rows_q, fp_q, en_q = queue["rows"], queue["fp"], queue["en"]
                aid_q, pidx_q, lane_q = (queue["aid"], queue["pidx"],
                                         queue["lane"])

                # -- stage 3: the written prefix, a piece at a time ----
                keep_q = en_q
                if por_active:
                    # C3 proviso (timing-immune level markers): a row
                    # takes the ample shortcut only if its ample
                    # successor is FRESH — absent from the visited set
                    # (-1) or committed while generating THIS level
                    # (marker pdepth+1).  A marker <= pdepth means the
                    # successor closes a potential cycle at this or an
                    # earlier level: fall back to full expansion.
                    # Probed on the PRE-insert slots, over the whole
                    # queue before its first piece commits, so a paused
                    # tile's re-entry sees its own earlier inserts as
                    # marker pdepth+1 (= fresh) and repeats the same
                    # decision bit-identically.  Violations/deadlock/
                    # need stay on the full en_q (stages 1-2 above)
                    is_amp = (en_q & has_cand[pidx_q]
                              & (aid_q == aid_star[pidx_q]))
                    g = lookup_gids({"slots": c["slots"]}, c["gids"],
                                    fp_q, is_amp)
                    old_i = is_amp & (g >= 0) & (g <= pdepth)
                    amp_bad = jnp.zeros((T,), bool).at[pidx_q].max(old_i)
                    take = has_cand & ~amp_bad
                    keep_q = en_q & (~take[pidx_q]
                                     | (aid_q == aid_star[pidx_q]))
                # what a tile may commit is known before its first
                # piece, but for a probe overflow (`ovf`, below)
                whole = commit0 & (first_bad >= n_act)
                mcommit = keep_q & (aid_q < first_bad) & commit0

                def piece(p, st):
                    """Commit queue lanes [p * P, (p + 1) * P)."""
                    def cut(v):
                        return (v if P == Q else
                                jax.lax.dynamic_slice_in_dim(v, p * P, P))
                    fp_p, pidx_p, aid_p = cut(fp_q), cut(pidx_q), cut(aid_q)
                    # stable first-occurrence dedup: the winner among
                    # equal fingerprints is the earliest queue item (=
                    # earliest action, matching the per-action commit
                    # order); the FPSet claim column then only has to
                    # arbitrate distinct fingerprints racing for one
                    # probe slot
                    perm, keep = dedup_batch(fp_p, cut(mcommit))
                    with jax.named_scope(spans.FPSET_INSERT):
                        canon = jnp.zeros((P,), bool).at[perm].set(keep)
                    tbl, fresh, ovf = insert_core(
                        {"slots": st["slots"]}, fp_p, canon)
                    nn = st["nn"]
                    st = dict(st, slots=tbl["slots"], ovf=st["ovf"] | ovf,
                              nn=nn + fresh.sum(dtype=I32))
                    if stats:
                        # over the states this piece commits, counted
                        # where `nn` is: an insert persists across a
                        # pause, and so does its count
                        new = jnp.where(fresh[:, None],
                                        cut(queue["stat"]), 0)
                        st["cs"] = jnp.where(
                            stat_sums,
                            st["cs"] + new.sum(0, dtype=jnp.uint32),
                            jnp.maximum(st["cs"], new.max(0)))
                    with jax.named_scope(spans.PACK_SCATTER):
                        dest = jnp.where(fresh, nn + jnp.cumsum(fresh) - 1,
                                         N_cap).astype(I32)
                        st["nb"] = jax.tree_util.tree_map(
                            lambda buf, v: buf.at[dest].set(
                                cut(v), mode="drop"),
                            st["nb"], rows_q)
                        st["nbp"] = st["nbp"].at[dest].set(
                            base + pidx_p, mode="drop")
                        st["nba"] = st["nba"].at[dest].set(
                            aid_p, mode="drop")
                        st["nbprm"] = st["nbprm"].at[dest].set(
                            cut(lane_q), mode="drop")
                    if por_active:
                        # level markers ride the insert UNGATED (mask =
                        # fresh), mirroring the edge-gid persistence
                        # rule: insert_core mutates slots even on a
                        # tile that ends up pausing, so the marker must
                        # land beside the fingerprint for re-entry to
                        # probe
                        st["gids"] = store_gids(
                            st["slots"], st["gids"], fp_p,
                            jnp.full((P,), 1, I32) * (pdepth + 1), fresh)
                    if edges_on:
                        # edge emission (ISSUE 15): the queue holds
                        # (source row, action, successor fp) for every
                        # enabled lane, fresh and duplicate.  Fresh
                        # states' gids (gid_base + next-buffer row) are
                        # stored next to their slots UNGATED, mirroring
                        # insert persistence across a pause, so a lane
                        # resolves its `dst` after its own piece's
                        # insert: a duplicate of an earlier piece as a
                        # duplicate of an earlier tile does.  Triples
                        # are appended past `edge_n`, which moves only
                        # when the tile COMMITS (the `gen` discipline):
                        # a paused tile's re-entry writes over them and
                        # emits exactly once, with its already-
                        # committed lanes resolving as duplicates
                        src_base, gid_base = edge_bases
                        with jax.named_scope(spans.EDGE_EMIT):
                            st["gids"] = store_gids(
                                st["slots"], st["gids"], fp_p,
                                (gid_base + dest).astype(I32), fresh)
                            emit = cut(en_q) & whole
                            dst_g = lookup_gids(
                                {"slots": st["slots"]}, st["gids"],
                                fp_p, emit)
                            edst = jnp.where(
                                emit, st["edge_n"] + jnp.cumsum(emit) - 1,
                                E_cap_e)
                            st["eb_src"] = st["eb_src"].at[edst].set(
                                (src_base + base + pidx_p).astype(I32),
                                mode="drop")
                            st["eb_aid"] = st["eb_aid"].at[edst].set(
                                aid_p, mode="drop")
                            st["eb_dst"] = st["eb_dst"].at[edst].set(
                                dst_g, mode="drop")
                            st["edge_n"] = st["edge_n"] + emit.sum(
                                dtype=I32)
                    return st

                st = {k: c[k] for k in ("slots", "nb", "nbp", "nba",
                                        "nbprm", "nn")}
                st["ovf"] = jnp.asarray(False)
                if stats:
                    st["cs"] = c["cs"]
                if por_active or edges_on:
                    st["gids"] = c["gids"]
                if edges_on:
                    for k in ("eb_src", "eb_aid", "eb_dst", "edge_n"):
                        st[k] = c[k]
                if P == Q:
                    n_pieces = jnp.asarray(1, I32)
                    st = piece(0, st)
                else:
                    n_pieces = (q_end + P - 1) // P
                    st = jax.lax.fori_loop(0, n_pieces, piece, st)
                ovf_i = st.pop("ovf")
                commit = whole & ~ovf_i

                # failure cause priority: violation > slot error > bag
                # growth > expand-capacity > fpset growth (same order
                # as the per-action body)
                new_reason = jnp.where(
                    viol_any, R_VIOLATION,
                    jnp.where(slot_err, R_SLOT_ERR,
                              jnp.where(bag_err, R_BAG_GROW,
                                        jnp.where(ovf_e, R_EXPAND_GROW,
                                                  jnp.where(ovf_i,
                                                            R_FPSET_GROW,
                                                            RUNNING)))))
                reason = jnp.where(reason == RUNNING, new_reason, reason)

                dead = valid & ~en_any
                dl = want_deadlock & commit & dead.any()
                reason = jnp.where(dl & (reason == RUNNING),
                                   R_DEADLOCK, reason)
                dead_i = jnp.where(dl, base + jnp.argmax(dead), c["dead"])
                ret = dict(st)
                ret.update({
                    "t": jnp.where(commit & (reason == RUNNING),
                                   t + 1, t),
                    "reason": reason, "viol": viol, "dead": dead_i,
                    "grow_aid": grow_aid, "need": need,
                    "dist": c["dist"] + (st["nn"] - nn),
                    "gen": c["gen"] + jnp.where(commit, gen_local, 0),
                    "act": c["act"] + jnp.where(
                        commit, cnts.astype(jnp.uint32), jnp.uint32(0)),
                    # blocks of stage 2 and pieces of stage 3 this pass
                    # ran, committed or not
                    "blk": c["blk"] + jnp.stack(blk_segs).astype(
                        jnp.uint32),
                    "cpl": c["cpl"] + n_pieces.astype(jnp.uint32),
                })
                if canon_counts:
                    # real lanes the canon stage ran for, and those
                    # whose least image is not the identity's; gated
                    # as `gen` is
                    ret["cn"] = c["cn"] + jnp.where(
                        commit, jnp.stack([
                            en_q.sum(dtype=jnp.uint32),
                            (en_q & queue["moved"]).sum(
                                dtype=jnp.uint32)]), jnp.uint32(0))
                if edges_on:
                    ret["edge_n"] = jnp.where(commit, st["edge_n"],
                                              c["edge_n"])
                if por_active:
                    # gen/act count the KEPT expansions (they feed
                    # states_generated and action_expansions, which
                    # must describe the reduced run); gfull keeps the
                    # unreduced count for the por_cut_ratio gauge, amp
                    # counts rows where the shortcut dropped real work
                    kept_act = jnp.zeros((n_act,), I32).at[aid_q].add(
                        keep_q.astype(I32))
                    ret["gen"] = c["gen"] + jnp.where(
                        commit, kept_act.sum(), 0)
                    ret["act"] = c["act"] + jnp.where(
                        commit, kept_act.astype(jnp.uint32),
                        jnp.uint32(0))
                    ret["gfull"] = c["gfull"] + jnp.where(
                        commit, gen_local, 0)
                    n_en_row = en_act.sum(axis=1, dtype=I32)
                    ret["amp"] = c["amp"] + jnp.where(
                        commit,
                        (take & (n_en_row > 1)).sum(dtype=I32), 0)
                return ret

            return body

        return caps, total_E, make_body

    def _make_level(self):
        T = self.tile
        K = self.chunk_tiles
        _caps, _tot, make_body = self._tile_body_factory()
        fused = self.commit == "fused"
        pk = self._pk
        kern = self.kern
        guard_mat = self._guard_matrix(kern) if fused else None

        por_active = self._por_active

        def level(table, frontier, n_front, start_t,
                  nb, nbp, nba, nbprm, n_next0, want_deadlock,
                  eb, edge_meta, pdepth=None):
            # `table` bundles the FPSet slots (+ the parallel gid
            # column in edge-emission mode); `eb` is None or the
            # (src, aid, dst) edge append buffers — DONATED, they are
            # rewritten every dispatch — while `edge_meta` carries the
            # chained fill scalar `n` plus the src_base/gid_base
            # offsets and is NOT donated (the pipelined collect reads
            # the fill level back after newer dispatches consumed the
            # buffers) — ISSUE 15
            n_tiles = (n_front + T - 1) // T
            chunk_ctx = None
            need0 = jnp.zeros((len(_caps),), jnp.uint32)
            if fused:
                # chunk-wide guard matrix (ISSUE 10 stage 1): evaluate
                # every guard for the WHOLE chunk of tiles in one
                # vmapped pass before the tile loop runs — the body
                # slices its tile's rows out, and the exact per-tile
                # per-action counts make a cap-overflow pause report
                # the exact need across the whole chunk (the host
                # grows once, not once per tile)
                F_cap = (frontier.shape[0] if pk is not None
                         else frontier["status"].shape[0])
                cidx = start_t * T + jnp.arange(K * T, dtype=I32)
                cvalid = cidx < n_front
                gidx = jnp.clip(cidx, 0, F_cap - 1)
                with jax.named_scope(spans.PACK_SCATTER):
                    if pk is not None:
                        cstates = jax.vmap(pk.unpack)(frontier[gidx])
                    else:
                        cstates = {k: v[gidx]
                                   for k, v in frontier.items()}
                csegs = guard_mat(cstates)
                with jax.named_scope(spans.GUARD_MATRIX):
                    csegs = [e & cvalid[:, None] for e in csegs]
                    need0 = jnp.stack(
                        [e.reshape(K, -1).sum(axis=1, dtype=I32).max()
                         for e in csegs]).astype(jnp.uint32)
                chunk_ctx = (cstates, csegs, start_t)

            def cond(c):
                return ((c["t"] < n_tiles) & (c["t"] < start_t + K)
                        & (c["reason"] == RUNNING))

            edge_bases = (None if eb is None
                          else (edge_meta["src_base"],
                                edge_meta["gid_base"]))
            body = make_body(frontier, n_front, want_deadlock,
                             chunk_ctx=chunk_ctx,
                             edge_bases=edge_bases, pdepth=pdepth)
            init = {
                "t": jnp.asarray(start_t, I32),
                "reason": jnp.asarray(RUNNING, I32),
                "viol": jnp.full((3,), -1, I32),
                "dead": jnp.asarray(-1, I32),
                "grow_aid": jnp.asarray(-1, I32),
                "need": need0,
                "slots": table["slots"],
                "nb": nb, "nbp": nbp, "nba": nba, "nbprm": nbprm,
                "nn": jnp.asarray(n_next0, I32),
                "dist": jnp.asarray(0, I32),
                "gen": jnp.asarray(0, I32),
                "act": jnp.zeros((len(_caps),), jnp.uint32),
                "blk": jnp.zeros((len(_caps),), jnp.uint32),
            }
            if fused:
                init["cpl"] = jnp.asarray(0, jnp.uint32)
            if self._canon_counts:
                init["cn"] = jnp.zeros((2,), jnp.uint32)
            if self._stat_fn is not None:
                init["cs"] = jnp.zeros((len(self._stat_sums),), jnp.uint32)
            if eb is not None:
                init["gids"] = table["gids"]
                init["eb_src"], init["eb_aid"], init["eb_dst"] = eb
                init["edge_n"] = edge_meta["n"]
            if por_active:
                init["gids"] = table["gids"]
                init["gfull"] = jnp.asarray(0, I32)
                init["amp"] = jnp.asarray(0, I32)
            return jax.lax.while_loop(cond, body, init)

        return level

    # ------------------------------------------------------------------
    # growth handlers
    # ------------------------------------------------------------------
    def _grow_msgs(self, device_states):
        """Double MAX_MSGS in place: all-zero padding slots change no
        fingerprint (only present slots contribute to the bag hash), so
        the FPSet and every recorded trace pointer stay valid.  Pads the
        given on-device state pytrees and rebuilds the jitted passes.

        Packed buffers round-trip through the OLD pack spec to dense,
        pad, and re-pack under the rebuilt spec (MAX_MSGS changes both
        the lane count and the spec version); unused zero rows are
        stable under the round trip, so the whole buffer converts."""
        old = self.codec.shape.MAX_MSGS
        old_pk = self._pk
        if old_pk is not None:
            dense = [old_pk.unpack_np(np.asarray(d))
                     for d in device_states]
            self._build(old * 2)
            dense = [self.codec.pad_msgs(d, old) for d in dense]
            return [jnp.asarray(self._pk.pack_np(d)) for d in dense]
        self._build(old * 2)
        return [self.codec.pad_msgs(d, old) for d in device_states]

    @staticmethod
    def _pad_rows(buf, add):
        """Append `add` zero rows to a frontier-format buffer (dense
        plane dict or packed [cap, words] array)."""
        def padv(v):
            shape = (add,) + v.shape[1:]
            return jnp.concatenate([v, jnp.zeros(shape, v.dtype)])
        if isinstance(buf, dict):
            return {k: padv(v) for k, v in buf.items()}
        return padv(buf)

    @classmethod
    def _grow_next(cls, bufs, factor=4):
        """Enlarge the next-frontier buffer set, preserving contents."""
        nb, nbp, nba, nbprm = bufs
        cap = nbp.shape[0]
        add = cap * (factor - 1)
        return (cls._pad_rows(nb, add), cls._pad_rows(nbp, add),
                cls._pad_rows(nba, add), cls._pad_rows(nbprm, add))

    # ------------------------------------------------------------------
    # exact-count expansion caps (ISSUE 10)
    # ------------------------------------------------------------------
    def _fold_need(self, need):
        """Fold one dispatch's chunk-wide per-action enabled maxima
        into the run-scoped observation (the exact-growth and
        calibration source)."""
        if self.commit == "fused" and self._need_seen is not None:
            self._need_seen = np.maximum(
                self._need_seen, np.asarray(need, np.int64))

    def _grow_expand(self, aid, obs, emit):
        """R_EXPAND_GROW handler shared by `run` and the paged loop.
        Fused commit: the chunk-wide guard matrix already measured the
        true per-action maxima, so one recompile re-caps EVERY action
        with headroom (``grown_caps``) instead of one doubling guess
        per tile.
        Per-action commit: the historical doubling of the overflowing
        action's tile multiplier."""
        kern = self.kern
        if self.commit == "fused":
            caps = self._expand_caps()
            new = grown_caps(
                caps, self._need_seen,
                [self.tile * kern._lane_count(n)
                 for n in kern.action_names])
            grown = [(n, c) for n, c, old in
                     zip(kern.action_names, new, caps) if c > old]
            self.expand_caps = new
            if not grown:
                # defensive: a pause whose need never reached the host
                # (should not happen — the paused ticket carries it)
                self.expand_caps[aid] = min(
                    self.tile * kern._lane_count(kern.action_names[aid]),
                    _align8(caps[aid] * 2))
                grown = [(kern.action_names[aid], self.expand_caps[aid])]
            for _name, cap in grown:
                obs.grow("expand_buffer", cap)
            emit(f"expand caps grown to {CAP_HEADROOM}x the exact chunk "
                 f"need: "
                 + ", ".join(f"{n}={c}" for n, c in grown)
                 + " (recompiling)")
        else:
            self.expand_mults[aid] *= 2
            obs.grow("expand_buffer", self.expand_mults[aid])
            emit(f"expand buffer for {kern.action_names[aid]} grown "
                 f"to tile x {self.expand_mults[aid]} (recompiling)")
        self._level_jit = None
        self._fresh_jit = True

    def _calibrate_caps(self, obs, emit, level_states):
        """Level-boundary cap calibration (fused commit): shrink the
        per-action expansion caps onto CAP_HEADROOM times the observed
        exact per-tile maxima once a representative level has been measured.  Only
        ever fires when it saves >= 20% of the dispatched expand lanes
        (each calibration is a recompile); caps can only shrink onto
        real observations, so a later bigger tile simply triggers an
        exact growth event.  Cap changes never affect results — only
        how many cap lanes are padding.  That padding costs the
        headroom gate alone (CAP_HEADROOM): stage 2 runs the blocks
        that hold enabled lanes (the occupancy gauge's denominator)
        and stage 3 the pieces of the queue they wrote, so a
        calibration moves neither stage nor the gauges, and a start
        that never shrinks (`static_cap`) is cheap."""
        if self.commit != "fused" or level_states < 4 * self.tile:
            return False
        kern, T = self.kern, self.tile
        # same headroom as growth (CAP_HEADROOM x the need), and never
        # below the static start: a calibration that shrank onto the
        # exact maxima was undone by growth events over the next
        # levels, as frontier states grew richer and idle actions woke
        tgt = [max(self._cap_floor(a, T * kern._lane_count(n)),
                   min(T * kern._lane_count(n),
                       _align8(CAP_HEADROOM * int(s))))
               for a, (n, s) in enumerate(
                   zip(kern.action_names, self._need_seen))]
        cur = self._expand_caps()
        if sum(tgt) * 5 > sum(cur) * 4:
            return False
        self.expand_caps = tgt
        self._level_jit = None
        self._fresh_jit = True
        obs.grow("expand_calibrate", sum(tgt))
        emit(f"expand caps calibrated to {CAP_HEADROOM}x the exact chunk "
             f"maxima "
             f"({sum(cur)} -> {sum(tgt)} lanes/tile; recompiling)")
        return True

    def _reset_accounting(self):
        """Run-scoped counters that the tickets feed: per-action
        expansions (the on-device accumulator), tiles committed, expand
        lanes the device ran, the expand blocks run of those the caps
        hold, and the commit lanes run of those."""
        n_act = len(self.kern.action_names)
        self._act_counts = np.zeros(n_act, np.int64)
        self._blocks_act = np.zeros(n_act, np.int64)
        self._blocks_cap = 0
        self._commit_run = 0
        self._commit_cap = 0
        self._tiles_done = 0
        self._lanes_disp = 0
        self._canon_cn = np.zeros(2, np.int64)
        self._stat_cn = np.zeros(len(self._stat_sums), np.int64)

    def _device_counts(self, out):
        """The counters only some level programs carry, as the tail of
        a ticket's pull: the kernel's commit stats, then canon's."""
        return ([out["cs"]] if self._stat_fn else []) \
            + ([out["cn"]] if self._canon_counts else [])

    def _fold_device_counts(self, pulled):
        """Fold that tail (`_device_counts`) into the run's totals."""
        if self._canon_counts:
            self._canon_cn += np.asarray(pulled[-1], np.int64)
        if self._stat_fn:
            got = np.asarray(pulled[-2 if self._canon_counts else -1],
                             np.int64)
            self._stat_cn = np.where(self._stat_sums, self._stat_cn + got,
                                     np.maximum(self._stat_cn, got))

    def _account_blocks(self, blk, pieces):
        """One collected ticket's per-action counts of expand blocks
        and its count of commit pieces (fused commit; zeros from the
        per-action body): the lanes the device really expanded and
        committed over, paused passes included."""
        blk = np.asarray(blk, np.int64)
        caps = self._expand_caps()
        self._blocks_act += blk
        self._lanes_disp += sum(
            int(n) * block_rows(cap) for n, cap in zip(blk, caps))
        self._commit_run += int(pieces) * piece_lanes(sum(caps))

    def _account_tiles(self, n_tiles):
        """`n_tiles` frontier tiles were committed under the current
        cap set.  The per-action body expands every cap lane of each;
        the fused body only its blocks (`_account_blocks`), of the
        `_blocks_cap` the caps hold."""
        caps = self._expand_caps()
        self._tiles_done += int(n_tiles)
        if self.commit == "fused":
            self._blocks_cap += int(n_tiles) * sum(
                -(-cap // block_rows(cap)) for cap in caps)
            self._commit_cap += int(n_tiles) * sum(caps)
        else:
            self._lanes_disp += int(n_tiles) * sum(caps)

    # ------------------------------------------------------------------
    def _alloc_bufs(self, cap):
        if self._pk is not None:
            nb = jnp.zeros((cap, self._pk.words), jnp.uint32)
        else:
            zero = self.codec.zero_state()
            nb = {k: jnp.zeros((cap,) + np.shape(v), np.int32)
                  for k, v in zero.items()}
        return (nb, jnp.zeros((cap,), I32), jnp.zeros((cap,), I32),
                jnp.zeros((cap,), I32))

    def _set_rows(self, buf, batch, n):
        """Write the first `n` rows of a dense host batch into a
        frontier-format buffer (packing them when the buffer is
        packed)."""
        if self._pk is not None:
            return buf.at[:n].set(jnp.asarray(self._pk.pack_np(batch)))
        return {k: buf[k].at[:n].set(jnp.asarray(batch[k]))
                for k in buf}

    def _snapshot_frontier(self, buf, n):
        """``save_checkpoint``'s frontier keywords for the first `n`
        rows of a frontier-format buffer: the packed rows as the device
        holds them (the loader unpacks them by the manifest's pack
        spec, so any engine/pack configuration still resumes dense
        planes), or the dense planes of a run that does not pack."""
        if all(isinstance(v, np.ndarray) for v in jax.tree.leaves(buf)):
            # the paged engine's frontier: the host cuts what it holds
            rows = jax.tree.map(lambda v: v[:n], buf)
        else:
            rows = self._pull(_cut_rows, buf, n).host()
        return {"frontier_packed" if self._pk is not None
                else "frontier": rows}

    def _pull(self, cut, buf, n, axis=0):
        """The first `n` rows of a device buffer, leaving for the host
        in pages of a chunk's rows: one program a buffer shape."""
        return RowPages(cut, buf, n, self.chunk_tiles * self.tile, axis)

    def _register_init(self, res, obs):
        """Encode, dedup, and FPSet-register the initial states; seed
        the host pointer store and check invariants on them.  Returns
        (table, init_batch, n0, viol_index); viol_index is non-None
        when an init state violates, with res.trace already built.
        Inside the init span, by its parts: the interpreter's share
        (`states`), the fingerprints up to their pull, and what is
        enqueued on the device."""
        spec, codec = self.spec, self.codec
        with obs.part(spans.INIT_DEVICE):
            table = empty_table(self.fpset_capacity)
        with obs.part(spans.INIT_STATES):
            init_states = list(spec.init_states())
            init_dense = [codec.encode(st) for st in init_states]
            init_batch = {k: np.stack([d[k] for d in init_dense])
                          for k in init_dense[0]}
        with obs.part(spans.INIT_FINGERPRINT):
            fps = np.asarray(self.model.fp_batch(init_batch))
        with obs.part(spans.INIT_STATES):
            keep, seen = [], set()
            for i in range(len(init_dense)):
                key = tuple(fps[i])
                if key not in seen:
                    seen.add(key)
                    keep.append(i)
            init_batch = {k: v[keep] for k, v in init_batch.items()}
            self._init_states = [init_states[i] for i in keep]
            self._init_dense = [init_dense[i] for i in keep]
            n0 = len(keep)
        with obs.part(spans.INIT_DEVICE):
            table, _, _ = insert_batch(
                table, jnp.asarray(fps[keep]), jnp.ones((n0,), bool))
            if self._edges_on:
                # gid column (ISSUE 15): graph node ids ARE commit
                # order, so the deduped init states take gids 0..n0-1
                table["gids"] = store_gids(
                    table["slots"],
                    jnp.full((self.fpset_capacity,), -1, jnp.int32),
                    jnp.asarray(fps[keep]),
                    jnp.arange(n0, dtype=jnp.int32),
                    jnp.ones((n0,), bool))
            if self._por_active:
                # C3 level-marker column (ISSUE 16): init states are
                # level 0, and a zeros column gives every one of them
                # marker 0 without a store pass; empty-slot values are
                # never read (lookup_gids returns -1 for absent
                # fingerprints)
                table["gids"] = jnp.zeros((self.fpset_capacity,),
                                          jnp.int32)
        # host trace store: gid -> (parent gid, action, param)
        self._h_parent = [np.full(n0, -1, np.int64)]
        self._h_action = [np.full(n0, -1, np.int32)]
        self._h_param = [np.zeros(n0, np.int32)]
        with obs.part(spans.INIT_STATES):
            for i in range(n0):
                bad = spec.check_invariants(self._init_states[i])
                if bad:
                    res.ok = False
                    res.violated_invariant = bad
                    res.trace = self._trace(i)
                    return table, init_batch, n0, i
        res.states_generated += len(init_dense)
        return table, init_batch, n0, None

    # ------------------------------------------------------------------
    # the host loop of the one-chip engines
    # ------------------------------------------------------------------
    @closes_observer
    def run(self, max_states=None, max_depth=None, max_seconds=None,
            check_deadlock=False, log=None, progress_every=10.0,
            checkpoint_path=None, checkpoint_every=None,
            resume_from=None, obs=None) -> CheckResult:
        """Check the spec breadth first, a level at a time.  The loop
        is one for the resident and the host-paged frontier; an engine
        says how its frontier starts (`_start_frontier`,
        `_open_levels`), how one level of it is expanded
        (`_expand_level`: each engine's own dispatch window), and how
        the level just made becomes the frontier (`_close_level`,
        `_hand_over`, `_snapshot_keywords`)."""
        run = self._open_run(
            log, progress_every, obs,
            max_states=max_states, max_depth=max_depth,
            max_seconds=max_seconds, check_deadlock=check_deadlock,
            checkpoint_path=checkpoint_path,
            checkpoint_every=checkpoint_every, resume_from=resume_from)
        res, obs = run.res, run.obs
        if resume_from is not None:
            ck = self._load_snapshot(run)
            self._start_frontier(run, ck["frontier"], ck["n_front"], ck)
            obs.log(f"resumed from {resume_from}: depth {run.depth}, "
                    f"{run.fp_count} distinct, frontier {run.n_front}")
        else:
            run.fp_cap = self.fpset_capacity
            # reset BEFORE registration: a reused engine instance must
            # not leak the previous run's trajectory into an
            # init-violation result
            self.level_sizes = []
            with obs.span(spans.INIT):
                run.table, init_batch, n0, viol = self._register_init(
                    res, obs)
                run.fp_count = n0
                if viol is None:
                    with obs.part(spans.INIT_DEVICE):
                        self._start_frontier(run, init_batch, n0)
            if viol is not None:
                return self._finish(res, obs, run.fp_count,
                                    table=run.table, fp_cap=run.fp_cap)
            run.n_front = n0
            self.level_sizes = [n0]
        run.last_checkpoint = time.time()
        self._open_levels(run)
        # the host between two units of device work (a level; a page
        # of the paged engine) and before the first: open from here,
        # or from such a unit's end, to the next launch
        obs.boundary(depth=run.depth)
        while run.n_front > 0:
            if max_depth is not None and run.depth >= max_depth:
                res.error = f"depth limit {max_depth} reached"
                break
            run.depth += 1
            fault_point("level", depth=run.depth, obs=obs)
            if self._expand_level(run) or not self._end_level(run):
                break
        res.diameter = run.depth
        return self._finish(res, obs, run.fp_count,
                            table=run.table, fp_cap=run.fp_cap)

    def _open_run(self, log, progress_every, obs, **asked):
        """Everything of a run before its frontier: the speclint gate,
        the observer and what it says of the levers, the run's
        counters, the dispatch window."""
        from ..analysis import preflight
        preflight(self.spec, log=log)   # fail fast, before any dispatch
        obs = self._observer(obs, log=log, progress_every=progress_every)
        self.model.announce(obs, pipeline=self.pipe_window)
        self._obs_active = obs          # closes_observer finalizes it
        # per-action expansion counters (on-device accumulator, pulled
        # with the control scalars; run-scoped, not checkpointed) +
        # occupancy accounting (ISSUE 10)
        self._reset_accounting()
        self._por_kept = self._por_full = self._por_amp = 0
        t0 = time.time()
        obs.start(t0, backend=jax.default_backend(),
                  resumed=asked["resume_from"] is not None)
        # the window of level-kernel dispatches (ISSUE 4), made as the
        # run starts: its unfed clock counts the set-up
        from .pipeline import DispatchPipeline
        pipe = DispatchPipeline(self.pipe_window, obs,
                                ready=lambda o: o["reason"])
        return self._run_record(CheckResult(), obs, pipe, t0, **asked)

    def _observer(self, obs, **kw):
        """The run's observer, under this engine's name in journals
        and metrics (obs/SCHEMA.md)."""
        return RunObserver.ensure(obs, "device", self.spec, **kw)

    def _load_snapshot(self, run):
        """The head of a resume: a level-boundary snapshot read,
        refused where it was written under other levers, and the
        table, the pointer store and the counts as it left them."""
        from .checkpoint import load_checkpoint, spec_digest
        path = run.resume_from
        ck = load_checkpoint(path, expect_digest=spec_digest(self.spec),
                             log=run.obs.log)
        if (ck.get("extra") or {}).get("sharded"):
            raise TLAError("checkpoint was written by the sharded "
                           "engine; resume it there")
        # an EMPTY expand_mults means the snapshot carries no
        # per-action multipliers (written by the sharded engine,
        # then converted single-device for the supervisor's paged
        # fallback, parallel.sharded_bfs.convert_sharded_snapshot) —
        # keep this engine's own defaults
        if ck["max_msgs"] != self.codec.shape.MAX_MSGS or \
                (ck["expand_mults"] and list(ck["expand_mults"])
                 != list(self.expand_mults)):
            if ck["expand_mults"]:
                self.expand_mults = list(ck["expand_mults"])
            self._build(ck["max_msgs"])
        self.model.check_manifests(ck, path)
        run.table = {"slots": jnp.asarray(ck["slots"])}
        run.fp_cap = int(ck["slots"].shape[0])
        if self._por_active:
            # the C3 level markers are NOT snapshotted: at a level
            # boundary every stored fingerprint belongs to the
            # frontier's level or earlier, so an all-zeros column
            # (marker 0 <= any pdepth = old) reproduces every decision
            run.table["gids"] = jnp.zeros((run.fp_cap,), jnp.int32)
        self._init_dense = ck["init_dense"]
        self._init_states = [self.codec.decode(d)
                             for d in ck["init_dense"]]
        self._h_parent = [ck["h_parent"]]
        self._h_action = [ck["h_action"]]
        self._h_param = [ck["h_param"]]
        self.level_sizes = list(ck["level_sizes"])
        run.depth = ck["depth"]
        run.fp_count = ck["fp_count"]
        run.res.states_generated = ck["states_generated"]
        run.t0 -= ck["elapsed"]            # keep cumulative wall clock
        run.obs.set_epoch(run.t0)
        run.n_front = ck["n_front"]
        run.level_base = sum(self.level_sizes[:-1])
        return ck

    def _start_frontier(self, run, batch, n, ck=None):
        """The run's first frontier from `n` dense rows (Init, inside
        the init span; or a snapshot `ck`'s): here on the device,
        beside the next buffers."""
        run.front, run.fpar, run.fact, run.fprm = self._alloc_bufs(
            max(self.next_cap, n))
        run.front = self._set_rows(run.front, batch, n)
        run.bufs = self._alloc_bufs(self.next_cap)

    def _open_levels(self, run):
        """What is left to set up once the frontier stands (here
        nothing)."""

    def _pull_scalars(self, o):
        """ONE host round-trip for all control scalars of a dispatch —
        separate int() pulls cost one device round-trip each."""
        vals = [o["reason"], o["t"], o["nn"], o["gen"], o["dist"],
                o["act"], o["need"], o["blk"], o.get("cpl", 0)]
        if self._edges_on:
            vals.append(o["edge_n"])
        if self._por_active:
            vals += [o["gfull"], o["amp"]]
        return jax.device_get(vals + self._device_counts(o))

    def _collect(self, run):
        """The oldest dispatch of the window, folded into the run's
        counters.  Returns its output, its reason, the tile it
        stopped at, the rows of the next buffer and (edge emission)
        of the edge buffer."""
        out, sc = run.pipe.collect(self._pull_scalars)
        reason, t, nn, gen_add, dist_add = (int(x) for x in sc[:5])
        run.res.states_generated += gen_add
        run.fp_count += dist_add
        self._act_counts += np.asarray(sc[5], np.int64)
        self._fold_need(sc[6])
        self._account_blocks(sc[7], sc[8])
        at, n_edge = 9, None
        if self._edges_on:
            at, n_edge = 10, int(sc[9])
        if self._por_active:
            self._por_kept += gen_add
            self._por_full += int(sc[at])
            self._por_amp += int(sc[at + 1])
        self._fold_device_counts(sc)
        return out, reason, t, nn, n_edge

    def _verdict(self, run, reason, out, base, row_of):
        """A reason that ends the run: a violation or a deadlock at
        row `base` + the index the device reports of the frontier
        (`row_of`: that row, dense) fills the result and returns True;
        a slot error raises.  False for a growth pause."""
        res = run.res
        if reason == R_VIOLATION:
            vp, va, vprm = (int(v) for v in np.asarray(out["viol"]))
            gid = run.level_base + base + vp
            vstate = self.model.materialize_one(row_of(base + vp), va, vprm)
            bad = self.spec.check_invariants(self.codec.decode(vstate))
            if bad is None:
                # device said violated, interpreter disagrees:
                # engine bug — fail loudly, don't fabricate a
                # counterexample (see device_sim for rationale)
                raise TLAError(
                    "device/interpreter divergence: device "
                    "invariant kernel reported a violation the "
                    "interpreter accepts (parent gid "
                    f"{gid}, action {self.kern.action_names[va]})")
            res.ok = False
            res.violated_invariant = bad
            res.trace = self._trace(gid, extra=(va, vprm))
            return True
        if reason == R_SLOT_ERR:
            raise TLAError(slot_error(self.codec))
        if reason == R_DEADLOCK:
            di = int(out["dead"])
            res.ok = False
            res.error = "deadlock"
            res.deadlock_state = self.codec.decode(row_of(base + di))
            res.trace = self._trace(run.level_base + base + di)
            return True
        return False

    def _grow_fpset(self, run):
        run.table = grow(run.table)
        run.fp_cap *= 4
        # shape change -> the next dispatch retraces and recompiles;
        # charge it to "compile", not "dispatch" (as every growth)
        self._fresh_jit = True
        run.obs.grow("fpset", run.fp_cap)
        run.obs.log(f"FPSet grown to {run.fp_cap} slots")

    def _expand_level(self, run):
        """One level of the resident frontier: the window re-launches
        the same `front`, chained on the device-side (t, nn) of the
        dispatch before, and is drained at the level's end.  Returns
        True where the level ended the run with a verdict."""
        obs, pipe, res = run.obs, run.pipe, run.res
        emit = obs.log
        depth, n_front = run.depth, run.n_front
        start_t = 0
        run.n_next = 0
        n_tiles = (n_front + self.tile - 1) // self.tile
        # device-side chain: the next dispatch's (start_t, nn)
        # come straight off the previous dispatch's outputs, so
        # filling the window costs zero host syncs
        pend_t = jnp.asarray(0, I32)
        pend_nn = jnp.asarray(0, I32)
        while True:
            # keep the window full (speculation past a pause or the
            # level end is safe: such dispatches commit nothing and
            # pipe.drain() discards their deltas — pipeline.py)
            while pipe.has_room():
                nb, nbp, nba, nbprm = run.bufs
                out = pipe.launch(
                    self._run_level, run.table, run.front,
                    jnp.asarray(n_front, I32), pend_t,
                    nb, nbp, nba, nbprm, pend_nn,
                    jnp.asarray(bool(run.check_deadlock)), None, None,
                    jnp.asarray(depth - 1, I32),
                    fresh=self._fresh_jit,
                    depth=depth)
                self._fresh_jit = False
                run.table = {"slots": out["slots"]}
                if self._por_active:
                    run.table["gids"] = out["gids"]
                run.bufs = (out["nb"], out["nbp"], out["nba"],
                            out["nbprm"])
                pend_t, pend_nn = out["t"], out["nn"]
            out, reason, start_t, run.n_next, _ = self._collect(run)

            if reason == RUNNING:
                obs.progress(depth=depth, distinct=run.fp_count,
                             generated=res.states_generated)
                if run.out_of_time(time.time()):
                    pipe.drain(reason="budget")
                    break
                if start_t >= n_tiles:
                    pipe.drain()     # in-flight tickets are no-ops
                    break            # level complete
                continue
            # pause or terminal reason: everything still in flight
            # is a replay of the same paused tile — drop it, then
            # handle the reason on the chain-tip table/buffers
            # (identical to the consumed ticket's: replays commit
            # nothing)
            pipe.drain()
            if self._verdict(run, reason, out, 0,
                             lambda i: self.model.fetch_row(run.front, i)):
                return True
            if reason == R_BAG_GROW:
                run.front, nb = self._grow_msgs([run.front, run.bufs[0]])
                run.bufs = (nb,) + run.bufs[1:]
                obs.grow("message_table", self.codec.shape.MAX_MSGS)
                emit(f"message table grown to "
                     f"{self.codec.shape.MAX_MSGS} slots (recompiling)")
            elif reason == R_FPSET_GROW:
                self._grow_fpset(run)
            elif reason == R_NEXT_GROW:
                run.bufs = self._grow_next(run.bufs)
                self._fresh_jit = True
                obs.grow("next_buffer", run.bufs[1].shape[0])
                emit(f"next-frontier buffer grown to "
                     f"{run.bufs[1].shape[0]}")
            elif reason == R_EXPAND_GROW:
                self._grow_expand(int(out["grow_aid"]), obs, emit)
            obs.progress(depth=depth, distinct=run.fp_count,
                         generated=res.states_generated)
            if run.out_of_time(time.time()):
                break
        self._account_tiles(min(start_t, n_tiles))
        return False

    def _close_level(self, run):
        """What of a level's device work is left when its window is
        empty (here nothing)."""

    def _hand_over(self, run):
        """The level just made becomes the frontier: its trace
        pointers start for the host, the buffer sets swap."""
        nb, nbp, nba, nbprm = run.bufs
        n_next = run.n_next
        if n_next:
            # async pointer fetch, in pages of one shape: the
            # copies overlap the next level's compute and are only
            # materialized on demand (_flush_pointers) — a blocking
            # device_get here costs a full device round-trip per
            # level, a slice of n_next rows a compile per level
            pages = self._pull(_cut_pointers, (nbp, nba, nbprm),
                               n_next, axis=1)
            run.obs.count("boundary_pull_pages", len(pages.pages))
            run.obs.count("boundary_pull_bytes", pages.nbytes)
            self._h_parent.append((pages, run.level_base))
            self._h_action.append(None)
            self._h_param.append(None)
            self.level_sizes.append(n_next)
        run.level_base += run.n_front
        # the old frontier set becomes the next scratch buffer set
        run.front, run.bufs = nb, (run.front, run.fpar, run.fact,
                                   run.fprm)
        run.fpar, run.fact, run.fprm = nbp, nba, nbprm
        run.n_front = n_next
        if self.debug_checks and n_next:
            self._debug_assert_widths(run.front, n_next, run.depth)

    def _snapshot_keywords(self, run):
        """``save_checkpoint``'s frontier keywords at a level's end."""
        return self._snapshot_frontier(run.front, run.n_front)

    def _end_level(self, run):
        """A level's end, the same for every frontier: the level is
        filed and handed over, then caps are calibrated, a due or
        forced snapshot is written, and the run's limits are tested.
        Returns False where no level follows."""
        res, obs, depth = run.res, run.obs, run.depth
        emit = obs.log
        obs.boundary(depth=depth)
        self._close_level(run)
        obs.level_done(depth, frontier=run.n_front, distinct=run.fp_count,
                       generated=res.states_generated)
        self._hand_over(run)
        if run.stop:
            # the budget cut this level short: what was handed over is
            # a part of one (the run's result says so, `levels[-1]`),
            # and nothing below may file it as a level.  The snapshot
            # on disk stays the last whole level's
            res.error = run.stop
            return False
        # fused commit: shrink the expansion caps onto the exact
        # observed maxima (the window is drained here, so the
        # recompile never races an in-flight dispatch)
        self._calibrate_caps(obs, emit, run.n_front)
        # a pending SIGTERM/SIGINT (supervisor's PreemptionGuard)
        # forces a rescue snapshot at this boundary regardless of
        # cadence; at fixpoint (no next level) the run finishes anyway
        rescue = preempt_signal() if run.n_front else None
        path = run.checkpoint_path
        if path and run.n_front and (
                rescue is not None
                or run.checkpoint_every is None
                or time.time() - run.last_checkpoint
                >= run.checkpoint_every):
            from .checkpoint import (FORMAT_VERSION, save_checkpoint,
                                     spec_digest)
            with obs.span(spans.CHECKPOINT, depth=depth):
                with obs.part(spans.CHECKPOINT_PULL):
                    self._flush_pointers()
                    front = self._snapshot_keywords(run)
                staged = save_checkpoint(
                    path,
                    slots=run.table["slots"],
                    **front,
                    n_front=run.n_front,
                    h_parent=np.concatenate(self._h_parent),
                    h_action=np.concatenate(self._h_action),
                    h_param=np.concatenate(self._h_param),
                    init_dense=self._init_dense,
                    level_sizes=self.level_sizes, depth=depth,
                    fp_count=run.fp_count,
                    states_generated=res.states_generated,
                    max_msgs=self.codec.shape.MAX_MSGS,
                    expand_mults=self.expand_mults,
                    elapsed=time.time() - run.t0,
                    digest=spec_digest(self.spec),
                    **self.model.manifests(), obs=obs)
            run.last_checkpoint = time.time()
            obs.checkpoint(path, depth, run.fp_count, staged,
                           FORMAT_VERSION)
            emit(f"checkpoint written to {path} "
                 f"(depth {depth}, {run.fp_count} distinct)")
        if rescue is not None:
            obs.rescue(path or "", depth, run.fp_count, rescue)
            emit(f"preempted by {rescue}: rescue snapshot at depth "
                 f"{depth} ({path}); exiting resumable")
            raise Preempted(path, depth, run.fp_count, rescue)
        if run.n_front == 0:
            return False
        if run.max_states and run.fp_count >= run.max_states:
            res.error = f"state limit {run.max_states} reached"
            return False
        # proactive FPSet growth between levels keeps probe chains
        # short and the in-level overflow pause rare
        if run.fp_count > 0.5 * run.fp_cap:
            self._grow_fpset(run)
        return True

    def _debug_assert_widths(self, front, n_front, depth):
        """TPUVSR_DEBUG_NANS=1 overflow guard: after each level, pull
        the view/op planes of the committed frontier and assert they
        stay inside the statically derived ranges (the widths lint
        pass).  Catches packed-field wrap the moment it happens instead
        of as a fingerprint anomaly millions of states later."""
        if self._pk is not None:
            front = self._pk.unpack_np(np.asarray(front[:n_front]))
        if not hasattr(self, "_debug_bounds"):
            from ..analysis.passes.widths import derive_ranges
            rng = derive_ranges(self.spec)
            self._debug_bounds = {
                k: rng[q] for k, q in (("view", "view_number"),
                                       ("op", "op_number"))
                if q in rng and k in front}
        for plane, (lo, hi) in self._debug_bounds.items():
            vals = np.asarray(front[plane][:n_front])
            if vals.size and (vals.min() < lo or vals.max() > hi):
                raise TLAError(
                    f"debug overflow guard: plane {plane!r} reached "
                    f"[{int(vals.min())}, {int(vals.max())}] at depth "
                    f"{depth}, outside the derived range [{lo}, {hi}] "
                    f"(TPUVSR_DEBUG_NANS width assertion)")

    # ------------------------------------------------------------------
    def _flush_pointers(self):
        """Materialize any still-on-device trace-pointer levels (the
        per-level fetches are issued async): until then a level's
        entry of `_h_parent` holds its pages and the gid of its
        frontier's first row, and its two neighbours nothing."""
        for i, v in enumerate(self._h_parent):
            if isinstance(v, tuple):
                pages, off = v
                par, act, prm = pages.host()
                self._h_parent[i] = par.astype(np.int64) + off
                # copies: a row of the joined pages would keep all
                # three alive
                self._h_action[i] = act.copy()
                self._h_param[i] = prm.copy()

    def _finish(self, res, obs, fp_count, table=None, fp_cap=None):
        """Uniform result finalization: the collector (not the engine)
        stamps elapsed/states_per_sec/levels/metrics (ISSUE 2
        satellite — no more post-hoc res.elapsed patching)."""
        obs.end_boundary()
        with obs.span(spans.FINISH):
            self._final_gauges(res, obs, fp_count, table, fp_cap)
        return obs.finish(res, levels=getattr(self, "level_sizes", None))

    def _final_gauges(self, res, obs, fp_count, table, fp_cap):
        """The run's last counters and gauges (span
        ``tpuvsr.engine.finish``); `table_stats` runs on the device."""
        res.distinct_states = fp_count
        self.model.gauges(obs, res.states_generated, fp_count,
                          (self._por_kept, self._por_full, self._por_amp))
        if self._canon_counts:
            lanes_c, moved_c = (int(x) for x in self._canon_cn)
            obs.count("canon_lanes", lanes_c)
            obs.count("canon_relabelled", moved_c)
        if self._stat_fn:
            for (name, how), value in zip(self.kern.COMMIT_STATS,
                                          self._stat_cn):
                (obs.count if how == "sum" else obs.gauge)(name,
                                                           int(value))
        if fp_cap:
            obs.gauge("fpset_capacity", int(fp_cap))
            obs.gauge("fpset_occupancy", fp_count / fp_cap)
        acts = getattr(self, "_act_counts", None)
        if acts is not None:
            # per-action expansion counters from the on-device
            # accumulator (ISSUE 4 satellite); sums to generated minus
            # the init states on a clean run
            obs.gauge("action_expansions",
                      {n: int(c) for n, c in
                       zip(self.kern.action_names, acts)})
        # occupancy = real work items / expand lanes the device ran
        # (fused: the blocks of stage 2 that held an enabled lane, of
        # the blocks the caps hold)
        lanes = getattr(self, "_lanes_disp", 0)
        if lanes and acts is not None:
            obs.gauge("occupancy",
                      round(float(acts.sum()) / lanes, 4))
        if self.commit == "fused" and acts is not None:
            obs.count("expand_blocks_run", int(self._blocks_act.sum()))
            obs.count("expand_blocks_cap", self._blocks_cap)
            # and what stage 3 walked of the queue the caps allow
            obs.count("commit_lanes_run", self._commit_run)
            obs.count("commit_lanes_cap", self._commit_cap)
            if self._commit_run:
                obs.gauge("commit_occupancy", round(
                    float(acts.sum()) / self._commit_run, 4))
        obs.gauge("commit_mode", self.commit)
        if table is not None and obs.detailed:
            from .fpset import table_stats
            st = table_stats(table["slots"])
            obs.gauge("fpset_occupancy", st["occupancy"])
            obs.gauge("fpset_collision_rate", st["collision_rate"])

    def _trace(self, gid, extra=None):
        """The counterexample that ends at `gid` (and one step
        `extra` past it), replayed from the host pointer table."""
        self._flush_pointers()
        return self.model.trace(
            (np.concatenate(self._h_parent),
             np.concatenate(self._h_action),
             np.concatenate(self._h_param)),
            self._init_states, gid, extra)


def device_bfs_check(spec: SpecModel, max_states=None, max_depth=None,
                     check_deadlock=False, tile_size=128, max_msgs=None,
                     log=None, obs=None) -> CheckResult:
    """Run the device BFS (message-table growth happens in place)."""
    eng = DeviceBFS(spec, max_msgs=max_msgs, tile_size=tile_size)
    return eng.run(max_states=max_states, max_depth=max_depth,
                   check_deadlock=check_deadlock, log=log, obs=obs)
