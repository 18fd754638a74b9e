"""Multi-chip BFS expansion: frontier + fingerprint set sharded over a
device mesh (SURVEY.md §5 "distributed communication backend";
BASELINE.json configs[4]).

Design (the TPU answer to TLC's shared-memory worker pool):

* the frontier is data-parallel over a 1-D mesh axis ``d`` — each device
  expands its own tile of states with the vmapped transition kernel;
* the fingerprint space is ownership-partitioned: fingerprint ``fp``
  belongs to device ``route(fp) % n_devices``;
* after local expansion + fingerprinting, successors' fingerprints are
  bucketed by owner and exchanged with a single ``all_to_all`` over ICI;
* each device dedups and inserts the fingerprints it owns into its local
  HBM FPSet shard (engine/fpset.py), so the global visited set is the
  disjoint union of shards and no two devices ever race on a slot.

The exchange uses fixed-capacity buckets (XLA needs static shapes); a
bucket overflow pauses the level so the host can grow the bucket and
re-enter.  The exchange ships whole dense states (plus 16-byte
fingerprint, 12-byte trace meta and, since ISSUE 55, the kernel's
``commit_stats`` words, which the owner sums over what it commits) to
their owner in ONE all_to_all —
chosen over a fps-only + verdict-round-trip design because owner-side
state residence is what keeps the frontier hash-balanced and the next
level's expansion collective-free; the measured cost is reported per
run as ``CheckResult.exchange`` (useful vs wire bytes — the wire moves
full ``D x bucket_cap`` buckets per tile regardless of occupancy).

Because wire volume is cap-bound, the bucket capacity is OCCUPANCY-
CALIBRATED by default (``bucket_cap=None``): start at a small cap and
let the existing overflow-pause-grow protocol converge it to the
run's real high-water bucket occupancy — r4 shipped 24x more bytes
than it used purely from a worst-case-sized static cap
(scripts/multihost.json; VERDICT r4 weak item 8).  Pass an explicit
``bucket_cap`` to pin it (pre-calibrated runs skip the growth
recompiles).  A fps-first exchange that ships only accepted states
would additionally cut the duplicate fraction at the price of a second
collective + owner-side re-materialization; revisit if ICI (not HBM)
ever profiles as the bottleneck.
"""

from __future__ import annotations

import time
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..engine import program_store
from ..engine.checked import CheckedModel, of_model
from ..engine.device_bfs import (Stage2, block_rows, grown_caps, slot_error,
                                 static_cap)
from ..engine.fpset import dedup_batch, insert_core
from ..obs import closes_observer, spans
from ..resilience.faults import InjectedExchangeDrop, fault_point
from ..resilience.supervisor import Preempted, preempt_signal
from .multihost import make_replicator, put_sharded

U32 = jnp.uint32


def _shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` without the replication/varying-axes check
    (the step's collectives are hand-placed)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def route(fps):
    """Owner of each fingerprint ([.., 4] uint32 -> [..] uint32).  Uses a
    mixed word decorrelated from both the FPSet claim tag (word 0) and
    the slot hash so shard choice doesn't bias probe chains."""
    return (fps[..., 1] * jnp.uint32(0x9E3779B9)) ^ (fps[..., 3] >> 7)


# ======================================================================
# Elastic resharding (ISSUE 5): host-side re-hash-partitioning of a
# snapshot's FPSet shards + frontier onto a different mesh size
# ======================================================================

def pool_shard_fingerprints(slots):
    """All occupied (keyed) fingerprint rows of a stacked [N, cap, 5]
    sharded table, shard-major.  The stored rows are the canonical
    keyed encoding (fpset._keyed: word 0 remapped 0 -> 1); re-keying
    is idempotent and ``route`` reads words 1/3 which the keying never
    touches, so the rows re-insert and re-route exactly like the raw
    fingerprints they came from."""
    s = np.asarray(slots)
    occ = s[..., 0] != 0
    return s[occ][:, :4].astype(np.uint32)


def build_shard_tables(fps, owner, n_shards, cap_start):
    """Rebuild per-shard FPSet tables from pooled keyed fingerprint
    rows and their new ownership: returns (slots [n_shards, cap, 5],
    per-shard counts).  The capacity is shared across shards (the
    stacked array is one global [D, cap, 5]) and grows — power of two,
    load factor <= 1/4 up front — until every shard inserts without a
    probe overflow."""
    counts = np.bincount(owner, minlength=n_shards).astype(np.int64)
    cap = int(cap_start)
    while cap < 4 * max(1, int(counts.max(initial=0))):
        cap *= 2
    chunk = 1 << 14
    while True:
        out = np.zeros((n_shards, cap, 5), np.uint32)
        ok = True
        for d in range(n_shards):
            tab = {"slots": jnp.zeros((cap, 5), U32)}
            part = fps[owner == d]
            for off in range(0, part.shape[0], chunk):
                p = part[off:off + chunk]
                pad = np.zeros((chunk - p.shape[0], 4), np.uint32)
                batch = jnp.asarray(np.concatenate([p, pad]))
                m = jnp.asarray(np.arange(chunk) < p.shape[0])
                tab, _, ovf = insert_core(tab, batch, m)
                if bool(ovf):
                    ok = False
                    break
            if not ok:
                break
            out[d] = np.asarray(tab["slots"])
        if ok:
            return out, counts
        cap *= 2


def convert_sharded_snapshot(path, spec, log=None):
    """Rewrite an N-shard sharded snapshot at ``path`` into the
    single-device engine format IN PLACE: merge the FPSet shards into
    one table (re-inserting every occupied keyed row) and drop the
    sharded ``extra`` — the frontier/trace payloads are already
    global.  The supervisor's sharded -> paged fallback calls this so
    the final rung of the mesh degrade ladder keeps the run's
    progress.  ``expand_mults`` is written empty; the single-device
    engines keep their own defaults when a snapshot carries none.
    Returns True when a conversion happened (False: the snapshot was
    not written by the sharded engine)."""
    from ..engine.checkpoint import (load_checkpoint, save_checkpoint,
                                     spec_digest)
    digest = spec_digest(spec)
    ck = load_checkpoint(path, expect_digest=digest, log=log)
    ex = ck.get("extra") or {}
    if not ex.get("sharded"):
        return False
    fps = pool_shard_fingerprints(ck["slots"])
    merged, _ = build_shard_tables(
        fps, np.zeros(fps.shape[0], np.int64), 1,
        int(np.asarray(ck["slots"]).shape[1]))
    if log:
        log(f"converted sharded snapshot {path} "
            f"({np.asarray(ck['slots']).shape[0]} shards, "
            f"{fps.shape[0]} fingerprints) to single-device format")
    save_checkpoint(
        path, slots=merged[0], frontier=ck["frontier"],
        n_front=ck["n_front"], h_parent=ck["h_parent"],
        h_action=ck["h_action"], h_param=ck["h_param"],
        init_dense=ck["init_dense"], level_sizes=ck["level_sizes"],
        depth=ck["depth"], fp_count=ck["fp_count"],
        states_generated=ck["states_generated"],
        max_msgs=ck["max_msgs"], expand_mults=[],
        elapsed=ck["elapsed"], digest=digest,
        # the identity manifests ride the conversion unchanged: the
        # merged fingerprints are still canon/bounds-dependent, and
        # the resuming engine's policy checks compare against them
        pack=ck.get("pack"), canon=ck.get("canon"),
        bounds=ck.get("bounds"), por=ck.get("por"), extra=None)
    return True


# ======================================================================
# Multi-chip BFS driver: sharded frontier run to fixpoint
# ======================================================================
#
# The full distributed BFS loop (SURVEY.md §5 "distributed communication
# backend"; BASELINE.json configs[4]).  The driver routes each fresh
# successor STATE to the device that owns its fingerprint, in the same
# single all_to_all as the fingerprint itself:
#
#   * the frontier is hash-partitioned: state S lives on device
#     route(fp(S)) % D — so load stays balanced for free and dedup,
#     storage, and the next level's expansion of S are all owner-local;
#   * per tile: expand all lanes -> fingerprint -> invariant -> local
#     dedup -> bucket (state + parent gid + action + param) by owner ->
#     ONE all_to_all -> owner inserts into its FPSet shard and scatters
#     the fresh rows straight into its next-frontier buffer;
#   * abort protocol: a tile commits nothing unless every device agrees
#     — sender-side flags (violation, bag overflow, layout slot error,
#     bucket overflow) are psum'd BEFORE the exchange, receiver-side
#     capacity (next-buffer headroom) is psum'd AFTER the exchange but
#     before any insert; on abort the level pauses with a reason code,
#     the host grows the relevant structure and re-enters the tile.
#     Within a committed tile, insert and scatter are atomic per lane
#     (claim-based insert: the lane that wins the slot is the one whose
#     row is scattered), so re-entry after an in-insert FPSet probe
#     overflow loses nothing: winners dedup on re-run, losers get a
#     bigger table.
#
# Trace pointers (parent gid, action, lane param) ride with the state
# rows; the host keeps only those per level (10 B/state) and
# `CheckedModel.trace` replays the recorded action chain.

RUNNING = 0
R_VIOLATION = 2
R_BAG_GROW = 3
R_FPSET_GROW = 4
R_NEXT_GROW = 5
R_SLOT_ERR = 6
R_DEADLOCK = 7
R_BUCKET_GROW = 8
R_EXPAND_GROW = 9   # fused commit: per-action compaction cap overflow


def fused_caps(kern, tile, expand_caps):
    """The fused step's per-action compaction caps, in slots: what the
    engine asks for (`expand_caps`), at least 8 and at most every lane
    of a tile."""
    return [min(tile * kern._lane_count(n), max(8, int(c)))
            for n, c in zip(kern.action_names, expand_caps)]


# The step's arguments that its jit donates: the FPSet shards + the
# next-frontier buffer set (args 0, 4-7).  The K-deep dispatch window
# chains each step on the previous one's outputs, so donation means the
# window holds ONE generation of the capacity-bound buffers instead of
# K (ISSUE 9 — the lever that makes pipeline=2 the sharded default).
# The frontier (1) and base_gid (9) are re-read by every dispatch of
# the level's chain and must NOT be donated.
STEP_DONATES = (0, 4, 5, 6, 7)


def make_sharded_level(*args, **kwargs):
    """The jitted one-tile sharded BFS step (`sharded_level`, which
    documents it, under ``jax.jit`` with the step's donations)."""
    return jax.jit(sharded_level(*args, **kwargs),
                   donate_argnums=STEP_DONATES)


def sharded_level(kern, inv_fn, mesh: Mesh, axis: str,
                  tile: int, bucket_cap: int,
                  check_deadlock: bool = False, pack_spec=None,
                  commit: str = "fused", expand_caps=None,
                  canon=None, por=None, stage2=None):
    """Build the one-tile sharded BFS step, mapped over the mesh and
    not yet jitted (`make_sharded_level`; `ShardedBFS` hands it to the
    store of traced programs).

    step(tables, frontier, n_front, start_t, nb, nbp, nba, nbprm, nn,
         base_gid)
      -> (tables, nb, nbp, nba, nbprm, nn, t, reason, viol, gen, sent,
          dead, act, need, gfull, amp, blk[, cs])
    Every array is sharded over `axis`; scalars come back as [D] arrays
    (one per device; identical where globally agreed).  With
    ``check_deadlock`` a frontier state with no enabled successor
    pauses the level with R_DEADLOCK and its device-local row index in
    the `dead` output (-1 on devices without a witness).

    With a ``pack_spec`` (engine/pack.PackSpec, ISSUE 9) the frontier
    and next-frontier are ``[D*cap, words]`` uint32 planes and — the
    lever that matters here — the all_to_all ships PACKED rows: the
    tile is unpacked on entry, successors are packed once right after
    expansion, and the exchange buckets/receive buffers/next frontier
    all carry the packed row, cutting wire and at-rest bytes by the
    pack ratio (~11x on the defect layout).  Receivers never unpack:
    dedup/insert work on the fingerprints that ride alongside.

    Its jit DONATES the FPSet shards and the next-frontier buffer set
    (`STEP_DONATES`, the ISSUE 9 donation lever): each dispatch
    consumes the previous one's buffers instead of holding K
    generations of them in HBM, which is what lets ``pipeline=2`` be
    the sharded default.  The read-only frontier and base_gid are NOT
    donated (the level's dispatch chain re-reads them).

    Fused commit (ISSUE 10): with ``commit="fused"`` the per-tile
    expansion is guard-compacted — a guard matrix over every lane of
    the tile picks the enabled (state, lane) items, which are packed
    into dense per-action segments sized by ``expand_caps``, and ONLY
    the blocks of a segment that hold an enabled lane are expanded,
    fingerprinted, invariant-checked and packed, into one dense queue:
    ``stage2``, the one-chip level program's own stage 2
    (engine/device_bfs.Stage2) since ISSUE 50.  (``step_all`` expanded
    all T x L lanes, mostly disabled padding, and until ISSUE 50 this
    step every slot of every cap.)  ``blk`` counts the blocks each
    action ran.  A per-action cap overflow is a
    new rank-agreed R_EXPAND_GROW pause carrying the exact per-action
    ``need`` so the host grows once to the true count.  The dedup that
    feeds the exchange tie-breaks on the canonical state-major flat
    index, so bucket contents — and every downstream result — are
    bit-identical to ``commit="per-action"`` (the step_all path).

    Ample-set partial-order reduction (ISSUE 16): with a ``por``
    filter (engine/por.PORFilter built with ``sharded=True``) the
    fused stage 1 masks the guard segments BEFORE compaction — on
    frontier states where a conflict-free candidate action exists,
    only that action's lanes enter the work queue.  Pre-expansion
    masking is what the owner-partitioned FPSet forces: successor
    freshness cannot be probed locally (the fingerprints live on
    other shards), so the C3 no-ignoring proviso is fully static —
    the filter only admits actions carrying a monotone progress
    witness (see engine/por.py).  Deadlock detection reads the
    UNMASKED guard matrix; the reduction is weaker than the
    single-device engines' level-marker proviso but deterministic
    and collective-free.  ``gfull``/``amp`` carry the unreduced
    generated count and the shortcut-state tally (equal to ``gen`` /
    zero when POR is off).

    What the kernel asks to have counted over the states a run commits
    (``commit_stats``, ISSUE 55): where `stage2` carries the kernel's
    hook, a successor's stat vector rides in its bucket beside the row
    (``n_stat`` more words on the wire) and the OWNER reduces it, sum
    or maximum by ``kern.COMMIT_STATS``, over the rows its insert
    finds fresh: a state is counted once, by the shard that commits
    it, whichever shards generated it.  ``cs`` is each shard's vector
    for the dispatch; a kernel without the hook has neither the plane
    nor the output, and the step it had."""
    n_dev = mesh.shape[axis]
    L = kern.n_lanes
    T = tile
    # symmetry canonicalization (ISSUE 11): fingerprints are taken on
    # the orbit-least image BEFORE ownership bucketing, so orbit-mates
    # hash — and therefore route — to the same shard and dedup there;
    # the exchanged STATE stays the generated representative
    fpf = (canon.fingerprint_fn(kern) if canon is not None
           else kern.fingerprint)
    n_act = len(kern.action_names)
    lane_aid = jnp.asarray(kern.lane_action)
    lane_prm = jnp.asarray(kern.lane_param)
    from ..models.vsr import ERR_BAG_OVERFLOW
    fused = commit == "fused"
    if fused:
        lane_counts = [kern._lane_count(n) for n in kern.action_names]
        seg_off = np.concatenate(
            [[0], np.cumsum(lane_counts)[:-1]]).astype(np.int32)
        caps = fused_caps(kern, T, expand_caps or [T] * n_act)
        E_tot = sum(caps)
        caps_v = jnp.asarray(caps, jnp.int32)
        seg_off_v = jnp.asarray(seg_off)
        guards = kern._guard_fns()
    stats = fused and stage2.stat_fn is not None
    if stats:
        # which entries of the stat vector add up (the others: maxima)
        stat_sums = jnp.asarray([how == "sum"
                                 for _n, how in kern.COMMIT_STATS])
    por_amat = (jnp.asarray(por.amat) if por is not None else None)

    def step_shard(tables, frontier, n_front, start_t,
                   nb, nbp, nba, nbprm, nn0, base_gid):
        tables = {k: v[0] for k, v in tables.items()}
        N = nbp.shape[0]
        n_loc = n_front[0]
        n_max = jax.lax.pmax(n_loc, axis)
        n_tiles = (n_max + T - 1) // T
        if fused:
            # stage 2: the queue is as wide as the caps, and everything
            # after it reads the queue.  Its block stages are traced
            # HERE, under the mesh axis the tile loop runs under (a
            # trace made outside `shard_map` is not found again inside
            # it) and outside the loops
            expand_blocks = stage2.tile_pass(caps, E_tot, like=n_loc)

        def cond(c):
            return (c["t"] < n_tiles) & (c["reason"] == RUNNING)

        def body(c):
            slots = c["slots"]
            nb, nbp, nba, nbprm = c["nb"], c["nbp"], c["nba"], c["nbprm"]
            nn = c["nn"]
            t = c["t"]
            base = t * T
            sidx = base + jnp.arange(T, dtype=jnp.int32)
            valid = sidx < n_loc
            with jax.named_scope(spans.PACK_SCATTER):
                if pack_spec is not None:
                    tile_st = jax.vmap(pack_spec.unpack)(
                        frontier[jnp.clip(sidx, 0, frontier.shape[0] - 1)])
                else:
                    tile_st = {k: v[jnp.clip(sidx, 0, v.shape[0] - 1)]
                               for k, v in frontier.items()}
            if fused:
                # -- stage 1 (ISSUE 10): guard matrix, exact counts --
                with jax.named_scope(spans.GUARD_MATRIX):
                    en_segs = []
                    for name, guard in zip(kern.action_names, guards):
                        lanes = jnp.arange(kern._lane_count(name),
                                           dtype=jnp.int32)
                        seg = jax.vmap(lambda st: jax.vmap(
                            lambda ln, g=guard: g(st, ln))(lanes))(tile_st)
                        en_segs.append(seg & valid[:, None])
                    # deadlock witness from the UNMASKED matrix (POR must
                    # not manufacture deadlocks), before any ample masking
                    en_state = jnp.zeros((T,), bool)
                    for e in en_segs:
                        en_state = en_state | e.any(axis=1)
                    if por_amat is not None:
                        # ample-set stage-1 masking (ISSUE 16): rows with
                        # a conflict-free candidate keep ONLY that
                        # action's lanes; everything downstream (counts,
                        # caps, compaction, exchange) sees the reduced
                        # queue.  aid_star = lowest candidate id — a
                        # deterministic pick keeps runs reproducible
                        en_act_m = jnp.stack(
                            [e.any(axis=1) for e in en_segs], axis=1)
                        n_full = jnp.stack(
                            [e.sum(dtype=jnp.int32)
                             for e in en_segs]).sum()
                        conflict = (en_act_m.astype(jnp.int32)
                                    @ (~por_amat).astype(jnp.int32).T) > 0
                        cand_m = en_act_m & ~conflict
                        has_cand = cand_m.any(axis=1)
                        aid_star = jnp.argmax(cand_m, axis=1
                                              ).astype(jnp.int32)
                        en_segs = [e & (~has_cand
                                        | (aid_star == a))[:, None]
                                   for a, e in enumerate(en_segs)]
                        amp_t = (has_cand
                                 & (en_act_m.sum(axis=1, dtype=jnp.int32)
                                    > 1)).sum(dtype=jnp.int32)
                    cnts = jnp.stack([e.sum(dtype=jnp.int32)
                                      for e in en_segs])
                    n_en = cnts.sum()
                    act_seg = cnts.astype(U32)
                    ovf_vec = cnts > caps_v
                    ovf_e = ovf_vec.any()
                    need = jnp.maximum(c["need"], cnts.astype(U32))

                # -- stage 2: the blocks of each action's segment that
                # hold an enabled lane, into one dense queue
                # (Stage2.tile_pass); each action's verdicts are folded
                # as its blocks end.  The first violating lane is the
                # one at the least CANONICAL state-major position: the
                # dense [T, L] index the item would occupy in the
                # step_all path — the dedup tie-break and all trace
                # metadata derive from it, which is what keeps
                # compacted results bit-identical
                no_pos = jnp.int32(2**31 - 1)
                vpos = no_pos
                bag_err = slot_err = jnp.asarray(False)

                def fold(aid, seg):
                    nonlocal vpos, bag_err, slot_err
                    pidx, lane_loc, sel_ok, en2, iok_a, err_a = seg
                    with jax.named_scope(spans.INVARIANTS):
                        en_a = en2 & sel_ok
                        err_a = jnp.where(en_a, err_a, 0)
                        viol_a = en_a & ~iok_a & (err_a == 0)
                        pos_a = pidx * L + int(seg_off[aid]) + lane_loc
                        vpos = jnp.minimum(
                            vpos, jnp.where(viol_a, pos_a, no_pos).min())
                        bag_err = bag_err | (
                            (err_a & ERR_BAG_OVERFLOW) != 0).any()
                        slot_err = slot_err | (
                            (err_a & ~ERR_BAG_OVERFLOW) != 0).any()

                queue, _q_end, blk_segs = expand_blocks(
                    tile_st, en_segs, cnts, fold)
                viol_any = vpos < no_pos
                blk_t = jnp.stack(blk_segs).astype(U32)
                with jax.named_scope(spans.COMPACT):
                    flat_src = (queue["rows"] if pack_spec is None
                                else {"rows": queue["rows"]})
                    if stats:
                        flat_src = dict(flat_src, stat=queue["stat"])
                    fps, en_f = queue["fp"], queue["en"]
                    flatpos = (queue["pidx"] * L + seg_off_v[queue["aid"]]
                               + queue["lane"])
            else:
                with jax.named_scope(spans.EXPAND):
                    succs, en = jax.vmap(kern.step_all)(tile_st)
                en = en & valid[:, None]
                en_state = en.any(axis=1)
                flat = {k: v.reshape((T * L,) + v.shape[2:])
                        for k, v in succs.items()}
                en_f = en.reshape(-1)
                n_en = en_f.sum()
                act_seg = jax.ops.segment_sum(
                    en_f.astype(U32), jnp.tile(lane_aid, T),
                    num_segments=n_act)
                ovf_e = jnp.asarray(False)
                need = c["need"]
                blk_t = jnp.zeros((n_act,), U32)
                flatpos = jnp.arange(T * L, dtype=jnp.int32)
                # pack successors ONCE, right after expansion: the
                # buckets, the wire, and the next frontier all move
                # the packed row from here on
                flat_src = (flat if pack_spec is None else
                            {"rows": jax.vmap(pack_spec.pack)(flat)})
                with jax.named_scope(spans.FINGERPRINT):
                    fps = jax.vmap(fpf)(flat)
                with jax.named_scope(spans.INVARIANTS):
                    iok = jax.vmap(inv_fn)(flat)
                errv = jnp.where(en_f, flat["err"], 0)
                viol_l = en_f & ~iok & (errv == 0)
                bag_err = ((errv & ERR_BAG_OVERFLOW) != 0).any()
                slot_err = ((errv & ~ERR_BAG_OVERFLOW) != 0).any()
                # the dense flat order IS the canonical one
                viol_any = viol_l.any()
                vpos = jnp.argmax(viol_l).astype(jnp.int32)
            if por_amat is None:
                n_full = n_en
                amp_t = jnp.asarray(0, jnp.int32)

            # the first violating lane as (parent gid, action, param).
            # The lane tables (length L) are indexed by vpos % L — a
            # bare lane_aid[i] silently CLAMPS for i >= L and records
            # the wrong action/param in the trace metadata
            vinfo = jnp.stack([
                base_gid[0] + base + (vpos // L).astype(jnp.int32),
                lane_aid[vpos % L], lane_prm[vpos % L]])
            viol = jnp.where(viol_any & (c["viol"][0] < 0), vinfo,
                             c["viol"])

            # local dedup, ownership bucketing (state + meta ride
            # along).  The tie key makes the winner among equal
            # fingerprints the canonically-first item, so the fused
            # (compacted) queue buckets exactly what the dense batch
            # would
            perm, cand = dedup_batch(fps, en_f,
                                     tie=flatpos if fused else None)
            with jax.named_scope(spans.SHARD_BUCKET):
                fps_s = fps[perm]
                owner = (route(fps_s) % jnp.uint32(n_dev)).astype(jnp.int32)
                pos_s = flatpos[perm]
                meta_p = base_gid[0] + (pos_s // L).astype(jnp.int32) + base
                meta_a = lane_aid[pos_s % L]
                meta_m = lane_prm[pos_s % L]

                cap = bucket_cap
                b_fps = jnp.zeros((n_dev, cap, 4), U32)
                b_mask = jnp.zeros((n_dev, cap), bool)
                b_p = jnp.zeros((n_dev, cap), jnp.int32)
                b_a = jnp.zeros((n_dev, cap), jnp.int32)
                b_m = jnp.zeros((n_dev, cap), jnp.int32)
                b_st = {k: jnp.zeros((n_dev, cap) + v.shape[1:], v.dtype)
                        for k, v in flat_src.items()}
                ovf_b = jnp.asarray(False)
                for d in range(n_dev):
                    m = cand & (owner == d)
                    pos = jnp.cumsum(m) - 1
                    ovf_b = ovf_b | ((pos[-1] + 1 > cap) & m.any())
                    idx = jnp.where(m & (pos < cap), pos, cap)
                    b_fps = b_fps.at[d, idx].set(fps_s, mode="drop")
                    b_mask = b_mask.at[d, idx].set(m, mode="drop")
                    b_p = b_p.at[d, idx].set(meta_p, mode="drop")
                    b_a = b_a.at[d, idx].set(meta_a, mode="drop")
                    b_m = b_m.at[d, idx].set(meta_m, mode="drop")
                    for k in b_st:
                        b_st[k] = b_st[k].at[d, idx].set(
                            flat_src[k][perm], mode="drop")

            # deadlock: a valid frontier state with no enabled lane
            # (en_state comes from the guard matrix in fused commit,
            # from step_all's enabled matrix in per-action)
            dead_l = valid & ~en_state if check_deadlock else \
                jnp.zeros((T,), bool)
            dead_i = jnp.where(dead_l.any() & (c["dead"] < 0),
                               base + jnp.argmax(dead_l), c["dead"]
                               ).astype(jnp.int32)

            # global pre-exchange abort vote (ovf_e: a fused-commit
            # compaction cap overflowed — the staged queue is
            # truncated, so nothing may commit until the exact-need
            # growth recompiles)
            flags = jnp.stack([viol_any, bag_err, slot_err, ovf_b,
                               dead_l.any(), ovf_e]).astype(jnp.int32)
            gflags = jax.lax.psum(flags, axis) > 0
            abort_pre = gflags.any()

            # ONE exchange moves fingerprints + states + trace meta
            with jax.named_scope(spans.SHARD_ALL_TO_ALL):
                a2a = lambda x: jax.lax.all_to_all(x, axis, 0, 0, tiled=False)
                i_fps = a2a(b_fps).reshape(n_dev * cap, 4)
                i_mask = a2a(b_mask).reshape(n_dev * cap)
                i_p = a2a(b_p).reshape(n_dev * cap)
                i_a = a2a(b_a).reshape(n_dev * cap)
                i_m = a2a(b_m).reshape(n_dev * cap)
                i_st = {k: a2a(v).reshape((n_dev * cap,) + v.shape[2:])
                        for k, v in b_st.items()}
                if stats:
                    i_stat = i_st.pop("stat")   # [D*cap, n_stat]
                if pack_spec is not None:
                    i_st = i_st["rows"]     # [D*cap, words] packed rows

            # receiver-side capacity vote (cross-sender dedup can only
            # shrink the count, so this bound is safe)
            perm2, cand2 = dedup_batch(i_fps, i_mask)
            n_inc = cand2.sum()
            room = (N - nn) >= n_inc
            abort_room = jax.lax.psum(
                (~room).astype(jnp.int32), axis) > 0
            commit = ~abort_pre & ~abort_room

            # insert into the CARRIED table (c["slots"]), not the
            # step argument: the argument is constant across the tile
            # while_loop, so using it dropped every prior tile's
            # inserts — tile t+1 re-admitted tile t's successors and
            # any level needing >1 tile/device flooded the next
            # frontier with duplicates (caught by the multihost
            # depth-14 artifact: 518,843 "distinct" in a 43,941-state
            # space; scripts/bucket_repro.py pins the level-8 onset)
            new_tab, fresh, probe_ovf = insert_core(
                {"slots": slots}, i_fps[perm2], cand2 & commit)
            slots2 = new_tab["slots"]
            with jax.named_scope(spans.PACK_SCATTER):
                dest = jnp.where(fresh, nn + jnp.cumsum(fresh) - 1, N
                                 ).astype(jnp.int32)
                src = perm2
                if pack_spec is not None:
                    nb = nb.at[dest].set(i_st[src], mode="drop")
                else:
                    for k in nb:
                        nb[k] = nb[k].at[dest].set(i_st[k][src],
                                                   mode="drop")
                nbp = nbp.at[dest].set(i_p[src], mode="drop")
                nba = nba.at[dest].set(i_a[src], mode="drop")
                nbprm = nbprm.at[dest].set(i_m[src], mode="drop")
            n_fresh = fresh.sum()
            if stats:
                # over the states this shard commits, counted where
                # `nn` is: an insert persists across a pause, and so
                # does its count
                new = jnp.where(fresh[:, None], i_stat[perm2], 0)
                cs = jnp.where(stat_sums,
                               c["cs"] + new.sum(0, dtype=U32),
                               jnp.maximum(c["cs"], new.max(0)))

            # committed-but-unresolved probes pause the level for table
            # growth; resolved lanes landed atomically so re-entry of
            # the same tile only re-dedups them (nothing lost)
            g_povf = jax.lax.psum(
                (commit & probe_ovf).astype(jnp.int32), axis) > 0
            # failure-cause priority (ISSUE 10): violation > slot >
            # bag > expand-grow > bucket > deadlock > next; fpset
            # growth last.  Expand outranks bucket because a truncated
            # queue makes the bucket contents meaningless
            reason = jnp.where(
                gflags[0], R_VIOLATION,
                jnp.where(gflags[2], R_SLOT_ERR,
                          jnp.where(gflags[1], R_BAG_GROW,
                                    jnp.where(gflags[5], R_EXPAND_GROW,
                                    jnp.where(gflags[3], R_BUCKET_GROW,
                                              jnp.where(gflags[4],
                                                        R_DEADLOCK,
                                              jnp.where(abort_room,
                                                        R_NEXT_GROW,
                                                        RUNNING)))))))
            reason = jnp.where((reason == RUNNING) & g_povf,
                               R_FPSET_GROW, reason)
            carried = {
                "t": jnp.where(commit & ~g_povf, t + 1, t),
                "reason": jnp.where(c["reason"] == RUNNING, reason,
                                    c["reason"]),
                "viol": viol, "dead": dead_i, "need": need,
                "slots": slots2,
                "nb": nb, "nbp": nbp, "nba": nba, "nbprm": nbprm,
                "nn": nn + jnp.where(commit, n_fresh, 0),
                "gen": c["gen"] + jnp.where(commit & ~g_povf, n_en, 0),
                "act": c["act"] + jnp.where(commit & ~g_povf, act_seg,
                                            jnp.uint32(0)),
                # exchange-occupancy metric: useful bucket rows this
                # device shipped (the wire moves full static buckets)
                "sent": c["sent"] + jnp.where(
                    commit & ~g_povf, b_mask.sum().astype(jnp.int32), 0),
                # POR accounting (ISSUE 16): unreduced generated count
                # and shortcut-state tally; gfull == gen, amp == 0
                # when the filter is off/inert
                "gfull": c["gfull"] + jnp.where(commit & ~g_povf,
                                                n_full, 0),
                "amp": c["amp"] + jnp.where(commit & ~g_povf,
                                            amp_t, 0),
                # blocks of stage 2 this pass ran, committed or not
                # (the one-chip body's `blk`)
                "blk": c["blk"] + blk_t,
            }
            if stats:
                carried["cs"] = cs
            return carried

        init = {
            "t": start_t[0],
            "reason": jnp.asarray(RUNNING, jnp.int32),
            "viol": jnp.full((3,), -1, jnp.int32),
            "dead": jnp.asarray(-1, jnp.int32),
            "need": jnp.zeros((n_act,), jnp.uint32),
            "slots": tables["slots"],
            "nb": nb, "nbp": nbp, "nba": nba, "nbprm": nbprm,
            "nn": nn0[0],
            "gen": jnp.asarray(0, jnp.int32),
            "act": jnp.zeros((n_act,), jnp.uint32),
            "sent": jnp.asarray(0, jnp.int32),
            "gfull": jnp.asarray(0, jnp.int32),
            "amp": jnp.asarray(0, jnp.int32),
            "blk": jnp.zeros((n_act,), jnp.uint32),
        }
        if stats:
            init["cs"] = jnp.zeros((len(kern.COMMIT_STATS),), U32)
        out = jax.lax.while_loop(cond, body, init)
        one = lambda x: x[None]
        return ({"slots": out["slots"][None]},
                out["nb"], out["nbp"], out["nba"], out["nbprm"],
                one(out["nn"]), one(out["t"]), one(out["reason"]),
                out["viol"][None], one(out["gen"]), one(out["sent"]),
                one(out["dead"]), out["act"][None], out["need"][None],
                one(out["gfull"]), one(out["amp"]), out["blk"][None]) \
            + ((out["cs"][None],) if stats else ())

    sp = P(axis)
    return _shard_map(step_shard, mesh=mesh, in_specs=(sp,) * 10,
                      out_specs=(sp,) * (17 + stats))


class ShardedBFS:
    """Host driver: run the sharded level kernel to fixpoint.

    What is checked (codec, kernel, lever specs, snapshot manifests and
    their refusals, trace replay) is the one-chip engines' own
    `CheckedModel` (engine/checked.py).  The host loop is this
    engine's: every decision in it is rank-agreed (`agree`), its
    frontier is `[D, ...]`, its budget is tested between levels only;
    the frontier and the fingerprint set are hash-partitioned over the
    mesh axis and states migrate to their owner in the in-level
    all_to_all.

    The kernel's ``commit_stats`` are counted as the one-chip engines
    count them, under the same counter and gauge names, by the shard
    that owns each committed state (`sharded_level`);
    `_stat_shard[d]` is shard d's part of each."""

    # what a caller may have sized its capacities by, and names in
    # `requires`: the start of a run packs on the host the rows that
    # exist, never D x next_capacity (ISSUE 27; before it 131 s a
    # run() at 4 x 262,144 rows); a kernel's ``commit_stats`` are
    # counted by the shard that commits each state (ISSUE 55; before it
    # a run reported none of them)
    PROVIDES = frozenset({"start_packs_live_rows",
                          "commit_stats_at_owner"})

    # what is checked lives on `self.model` (engine/checked.py); the
    # names this file and the tests read it by
    codec, kern = of_model("codec"), of_model("kern")
    commit, _inv, _facts = (of_model("commit"), of_model("inv"),
                            of_model("facts"))
    _pk, _canon = of_model("pk"), of_model("canon")
    _por, _por_active = of_model("por"), of_model("por_active")

    def __init__(self, spec, mesh: Mesh, axis: str = "d", max_msgs=None,
                 tile=32, bucket_cap=None, next_capacity=1 << 12,
                 fpset_capacity=1 << 14, check_deadlock=False,
                 model_factory=None, pipeline=2, exchange_retries=5,
                 exchange_backoff=0.05, exchange_backoff_cap=2.0,
                 sleep=time.sleep, pack="auto", commit="fused",
                 symmetry="auto", bounds="auto", por="off", requires=()):
        from ..core.values import TLAError
        # refuse before anything is built: an engine without a
        # property the capacities were sized by would run, for minutes
        missing = sorted(set(requires) - self.PROVIDES)
        if missing:
            raise TLAError(f"this ShardedBFS does not provide {missing} "
                           f"(it provides {sorted(self.PROVIDES)})")
        # the spec and the five levers as a codec, a kernel and the
        # specs bound to them (engine/checked.py).  Edge emission
        # (ISSUE 15) is a single-device paged seam: the key is
        # journaled off.  The POR filter is the static one (the
        # monotone-witness C3 proviso the owner-partitioned FPSet
        # forces, see make_sharded_level), the canon spec runs inside
        # the step, pre-bucketing
        self.model = CheckedModel(
            spec, model_factory, pack=pack, commit=commit,
            symmetry=symmetry, bounds=bounds, por=por, sharded=True)
        self.spec = spec
        self.mesh = mesh
        self.axis = axis
        self.D = mesh.shape[axis]
        self.tile = tile
        self.expand_caps = None       # fused per-action caps (lanes)
        self._need_seen = None
        # bounded exponential-backoff budget for transient exchange
        # failures (ISSUE 5): a dropped exchange re-issues the level
        # step (lossless — committed lanes just dedup) up to
        # `exchange_retries` CONSECUTIVE times before the run fails
        # loudly; `sleep` is injectable so tests don't wait
        self.exchange_retries = int(exchange_retries)
        self.exchange_backoff = float(exchange_backoff)
        self.exchange_backoff_cap = float(exchange_backoff_cap)
        self._sleep = sleep
        # set by an elastic resume that re-hash-partitioned an N-shard
        # snapshot onto this mesh (None: no reshard happened)
        self.resharded_from = None
        # dispatch-window depth (ISSUE 4; 1 = synchronous).  Default 2
        # like the device/paged engines (ISSUE 9): the step's jit now
        # DONATES the FPSet shards and next-frontier buffers, so a
        # K-deep window holds ONE generation of the capacity-bound
        # buffers instead of K — the HBM cost that made K>1 opt-in is
        # gone.  Semantics are identical at every K
        # (tests/test_pipeline.py).
        self.pipe_window = max(1, int(pipeline))
        # bucket_cap=None: occupancy-calibrated — start minimal and let
        # R_BUCKET_GROW converge to the run's high-water mark (wire
        # volume is cap-bound; see module docstring)
        self.bucket_cap = bucket_cap if bucket_cap is not None \
            else max(64, tile)
        self.N = next_capacity          # per-device frontier capacity
        self.fp_cap = fpset_capacity    # per-device FPSet slots
        self._ckd = bool(check_deadlock)
        self._por_kept = self._por_full = self._por_amp = 0
        self._build(max_msgs)

    def _build(self, max_msgs):
        from ..models import registry
        registry.ensure_compile_cache()
        registry.ensure_debug_flags()
        self.model.build(max_msgs)
        if self.commit == "fused":
            names = self.kern.action_names
            tl = [self.tile * self.kern._lane_count(n) for n in names]
            if self.expand_caps is None:
                self.expand_caps = [static_cap(self.tile, t) for t in tl]
                # static fanout bounds seed the caps (ISSUE 13): zero
                # growth redraws on exact-bounds fixtures
                if self._facts is not None:
                    for a, n in enumerate(names):
                        fo = self._facts.fanout.get(n)
                        if fo:
                            self.expand_caps[a] = min(
                                tl[a], max(8, self.tile * fo))
            else:   # re-clamp after a MAX_MSGS rebuild (lanes grow)
                self.expand_caps = [min(t, max(8, int(c)))
                                    for t, c in zip(tl,
                                                    self.expand_caps)]
            if self._need_seen is None or \
                    len(self._need_seen) != len(names):
                self._need_seen = np.zeros(len(names), np.int64)
        # what the kernel asks to have counted over the states a run
        # commits (``commit_stats``), as `DeviceBFS._build` reads it: a
        # kernel without the hook, or one that returns None for its
        # shape, gets the step it always had
        self._stat_fn = (getattr(self.kern, "commit_stats", None)
                         if self.commit == "fused" else None)
        # which entries of the stat vector add up (the others: maxima)
        self._stat_sums = np.array(
            [how == "sum" for _n, how in self.kern.COMMIT_STATS]
            if self._stat_fn else [], bool)
        # stage 2 of the fused step is the one-chip level program's
        # (ISSUE 50), made per built kernel, so a grown cap or bucket
        # finds the block stages traced.  It hashes whole successors,
        # as the one-chip engines do at their defaults since ISSUE 52:
        # in blocks of 32 slots the incremental hash (the parent's
        # parts a tile, the touched rows a slot) read 0.053 s a chip
        # over the four-chip cell's slice where the full hash of every
        # cap slot had read 0.006, and the step with the full hash
        # commits 42,464 states/s on one chip where the incremental
        # one commits 36,069 (PERF.md, PR 50)
        self._stage2 = (Stage2(self.model, incremental=False,
                               stat_fn=self._stat_fn)
                        if self.commit == "fused" else None)
        self._make_step()
        # the start's two programs, built once per engine: a run()
        # of a built engine finds them compiled
        self._sharded_ins = make_sharded_insert(self.mesh, self.axis)
        self._fill_packed = make_packed_fill(self.mesh, self.axis)
        self._sh = NamedSharding(self.mesh, P(self.axis))
        self._rep_sh = NamedSharding(self.mesh, P())
        # ... and the two a level needs.  fill(shape, dtype): a zero
        # global array sharded over its rows, one program a shape and
        # dtype; each device fills its own piece, nothing comes from
        # the host.  And the one pull of a dispatch's control
        # scalars.  No jax.jit is created inside a run(): a new
        # function object misses JAX's in-process cache and compiles
        # with the chips idle
        self._zero_fill = jax.jit(jnp.zeros, static_argnums=(0, 1),
                                  out_shardings=self._sh)
        self._pack_scalars = jax.jit(pack_scalars)
        # multi-process: host pulls of globally-sharded arrays must
        # reshard to replicated first (parallel/multihost.py)
        self._pull = make_replicator(self.mesh)

    def _make_step(self):
        """The sharded step for the caps, the bucket and the levers as
        they stand (`_build`, and a growth of a cap or the bucket).
        It goes through the store of traced programs
        (engine/program_store.py, as `DeviceBFS._level` does): a
        process that finds this engine's step there does not trace and
        lower it again, which is most of a warm set-up (ISSUE 50: the
        step with stage 2 in blocks is 5.5 MB of lowered text where it
        was 2.2)."""
        def mapped():
            return sharded_level(
                self.kern, self._inv, self.mesh, self.axis, self.tile,
                self.bucket_cap, check_deadlock=self._ckd,
                pack_spec=self._pk, commit=self.commit,
                expand_caps=self.expand_caps, canon=self._canon,
                por=self._por if self._por_active else None,
                stage2=self._stage2)

        self._step = program_store.StoredProgram(
            mapped, "step", STEP_DONATES, self._step_key_doc())
        self._fresh_jit = True   # first dispatch after a (re)jit is
        #                          charged to the "compile" phase

    def _step_key_doc(self):
        """Everything the trace of `sharded_level` reads of this
        engine, for the store's key (the arguments' types, where the
        capacities live, come with the call), or None where the store
        is not to be used: a kernel, codec or engine class the
        package's source does not determine, or a mesh of several
        processes (each would export its own view of one program)."""
        import sys

        from ..engine import device_bfs, fpset
        from ..engine.checkpoint import spec_digest
        if jax.process_count() > 1:
            return None
        spec, model = self.spec, self.model
        try:
            return program_store.describe({
                "engine": type(self),
                "spec": spec_digest(spec), "module": spec.module,
                "kernel": self.kern, "codec": self.codec,
                "pruned": model.pruned,
                "mesh": [self.axis, list(self.mesh.axis_names),
                         list(self.mesh.devices.shape)],
                "tile": self.tile, "bucket_cap": self.bucket_cap,
                "check_deadlock": self._ckd, "commit": self.commit,
                "expand_caps": self.expand_caps,
                "constants": program_store.module_constants(
                    sys.modules[__name__], device_bfs, fpset),
                "inv_names": model.inv_names,
                "pack": model.pack_manifest(),
                "canon": model.canon_manifest(),
                "bounds": model.bounds_manifest(),
                "por": [model.por_manifest(), self._por_active]})
        except program_store.Uncovered:
            return None

    def _trace(self, gid, extra=None):
        """The counterexample that ends at `gid` (and one step
        `extra` past it), replayed from the host pointer table (the
        pointer pulls are synchronous: nothing to flush)."""
        return self.model.trace(
            (np.concatenate(self._h_parent),
             np.concatenate(self._h_action),
             np.concatenate(self._h_param)),
            self._init_states, gid, extra)

    def _put(self, arr, obs=None):
        """Host array -> sharded global array.  With `obs` (the puts a
        level starts with) its bytes count as ``boundary_put_bytes``."""
        if obs is not None:
            obs.count("boundary_put_bytes", arr.nbytes)
        return put_sharded(arr, self._sh)

    def _rep(self, arr):
        """Host value (identical on all processes) -> replicated
        global array (a P() input of the sharded kernels)."""
        return put_sharded(arr, self._rep_sh)

    def _zeros(self, shape, dtype, obs):
        """A zero-filled global array, row-sharded like every buffer
        of the step, filled on the device (every process of a
        multi-process mesh calls the same program; the host makes and
        moves nothing).  Its bytes count as ``boundary_fill_bytes``."""
        arr = self._zero_fill(tuple(shape), np.dtype(dtype))
        obs.count("boundary_fill_bytes", arr.nbytes)
        return arr

    def _alloc_frontier(self, cap, obs):
        """A level's next buffers: zeros, made on the device."""
        D = self.D
        if self._pk is not None:
            # packed at-rest frontier (ISSUE 9): [D*cap, words] uint32
            # planes — the exchange and the next frontier move packed
            # rows, so this buffer IS the interchange format
            nb = self._zeros((D * cap, self._pk.words), np.uint32, obs)
        else:
            zero = self.codec.zero_state()
            nb = {k: self._zeros((D * cap,) + np.shape(v), np.int32, obs)
                  for k, v in zero.items()}
        z = lambda: self._zeros((D * cap,), np.int32, obs)
        return nb, z(), z(), z()

    def _start_frontier(self, rows, counts0, obs):
        """The run's first frontier as a global array: the dense batch
        `rows` (shard-major: `counts0[d]` rows for each shard d) at the
        head of each shard's `self.N` rows, the zero state behind them.
        With packing on the host packs the rows that exist and every
        shard pads its own piece on the device (`_fill_packed`): no
        D x N array on the host, at any capacity."""
        D, F = self.D, self.N
        counts0 = [int(c) for c in counts0]
        starts = np.concatenate([[0], np.cumsum(counts0)])

        def heads(plane, batch):
            # plane[d, :counts0[d]] = shard d's rows of the batch
            for d, c in enumerate(counts0):
                plane[d, :c] = batch[starts[d]:starts[d] + c]
            return self._put(plane.reshape((-1,) + plane.shape[2:]))

        if self._pk is not None:
            obs.count("init_packed_rows", int(starts[-1]))
            zero_row = self._pk.zero_row
            head = np.empty((D, max(counts0 + [1]), zero_row.size),
                            np.uint32)
            head[:] = zero_row
            return self._fill_packed(
                self._rep(zero_row), heads(head, self._pk.pack_np(rows)),
                F)
        # dense planes are built host-side and put once: pulling a
        # freshly-allocated GLOBAL array is illegal in multi-process mode
        return {k: heads(np.zeros((D, F) + np.shape(v), np.int32), rows[k])
                for k, v in self.codec.zero_state().items()}

    def _pull_rows(self, garr, counts, obs=None):
        """Gather per-device live rows of a [D*cap, ...] global array:
        the whole array comes to the host.  With `obs` (a level's
        pointer planes) its bytes count as ``boundary_pull_bytes``."""
        cap = garr.shape[0] // self.D
        host = self._pull(garr)
        if obs is not None:
            obs.count("boundary_pull_bytes", host.nbytes)
        return np.concatenate(
            [host[d * cap:d * cap + int(counts[d])]
             for d in range(self.D)], axis=0)

    def _grow_global(self, garr, old_cap, new_cap):
        host = self._pull(garr)
        D = self.D
        host = host.reshape((D, old_cap) + host.shape[1:])
        pad = np.zeros((D, new_cap - old_cap) + host.shape[2:],
                       host.dtype)
        out = np.concatenate([host, pad], axis=1)
        return self._put(out.reshape((D * new_cap,) + host.shape[2:]))

    @closes_observer
    def run(self, max_depth=None, max_states=None, max_seconds=None,
            log=None, check_deadlock=None, checkpoint_path=None,
            checkpoint_every=None, resume_from=None,
            progress_every=10.0, obs=None) -> "CheckResult":
        import time as _time
        from ..analysis import preflight
        from ..core.values import TLAError
        from ..engine.bfs import CheckResult
        from ..engine.fpset import grow as fp_grow
        from ..obs import RunObserver
        preflight(self.spec, log=log)   # fail fast, before any dispatch
        obs = RunObserver.ensure(obs, "sharded", self.spec, log=log,
                                 progress_every=progress_every)
        self.model.announce(obs, pipeline=self.pipe_window)
        self._obs_active = obs          # closes_observer finalizes it
        self._act_counts = np.zeros(len(self.kern.action_names),
                                    np.int64)
        self._tiles_done = 0
        self._lanes_disp = 0
        # fused commit: blocks of stage 2 the shards ran, by action,
        # of the blocks the caps of the committed tiles hold
        self._blocks_act = np.zeros(len(self.kern.action_names), np.int64)
        self._blocks_cap = 0
        # the kernel's commit stats, shard by shard ([D, n_stat])
        self._stat_shard = np.zeros((self.D, len(self._stat_sums)),
                                    np.int64)
        self._por_kept = self._por_full = self._por_amp = 0
        # multi-process: every rank collects, only host 0 writes the
        # journal / metrics file / stats table (per-shard numbers are
        # reduced host-side before they reach the collector)
        if jax.process_index() != 0:
            obs.primary = False
            obs.journal.close()     # write() no-ops once closed
        spec, codec = self.spec, self.codec
        D = self.D
        res = CheckResult()
        t0 = _time.time()
        obs.start(t0, backend=jax.default_backend(),
                  resumed=resume_from is not None)
        emit = obs.log
        # pipelined dispatch window (ISSUE 4): the sharded step is one
        # whole-level attempt, chained on its own outputs; the host
        # blocks only on the oldest in-flight step's reason.  Replays
        # behind a pause commit nothing (every sharded abort is a
        # pre-commit vote), so pipe.drain() discarding them keeps
        # counts/levels/traces identical to -pipeline 1.  Made as the
        # run starts: its unfed clock counts the set-up
        from ..engine.pipeline import DispatchPipeline
        pipe = DispatchPipeline(self.pipe_window, obs,
                                ready=lambda o: o[7])

        if check_deadlock is not None and bool(check_deadlock) != self._ckd:
            self._ckd = bool(check_deadlock)
            self._build(self.codec.shape.MAX_MSGS)
        # exchange metrics: useful rows shipped vs static wire volume
        # (all_to_all always moves full D x bucket_cap buckets).  Bytes
        # are accumulated with the row size current at the time (the
        # codec — and so the state row — grows on R_BAG_GROW)
        def _row_bytes():
            # state bytes as the wire actually moves them (the
            # exchange buckets carry packed rows where a pack spec is
            # bound) + fps/mask/meta + the kernel's stat words
            return (self.model.row_bytes() + 16 + 1 + 12
                    + 4 * len(self._stat_sums))
        exch_rows_useful = 0
        exch_rows_wire = 0
        exch_bytes_useful = 0
        exch_bytes_wire = 0
        # of the wire bytes, those that leave a chip: a sender's
        # buckets for the other D-1 owners, padding included
        exch_bytes_offchip = 0

        if resume_from is not None:
            # --- resume from a level-boundary snapshot ----------------
            from ..engine.checkpoint import load_checkpoint, spec_digest
            ck = load_checkpoint(resume_from,
                                 expect_digest=spec_digest(spec),
                                 log=emit)
            ex = ck["extra"] or {}
            if not ex.get("sharded"):
                raise TLAError("checkpoint was written by the "
                               "single-device engine; resume it there")
            # the per-shard counts drive the frontier re-scatter below:
            # verify them against the actual snapshot arrays so a
            # snapshot written under a different shard layout fails
            # here with a clear message instead of an index error
            _counts = [int(x) for x in ex["shard_counts"]]
            n_src = len(_counts)
            if min(_counts, default=0) < 0 or \
                    sum(_counts) != int(ck["n_front"]):
                raise TLAError(
                    f"checkpoint extra.shard_counts {_counts} (sum "
                    f"{sum(_counts)}) does not match the manifest "
                    f"frontier count {ck['n_front']}: snapshot was "
                    f"written under a different shard layout; "
                    f"refusing to resume")
            if len(ex.get("dev_distinct", [])) != n_src:
                raise TLAError(
                    f"checkpoint extra.dev_distinct has "
                    f"{len(ex.get('dev_distinct', []))} entries for "
                    f"{n_src} FPSet shards; refusing to resume")
            if ck["max_msgs"] != self.codec.shape.MAX_MSGS or \
                    ex["bucket_cap"] != self.bucket_cap:
                self.bucket_cap = int(ex["bucket_cap"])
                self._build(ck["max_msgs"])
            # AFTER the max_msgs rebuild (no POR level markers to
            # rebuild here: the sharded C3 proviso is fully static)
            self.model.check_manifests(ck, resume_from)
            rows = ck["frontier"]
            h_parent = np.asarray(ck["h_parent"])
            h_action = np.asarray(ck["h_action"])
            h_param = np.asarray(ck["h_param"])
            if n_src != D:
                # --- elastic resume: re-hash-partition N -> D ---------
                # (ISSUE 5 tentpole).  Every fingerprint and frontier
                # state migrates to route(fp) % D — the same ownership
                # rule the live exchange uses — so the resumed run is
                # indistinguishable from one that ran on this mesh all
                # along (modulo within-shard frontier order, which the
                # stable partition keeps in saved global order).
                fps_pool = pool_shard_fingerprints(ck["slots"])
                if fps_pool.shape[0] != int(ck["fp_count"]):
                    raise TLAError(
                        f"checkpoint FPSet shards hold "
                        f"{fps_pool.shape[0]} fingerprints, manifest "
                        f"says fp_count={ck['fp_count']}: snapshot "
                        f"is inconsistent; refusing to resume")
                owner = (np.asarray(route(jnp.asarray(fps_pool)))
                         % np.uint32(D)).astype(np.int64)
                slots, dev_distinct = build_shard_tables(
                    fps_pool, owner, D,
                    int(np.asarray(ck["slots"]).shape[1]))
                # frontier rows migrate to their new owner; the LAST
                # level's trace-pointer block permutes with them so
                # gid -> (parent, action, param) stays aligned (the
                # frontier IS the last level_sizes entry, saved in the
                # same global order as the trace tail)
                # canonical fingerprints (when symmetry is on) so the
                # re-route matches the live exchange's ownership rule
                ffps = np.asarray(self.model.fp_batch(
                    {k: np.asarray(v) for k, v in rows.items()}))
                fowner = (np.asarray(route(jnp.asarray(ffps)))
                          % np.uint32(D)).astype(np.int64)
                perm = np.argsort(fowner, kind="stable")
                rows = {k: np.asarray(v)[perm] for k, v in rows.items()}
                counts0 = np.bincount(fowner, minlength=D
                                      ).astype(np.int64)
                nf = int(ck["n_front"])
                if nf:
                    h_parent = np.concatenate(
                        [h_parent[:-nf], h_parent[-nf:][perm]])
                    h_action = np.concatenate(
                        [h_action[:-nf], h_action[-nf:][perm]])
                    h_param = np.concatenate(
                        [h_param[:-nf], h_param[-nf:][perm]])
                self.resharded_from = n_src
                obs.reshard(n_src, D, int(ck["fp_count"]))
                emit(f"resharded snapshot: {n_src} shards -> {D} "
                     f"devices ({fps_pool.shape[0]} fingerprints, "
                     f"{nf} frontier rows re-hash-partitioned)")
            else:
                slots = np.asarray(ck["slots"])
                counts0 = np.asarray(_counts, np.int64)
                dev_distinct = np.asarray(ex["dev_distinct"], np.int64)
            self.fp_cap = int(slots.shape[1])
            tables = {"slots": self._put(slots)}
            self.N = max(self.N, int(counts0.max(initial=0)))
            codec = self.codec
            self._init_states = [codec.decode(d)
                                 for d in ck["init_dense"]]
            self._h_parent = [h_parent]
            self._h_action = [h_action]
            self._h_param = [h_param]
            self.level_sizes = list(ck["level_sizes"])
            depth0 = ck["depth"]
            fp_count = ck["fp_count"]
            res.states_generated = ck["states_generated"]
            t0 -= ck["elapsed"]
            obs.set_epoch(t0)
            self._dev_distinct = dev_distinct
            xc = ex.get("exchange") or {}
            exch_rows_useful = xc.get("useful_rows", 0)
            exch_rows_wire = xc.get("wire_rows", 0)
            exch_bytes_useful = xc.get("useful_bytes", 0)
            exch_bytes_wire = xc.get("wire_bytes", 0)
            exch_bytes_offchip = xc.get("offchip_bytes", 0)
            F = self.N
            # snapshots load as dense planes (the engine-agnostic
            # interchange format); the start packs them when packing
            # is on
            with obs.span(spans.INIT), obs.part(spans.INIT_DEVICE):
                front = self._start_frontier(rows, counts0, obs)
                n_front = self._put(counts0.astype(np.int32))
            base_dev = (sum(self.level_sizes[:-1])
                        + np.concatenate([[0], np.cumsum(counts0)[:-1]]))
            emit(f"resumed from {resume_from}: depth {depth0}, "
                 f"{fp_count} distinct, frontier {int(counts0.sum())}")
        else:
            with obs.span(spans.INIT):
                # global FPSet: one independent shard per device,
                # stacked on the leading (sharded) axis
                with obs.part(spans.INIT_DEVICE):
                    tables = {"slots": self._zeros((D, self.fp_cap, 5),
                                                   np.uint32, obs)}

                # --- init states: dedup, assign to owner devices ----------
                with obs.part(spans.INIT_STATES):
                    init_states = list(spec.init_states())
                    dense = [codec.encode(st) for st in init_states]
                    batch = {k: np.stack([d[k] for d in dense])
                             for k in dense[0]}
                with obs.part(spans.INIT_FINGERPRINT):
                    fps = np.asarray(self.model.fp_batch(batch))
                with obs.part(spans.INIT_STATES):
                    keep, seen = [], set()
                    for i in range(len(dense)):
                        t = tuple(fps[i])
                        if t not in seen:
                            seen.add(t)
                            keep.append(i)
                with obs.part(spans.INIT_DEVICE):
                    owners = (np.asarray(route(jnp.asarray(fps[keep])))
                              % np.uint32(D)).astype(int)
                with obs.part(spans.INIT_STATES):
                    order = np.argsort(owners, kind="stable")
                    keep = [keep[i] for i in order]
                    owners = owners[order]
                    self._init_states = [init_states[i] for i in keep]
                    n0 = len(keep)
                    counts0 = np.bincount(owners, minlength=D)

                F = self.N
                self._dev_distinct = counts0.astype(np.int64).copy()
                # the fills, the puts and the insert; the overflow
                # flag's pull is the one wait on the device
                with obs.part(spans.INIT_DEVICE):
                    front = self._start_frontier(
                        {k: v[keep] for k, v in batch.items()}, counts0,
                        obs)
                    n_front = self._put(counts0.astype(np.int32))
                    tables, _fr, ovf = self._sharded_ins(
                        tables, self._rep(fps[keep]),
                        self._rep(np.ones((n0,), bool)))
                    assert not bool(self._pull(ovf).any())
            fp_count = n0

            self._h_parent = [np.full(n0, -1, np.int64)]
            self._h_action = [np.full(n0, -1, np.int32)]
            self._h_param = [np.zeros(n0, np.int32)]
            self.level_sizes = [n0]
            depth0 = 0
            base_dev = np.concatenate([[0], np.cumsum(counts0)[:-1]])
            for i, st in enumerate(self._init_states):
                bad = spec.check_invariants(st)
                if bad:
                    res.ok = False
                    res.violated_invariant = bad
                    res.trace = self._trace(i)
                    return self._finish(res, obs, fp_count)
            res.states_generated += len(dense)

        def _attach_exchange(r):
            r.exchange = {
                "row_bytes": _row_bytes(),
                "useful_rows": exch_rows_useful,
                "useful_bytes": exch_bytes_useful,
                "wire_rows": exch_rows_wire,
                "wire_bytes": exch_bytes_wire,
                "offchip_bytes": exch_bytes_offchip,
            }
            for k, v in r.exchange.items():
                obs.gauge(f"exchange_{k}", int(v))
            emit(f"exchange: {exch_rows_useful} useful rows "
                 f"({exch_bytes_useful / 1e6:.1f} MB) / "
                 f"{exch_rows_wire} wire rows "
                 f"({exch_bytes_wire / 1e6:.1f} MB)")

        depth = depth0
        last_checkpoint = _time.time()

        # multi-process SPMD discipline: any control decision based on
        # wall clocks must be rank-agreed, or ranks issue mismatched
        # collectives (rank 0 enters the checkpoint pull — a reshard
        # collective — while rank 1 proceeds to the next level's step).
        # Rank 0's verdict is broadcast; single-process it's a no-op.
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            def agree(flag):
                return bool(int(multihost_utils.broadcast_one_to_all(
                    np.int32(bool(flag)))))

            def agree_any(flag):
                # any-rank reduce (vs rank 0's verdict): an exchange
                # drop observed on ONE host must make EVERY host take
                # the retry branch, or the pack issues mismatched
                # collectives.  One int32 allgather per dispatch —
                # noise next to the step's own all_to_alls
                return bool(multihost_utils.process_allgather(
                    np.int32(bool(flag))).any())
        else:
            def agree(flag):
                return bool(flag)

            agree_any = bool

        def pull(o):
            # ONE replication pull for all per-dispatch control
            # scalars — separate _pull calls cost one collective (and
            # one device round-trip) EACH; pack [D] reason/sent/
            # gen/gfull/amp, the [D, A] act and blk counters and the
            # kernel's [D, n_stat] commit stats (o[17:], where the step
            # carries them) into a single [D, 5+2A+n_stat] array first
            packed = np.asarray(self._pull(
                self._pack_scalars(o[7], o[10], o[9], o[14], o[15],
                                   o[12], o[16], *o[17:])), np.int64)
            reason = int(packed[0, 0])
            sent = int(packed[:, 1].sum())
            gen = int(packed[:, 2].sum())
            gfull = int(packed[:, 3].sum())
            amp = int(packed[:, 4].sum())
            n_act = len(self._act_counts)
            act, blk = np.split(packed[:, 5:5 + 2 * n_act].sum(axis=0), 2)
            # each shard's own, uint32 on the device
            stat = packed[:, 5 + 2 * n_act:] & 0xFFFFFFFF
            return reason, sent, gen, gfull, amp, act, blk, stat

        # shard context for fault hooks: the HOST process in
        # multi-process runs; a single-process mesh drives every
        # shard, so any armed shard matches (shard=None)
        my_shard = (jax.process_index() if jax.process_count() > 1
                    else None)
        xretry = 0      # consecutive exchange-drop retries (bounded)

        # the host between two levels' device work (and before the
        # first): open from here, or from a level's end, to the launch
        obs.boundary(depth=depth)
        while True:
            with obs.span(spans.HOST_SYNC):
                front_total = int(self._pull(n_front).sum())
            if front_total <= 0:
                break
            if max_depth is not None and depth >= max_depth:
                res.error = f"depth limit {max_depth} reached"
                break
            depth += 1
            fault_point("level", depth=depth, shard=my_shard, obs=obs)
            nb, nbp, nba, nbprm = self._alloc_frontier(self.N, obs)
            nn = self._put(np.zeros(D, np.int32), obs)
            start_t = self._put(np.zeros(D, np.int32), obs)
            base_gid = self._put(base_dev.astype(np.int32), obs)
            while True:
                while pipe.has_room():
                    # transient exchange failure: bounded exponential-
                    # backoff retry loop (ISSUE 5; was a one-shot
                    # re-issue).  The pause/re-enter protocol makes
                    # every retry lossless — committed lanes just
                    # dedup — so the only budget is patience: after
                    # `exchange_retries` CONSECUTIVE drops the run
                    # fails loudly instead of spinning forever.  The
                    # retry branch is rank-agreed (any-rank reduce):
                    # a drop seen on one host process must send every
                    # process down the same branch
                    dropped = False
                    try:
                        fault_point("exchange", depth=depth,
                                    shard=my_shard, obs=obs)
                    except InjectedExchangeDrop:
                        dropped = True
                    if agree_any(dropped):
                        xretry += 1
                        if xretry > self.exchange_retries:
                            raise TLAError(
                                f"sharded exchange failed {xretry} "
                                f"consecutive times at level {depth} "
                                f"(retry budget "
                                f"{self.exchange_retries}); giving up")
                        from ..resilience.backoff import backoff_delay
                        backoff = backoff_delay(
                            xretry, self.exchange_backoff,
                            self.exchange_backoff_cap)
                        obs.retry(attempt=xretry, backoff_s=backoff,
                                  what="exchange")
                        emit(f"exchange drop at level {depth}: retry "
                             f"{xretry}/{self.exchange_retries} in "
                             f"{backoff:.2f}s")
                        if backoff > 0:
                            self._sleep(backoff)
                        continue
                    xretry = 0
                    out = pipe.launch(
                        self._step, tables, front, n_front, start_t,
                        nb, nbp, nba, nbprm, nn, base_gid,
                        fresh=self._fresh_jit,
                        depth=depth)
                    self._fresh_jit = False
                    (tables, nb, nbp, nba, nbprm, nn,
                     start_t) = out[:7]
                out, sc = pipe.collect(pull)
                (reason, sent, gen_add, gfull_add, amp_add, act_add,
                 blk_add, stat_add) = sc
                # counted on the device where a row lands, so a paused
                # attempt's commits count once, as `nn` does
                self._stat_shard = np.where(
                    self._stat_sums, self._stat_shard + stat_add,
                    np.maximum(self._stat_shard, stat_add))
                exch_rows_useful += sent
                exch_bytes_useful += sent * _row_bytes()
                # generated is accumulated per dispatch attempt (a
                # paused attempt's committed tiles count once; its
                # replays in the window are discarded by drain())
                res.states_generated += gen_add
                self._act_counts += act_add
                # the lanes stage 2 really expanded, paused attempts
                # included, under the caps this dispatch ran with
                self._blocks_act += blk_add
                if self.commit == "fused":
                    self._lanes_disp += sum(
                        int(n) * block_rows(cap)
                        for n, cap in zip(blk_add, self._caps()))
                if self._por_active:
                    self._por_kept += gen_add
                    self._por_full += gfull_add
                    self._por_amp += amp_add
                if reason == RUNNING:
                    pipe.drain()     # trailing tickets are no-ops
                    break
                pipe.drain()         # trailing tickets replay the pause
                if reason == R_VIOLATION:
                    viol_out = out[8]
                    vrows = self._pull(viol_out)
                    sel = vrows[vrows[:, 0] >= 0][0]
                    gid, va, vprm = (int(x) for x in sel)
                    res.ok = False
                    res.trace = self._trace(gid, extra=(va, vprm))
                    bad = spec.check_invariants(res.trace[-1].state)
                    if bad is None:
                        raise TLAError(
                            "device/interpreter divergence in sharded "
                            "BFS: interpreter accepts the replayed "
                            f"violation state (action "
                            f"{self.kern.action_names[va]})")
                    res.violated_invariant = bad
                    res.diameter = depth
                    _attach_exchange(res)
                    return self._finish(res, obs, fp_count)
                if reason == R_SLOT_ERR:
                    raise TLAError(slot_error(self.codec))
                if reason == R_DEADLOCK:
                    dd = self._pull(out[11])
                    d = int(np.nonzero(dd >= 0)[0][0])
                    di = int(dd[d])
                    gid = int(base_dev[d]) + di
                    res.ok = False
                    res.error = "deadlock"
                    res.deadlock_state = self.codec.decode(
                        self._pk.unpack_row_np(
                            self._pull(front[d * F + di]))
                        if self._pk is not None else
                        {k: self._pull(v[d * F + di])
                         for k, v in front.items()})
                    res.trace = self._trace(gid)
                    res.diameter = depth
                    _attach_exchange(res)
                    return self._finish(res, obs, fp_count)
                if reason == R_BAG_GROW:
                    old = self.codec.shape.MAX_MSGS
                    old_pk = self._pk
                    self._build(old * 2)

                    def regrow_packed(garr):
                        # packed buffers round-trip through the OLD
                        # spec to dense, pad, re-pack under the rebuilt
                        # one (MAX_MSGS changes the lane count AND the
                        # spec version; see DeviceBFS._grow_msgs)
                        host = old_pk.unpack_np(self._pull(garr))
                        host = self.codec.pad_msgs(host, old)
                        return self._put(self._pk.pack_np(host))

                    # pad the message-table axis of every state array
                    def pad_msgs_global(g_dict, cap):
                        host = {k: self._pull(v).reshape(
                            (D, cap) + v.shape[1:])
                            for k, v in g_dict.items()}
                        out = {}
                        for k, v in host.items():
                            if k in self.codec.MSG_KEYS:
                                shape = list(v.shape)
                                shape[2] = (self.codec.shape.MAX_MSGS
                                            - old)
                                v = np.concatenate(
                                    [v, np.zeros(shape, v.dtype)],
                                    axis=2)
                            out[k] = self._put(v.reshape(
                                (D * cap,) + v.shape[2:]))
                        return out
                    if old_pk is not None:
                        front = regrow_packed(front)
                        nb = regrow_packed(nb)
                    else:
                        front = pad_msgs_global(front, F)
                        nb = pad_msgs_global(nb, self.N)
                    obs.grow("message_table", self.codec.shape.MAX_MSGS)
                    emit(f"message table grown to "
                         f"{self.codec.shape.MAX_MSGS} (recompiling)")
                elif reason == R_BUCKET_GROW:
                    self.bucket_cap *= 2
                    self._make_step()
                    obs.grow("exchange_bucket", self.bucket_cap)
                    emit(f"exchange bucket grown to {self.bucket_cap} "
                         f"(recompiling)")
                elif reason == R_EXPAND_GROW:
                    # fused commit: re-cap every action from the
                    # rank-maxed exact observed need (ISSUE 10) with
                    # the shared headroom policy — one recompile
                    need = np.asarray(self._pull(out[13]),
                                      np.int64).max(axis=0)
                    self._need_seen = np.maximum(self._need_seen, need)
                    names = self.kern.action_names
                    new = grown_caps(
                        self.expand_caps, self._need_seen,
                        [self.tile * self.kern._lane_count(n)
                         for n in names])
                    grown = [(n, c) for n, c, old in
                             zip(names, new, self.expand_caps)
                             if c > old]
                    self.expand_caps = new
                    if not grown:   # defensive: strict growth anyway
                        a = int(np.argmax(need))
                        self.expand_caps[a] = min(
                            self.tile * self.kern._lane_count(
                                self.kern.action_names[a]),
                            self.expand_caps[a] * 2)
                        grown = [(self.kern.action_names[a],
                                  self.expand_caps[a])]
                    self._make_step()
                    for _n, cap in grown:
                        obs.grow("expand_buffer", cap)
                    emit("expand caps grown (headroom over the exact need): "
                         + ", ".join(f"{n}={c}" for n, c in grown)
                         + " (recompiling)")
                elif reason == R_NEXT_GROW:
                    new_n = self.N * 2
                    nb = (self._grow_global(nb, self.N, new_n)
                          if self._pk is not None else
                          {k: self._grow_global(v, self.N, new_n)
                           for k, v in nb.items()})
                    nbp = self._grow_global(nbp, self.N, new_n)
                    nba = self._grow_global(nba, self.N, new_n)
                    nbprm = self._grow_global(nbprm, self.N, new_n)
                    self.N = new_n
                    self._fresh_jit = True   # shape change: retrace
                    obs.grow("next_buffer", new_n)
                    emit(f"next-frontier grown to {new_n}/device")
                elif reason == R_FPSET_GROW:
                    slots = self._pull(tables["slots"])
                    grown = [fp_grow({"slots": jnp.asarray(slots[d])}
                                     )["slots"] for d in range(D)]
                    self.fp_cap = int(grown[0].shape[0])
                    tables = {"slots": self._put(np.stack(
                        [np.asarray(g) for g in grown]))}
                    self._fresh_jit = True   # shape change: retrace
                    obs.grow("fpset", self.fp_cap)
                    emit(f"FPSet shards grown to {self.fp_cap}/device")
                else:
                    raise TLAError(f"unknown sharded reason {reason}")

            # committed tiles this level x full static bucket volume
            # (generated was already accumulated per dispatch attempt)
            obs.boundary(depth=depth)
            with obs.span(spans.HOST_SYNC):
                tiles_lvl = int(self._pull(start_t).max())
                wire = tiles_lvl * D * D * self.bucket_cap
                exch_rows_wire += wire
                exch_bytes_wire += wire * _row_bytes()
                exch_bytes_offchip += (tiles_lvl * D * (D - 1)
                                       * self.bucket_cap * _row_bytes())
                nn_h = self._pull(nn)
            # occupancy accounting (ISSUE 10): the per-action step
            # expands every lane of every tile; the fused one only the
            # blocks it counted (above), of the blocks the caps in
            # effect at the level's end hold
            self._tiles_done += tiles_lvl * D
            if self.commit == "fused":
                self._blocks_cap += tiles_lvl * D * sum(
                    -(-cap // block_rows(cap)) for cap in self._caps())
            else:
                self._lanes_disp += (tiles_lvl * D * self.tile
                                     * self.kern.n_lanes)
            n_next = int(nn_h.sum())
            fp_count += n_next
            obs.level_done(depth, frontier=front_total,
                           distinct=fp_count,
                           generated=res.states_generated)
            if n_next:
                with obs.span(spans.HOST_SYNC):
                    self._h_parent.append(
                        self._pull_rows(nbp, nn_h, obs).astype(np.int64))
                    self._h_action.append(
                        self._pull_rows(nba, nn_h, obs))
                    self._h_param.append(
                        self._pull_rows(nbprm, nn_h, obs))
                self.level_sizes.append(n_next)
                self._dev_distinct += nn_h
            # gid bases of the new frontier (device-order concatenation)
            base_dev = (sum(self.level_sizes[:-1])
                        + np.concatenate([[0], np.cumsum(nn_h)[:-1]]))
            front = nb
            F = self.N
            n_front = nn

            # pending preemption (supervisor's PreemptionGuard) forces
            # a rescue snapshot at this boundary; the decision is
            # rank-agreed like every wall-clock one (n_next is a global
            # sum, so the agree() call pattern matches across ranks)
            rescue = preempt_signal()
            want_rescue = bool(n_next) and agree(rescue is not None)
            if checkpoint_path and n_next and (want_rescue or agree(
                    checkpoint_every is None or
                    _time.time() - last_checkpoint >= checkpoint_every)):
                from ..engine.checkpoint import (FORMAT_VERSION,
                                                 save_checkpoint,
                                                 spec_digest)
                with obs.span(spans.CHECKPOINT, depth=depth):
                    # the pulls are collectives in multi-process mode —
                    # every process participates; only rank 0 writes
                    with obs.part(spans.CHECKPOINT_PULL):
                        ck_slots = self._pull(tables["slots"])
                        # the packed rows as the shards hold them (the
                        # loader unpacks them: any engine/pack
                        # configuration resumes dense planes), or the
                        # dense planes of a run that does not pack
                        ck_front = (
                            {"frontier_packed":
                             self._pull_rows(front, nn_h)}
                            if self._pk is not None else
                            {"frontier": {k: self._pull_rows(v, nn_h)
                                          for k, v in front.items()}})
                    staged = 0      # only rank 0 writes
                    if jax.process_index() == 0:
                        staged = save_checkpoint(
                            checkpoint_path,
                            slots=ck_slots,
                            **ck_front,
                            n_front=n_next,
                            h_parent=np.concatenate(self._h_parent),
                            h_action=np.concatenate(self._h_action),
                            h_param=np.concatenate(self._h_param),
                            init_dense=[self.codec.encode(st)
                                        for st in self._init_states],
                            level_sizes=self.level_sizes, depth=depth,
                            fp_count=fp_count,
                            states_generated=res.states_generated,
                            max_msgs=self.codec.shape.MAX_MSGS,
                            expand_mults=[],
                            elapsed=_time.time() - t0,
                            digest=spec_digest(spec),
                            **self.model.manifests(), obs=obs,
                            extra={"sharded": True,
                                   "shard_counts": [int(x) for x in nn_h],
                                   "bucket_cap": self.bucket_cap,
                                   "fp_cap": self.fp_cap, "N": self.N,
                                   "dev_distinct": [int(x) for x in
                                                    self._dev_distinct],
                                   "exchange": {
                                       "useful_rows": exch_rows_useful,
                                       "wire_rows": exch_rows_wire,
                                       "useful_bytes": exch_bytes_useful,
                                       "wire_bytes": exch_bytes_wire,
                                       "offchip_bytes":
                                           exch_bytes_offchip}})
                last_checkpoint = _time.time()
                obs.checkpoint(checkpoint_path, depth, fp_count, staged,
                               FORMAT_VERSION)
                emit(f"checkpoint written to {checkpoint_path} "
                     f"(depth {depth}, {fp_count} distinct)")
            if want_rescue:
                sig = rescue or "SIGTERM"
                obs.rescue(checkpoint_path or "", depth, fp_count, sig)
                emit(f"preempted by {sig}: rescue snapshot at depth "
                     f"{depth} ({checkpoint_path}); exiting resumable")
                _attach_exchange(res)
                raise Preempted(checkpoint_path, depth, fp_count, sig)

            obs.progress(depth=depth, distinct=fp_count,
                         generated=res.states_generated)
            if max_seconds and agree(_time.time() - t0 > max_seconds):
                res.error = f"time budget {max_seconds}s reached"
                break
            if max_states and fp_count >= max_states:
                res.error = f"state limit {max_states} reached"
                break
            # proactive shard growth keeps in-level probe overflow rare
            if self._dev_distinct.max() > 0.4 * self.fp_cap:
                slots = self._pull(tables["slots"])
                grown = [fp_grow({"slots": jnp.asarray(slots[d])}
                                 )["slots"] for d in range(D)]
                self.fp_cap = int(grown[0].shape[0])
                tables = {"slots": self._put(np.stack(
                    [np.asarray(g) for g in grown]))}
                self._fresh_jit = True       # shape change: retrace
                obs.grow("fpset", self.fp_cap)
                emit(f"FPSet shards grown to {self.fp_cap}/device")

        res.diameter = depth
        _attach_exchange(res)
        return self._finish(res, obs, fp_count)

    def _finish(self, res, obs, fp_count):
        obs.end_boundary()
        with obs.span(spans.FINISH):
            self._final_gauges(res, obs, fp_count)
        return obs.finish(res,
                          levels=getattr(self, "level_sizes", None))

    def _final_gauges(self, res, obs, fp_count):
        res.distinct_states = fp_count
        self.model.gauges(obs, res.states_generated, fp_count,
                          (self._por_kept, self._por_full, self._por_amp))
        if self._stat_fn:
            # the shards' vectors reduced across the mesh, under the
            # names `DeviceBFS._final_gauges` writes
            total = np.where(self._stat_sums, self._stat_shard.sum(axis=0),
                             self._stat_shard.max(axis=0))
            for (name, how), value in zip(self.kern.COMMIT_STATS, total):
                (obs.count if how == "sum" else obs.gauge)(name,
                                                           int(value))
        cap_total = self.fp_cap * self.D
        obs.gauge("fpset_capacity", cap_total)
        obs.gauge("fpset_occupancy",
                  fp_count / cap_total if cap_total else 0.0)
        # mesh size of the run (compare_bench treats mesh mismatches
        # between docs as advisory — a 4-device run and an 8-device
        # run measure different regimes, not a regression)
        obs.gauge("mesh_devices", int(self.D))
        if hasattr(self, "_dev_distinct"):
            # per-shard distinct counts, reduced on host 0 (the only
            # rank that writes the metrics file / journal)
            obs.gauge("shard_distinct",
                      [int(x) for x in self._dev_distinct])
            # the fullest shard against the mean: 1.0 is an even split
            obs.gauge("shard_skew",
                      round(float(self._dev_distinct.max()
                                  / self._dev_distinct.mean()), 4))
        acts = getattr(self, "_act_counts", None)
        if acts is not None:
            obs.gauge("action_expansions",
                      {n: int(c) for n, c in
                       zip(self.kern.action_names, acts)})
        # occupancy = real work items / expand lanes the shards ran
        # (ISSUE 10; fused: the blocks of stage 2 that held an enabled
        # lane, of the blocks the caps hold, as DeviceBFS counts them)
        lanes = getattr(self, "_lanes_disp", 0)
        if lanes and acts is not None:
            obs.gauge("occupancy",
                      round(float(acts.sum()) / lanes, 4))
        if self.commit == "fused" and acts is not None:
            obs.count("expand_blocks_run", int(self._blocks_act.sum()))
            obs.count("expand_blocks_cap", self._blocks_cap)
        obs.gauge("commit_mode", self.commit)

    def _caps(self):
        """The caps the fused step runs with (make_sharded_level)."""
        return fused_caps(self.kern, self.tile, self.expand_caps)


def pack_scalars(reason, sent, gen, gfull, amp, act, blk, *stat):
    """A dispatch's per-shard control scalars, [D] each, its [D, A]
    action and block counters and, where the step carries them, its
    [D, n_stat] commit stats (uint32, which the host reads them back
    as) as one [D, 5+2A+n_stat] int32 array."""
    return jnp.concatenate(
        [reason[:, None], sent[:, None], gen[:, None], gfull[:, None],
         amp[:, None], act.astype(jnp.int32), blk.astype(jnp.int32)]
        + [jax.lax.bitcast_convert_type(x, jnp.int32) for x in stat],
        axis=1)


def make_packed_fill(mesh: Mesh, axis: str):
    """fill(zero_row, head, F) -> a `[D*F, words]` packed frontier
    sharded over `axis`: on every shard its `head` rows (`[D*k, words]`
    sharded, k <= F) followed by F - k copies of the replicated packed
    zero row."""
    def fill(zero_row, head, F):
        def piece(zero_row, head):
            pad = jnp.broadcast_to(
                zero_row, (F - head.shape[0],) + zero_row.shape)
            return jnp.concatenate([head, pad])

        return _shard_map(piece, mesh=mesh, in_specs=(P(), P(axis)),
                          out_specs=P(axis))(zero_row, head)

    return jax.jit(fill, static_argnums=2)


def make_sharded_insert(mesh: Mesh, axis: str):
    """Insert a replicated fingerprint batch into the owning shards
    (used to register init states)."""
    n_dev = mesh.shape[axis]

    def ins(tables, fps, mask):
        tables = {k: v[0] for k, v in tables.items()}
        me = jax.lax.axis_index(axis)
        mine = mask & ((route(fps) % jnp.uint32(n_dev)).astype(jnp.int32)
                       == me)
        tables, fresh, ovf = insert_core(tables, fps, mine)
        return ({k: v[None] for k, v in tables.items()},
                jnp.asarray([fresh.sum()]), jnp.asarray([ovf]))

    return jax.jit(_shard_map(
        ins, mesh=mesh, in_specs=(P(axis), P(), P()),
        out_specs=(P(axis), P(axis), P(axis))))
