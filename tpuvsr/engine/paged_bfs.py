"""Host-paged BFS engine: the frontier spill tier for defect-scale runs.

The reference's flagship run — exhaustive BFS of VSR.tla at the
defect-repro constants — drove TLC to >=500 GB of disk, nearly all of
it queue/state storage, not fingerprints
(/root/reference/README.md:20; CAPACITY.md).  On a TPU the same wall
hits sooner: at ~7 KiB per dense state one chip's spare HBM holds
under a million frontier states, while a defect-scale BFS level can
exceed that by orders of magnitude.  This engine keeps ONLY the
fingerprint set resident in device memory and pages the frontier
through the device in fixed-size chunks.  (The set is not the binding
constraint, but it is dearer than its 20 bytes a slot: the v5e lays
`u32[N, 5]` out with the 5 words padded to 8, 32 bytes a slot, so a
table of 1<<28 slots is 8.59 GB of the chip's 16 — the largest power
of two that fits, 134 M states at 50 % load — PERF.md §4, PR 31.)

  host frontier (numpy; the 125 GB host holds ~17 M dense states)
      --page in-->  device chunk buffer [chunk_tiles x tile states]
      --level kernel (DeviceBFS._make_level, unchanged)-->
      next-frontier buffer fills --> PAGE OUT to host, reset, continue

A page has ONE shape, in and out (ISSUE 31): a chunk goes in as a
block of `chunk_tiles x tile` rows whatever it holds, and the next
buffer's committed rows come out in blocks of as many, cut by one
program whose offset is a scalar (`_drain_page`); the host pads the
one and cuts the tail of the other.  So a run builds no program for a
page; before, every page of a new length was three to five.

The drain reuses the level kernel's existing pause protocol: the
headroom check that raised R_NEXT_GROW in the resident engine (grow
the buffer in HBM) here means "spill what you have" — the paused tile
has committed nothing, so the host copies the nn valid rows out,
zeroes the counter, and re-enters at the same tile.  Transfers are
sequential block copies proportional to bytes/state x generated/s
(CAPACITY.md mitigation 1).

The dispatch window runs over the PAGES of a level (ISSUE 38): while
page i runs, the host cuts page i+1, puts it on the device and
launches its dispatch behind page i's, chained on that one's outputs
(table, next buffers, `nn`), so the pages of a burst append to one
next buffer in commit order and the buffer goes out when it fills or
the level ends.  Whether the queued dispatch runs is decided on the
device (`_page_start`): behind a page that paused it starts past its
own last tile, runs none, and is dropped; the host handles the pause,
re-enters page i at the paused tile and launches page i+1 again from
the array it holds.  Nothing is launched behind a level's last page.
(Until ISSUE 38 the second ticket of the window went to the page just
finished: it found no tile to run, but ran the level program's stage
1 over a whole page first, once a page.)

Everything else — fingerprinting, invariant evaluation, growth of the
message table / FPSet / per-action expand buffers, violation handling,
deadlock detection, trace replay — is inherited from DeviceBFS; the
two engines run the SAME jitted level pass, so paged results match
resident results exactly (asserted in tests/test_paged.py).

Checkpoint/resume reuses the level-boundary snapshot format of
engine/checkpoint.py (the frontier is already host-side here, making
snapshots cheap).
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from ..core.values import TLAError
from ..obs import RunObserver, closes_observer, spans
from ..resilience.faults import fault_point
from ..resilience.supervisor import Preempted, preempt_signal
from .bfs import CheckResult
from .device_bfs import (DeviceBFS, I32, R_BAG_GROW, R_DEADLOCK,
                         R_EDGE_FLUSH, R_EXPAND_GROW, R_FPSET_GROW,
                         R_NEXT_GROW, R_SLOT_ERR, R_VIOLATION, RUNNING,
                         slot_error)
from .fpset import grow
from .spill import EdgeCSR


@partial(jax.jit, static_argnames=("rows",))
def _drain_page(bufs, start, rows):
    """Rows [start, start + rows) of the next buffers: the frontier
    rows as they lie there, and the three trace-pointer columns as one
    [3, rows] array.  `start` is a traced scalar, so every page-out of
    an engine is this one program; the caller keeps start + rows
    inside the buffers (a slice past the end would be clamped)."""
    def cut(v):
        return jax.lax.dynamic_slice_in_dim(v, start, rows, axis=0)
    nb, nbp, nba, nbprm = bufs
    return jax.tree.map(cut, nb), jnp.stack(
        [cut(nbp), cut(nba), cut(nbprm)])


@jax.jit
def _page_start(prev_t, prev_reason, prev_tiles, resume_t, own_tiles):
    """The tile at which a dispatch queued behind another starts, as a
    device scalar, so that queuing it costs no host sync (`prev_t`,
    `prev_reason`: that one's outputs, still on the device;
    `prev_tiles`: the tiles of its page).  `resume_t` where the
    dispatch before ran its page to the end; past its own last tile
    otherwise: behind a pause it then runs no tile, commits nothing,
    and its own `t` (one past `own_tiles`) says the same to whatever is
    queued behind IT.  A dispatch with nothing before it gives zeros
    and `RUNNING`, and starts at `resume_t`."""
    clean = (prev_t == prev_tiles) & (prev_reason == RUNNING)
    return jnp.where(clean, resume_t, own_tiles + 1).astype(I32)


@dataclass
class _Page:
    """A page of the level being expanded: frontier rows [start,
    start + n), `tiles` tiles of them, and the device array once it
    has gone in."""
    start: int
    n: int
    tiles: int
    dev: object = None


def _shape_key(tree):
    return tuple(np.shape(v) for v in jax.tree.leaves(tree))


class PagedBFS(DeviceBFS):
    """DeviceBFS with a host-RAM frontier paged through the device.

    With ``retain_levels=True`` every expanded frontier level's host
    block is kept on ``self.level_blocks`` (gid order) — the state
    enumeration pass the device liveness graph builder reuses
    (engine/device_liveness.py)."""

    # what a caller may have sized its capacities by, and names in
    # `requires` (ISSUE 31).  fixed_page_shapes: a page goes in as one
    # [chunk_tiles x tile]-row block and comes out as blocks of the
    # same row count, whatever it holds, so a run builds no program
    # per page (before: three to five eager programs for every page
    # of a new length).  device_table_stats: the end of a run reduces
    # the FPSet's occupancy on the device and pulls two scalars, not
    # the table (5.4 GB at 1<<28 slots)
    PROVIDES = frozenset({"fixed_page_shapes", "device_table_stats"})

    def __init__(self, *args, retain_levels=False, spill_dir=None,
                 spill_ram_rows=None, edges=False, edge_capacity=None,
                 edge_spill_dir=None, edge_ram_rows=None, requires=(),
                 **kwargs):
        # refuse before anything is built, as ShardedBFS does
        missing = sorted(set(requires) - self.PROVIDES)
        if missing:
            raise TLAError(f"this PagedBFS does not provide {missing} "
                           f"(it provides {sorted(self.PROVIDES)})")
        # the shapes of every page this engine moved, and this run
        self.page_shapes = {"in": set(), "out": set()}
        self._run_page_shapes = set()
        self.retain_levels = retain_levels
        self.level_blocks = []
        # streamed edge emission (ISSUE 15): the fused commit's stage 3
        # resolves every enabled lane's successor fingerprint to a gid
        # on device (gid-valued FPSet) and appends (src gid, action,
        # dst gid) triples to a device append buffer, drained into the
        # incremental host CSR builder (engine/spill.EdgeCSR) at chunk
        # boundaries — the behavior graph streams OUT of the safety
        # BFS instead of being re-derived by a second expansion pass.
        # `edge_spill_dir` tiers the drained triples to disk for
        # graphs past the RAM budget.  Must be set BEFORE the parent
        # constructor runs (the tile bodies close over it)
        self._edges_on = bool(edges)
        self._edge_capacity = edge_capacity
        self._edge_spill_dir = edge_spill_dir
        self._edge_ram_rows = edge_ram_rows
        self.edge_sink = None
        self._edge_rows_total = 0
        self._edge_hw = 0
        self._run_t0 = None
        # disk spill tier (ISSUE 11, CAPACITY.md mitigation 2): with a
        # spill directory, each level's host pages live in a SpillTier
        # — at most `spill_ram_rows` rows resident, the rest in
        # append-only level files re-read sequentially when the level
        # pages through the device.  The host-RAM frontier ceiling
        # becomes a disk-priced one; results are bit-identical (the
        # tier only changes WHERE at-rest rows live)
        self._spill_dir = spill_dir
        self._spill_ram_rows = int(spill_ram_rows or (1 << 20))
        self._tiers = []
        if spill_dir and retain_levels:
            raise TLAError(
                "retain_levels (the liveness graph enumeration) needs "
                "the whole level resident; it cannot be combined with "
                "the disk spill tier")
        super().__init__(*args, **kwargs)

    # -- disk-tier helpers (no-ops when spill_dir is None) -------------
    def _tier(self, level, block, obs):
        from .spill import SpillTier
        t = SpillTier(self._spill_dir, level, self._spill_ram_rows,
                      obs=obs, depth=level)
        self._tiers.append(t)
        if block is not None:
            t.append(block)
        return t

    def _front_block(self, host_front, start, n):
        """Rows [start, start+n) of the (possibly disk-tiered) host
        frontier, in the at-rest row format."""
        from .spill import SpillTier
        if isinstance(host_front, SpillTier):
            return host_front.block(start, n)
        if self._pk is not None:
            return host_front[start:start + n]
        return {k: v[start:start + n] for k, v in host_front.items()}

    def _front_dense_blocks(self, tier, n):
        """Generator of dense plane-dict blocks over a disk-tiered
        frontier, page by page — the streaming checkpoint writer's
        input (ISSUE 13 satellite: the PR 11 save_checkpoint residual).
        Peak residency is ONE page, tracked run-wide on
        ``_ckpt_peak_rows`` / ``_ckpt_blocks`` (the test assertion
        hooks)."""
        self._ckpt_peak_rows = getattr(self, "_ckpt_peak_rows", 0)
        self._ckpt_blocks = max(getattr(self, "_ckpt_blocks", 0), 0)
        done = 0
        for _pos, rows, load in tier._iter_pages():
            if done >= n:
                break
            take = min(rows, n - done)
            block = load()
            if take < rows:
                from .spill import _slice
                block = _slice(block, 0, take)
            dense = (self._pk.unpack_np(np.asarray(block))
                     if self._pk is not None else
                     {k: np.asarray(v) for k, v in block.items()})
            done += take
            self._ckpt_peak_rows = max(self._ckpt_peak_rows, take)
            self._ckpt_blocks += 1
            yield dense

    # -- host-side helpers ---------------------------------------------
    def _host_zero(self, n):
        if self._pk is not None:
            # packed host frontier: spill pages and the at-rest host
            # store move [words] uint32 rows, not dense planes
            # (ISSUE 9 — 4-8x fewer bytes over the chunk-in/drain-out
            # transfers that bound this engine)
            return np.zeros((n, self._pk.words), np.uint32)
        zero = self.codec.zero_state()
        return {k: np.zeros((n,) + np.shape(v), np.int32)
                for k, v in zero.items()}

    def _host_row(self, host_front, i):
        """One dense state row of the (possibly packed, possibly
        disk-tiered) host frontier."""
        from .spill import SpillTier
        if isinstance(host_front, SpillTier):
            block = host_front.row(i)
            if self._pk is not None:
                return self._pk.unpack_row_np(np.asarray(block)[0])
            return {k: v[0] for k, v in block.items()}
        if self._pk is not None:
            return self._pk.unpack_row_np(host_front[i])
        return {k: host_front[k][i] for k in host_front}

    def _chunk_cap(self):
        """Rows of a page, in and out."""
        return self.chunk_tiles * self.tile

    def _floor_next_cap(self):
        """The next buffer holds at least one tile's commit on top of
        the caps (the level kernel's headroom test), in whole pages:
        the last page-out of a full buffer then ends at its end."""
        page = self._chunk_cap()
        need = max(self.next_cap, self._total_E() + self.tile)
        self.next_cap = -(-need // page) * page

    def _note_shape(self, way, page):
        key = _shape_key(page)
        self.page_shapes[way].add(key)
        self._run_page_shapes.add((way, key))

    def _page_in(self, block, n):
        """The first `n` rows of a host block on the device as one
        page: always `_chunk_cap()` rows (the tail zeros, which the
        level kernel masks by its row count), so no page's length
        makes a program."""
        cc = self._chunk_cap()

        def pad(v):
            v = np.asarray(v)
            if n == cc:
                return v
            out = np.zeros((cc,) + v.shape[1:], v.dtype)
            out[:n] = v
            return out
        # blocked on: the span around this is the transfer's time
        page = jax.block_until_ready(
            jax.device_put(jax.tree.map(pad, block)))
        self._note_shape("in", page)
        return page

    def _page_out(self, bufs, n):
        """The first `n` rows of the next buffers as host arrays, a
        page at a time: (rows, parent, action, param) per page, the
        tail of the last page cut here.  One program for every page
        (`_drain_page`; the buffers are whole pages long, see
        `_floor_next_cap`), and the copies of one call overlap."""
        cc = self._chunk_cap()
        pages = [_drain_page(bufs, np.int32(at), rows=cc)
                 for at in range(0, n, cc)]
        self._note_shape("out", pages[0])
        out = []
        for i, (rows, meta) in enumerate(jax.device_get(pages)):
            take = min(cc, n - i * cc)
            if take < cc:
                # copies: a view would keep the whole page alive
                rows = jax.tree.map(lambda v: v[:take].copy(), rows)
                meta = meta[:, :take].copy()
            out.append((rows, meta[0], meta[1], meta[2]))
        return out

    def _total_E(self):
        # same caps the level kernel compacts with (fused commit: the
        # exact-count caps; per-action: the tile-multiple formula) —
        # the next-buffer headroom floor must track whichever is live
        return sum(self._expand_caps())

    def _pad_init_dense(self, old):
        for i, d in enumerate(self._init_dense):
            padded = self.codec.pad_msgs(
                {k: np.asarray(v)[None] for k, v in d.items()}, old)
            self._init_dense[i] = {k: v[0] for k, v in padded.items()}

    def _state_row_bytes(self):
        """Bytes of one frontier row as the paged tier actually moves
        it: packed words when the pack spec is bound, dense otherwise
        (the spill `bytes` journal field and gauges report REAL
        transfer volume)."""
        if self._pk is not None:
            return self._pk.packed_bytes
        zero = self.codec.zero_state()
        return sum(int(np.prod(np.shape(v)) or 1) * 4
                   for v in zero.values())

    @closes_observer
    def run(self, max_states=None, max_depth=None, max_seconds=None,
            check_deadlock=False, log=None, progress_every=10.0,
            checkpoint_path=None, checkpoint_every=None,
            resume_from=None, obs=None) -> CheckResult:
        from ..analysis import preflight
        preflight(self.spec, log=log)   # fail fast, before any dispatch
        obs = RunObserver.ensure(obs, "paged", self.spec, log=log,
                                 progress_every=progress_every)
        obs.pipeline = self.pipe_window
        obs.pack = self._pk is not None
        obs.commit = self.commit
        obs.symmetry = self._symmetry_on()
        obs.bounds = self._bounds_doc()
        obs.edges = self._edges_on
        obs.por = self._por_doc()
        self._obs_active = obs          # closes_observer finalizes it
        spec = self.spec
        self._reset_accounting()
        self._por_kept = self._por_full = self._por_amp = 0
        res = CheckResult()
        t0 = time.time()
        self._run_t0 = t0
        obs.start(t0, backend=jax.default_backend(),
                  resumed=resume_from is not None)
        emit = obs.log
        # pipelined dispatch window (ISSUE 4) over the PAGES of a level
        # (ISSUE 38): while a page runs, the next one is cut, put and
        # launched behind it, chained on device-side (start_t, nn)
        # scalars; what is queued behind a pause runs no tile
        # (`_page_start`) and is dropped as a replay that committed
        # nothing (engine/pipeline.py).  Nothing is launched past a
        # level's last page.  Made as the run starts: its unfed clock
        # counts the set-up
        from .pipeline import DispatchPipeline
        pipe = DispatchPipeline(self.pipe_window, obs,
                                ready=lambda o: o["reason"])

        self.spill_count = 0     # drains triggered by a full buffer
        self.spill_rows = 0      # total rows paged out to host
        self._run_page_shapes = set()   # of this run's pages
        self.level_blocks = []   # fresh per run (retain_levels)
        if self._edges_on:
            # incremental host CSR builder the edge drains feed
            # (ISSUE 15); fresh per run like the level blocks
            self.edge_sink = EdgeCSR(spill_dir=self._edge_spill_dir,
                                     ram_rows=self._edge_ram_rows,
                                     obs=obs)
            self._edge_rows_total = 0
            self._edge_hw = 0

        if resume_from is not None:
            from .checkpoint import load_checkpoint, spec_digest
            ck = load_checkpoint(resume_from,
                                 expect_digest=spec_digest(spec),
                                 log=emit)
            if (ck.get("extra") or {}).get("sharded"):
                raise TLAError("checkpoint was written by the sharded "
                               "engine; resume it there")
            # empty expand_mults (a converted sharded snapshot, see
            # parallel.sharded_bfs.convert_sharded_snapshot): keep
            # this engine's own per-action defaults
            if ck["max_msgs"] != self.codec.shape.MAX_MSGS or \
                    (ck["expand_mults"] and list(ck["expand_mults"])
                     != list(self.expand_mults)):
                if ck["expand_mults"]:
                    self.expand_mults = list(ck["expand_mults"])
                self._build(ck["max_msgs"])
            self._check_bounds_manifest(ck, resume_from)
            self._check_pack_manifest(ck, resume_from)
            self._check_canon_manifest(ck, resume_from)
            table = {"slots": jnp.asarray(ck["slots"])}
            fp_cap = int(ck["slots"].shape[0])
            # POR manifest policy (ISSUE 16): resuming under a flipped
            # -por or changed independence facts is a loud error; on a
            # matching resume the C3 level markers are rebuilt as
            # zeros — at a level boundary every stored fingerprint is
            # old, which reproduces the writer's decisions exactly
            if self._por_active:
                self._check_por_manifest(ck, resume_from)
                table["gids"] = jnp.zeros((fp_cap,), jnp.int32)
            elif ck.get("por"):
                self._check_por_manifest(ck, resume_from)
            if self._edges_on:
                # edge-stream resume seam (ISSUE 15): the snapshot
                # must carry the gid column and the drained edge rows
                # up to its committed level — resuming a plain-BFS
                # snapshot with edges on would leave every pre-resume
                # state gid-less, so it is a policy error
                if ck.get("gids") is None:
                    raise TLAError(
                        f"checkpoint {resume_from} was written "
                        f"without the edge stream (no gid column); "
                        f"resume with edges off, or restart the "
                        f"temporal run from scratch")
                table["gids"] = jnp.asarray(ck["gids"])
                if ck.get("edges") is not None:
                    self.edge_sink.seed(ck["edges"])
                    self._edge_rows_total = self.edge_sink.rows
                if self.retain_levels:
                    g = ck.get("graph")
                    sizes = [int(x) for x in ck["level_sizes"][:-1]]
                    have = (0 if g is None
                            else int(next(iter(g.values())).shape[0]))
                    if have != sum(sizes):
                        raise TLAError(
                            f"checkpoint {resume_from} retains "
                            f"{have} graph rows, the committed "
                            f"levels hold {sum(sizes)} — snapshot "
                            f"not written by a retain_levels run")
                    off = 0
                    for s in sizes:
                        self.level_blocks.append(
                            {k: v[off:off + s] for k, v in g.items()})
                        off += s
            self._init_dense = ck["init_dense"]
            self._init_states = [self.codec.decode(d)
                                 for d in ck["init_dense"]]
            self._h_parent = [ck["h_parent"]]
            self._h_action = [ck["h_action"]]
            self._h_param = [ck["h_param"]]
            self.level_sizes = list(ck["level_sizes"])
            depth = ck["depth"]
            fp_count = ck["fp_count"]
            res.states_generated = ck["states_generated"]
            t0 -= ck["elapsed"]
            obs.set_epoch(t0)
            n_front = ck["n_front"]
            # snapshots load as dense planes (the engine-agnostic
            # interchange format); pack them when packing is on
            host_front = (self._pk.pack_np(
                {k: np.asarray(v) for k, v in ck["frontier"].items()})
                if self._pk is not None else
                {k: np.asarray(v) for k, v in ck["frontier"].items()})
            if self._spill_dir is not None:
                # reload through the tier: a resumed frontier larger
                # than the RAM budget spills right back to disk
                host_front = self._tier(depth, host_front, obs)
            level_base = sum(self.level_sizes[:-1])
            emit(f"resumed from {resume_from}: depth {depth}, "
                 f"{fp_count} distinct, frontier {n_front}")
        else:
            fp_cap = self.fpset_capacity
            self.level_sizes = []  # no stale trajectory on init-viol
            with obs.span(spans.INIT):
                table, init_batch, n0, viol = self._register_init(res)
            fp_count = n0
            if viol is not None:
                return self._finish(res, obs, fp_count,
                                    table=table, fp_cap=fp_cap)
            init_rows = {k: init_batch[k][:n0].astype(np.int32)
                         for k in init_batch}
            host_front = (self._pk.pack_np(init_rows)
                          if self._pk is not None else init_rows)
            if self._spill_dir is not None:
                host_front = self._tier(0, host_front, obs)
            n_front = n0
            level_base = 0
            depth = 0
            self.level_sizes = [n0]

        last_checkpoint = time.time()
        # the level kernel refuses to commit a tile unless the next
        # buffer has total_E rows of headroom, so total_E + one tile's
        # worth is the functional floor; size it larger (the default
        # next_capacity) to keep drains block-sized rather than
        # per-tile.  Floored AFTER any resume rebuild (expand_mults /
        # max_msgs from the checkpoint can enlarge total_E) and
        # re-floored on every in-run rebuild — a stale floor live-locks
        # the drain loop (commit never true with an empty buffer).
        self._floor_next_cap()
        with obs.span(spans.INIT):
            bufs = self._alloc_bufs(self.next_cap)
        # edge append buffer (ISSUE 15): same total_E + one-tile floor
        # as the next buffer (the kernel refuses to commit a tile
        # without total_E triples of headroom); default sized 4x the
        # next buffer so R_EDGE_FLUSH drains stay block-sized
        ebufs = None
        n_edge = 0
        if self._edges_on:
            self.edge_cap = max(int(self._edge_capacity
                                    or 4 * self.next_cap),
                                self._total_E() + self.tile)
            ebufs = tuple(jnp.zeros((self.edge_cap,), I32)
                          for _ in range(3))
        stop = None
        # a host scalar as a dispatch's outputs are: int32, committed to
        # the table's device.  What is chained on a dispatch takes its
        # outputs where a first launch takes these, and an argument of
        # another kind is a program more — built inside a window whose
        # warm-up saw one-page levels only
        home = next(iter(table["slots"].devices()))

        def scalar(x):
            return jax.device_put(np.int32(x), home)
        zero, running = scalar(0), scalar(RUNNING)

        def pull(o):
            keys = [o["reason"], o["t"], o["nn"], o["gen"],
                    o["dist"], o["act"], o["need"], o["blk"],
                    o.get("cpl", 0)]
            if self._edges_on:
                keys.append(o["edge_n"])
            if self._por_active:
                keys += [o["gfull"], o["amp"]]
            return jax.device_get(keys + self._device_counts(o))

        # the host between two units of device work (a chunk, a level)
        # and before the first: open from here, or from a chunk's end,
        # to the next launch
        obs.boundary(depth=depth)
        while n_front > 0 and stop is None:
            if max_depth is not None and depth >= max_depth:
                res.error = f"depth limit {max_depth} reached"
                break
            if self.retain_levels:
                # level blocks stay DENSE: the device liveness graph
                # builder enumerates them as plane dicts
                self.level_blocks.append(
                    self._pk.unpack_np(host_front)
                    if self._pk is not None else host_front)
            depth += 1
            fault_point("level", depth=depth, obs=obs)
            # per-level host accumulators for drained next states and
            # their (level-relative) trace pointers.  Disk tier:
            # `drained` is a SpillTier — same .append seam, but pages
            # beyond the RAM budget flush to level files
            drained = (self._tier(depth, None, obs)
                       if self._spill_dir is not None else [])
            d_par, d_act, d_prm = [], [], []
            n_next_total = 0
            n_next = 0
            # whose rows the next buffer holds: (first frontier row of a
            # page, rows of the buffer up to that page's last collect),
            # in commit order — the pages of a burst append to one
            # buffer, and a trace pointer is relative to its own page
            segs = []

            def spill():
                """Page the first n_next rows of the next buffers out to
                host RAM and reset the counter.  Only with no real page
                in flight: it reads the chain-tip buffers, which are the
                last collected dispatch's (what is dropped behind a
                pause committed nothing)."""
                nonlocal n_next_total, n_next
                if n_next == 0:
                    return
                if self.pipe_window > 1:
                    # the chain tip may still run (a dispatch dropped
                    # behind a pause): that wait is the device's work
                    pipe.wait(bufs[1])
                with obs.span(spans.PAGE_OUT, depth=depth, rows=n_next):
                    pages = self._page_out(bufs, n_next)
                # par is page-relative; lift to level-relative now (the
                # newest collect left n_next, so the last seg ends there)
                firsts, ends = zip(*segs)
                lift = np.repeat(np.asarray(firsts, np.int64),
                                 np.diff((0,) + ends))
                segs.clear()
                row_bytes = self._state_row_bytes()
                at = 0
                for rows, par, act, prm in pages:
                    drained.append(rows)
                    d_par.append(par.astype(np.int64)
                                 + lift[at:at + len(par)])
                    at += len(par)
                    d_act.append(act)
                    d_prm.append(prm)
                    obs.spill(depth, len(par), len(par) * row_bytes)
                n_next_total += n_next
                self.spill_rows += n_next
                n_next = 0

            def refloor_edges():
                """Kernel rebuilt with (possibly) wider caps: drain
                the plain-int triples, re-floor the append buffer
                against the new total_E headroom requirement, and
                re-zero it (a stale floor live-locks the commit gate,
                exactly like the next_cap floor above)."""
                nonlocal ebufs
                drain_edges()
                self.edge_cap = max(self.edge_cap,
                                    self._total_E() + self.tile)
                ebufs = tuple(jnp.zeros((self.edge_cap,), I32)
                              for _ in range(3))

            def drain_edges():
                """Drain the committed edge triples off the device
                append buffer into the CSR builder (ISSUE 15).  As
                `spill`: with no real page in flight, off the chain
                tip."""
                nonlocal n_edge
                if not self._edges_on or n_edge == 0:
                    return
                es, ea, ed = ebufs
                with obs.span(spans.HOST_SYNC):
                    s, a, d = jax.device_get(
                        (es[:n_edge], ea[:n_edge], ed[:n_edge]))
                self.edge_sink.append(np.asarray(s), np.asarray(a),
                                      np.asarray(d))
                self._edge_rows_total += n_edge
                self._edge_hw = max(self._edge_hw, n_edge)
                obs.edge_flush(depth, n_edge,
                               n_edge * EdgeCSR.ROW_BYTES)
                n_edge = 0

            def put(page):
                block = self._front_block(host_front, page.start, page.n)
                with obs.span(spans.PAGE_IN, depth=depth, rows=page.n):
                    page.dev = self._page_in(block, page.n)
                obs.page_in(depth, page.n,
                            page.n * self._state_row_bytes())

            cc = self._chunk_cap()
            next_row = 0        # the first frontier row no page holds yet
            # pages to launch before any new one is cut, oldest first: a
            # paused page (it re-enters at `resume_t`) and what was
            # queued behind it
            todo = deque()
            resume_t = 0
            flying = deque()    # the pages of the window's dispatches
            # the newest dispatch's output and its page's tiles, while
            # the next launch chains on it; None where the host knows
            # the scalars (a level's first page, after a pause)
            tip = None
            while True:
                while stop is None and pipe.has_room() and (
                        todo or next_row < n_front):
                    if todo:
                        page = todo.popleft()
                    else:
                        n = min(cc, n_front - next_row)
                        page = _Page(next_row, n, -(-n // self.tile))
                        next_row += n
                    if page.dev is None:
                        put(page)
                    if tip is None:
                        pend_t = _page_start(
                            zero, running, zero,
                            scalar(resume_t), scalar(page.tiles))
                        pend_nn = scalar(n_next)
                        pend_en = scalar(n_edge)
                    else:
                        # behind a dispatch the host has not read: the
                        # device decides whether this one runs
                        prev, prev_tiles = tip
                        pend_t = _page_start(
                            prev["t"], prev["reason"],
                            scalar(prev_tiles), zero,
                            scalar(page.tiles))
                        pend_nn = prev["nn"]
                        pend_en = prev.get("edge_n")
                        if pipe.in_flight:
                            obs.count("pages_ahead")
                    nb, nbp, nba, nbprm = bufs
                    eb_arg, emeta_arg = None, None
                    if self._edges_on:
                        # gid_base maps a next-buffer row to its
                        # global gid (spilled rows precede the
                        # buffer); src_base lifts a page row to its
                        # frontier gid.  gid_base is constant within
                        # a pipelined burst: spills only happen with
                        # nothing in flight
                        eb_arg = ebufs
                        emeta_arg = {
                            "n": pend_en,
                            "src_base": jnp.asarray(
                                level_base + page.start, I32),
                            "gid_base": jnp.asarray(
                                level_base + n_front
                                + n_next_total, I32)}
                    out = pipe.launch(
                        self._run_level, table, page.dev,
                        jnp.asarray(page.n, I32), pend_t,
                        nb, nbp, nba, nbprm, pend_nn,
                        jnp.asarray(bool(check_deadlock)),
                        eb_arg, emeta_arg,
                        jnp.asarray(depth - 1, I32),
                        fresh=self._fresh_jit,
                        depth=depth)
                    self._fresh_jit = False
                    table = {"slots": out["slots"]}
                    if self._por_active:
                        table["gids"] = out["gids"]
                    bufs = (out["nb"], out["nbp"], out["nba"],
                            out["nbprm"])
                    if self._edges_on:
                        table["gids"] = out["gids"]
                        ebufs = (out["eb_src"], out["eb_aid"],
                                 out["eb_dst"])
                    resume_t = 0
                    tip = (out, page.tiles)
                    flying.append(page)
                if not flying:
                    break       # the level's last page, or a stop
                page = flying.popleft()
                out, sc = pipe.collect(pull)
                reason, start_t, n_next, gen_add, dist_add = (
                    int(x) for x in sc[:5])
                res.states_generated += gen_add
                fp_count += dist_add
                self._act_counts += np.asarray(sc[5], np.int64)
                self._fold_need(sc[6])
                self._account_blocks(sc[7], sc[8])
                if self._edges_on:
                    n_edge = int(sc[9])
                if self._por_active:
                    self._por_kept += gen_add
                    self._por_full += int(sc[9])
                    self._por_amp += int(sc[10])
                self._fold_device_counts(sc)
                segs.append((page.start, n_next))

                if reason == RUNNING and start_t >= page.tiles:
                    # the page is done; what is queued behind it is
                    # the next page, running: on a stop it is
                    # collected and counted like this one
                    self._account_tiles(page.tiles)
                    obs.boundary(depth=depth, chunk=page.start // cc)
                    obs.progress(depth=depth, distinct=fp_count,
                                 generated=res.states_generated,
                                 frontier=n_front, extra="host-paged")
                    if max_seconds and time.time() - t0 > max_seconds:
                        stop = f"time budget {max_seconds}s reached"
                    continue
                # pause/terminal: what is queued behind ran no tile —
                # drop it, keep its pages for a second launch, and
                # handle the pause on the chain-tip table/buffers
                void = pipe.drain()
                if void:
                    obs.count("pages_ahead_void", void)
                todo.extendleft(reversed([page, *flying]))
                flying.clear()
                resume_t, tip = start_t, None
                if reason == R_VIOLATION:
                    vp, va, vprm = (int(v)
                                    for v in np.asarray(out["viol"]))
                    gid = level_base + page.start + vp
                    parent_dense = self._host_row(
                        host_front, page.start + vp)
                    vstate = self._materialize_one(
                        parent_dense, va, vprm)
                    bad = spec.check_invariants(
                        self.codec.decode(vstate))
                    if bad is None:
                        raise TLAError(
                            "device/interpreter divergence: device "
                            "invariant kernel reported a violation "
                            "the interpreter accepts (parent gid "
                            f"{gid}, action "
                            f"{self.kern.action_names[va]})")
                    res.ok = False
                    res.violated_invariant = bad
                    res.trace = self._trace(gid, extra=(va, vprm))
                    res.diameter = depth
                    return self._finish(res, obs, fp_count,
                                        table=table, fp_cap=fp_cap)
                elif reason == R_SLOT_ERR:
                    raise TLAError(slot_error(self.codec))
                elif reason == R_DEADLOCK:
                    di = int(out["dead"])
                    gid = level_base + page.start + di
                    res.ok = False
                    res.error = "deadlock"
                    res.deadlock_state = self.codec.decode(
                        self._host_row(host_front, page.start + di))
                    res.trace = self._trace(gid)
                    res.diameter = depth
                    return self._finish(res, obs, fp_count,
                                        table=table, fp_cap=fp_cap)
                elif stop is not None:
                    # a page collected behind a stop paused: what it
                    # committed is counted, and nothing re-enters
                    break
                elif reason == R_NEXT_GROW:
                    # the spill tier: page the filled buffer out to
                    # host RAM instead of growing it in HBM
                    self.spill_count += 1
                    spill()
                elif reason == R_EDGE_FLUSH:
                    # edge append buffer full (ISSUE 15): drain the
                    # committed triples into the CSR builder and
                    # re-enter — the edge analog of the spill above
                    drain_edges()
                elif reason == R_BAG_GROW:
                    old = self.codec.shape.MAX_MSGS
                    spill()
                    old_pk = self._pk
                    self._build(old * 2)
                    obs.grow("message_table",
                             self.codec.shape.MAX_MSGS)
                    if old_pk is not None:
                        # packed pages: round-trip through the OLD
                        # spec to dense, pad, re-pack under the
                        # rebuilt one (see DeviceBFS._grow_msgs)
                        def regrow(rows):
                            d = self.codec.pad_msgs(
                                old_pk.unpack_np(rows), old)
                            return self._pk.pack_np(d)
                    else:
                        def regrow(rows):
                            return self.codec.pad_msgs(rows, old)
                    if self._spill_dir is not None:
                        host_front.map_pages(regrow)
                        drained.map_pages(regrow)
                    else:
                        host_front = regrow(host_front)
                        drained = [regrow(d) for d in drained]
                    self.level_blocks = [
                        self.codec.pad_msgs(b, old)
                        for b in self.level_blocks]
                    self._pad_init_dense(old)
                    self._floor_next_cap()
                    bufs = self._alloc_bufs(self.next_cap)
                    if self._edges_on:
                        refloor_edges()
                    for held in todo:   # cut again, at the new width
                        held.dev = None
                    emit(f"message table grown to "
                         f"{self.codec.shape.MAX_MSGS} slots "
                         f"(recompiling)")
                elif reason == R_FPSET_GROW:
                    table = grow(table)
                    fp_cap *= 4
                    self._fresh_jit = True   # shape change
                    obs.grow("fpset", fp_cap)
                    emit(f"FPSet grown to {fp_cap} slots")
                elif reason == R_EXPAND_GROW:
                    self._grow_expand(int(out["grow_aid"]), obs,
                                      emit)
                    if self.next_cap < self._total_E() + self.tile:
                        spill()
                        self._floor_next_cap()
                        bufs = self._alloc_bufs(self.next_cap)
                    if self._edges_on and self.edge_cap < \
                            self._total_E() + self.tile:
                        refloor_edges()
                # a growth pause (or a dispatch that ran out of tiles
                # to run, which re-enters as it is) falls through here
                obs.progress(depth=depth, distinct=fp_count,
                             generated=res.states_generated,
                             frontier=n_front, extra="host-paged")
                if max_seconds and time.time() - t0 > max_seconds:
                    stop = f"time budget {max_seconds}s reached"
                    break
            # level done (or stopped): page out what accumulated, and
            # drain the committed edge triples (a level boundary
            # always finds both buffers empty)
            obs.boundary(depth=depth)
            if todo:
                # a stop behind a pause: the tiles the page had done
                self._account_tiles(resume_t)
            spill()
            drain_edges()

            # ---- level complete: assemble next frontier on host ------
            obs.level_done(depth, frontier=n_front, distinct=fp_count,
                           generated=res.states_generated)
            if n_next_total:
                if self._spill_dir is not None:
                    host_next = drained       # the tier holds the rows
                elif self._pk is not None:
                    host_next = np.concatenate(drained)
                else:
                    host_next = {k: np.concatenate(
                        [d[k] for d in drained]) for k in host_front}
                self._h_parent.append(
                    np.concatenate(d_par) + level_base)
                self._h_action.append(np.concatenate(d_act))
                self._h_param.append(np.concatenate(d_prm))
                self.level_sizes.append(n_next_total)
            else:
                host_next = (drained if self._spill_dir is not None
                             else self._host_zero(0))
            level_base += n_front
            if self._spill_dir is not None:
                # the consumed level's files are dead weight now:
                # steady-state disk holds two levels' worth of rows
                host_front.drop()
            host_front = host_next
            n_front = n_next_total

            if stop:
                res.error = stop
                break
            # fused commit: shrink the expansion caps onto the exact
            # observed maxima (window drained at the level boundary)
            self._calibrate_caps(obs, emit, n_front)
            # pending preemption forces a rescue snapshot at this
            # boundary regardless of cadence (see device_bfs)
            rescue = preempt_signal() if n_front else None
            if checkpoint_path and n_front and (
                    rescue is not None
                    or checkpoint_every is None
                    or time.time() - last_checkpoint >= checkpoint_every):
                from .checkpoint import (FORMAT_VERSION, save_checkpoint,
                                         spec_digest)
                from .spill import SpillTier
                # disk-tiered frontier: STREAM pages into the staged
                # npz (peak residency = one page) instead of
                # materializing n_front dense rows (ISSUE 13 satellite
                # — the PR 11 save_checkpoint residual)
                fr_kw = (
                    {"frontier_blocks":
                     self._front_dense_blocks(host_front, n_front)}
                    if isinstance(host_front, SpillTier) else
                    self._snapshot_frontier(host_front, n_front))
                if self._edges_on:
                    # edge-stream seam (ISSUE 15): the gid column,
                    # the drained edge rows up to this committed
                    # level, and — on a retain_levels (temporal) run
                    # — the retained level blocks, so a SIGTERM'd
                    # temporal run resumes to a bit-identical CSR
                    fr_kw["gids"] = np.asarray(table["gids"])
                    fr_kw["edge_blocks"] = self.edge_sink.blocks()
                    if self.retain_levels:
                        fr_kw["graph_blocks"] = iter(
                            self.level_blocks)
                with obs.span(spans.CHECKPOINT, depth=depth):
                    staged = save_checkpoint(
                        checkpoint_path,
                        slots=table["slots"],
                        n_front=n_front,
                        **fr_kw,
                        h_parent=np.concatenate(self._h_parent),
                        h_action=np.concatenate(self._h_action),
                        h_param=np.concatenate(self._h_param),
                        init_dense=self._init_dense,
                        level_sizes=self.level_sizes, depth=depth,
                        fp_count=fp_count,
                        states_generated=res.states_generated,
                        max_msgs=self.codec.shape.MAX_MSGS,
                        expand_mults=self.expand_mults,
                        elapsed=time.time() - t0,
                        digest=spec_digest(spec),
                        pack=self._pack_manifest(),
                        canon=self._canon_manifest(),
                        bounds=self._bounds_manifest(),
                        por=self._por_manifest(), obs=obs)
                last_checkpoint = time.time()
                obs.checkpoint(checkpoint_path, depth, fp_count, staged,
                               FORMAT_VERSION)
                emit(f"checkpoint written to {checkpoint_path} "
                     f"(depth {depth}, {fp_count} distinct)")
            if rescue is not None:
                obs.rescue(checkpoint_path or "", depth, fp_count,
                           rescue)
                emit(f"preempted by {rescue}: rescue snapshot at depth "
                     f"{depth} ({checkpoint_path}); exiting resumable")
                raise Preempted(checkpoint_path, depth, fp_count,
                                rescue)
            if n_front == 0:
                break
            if max_states and fp_count >= max_states:
                res.error = f"state limit {max_states} reached"
                break
            if fp_count > 0.5 * fp_cap:
                table = grow(table)
                fp_cap *= 4
                self._fresh_jit = True       # shape change
                obs.grow("fpset", fp_cap)
                emit(f"FPSet grown to {fp_cap} slots")

        res.diameter = depth
        return self._finish(res, obs, fp_count,
                            table=table, fp_cap=fp_cap)


    def _final_gauges(self, res, obs, fp_count, table, fp_cap):
        obs.count("page_shapes", len(self._run_page_shapes))
        if self._spill_dir is not None:
            # cumulative bytes the run wrote to the disk tier (files
            # of consumed levels included), then release what is left
            obs.gauge("spill_tier_bytes",
                      int(sum(t.disk_bytes for t in self._tiers)))
            for t in self._tiers:
                t.drop()
            self._tiers = []
        if self._edges_on:
            # edge-stream gauges (ISSUE 15): cumulative drained bytes,
            # the append buffer's observed high water, and the
            # headline emission rate over the run's wall clock
            from .spill import EdgeCSR as _E
            obs.gauge("edge_bytes",
                      int(self._edge_rows_total) * _E.ROW_BYTES)
            obs.gauge("edge_buf_high_water", int(self._edge_hw))
            el = max(time.time() - (self._run_t0 or time.time()),
                     1e-9)
            obs.gauge("edges_per_s",
                      round(self._edge_rows_total / el, 1))
        super()._final_gauges(res, obs, fp_count, table, fp_cap)


def paged_bfs_check(spec, max_states=None, max_depth=None,
                    check_deadlock=False, tile_size=128, max_msgs=None,
                    chunk_tiles=64, log=None, obs=None) -> CheckResult:
    eng = PagedBFS(spec, max_msgs=max_msgs, tile_size=tile_size,
                   chunk_tiles=chunk_tiles)
    return eng.run(max_states=max_states, max_depth=max_depth,
                   check_deadlock=check_deadlock, log=log, obs=obs)
