"""Host-paged BFS engine: `DeviceBFS`'s host loop over a frontier that
lies in host RAM (or on disk) and is paged through the device.

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

`PagedBFS` defines no `run`: the run's opening, the resume, a level's
end (calibration, snapshot, rescue, limits) and the verdicts are
`DeviceBFS`'s, and so are the level program, the growth handlers and
the trace replay — paged results match resident results exactly
(tests/test_paged.py).  What is here is what a host frontier changes:
how it starts (`_start_frontier`, `_open_levels`), how one level of it
is expanded (`_expand_level`), and how the pages a level spilled become
the next frontier and a snapshot's keywords (`_close_level`,
`_hand_over`, `_snapshot_keywords`).

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
1 over a whole page first, once a page.)  A budget that trips is
tested at a page's end: the page in flight behind it is collected and
counted, where the resident window drops what it has in flight.
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
from ..obs import RunObserver, spans
from .bfs import CheckResult
from .device_bfs import (DeviceBFS, I32, R_BAG_GROW, R_EDGE_FLUSH,
                         R_EXPAND_GROW, R_FPSET_GROW, R_NEXT_GROW, RUNNING,
                         _Run)
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


@dataclass
class _PagedRun(_Run):
    """A run whose frontier lies on the host: `front` stays None."""
    host_front: object = None   # rows at rest, or their SpillTier
    ebufs: tuple = None         # the edge append buffers (ISSUE 15)
    n_edge: int = 0             # triples they hold
    # host scalars as a dispatch's outputs are (`_open_levels`)
    home: object = None
    zero: object = None
    running: object = None
    # of the level being expanded: the pages it has spilled, their
    # level-relative pointers and row count, and whose rows the next
    # buffer holds
    drained: object = None
    d_par: list = None
    d_act: list = None
    d_prm: list = None
    n_next_total: int = 0
    segs: list = None


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
    _run_record = _PagedRun

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
        return self.model.row_bytes()

    # ------------------------------------------------------------------
    # DeviceBFS.run's steps over a host-paged frontier
    # ------------------------------------------------------------------
    def _observer(self, obs, **kw):
        return RunObserver.ensure(obs, "paged", self.spec, **kw)

    def _open_run(self, log, progress_every, obs, **asked):
        run = super()._open_run(log, progress_every, obs, **asked)
        self._run_t0 = run.t0
        self.spill_count = 0     # drains triggered by a full buffer
        self.spill_rows = 0      # total rows paged out to host
        self._run_page_shapes = set()   # of this run's pages
        self.level_blocks = []   # fresh per run (retain_levels)
        if self._edges_on:
            # incremental host CSR builder the edge drains feed
            # (ISSUE 15); fresh per run like the level blocks
            self.edge_sink = EdgeCSR(spill_dir=self._edge_spill_dir,
                                     ram_rows=self._edge_ram_rows,
                                     obs=run.obs)
            self._edge_rows_total = 0
            self._edge_hw = 0
        return run

    def _start_frontier(self, run, batch, n, ck=None):
        """The first frontier stays on the host, in the at-rest row
        format (snapshots load as dense planes, the engine-agnostic
        interchange format); with edge emission a snapshot also gives
        back the gid column, the edge rows drained up to its level and
        the retained level blocks."""
        if ck is not None and self._edges_on:
            # edge-stream resume seam (ISSUE 15): resuming a plain-BFS
            # snapshot with edges on would leave every pre-resume
            # state gid-less, so it is a policy error
            if ck.get("gids") is None:
                raise TLAError(
                    f"checkpoint {run.resume_from} was written "
                    f"without the edge stream (no gid column); "
                    f"resume with edges off, or restart the "
                    f"temporal run from scratch")
            run.table["gids"] = jnp.asarray(ck["gids"])
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
                        f"checkpoint {run.resume_from} retains "
                        f"{have} graph rows, the committed "
                        f"levels hold {sum(sizes)} — snapshot "
                        f"not written by a retain_levels run")
                off = 0
                for s in sizes:
                    self.level_blocks.append(
                        {k: v[off:off + s] for k, v in g.items()})
                    off += s
        rows = {k: np.asarray(v[:n], np.int32) for k, v in batch.items()}
        run.host_front = (self._pk.pack_np(rows)
                          if self._pk is not None else rows)
        if self._spill_dir is not None:
            # through the tier: a frontier larger than the RAM budget
            # (a resumed one) spills right back to disk
            run.host_front = self._tier(run.depth, run.host_front,
                                        run.obs)

    def _open_levels(self, run):
        # the level kernel refuses to commit a tile unless the next
        # buffer has total_E rows of headroom, so total_E + one tile's
        # worth is the functional floor; size it larger (the default
        # next_capacity) to keep drains block-sized rather than
        # per-tile.  Floored AFTER any resume rebuild (expand_mults /
        # max_msgs from the checkpoint can enlarge total_E) and
        # re-floored on every in-run rebuild — a stale floor live-locks
        # the drain loop (commit never true with an empty buffer).
        self._floor_next_cap()
        with run.obs.span(spans.INIT), \
                run.obs.part(spans.INIT_DEVICE):
            run.bufs = self._alloc_bufs(self.next_cap)
        # edge append buffer (ISSUE 15): same total_E + one-tile floor
        # as the next buffer (the kernel refuses to commit a tile
        # without total_E triples of headroom); default sized 4x the
        # next buffer so R_EDGE_FLUSH drains stay block-sized
        if self._edges_on:
            self.edge_cap = max(int(self._edge_capacity
                                    or 4 * self.next_cap),
                                self._total_E() + self.tile)
            run.ebufs = tuple(jnp.zeros((self.edge_cap,), I32)
                              for _ in range(3))
        # a host scalar as a dispatch's outputs are: int32, committed to
        # the table's device.  What is chained on a dispatch takes its
        # outputs where a first launch takes these, and an argument of
        # another kind is a program more — built inside a window whose
        # warm-up saw one-page levels only
        run.home = next(iter(run.table["slots"].devices()))
        run.zero, run.running = (self._scalar(run, 0),
                                 self._scalar(run, RUNNING))

    @staticmethod
    def _scalar(run, x):
        return jax.device_put(np.int32(x), run.home)

    def _spill(self, run):
        """Page the first n_next rows of the next buffers out to
        host RAM and reset the counter.  Only with no real page
        in flight: it reads the chain-tip buffers, which are the
        last collected dispatch's (what is dropped behind a
        pause committed nothing)."""
        if run.n_next == 0:
            return
        obs, depth = run.obs, run.depth
        if self.pipe_window > 1:
            # the chain tip may still run (a dispatch dropped
            # behind a pause): that wait is the device's work
            run.pipe.wait(run.bufs[1])
        with obs.span(spans.PAGE_OUT, depth=depth, rows=run.n_next):
            pages = self._page_out(run.bufs, run.n_next)
        # par is page-relative; lift to level-relative now (the
        # newest collect left n_next, so the last seg ends there)
        firsts, ends = zip(*run.segs)
        lift = np.repeat(np.asarray(firsts, np.int64),
                         np.diff((0,) + ends))
        run.segs.clear()
        row_bytes = self._state_row_bytes()
        at = 0
        for rows, par, act, prm in pages:
            run.drained.append(rows)
            run.d_par.append(par.astype(np.int64)
                             + lift[at:at + len(par)])
            at += len(par)
            run.d_act.append(act)
            run.d_prm.append(prm)
            obs.spill(depth, len(par), len(par) * row_bytes)
        run.n_next_total += run.n_next
        self.spill_rows += run.n_next
        run.n_next = 0

    def _refloor_edges(self, run):
        """Kernel rebuilt with (possibly) wider caps: drain
        the plain-int triples, re-floor the append buffer
        against the new total_E headroom requirement, and
        re-zero it (a stale floor live-locks the commit gate,
        exactly like the next_cap floor)."""
        self._drain_edges(run)
        self.edge_cap = max(self.edge_cap, self._total_E() + self.tile)
        run.ebufs = tuple(jnp.zeros((self.edge_cap,), I32)
                          for _ in range(3))

    def _drain_edges(self, run):
        """Drain the committed edge triples off the device
        append buffer into the CSR builder (ISSUE 15).  As
        `_spill`: with no real page in flight, off the chain
        tip."""
        n_edge = run.n_edge
        if not self._edges_on or n_edge == 0:
            return
        es, ea, ed = run.ebufs
        with run.obs.span(spans.HOST_SYNC):
            s, a, d = jax.device_get(
                (es[:n_edge], ea[:n_edge], ed[:n_edge]))
        self.edge_sink.append(np.asarray(s), np.asarray(a),
                              np.asarray(d))
        self._edge_rows_total += n_edge
        self._edge_hw = max(self._edge_hw, n_edge)
        run.obs.edge_flush(run.depth, n_edge, n_edge * EdgeCSR.ROW_BYTES)
        run.n_edge = 0

    def _put_page(self, run, page):
        block = self._front_block(run.host_front, page.start, page.n)
        with run.obs.span(spans.PAGE_IN, depth=run.depth, rows=page.n):
            page.dev = self._page_in(block, page.n)
        run.obs.page_in(run.depth, page.n,
                        page.n * self._state_row_bytes())

    def _expand_level(self, run):
        """One level of the host frontier: the window runs over its
        PAGES (module docstring), the next buffer goes out to the host
        when it fills, the edge buffer when it does.  Returns True
        where the level ended the run with a verdict."""
        obs, pipe, res = run.obs, run.pipe, run.res
        emit = obs.log
        depth, n_front, level_base = run.depth, run.n_front, run.level_base
        if self.retain_levels:
            # level blocks stay DENSE: the device liveness graph
            # builder enumerates them as plane dicts
            self.level_blocks.append(
                self._pk.unpack_np(run.host_front)
                if self._pk is not None else run.host_front)
        # per-level host accumulators for drained next states and
        # their (level-relative) trace pointers.  Disk tier:
        # `drained` is a SpillTier — same .append seam, but pages
        # beyond the RAM budget flush to level files
        run.drained = (self._tier(depth, None, obs)
                       if self._spill_dir is not None else [])
        run.d_par, run.d_act, run.d_prm = [], [], []
        run.n_next_total = 0
        run.n_next = 0
        # whose rows the next buffer holds: (first frontier row of a
        # page, rows of the buffer up to that page's last collect),
        # in commit order — the pages of a burst append to one
        # buffer, and a trace pointer is relative to its own page
        run.segs = []
        zero, running = run.zero, run.running

        def scalar(x):
            return self._scalar(run, x)

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
            while run.stop is None and pipe.has_room() and (
                    todo or next_row < n_front):
                if todo:
                    page = todo.popleft()
                else:
                    n = min(cc, n_front - next_row)
                    page = _Page(next_row, n, -(-n // self.tile))
                    next_row += n
                if page.dev is None:
                    self._put_page(run, page)
                if tip is None:
                    pend_t = _page_start(
                        zero, running, zero,
                        scalar(resume_t), scalar(page.tiles))
                    pend_nn = scalar(run.n_next)
                    pend_en = scalar(run.n_edge)
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
                nb, nbp, nba, nbprm = run.bufs
                eb_arg, emeta_arg = None, None
                if self._edges_on:
                    # gid_base maps a next-buffer row to its
                    # global gid (spilled rows precede the
                    # buffer); src_base lifts a page row to its
                    # frontier gid.  gid_base is constant within
                    # a pipelined burst: spills only happen with
                    # nothing in flight
                    eb_arg = run.ebufs
                    emeta_arg = {
                        "n": pend_en,
                        "src_base": jnp.asarray(
                            level_base + page.start, I32),
                        "gid_base": jnp.asarray(
                            level_base + n_front
                            + run.n_next_total, I32)}
                out = pipe.launch(
                    self._run_level, run.table, page.dev,
                    jnp.asarray(page.n, I32), pend_t,
                    nb, nbp, nba, nbprm, pend_nn,
                    jnp.asarray(bool(run.check_deadlock)),
                    eb_arg, emeta_arg,
                    jnp.asarray(depth - 1, I32),
                    fresh=self._fresh_jit,
                    depth=depth)
                self._fresh_jit = False
                run.table = {"slots": out["slots"]}
                if self._por_active:
                    run.table["gids"] = out["gids"]
                run.bufs = (out["nb"], out["nbp"], out["nba"],
                            out["nbprm"])
                if self._edges_on:
                    run.table["gids"] = out["gids"]
                    run.ebufs = (out["eb_src"], out["eb_aid"],
                                 out["eb_dst"])
                resume_t = 0
                tip = (out, page.tiles)
                flying.append(page)
            if not flying:
                break       # the level's last page, or a stop
            page = flying.popleft()
            out, reason, start_t, run.n_next, n_edge = self._collect(run)
            if self._edges_on:
                run.n_edge = n_edge
            run.segs.append((page.start, run.n_next))

            if reason == RUNNING and start_t >= page.tiles:
                # the page is done; what is queued behind it is
                # the next page, running: on a stop it is
                # collected and counted like this one
                self._account_tiles(page.tiles)
                obs.boundary(depth=depth, chunk=page.start // cc)
                obs.progress(depth=depth, distinct=run.fp_count,
                             generated=res.states_generated,
                             frontier=n_front, extra="host-paged")
                run.out_of_time(time.time())
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
            if self._verdict(run, reason, out, page.start,
                             lambda i: self._host_row(run.host_front, i)):
                return True
            if run.stop is not None:
                # a page collected behind a stop paused: what it
                # committed is counted, and nothing re-enters
                break
            if reason == R_NEXT_GROW:
                # the spill tier: page the filled buffer out to
                # host RAM instead of growing it in HBM
                self.spill_count += 1
                self._spill(run)
            elif reason == R_EDGE_FLUSH:
                # edge append buffer full (ISSUE 15): drain the
                # committed triples into the CSR builder and
                # re-enter — the edge analog of the spill above
                self._drain_edges(run)
            elif reason == R_BAG_GROW:
                self._spill(run)
                self._grow_bag(run)
                for held in todo:   # cut again, at the new width
                    held.dev = None
                emit(f"message table grown to "
                     f"{self.codec.shape.MAX_MSGS} slots "
                     f"(recompiling)")
            elif reason == R_FPSET_GROW:
                self._grow_fpset(run)
            elif reason == R_EXPAND_GROW:
                self._grow_expand(int(out["grow_aid"]), obs, emit)
                if self.next_cap < self._total_E() + self.tile:
                    self._spill(run)
                    self._floor_next_cap()
                    run.bufs = self._alloc_bufs(self.next_cap)
                if self._edges_on and self.edge_cap < \
                        self._total_E() + self.tile:
                    self._refloor_edges(run)
            # a growth pause (or a dispatch that ran out of tiles
            # to run, which re-enters as it is) falls through here
            obs.progress(depth=depth, distinct=run.fp_count,
                         generated=res.states_generated,
                         frontier=n_front, extra="host-paged")
            if run.out_of_time(time.time()):
                break
        if todo:
            # a stop behind a pause: the tiles the page had done
            self._account_tiles(resume_t)
        return False

    def _grow_bag(self, run):
        """R_BAG_GROW with the next buffer paged out: rebuild at twice
        the message table, and pad every row the host holds."""
        old = self.codec.shape.MAX_MSGS
        old_pk = self._pk
        self._build(old * 2)
        run.obs.grow("message_table", self.codec.shape.MAX_MSGS)
        if old_pk is not None:
            # packed pages: round-trip through the OLD
            # spec to dense, pad, re-pack under the
            # rebuilt one (see DeviceBFS._grow_msgs)
            def regrow(rows):
                d = self.codec.pad_msgs(old_pk.unpack_np(rows), old)
                return self._pk.pack_np(d)
        else:
            def regrow(rows):
                return self.codec.pad_msgs(rows, old)
        if self._spill_dir is not None:
            run.host_front.map_pages(regrow)
            run.drained.map_pages(regrow)
        else:
            run.host_front = regrow(run.host_front)
            run.drained = [regrow(d) for d in run.drained]
        self.level_blocks = [self.codec.pad_msgs(b, old)
                             for b in self.level_blocks]
        self._pad_init_dense(old)
        self._floor_next_cap()
        run.bufs = self._alloc_bufs(self.next_cap)
        if self._edges_on:
            self._refloor_edges(run)

    def _close_level(self, run):
        # level done (or stopped): page out what accumulated, and
        # drain the committed edge triples (a level boundary
        # always finds both buffers empty)
        self._spill(run)
        self._drain_edges(run)

    def _hand_over(self, run):
        """Assemble the next frontier on the host from what the level
        paged out, lift its pointers to gids, drop the level it came
        from."""
        host_front, drained = run.host_front, run.drained
        if run.n_next_total:
            if self._spill_dir is not None:
                host_next = drained       # the tier holds the rows
            elif self._pk is not None:
                host_next = np.concatenate(drained)
            else:
                host_next = {k: np.concatenate(
                    [d[k] for d in drained]) for k in host_front}
            self._h_parent.append(
                np.concatenate(run.d_par) + run.level_base)
            self._h_action.append(np.concatenate(run.d_act))
            self._h_param.append(np.concatenate(run.d_prm))
            self.level_sizes.append(run.n_next_total)
        else:
            host_next = (drained if self._spill_dir is not None
                         else self._host_zero(0))
        run.level_base += run.n_front
        if self._spill_dir is not None:
            # the consumed level's files are dead weight now:
            # steady-state disk holds two levels' worth of rows
            host_front.drop()
        run.host_front = host_next
        run.n_front = run.n_next_total

    def _snapshot_keywords(self, run):
        from .spill import SpillTier
        # disk-tiered frontier: STREAM pages into the staged
        # npz (peak residency = one page) instead of
        # materializing n_front dense rows (ISSUE 13 satellite
        # — the PR 11 save_checkpoint residual)
        kw = ({"frontier_blocks":
               self._front_dense_blocks(run.host_front, run.n_front)}
              if isinstance(run.host_front, SpillTier) else
              self._snapshot_frontier(run.host_front, run.n_front))
        if self._edges_on:
            # edge-stream seam (ISSUE 15): the gid column,
            # the drained edge rows up to this committed
            # level, and — on a retain_levels (temporal) run
            # — the retained level blocks, so a SIGTERM'd
            # temporal run resumes to a bit-identical CSR
            kw["gids"] = np.asarray(run.table["gids"])
            kw["edge_blocks"] = self.edge_sink.blocks()
            if self.retain_levels:
                kw["graph_blocks"] = iter(self.level_blocks)
        return kw

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
