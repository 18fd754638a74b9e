"""NVMe/disk spill tier for the paged engine's host frontier pages
(ISSUE 11, the CAPACITY.md mitigation-2 ladder).

The paged engine (engine/paged_bfs.py) already tiers the frontier out
of HBM into host RAM — which prices ~189 M packed defect-layout states
on a 125 GB host (CAPACITY.md).  TLC solved the same wall with a
disk-backed state queue and burned 500 GB on the reference corpus
(arxiv 2211.07216 frames that as the bound to beat); this module adds
the equivalent third rung: when a level's accumulated host pages
exceed a RAM budget, whole pages are flushed to append-only level
files on disk and re-read sequentially when the next level pages them
through the device, turning the host-RAM ceiling into a disk-priced
10^9-state ceiling.

Design:

* one :class:`SpillTier` per FRONTIER LEVEL — the paged engine's
  drains append blocks (packed ``[n, words]`` uint32 rows, or dense
  plane dicts when packing is off) in commit order;
* an in-RAM page index only: ``(path, rows)`` per flushed file plus
  the un-flushed RAM tail — the tier never holds more than
  ``ram_rows`` resident rows (plus one in-flight drain block);
* level files are append-only and immutable once written
  (``L<level>_<seq>.npz``); the consumed level's tier is dropped
  (files deleted) once the next level is assembled, so steady-state
  disk usage is two levels' worth of packed rows;
* reads are sequential block gathers (``block(start, n)``) matching
  the chunk-in transfer pattern, plus ``row(i)`` random access for
  violation/deadlock parent materialization;
* ``map_pages`` rewrites every page through a transform — the
  MAX_MSGS bag-growth re-pack rides it;
* a spilled frontier's checkpoint stores DENSE planes (the
  engine-agnostic interchange format every snapshot loads as), so a
  resume re-packs and re-spills under the resuming run's own budget.
  Snapshot WRITES stream (ISSUE 13
  satellite — the PR 11 residual): ``save_checkpoint`` accepts a
  block iterator (``frontier_blocks``) and the paged engine feeds it
  the tier's pages one at a time (``PagedBFS._front_dense_blocks``),
  so peak residency during a checkpoint is one page, not the dense
  frontier; ``load_checkpoint`` reassembles the chunked payload
  transparently and a resume re-spills past the RAM budget as before.

The journal records each disk flush as a ``spill`` event with
``tier: "disk"`` (device->host RAM drains carry no ``tier`` key), and
the engine gauges cumulative ``spill_tier_bytes``.
"""

from __future__ import annotations

import glob
import os

import numpy as np


def _block_rows(block):
    if isinstance(block, dict):
        k = next(iter(block))
        return int(block[k].shape[0])
    return int(block.shape[0])


def _concat(blocks):
    if isinstance(blocks[0], dict):
        return {k: np.concatenate([b[k] for b in blocks])
                for k in blocks[0]}
    return np.concatenate(blocks)


def _slice(block, lo, hi):
    if isinstance(block, dict):
        return {k: v[lo:hi] for k, v in block.items()}
    return block[lo:hi]


class SpillTier:
    """Append-only disk-backed row store for one frontier level."""

    def __init__(self, dirpath, level, ram_rows, obs=None, depth=None):
        self.dir = dirpath
        self.level = int(level)
        self.ram_rows = max(1, int(ram_rows))
        self._ram = []           # un-flushed blocks, in append order
        self._ram_count = 0
        self._pages = []         # [(path, rows)], flush order
        self._seq = 0
        self.rows = 0
        self.disk_bytes = 0      # cumulative bytes written to disk
        self._obs = obs
        self._depth = depth if depth is not None else level
        self._last = None        # (path, data) — one-page read cache
        os.makedirs(dirpath, exist_ok=True)
        # a killed run may have left THIS level's files behind; the
        # resumed run can flush fewer/differently-sized pages under
        # the same names, so stale leftovers would leak past drop()
        # forever — reclaim them up front (the tier owns its dir)
        for stale in glob.glob(os.path.join(
                dirpath, f"L{self.level:05d}_*.npz")):
            try:
                os.unlink(stale)
            except OSError:
                pass

    # -- write side ----------------------------------------------------
    def append(self, block):
        n = _block_rows(block)
        if n == 0:
            return
        self._ram.append(block)
        self._ram_count += n
        self.rows += n
        if self._ram_count > self.ram_rows:
            self._flush()

    def _flush(self):
        if not self._ram_count:
            return
        block = _concat(self._ram)
        path = os.path.join(self.dir,
                            f"L{self.level:05d}_{self._seq:05d}.npz")
        self._seq += 1
        with open(path, "wb") as f:
            if isinstance(block, dict):
                np.savez(f, **block)
            else:
                np.savez(f, rows=block)
            f.flush()
            os.fsync(f.fileno())
        nbytes = os.path.getsize(path)
        self._pages.append((path, self._ram_count))
        self.disk_bytes += nbytes
        if self._obs is not None:
            self._obs.spill(self._depth, self._ram_count, nbytes,
                            tier="disk")
        self._ram = []
        self._ram_count = 0

    # -- read side -----------------------------------------------------
    def _load(self, path):
        # one-page cache: the chunk loop's reads are monotonic, so a
        # page overlapping several chunks would otherwise be re-read
        # (and re-decoded) once per chunk instead of once per level
        if self._last is not None and self._last[0] == path:
            return self._last[1]
        with np.load(path, allow_pickle=False) as z:
            if z.files == ["rows"]:
                data = z["rows"]
            else:
                data = {k: z[k] for k in z.files}
        self._last = (path, data)
        return data

    def _iter_pages(self):
        """Yield (start_row, rows, loader) over disk pages then the
        RAM tail, in global row order."""
        pos = 0
        for path, n in self._pages:
            yield pos, n, (lambda p=path: self._load(p))
            pos += n
        for b in self._ram:
            n = _block_rows(b)
            yield pos, n, (lambda b=b: b)
            pos += n

    def block(self, start, n):
        """Rows [start, start+n) assembled across page boundaries."""
        assert 0 <= start and start + n <= self.rows
        parts = []
        for pos, pn, load in self._iter_pages():
            if pos + pn <= start or pos >= start + n:
                continue
            data = load()
            lo = max(0, start - pos)
            hi = min(pn, start + n - pos)
            parts.append(_slice(data, lo, hi))
        return _concat(parts)

    def row(self, i):
        return self.block(int(i), 1)

    # -- maintenance ---------------------------------------------------
    def map_pages(self, fn):
        """Rewrite every page (disk and RAM) through ``fn(block) ->
        block`` — the bag-growth re-pack path.  Row counts must be
        preserved."""
        new_pages = []
        for path, n in self._pages:
            block = fn(self._load(path))
            assert _block_rows(block) == n
            self.disk_bytes -= os.path.getsize(path)
            with open(path, "wb") as f:
                if isinstance(block, dict):
                    np.savez(f, **block)
                else:
                    np.savez(f, rows=block)
                f.flush()
                os.fsync(f.fileno())
            self.disk_bytes += os.path.getsize(path)
            new_pages.append((path, n))
        self._pages = new_pages
        self._ram = [fn(b) for b in self._ram]
        self._last = None

    def drop(self):
        """Delete this level's files (the level has been consumed)."""
        for path, _n in self._pages:
            try:
                os.unlink(path)
            except OSError:
                pass
        self._pages = []
        self._ram = []
        self._ram_count = 0
        self._last = None


class EdgeCSR:
    """Incremental host CSR builder for the streamed behavior graph
    (ISSUE 15).  The level kernel's edge-emission commit drains
    ``(src gid, action id, dst gid)`` triples here in COMMIT ORDER;
    ``finalize(n)`` assembles the CSR arrays ``(indptr[n+1], aid[m],
    tid[m])`` the fair-SCC machinery consumes, preserving the drained
    order within each source's segment (the documented bit-identity
    contract: streamed vs two-pass CSRs agree modulo edge order within
    a (src, level) segment).

    Two storage modes: plain RAM blocks, or — past a RAM budget — the
    :class:`SpillTier` disk tier (append-only edge page files under
    ``<spill_dir>/edges``), so a 10^8-edge graph's triples never
    compete with the frontier for host RAM during the BFS.  A per-src
    degree count accumulates as blocks arrive, so ``finalize`` is two
    sequential passes (prefix-sum the counts, then scatter each block
    into its cursor positions) with no global sort."""

    #: bytes one edge row costs on the device append buffer
    ROW_BYTES = 12          # 3 x int32

    def __init__(self, spill_dir=None, ram_rows=None, obs=None):
        self._tier = None
        self._blocks = []
        if spill_dir:
            self._tier = SpillTier(os.path.join(spill_dir, "edges"),
                                   0, ram_rows or (1 << 20), obs=obs)
        self._counts = np.zeros(1024, np.int64)
        self.rows = 0

    def append(self, src, aid, dst):
        src = np.ascontiguousarray(src, np.int64)
        n = int(src.shape[0])
        if n == 0:
            return
        hi = int(src.max()) + 1
        if hi > self._counts.shape[0]:
            grown = np.zeros(max(hi, 2 * self._counts.shape[0]),
                             np.int64)
            grown[:self._counts.shape[0]] = self._counts
            self._counts = grown
        self._counts[:hi] += np.bincount(src, minlength=hi)
        block = {"src": src,
                 "aid": np.ascontiguousarray(aid, np.int32),
                 "dst": np.ascontiguousarray(dst, np.int32)}
        if self._tier is not None:
            self._tier.append(block)
        else:
            self._blocks.append(block)
        self.rows += n

    def seed(self, block):
        """Re-seed from a checkpoint's reassembled edge payload (one
        dict of concatenated src/aid/dst arrays): the resumed stream
        continues in the same order, so the final CSR is bit-identical
        to an uninterrupted run's."""
        self.append(block["src"], block["aid"], block["dst"])

    def blocks(self):
        """Iterator of the accumulated blocks in drain order — the
        checkpoint writer's streaming input (one page resident at a
        time on the disk tier)."""
        if self._tier is not None:
            for _pos, _n, load in self._tier._iter_pages():
                yield load()
        else:
            yield from self._blocks

    def finalize(self, n):
        """Assemble ``(indptr, aid, tid)`` over node ids ``0..n-1``."""
        assert int(self._counts[n:].sum()) == 0, \
            "edge stream names a src gid beyond the state count"
        if self._counts.shape[0] < n:
            # counts only grow to the highest EDGE-EMITTING src gid —
            # trailing terminal states (no enabled action) are legal
            # zero-degree nodes, so pad rather than crash
            grown = np.zeros(n, np.int64)
            grown[:self._counts.shape[0]] = self._counts
            self._counts = grown
        indptr = np.zeros(n + 1, np.int64)
        np.cumsum(self._counts[:n], out=indptr[1:])
        assert int(indptr[-1]) == self.rows
        aid = np.empty(self.rows, np.int32)
        tid = np.empty(self.rows, np.int32)
        cursor = indptr[:-1].copy()
        for block in self.blocks():
            s = np.asarray(block["src"], np.int64)
            order = np.argsort(s, kind="stable")
            ss = s[order]
            first = np.concatenate([[True], ss[1:] != ss[:-1]])
            starts = np.flatnonzero(first)
            runs = np.diff(np.concatenate([starts, [ss.shape[0]]]))
            rank = np.arange(ss.shape[0]) - np.repeat(starts, runs)
            pos = cursor[ss] + rank
            aid[pos] = np.asarray(block["aid"], np.int32)[order]
            tid[pos] = np.asarray(block["dst"], np.int32)[order]
            cursor[ss[starts]] += runs
        assert (cursor == indptr[1:]).all()
        return indptr, aid, tid

    def drop(self):
        if self._tier is not None:
            self._tier.drop()
        self._blocks = []
