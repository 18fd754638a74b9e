"""Packed bit-planed frontier encoding (ISSUE 9 tentpole).

The dense state layout (models/*.py ``zero_state``) spends a full
int32 lane on every field, but the speclint ``widths`` pass
(analysis/passes/widths.py) proves most fields fit a handful of bits:
at the defect constants a view number needs 3 bits, a log-entry code
4, a replica id 2 — yet the at-rest frontier, the host spill pages and
the sharded all-to-all all move 32 bits per field.  CAPACITY.md shows
the dense frontier (7.2 KB/state at MAX_MSGS=48), not fingerprints, is
the binding HBM constraint of the defect-scale BFS — so shrinking
bytes/state multiplies both frontier capacity and exchange bandwidth
(the Lazy-TSO-Reachability move, arxiv 1501.02683: pay only for what
the reachability front actually needs).

This module turns the per-field bit budgets into a first-class
interchange format:

* ``build_pack_spec(codec, spec)`` derives a :class:`PackSpec` from the
  codec's ``plane_bounds`` (per-plane — or per-column, for
  heterogeneous planes like ``m_hdr`` — value ranges computed from the
  SAME shape attributes and ``widths.derive_ranges`` table the lint
  pass verifies) — the widths table is the single source of truth for
  field widths (ISSUE 9 satellite; the drift pass cross-checks the
  codec constants against it);
* ``pack``/``unpack`` convert one int32 struct-of-arrays state row to
  and from a ``[words]`` uint32 plane: every lane is biased by its
  lower bound and laid into a contiguous bit stream (a lane may
  straddle two words), so a row costs ``ceil(total_bits / 32)`` words
  instead of one word per lane.  Both directions are pure jnp integer
  ops — jit- and vmap-friendly — and ``pack_np``/``unpack_np`` are
  bit-identical numpy twins for host-side work (paged spill
  compaction, checkpoint conversion);
* the round trip is EXACT for every in-range value (the pack property
  tests drive edge values at each field's width boundary), so the
  engines' distinct/generated/level_sizes/traces stay bit-identical
  with packing on or off — the PR 4 drain-and-replay discipline
  extended to the state representation;
* ``manifest()``/``from_manifest`` serialize the spec into checkpoint
  manifests: a snapshot records the packing-spec ``version`` (a digest
  of the plane table), resume under a mismatched widths table is a
  policy error (TLAError), and a pack=off engine can still read a
  packed snapshot through the manifest's own table (and vice versa).

Planes without a provable bound (e.g. the message-bag ``m_count``
column — TLC bag counts have no static bound) keep their full 32 bits;
the format degrades gracefully to ratio 1.0 for codecs that declare no
bounds at all (``build_pack_spec`` returns None and the engines run
dense unless packing is forced).
"""

from __future__ import annotations

import hashlib
import json

import numpy as np

from ..core.values import TLAError
from ..obs import spans

WORD_BITS = 32
_FULL = np.uint32(0xFFFFFFFF)


def _bits_for(lo, hi):
    """Bits needed to store values lo..hi (biased by -lo); >= 32 falls
    back to a raw 32-bit lane (lo forced to 0 so negative int32 values
    round-trip through the uint32 reinterpretation)."""
    span = int(hi) - int(lo)
    if span < 0:
        raise TLAError(f"packing bound ({lo}, {hi}) is empty")
    bits = max(1, span.bit_length())
    if bits >= WORD_BITS:
        return 0, WORD_BITS
    return int(lo), bits


def _normalize_bounds(key, shape, bound):
    """One plane's declared bound -> per-lane (lo, bits) numpy vectors.

    ``bound`` is ``(lo, hi)`` (uniform) or a sequence of per-column
    ``(lo, hi)`` pairs applying along the plane's LAST axis (the
    column axis of heterogeneous planes like ``m_hdr``/``log``);
    ``None`` keeps raw 32-bit lanes."""
    lanes = int(np.prod(shape) or 1)
    if bound is None:
        return (np.zeros(lanes, np.int64),
                np.full(lanes, WORD_BITS, np.int64), None)
    if isinstance(bound, tuple) and len(bound) == 2 and \
            not isinstance(bound[0], (tuple, list)):
        lo, bits = _bits_for(*bound)
        return (np.full(lanes, lo, np.int64),
                np.full(lanes, bits, np.int64), (lo, bits))
    cols = list(bound)
    if not shape or shape[-1] != len(cols):
        raise TLAError(
            f"plane {key!r}: per-column bounds ({len(cols)} entries) "
            f"do not match the last axis of shape {shape}")
    per = [_bits_for(*b) for b in cols]
    reps = lanes // len(cols)
    lo = np.tile(np.asarray([p[0] for p in per], np.int64), reps)
    bits = np.tile(np.asarray([p[1] for p in per], np.int64), reps)
    return lo, bits, [list(p) for p in per]


class PackSpec:
    """Static layout of the packed row format for one codec binding.

    ``entries`` is a list of ``(key, shape, lo_norm, bits_norm)`` in
    the codec's ``zero_state`` plane order; lo/bits are normalized to
    either an ``(lo, bits)`` pair or a per-column list."""

    def __init__(self, entries):
        self.entries = entries
        self.keys = [e[0] for e in entries]
        self.shapes = {e[0]: tuple(e[1]) for e in entries}
        lo_parts, bit_parts, self._splits = [], [], []
        pos = 0
        for key, shape, _norm, (lo_vec, bits_vec) in (
                (e[0], e[1], e[2], e[3]) for e in entries):
            lanes = lo_vec.shape[0]
            self._splits.append((key, tuple(shape), pos, pos + lanes))
            pos += lanes
            lo_parts.append(lo_vec)
            bit_parts.append(bits_vec)
        self.lanes = pos
        lo = np.concatenate(lo_parts)
        bits = np.concatenate(bit_parts)
        start = np.concatenate([[0], np.cumsum(bits)[:-1]])
        self.total_bits = int(bits.sum())
        self.words = max(1, -(-self.total_bits // WORD_BITS))
        # static per-lane tables (numpy; closed over by the jnp fns)
        self._lo = lo.astype(np.int32)
        self._bits = bits
        self._mask = np.where(
            bits >= WORD_BITS, _FULL,
            (np.uint64(1) << bits.astype(np.uint64)) - 1
        ).astype(np.uint32)
        self._widx = (start // WORD_BITS).astype(np.int32)
        self._off = (start % WORD_BITS).astype(np.uint32)
        self._hishift = (WORD_BITS - 1 - self._off).astype(np.uint32)
        canon = [[k, list(s), n] for k, s, n, _v in entries]
        self.version = hashlib.sha256(
            json.dumps(canon, sort_keys=True).encode()).hexdigest()[:12]
        # the packed all-zero state, one ``[words]`` row: what pads a
        # packed buffer.  Not all-zero words: a lane whose range starts
        # below 0 packs 0 as ``-lo``
        self.zero_row = self.pack_np(
            {k: np.zeros((1,) + s, np.int32)
             for k, s, _a, _b in self._splits})[0]
        self.zero_row.setflags(write=False)

    # -- sizing --------------------------------------------------------
    @property
    def dense_bytes(self):
        """Bytes of one dense int32 row (the format packing replaces)."""
        return self.lanes * 4

    @property
    def packed_bytes(self):
        return self.words * 4

    @property
    def ratio(self):
        return self.dense_bytes / self.packed_bytes

    # -- manifest ------------------------------------------------------
    def manifest(self):
        """JSON-able description stored in checkpoint manifests: enough
        to rebuild the exact layout (``from_manifest``) plus the
        ``version`` digest resume compatibility is judged by."""
        return {"version": self.version, "words": self.words,
                "planes": [[k, list(s), n]
                           for k, s, n, _v in self.entries]}

    @classmethod
    def from_manifest(cls, mf):
        entries = []
        for key, shape, norm in mf["planes"]:
            shape = tuple(shape)
            if norm is None:
                bound = None
            elif norm and isinstance(norm[0], list):
                # per-column [lo, bits] pairs -> reconstruct (lo, hi)
                bound = [(lo, lo + (1 << b) - 1) if b < WORD_BITS
                         else None for lo, b in norm]
                # a raw column inside a per-column plane: widen to the
                # 32-bit sentinel range understood by _bits_for
                bound = [(0, (1 << 31)) if b is None else b
                         for b in bound]
            else:
                lo, b = norm
                bound = (lo, lo + (1 << b) - 1) if b < WORD_BITS \
                    else (0, 1 << 31)
            lo_vec, bits_vec, norm2 = _normalize_bounds(key, shape,
                                                        bound)
            entries.append((key, shape, norm2, (lo_vec, bits_vec)))
        spec = cls(entries)
        if spec.version != mf["version"] or spec.words != mf["words"]:
            raise TLAError(
                f"packing manifest is internally inconsistent "
                f"(version {mf['version']} / {mf['words']} words vs "
                f"rebuilt {spec.version} / {spec.words})")
        return spec

    # -- jnp pack/unpack (one row; vmap for batches) -------------------
    def pack(self, state):
        """Dense per-row state dict (int32 leaves, per-plane shapes)
        -> ``[words]`` uint32 row.  Pure jnp; call under jit/vmap."""
        import jax
        import jax.numpy as jnp
        with jax.named_scope(spans.PACK_SCATTER):
            parts = [jnp.asarray(state[k], jnp.int32).reshape(-1)
                     for k, _s, _p0, _p1 in self._splits_iter()]
            flat = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
            v = (flat - jnp.asarray(self._lo)).astype(jnp.uint32) \
                & jnp.asarray(self._mask)
            off = jnp.asarray(self._off)
            lo_w = jnp.left_shift(v, off)
            hi_w = jnp.right_shift(
                jnp.right_shift(v, jnp.asarray(self._hishift)), 1)
            widx = jnp.asarray(self._widx)
            words = jax.ops.segment_sum(
                jnp.concatenate([lo_w, hi_w]),
                jnp.concatenate([widx, widx + 1]),
                num_segments=self.words + 1)
            return words[:self.words].astype(jnp.uint32)

    def unpack(self, row):
        """``[words]`` uint32 row -> dense per-row state dict."""
        import jax
        import jax.numpy as jnp
        with jax.named_scope(spans.PACK_SCATTER):
            w = jnp.asarray(row, jnp.uint32)
            widx = jnp.asarray(self._widx)
            w0 = w[widx]
            w1 = w[jnp.minimum(widx + 1, self.words - 1)]
            off = jnp.asarray(self._off)
            v = (jnp.right_shift(w0, off)
                 | jnp.left_shift(
                     jnp.left_shift(w1, jnp.asarray(self._hishift)), 1)) \
                & jnp.asarray(self._mask)
            flat = v.astype(jnp.int32) + jnp.asarray(self._lo)
            return {k: flat[a:b].reshape(s)
                    for k, s, a, b in self._splits}

    def _splits_iter(self):
        return self._splits

    # -- numpy twins (batched; host-side spill/checkpoint work) --------
    def pack_np(self, batch):
        """Dense batch dict (``[N, ...plane]`` arrays) -> ``[N, words]``
        uint32.  Bit-identical to the jnp ``pack``."""
        first = batch[self._splits[0][0]]
        n = np.asarray(first).shape[0]
        flat = np.concatenate(
            [np.asarray(batch[k], np.int32).reshape(n, -1)
             for k, _s, _a, _b in self._splits], axis=1)
        v = (flat.astype(np.int64) - self._lo[None, :]).astype(
            np.uint32) & self._mask[None, :]
        lo_w = np.left_shift(v, self._off[None, :])
        hi_w = np.right_shift(
            np.right_shift(v, self._hishift[None, :]), 1)
        out = np.zeros((n, self.words + 1), np.uint32)
        np.add.at(out, (slice(None),
                        np.concatenate([self._widx, self._widx + 1])),
                  np.concatenate([lo_w, hi_w], axis=1))
        return out[:, :self.words]

    def unpack_np(self, rows):
        """``[N, words]`` uint32 -> dense batch dict of int32 arrays."""
        w = np.asarray(rows, np.uint32)
        if w.ndim == 1:
            w = w[None]
            squeeze = True
        else:
            squeeze = False
        w0 = w[:, self._widx]
        w1 = w[:, np.minimum(self._widx + 1, self.words - 1)]
        v = (np.right_shift(w0, self._off[None, :])
             | np.left_shift(
                 np.left_shift(w1, self._hishift[None, :]), 1)) \
            & self._mask[None, :]
        flat = v.astype(np.uint32).view(np.int32) + self._lo[None, :]
        out = {}
        for k, s, a, b in self._splits:
            arr = flat[:, a:b].reshape((w.shape[0],) + s)
            out[k] = arr[0] if squeeze else arr
        return out

    def unpack_row_np(self, row):
        """One ``[words]`` row -> per-row dense dict (numpy): plane
        shapes WITHOUT a leading batch axis (the 1-D input takes
        ``unpack_np``'s squeeze path)."""
        return self.unpack_np(np.asarray(row).reshape(-1))


def build_pack_spec(codec, spec=None, ranges=None, force=False,
                    tighten=None):
    """Derive the :class:`PackSpec` for a codec binding.

    ``ranges`` is the widths-pass field-range table
    (``analysis.passes.widths.derive_ranges``); when absent it is
    derived from ``spec`` — the ONE declared-range source the lint
    table, the codecs' ``plane_bounds`` hooks and the bounds pass all
    read (ISSUE 13 satellite).  Codecs that declare no
    ``plane_bounds`` return None (dense is already optimal
    knowledge-free) unless ``force`` — then every lane keeps 32 bits
    (ratio 1.0) so the interchange format still exists.

    ``tighten`` is the bounds pass's reachable-interval map
    (``BoundsFacts.plane_tighten()``, ISSUE 13): plane keys matching a
    tightened state variable have their declared bound INTERSECTED
    with the reachable interval — fewer bits per lane, and since the
    intervals over-approximate reachability the round trip stays
    exact for every reachable state (the bit-identity oracle in
    tests/test_bounds.py).  Only uniform (or absent) declared bounds
    tighten; per-column planes keep their declared table."""
    bounds = {}
    if hasattr(codec, "plane_bounds"):
        if ranges is None and spec is not None:
            from ..analysis.passes.widths import derive_ranges
            ranges = derive_ranges(spec)
        bounds = codec.plane_bounds(ranges or {})
    elif not force:
        return None
    zero = codec.zero_state()
    if tighten:
        bounds = dict(bounds)
        for key, (tlo, thi) in tighten.items():
            if key not in zero:
                continue                    # not a plane of this codec
            cur = bounds.get(key)
            if cur is None:
                bounds[key] = (int(tlo), int(thi))
            elif isinstance(cur, tuple) and len(cur) == 2 and \
                    not isinstance(cur[0], (tuple, list)):
                lo, hi = max(cur[0], int(tlo)), min(cur[1], int(thi))
                if lo <= hi:
                    bounds[key] = (lo, hi)  # reachable ∩ declared
            # per-column declared tables keep their own budgets
    entries = []
    for key, z in zero.items():
        shape = tuple(np.shape(z))
        lo_vec, bits_vec, norm = _normalize_bounds(
            key, shape, bounds.get(key))
        entries.append((key, shape, norm, (lo_vec, bits_vec)))
    return PackSpec(entries)
