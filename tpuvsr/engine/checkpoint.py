"""Checker-level checkpoint/resume (SURVEY.md §5): snapshot the BFS
engine at a level boundary — fingerprint table, live frontier, host
trace-pointer store, and counters — so multi-day runs survive
preemption, the analog of TLC's queue/FPSet checkpointing implied by
the reference's 500 GB multi-day guidance (README:20).

A checkpoint is one directory holding .npz payloads plus a JSON
manifest, written atomically and durably: the payloads are staged in a
tmp dir, fsynced (files, then the staged dir), the previous checkpoint
is renamed aside to ``<path>.old`` (rename is instant, unlike the
rmtree of a multi-GB snapshot), the tmp dir is renamed into place, the
parent directory is fsynced so the renames survive power loss, and
only then is ``.old`` deleted — so a crash or preemption at any point
leaves either the previous or the new snapshot loadable.

The manifest records a CRC32 per payload file; ``load_checkpoint``
verifies them (plus np.load-ability and frontier row counts) and falls
back to ``<path>.old`` on ANY payload-level corruption — a truncated
``fpset.npz`` with an intact manifest recovers the previous snapshot
instead of raising deep inside ``np.load`` (ISSUE 3 hardening).
Policy errors (format version, spec-digest mismatch) never fall back:
``.old`` would carry the same spec identity, and masking them behind a
silent downgrade would resume the wrong model.

The manifest also records a digest of the spec identity (module name,
constants, invariants, view/symmetry) so ``-recover`` with a mismatched
spec or .cfg is rejected instead of silently resuming with
incompatible fingerprints (TLC likewise errors on recover mismatch).

**Format 4** (ISSUE 36): the payloads hold what the run holds, in the
form the device holds it, so a snapshot costs what the run found and
not what its table could hold.  ``fpset.npz`` stores the OCCUPIED slots
(``index``: flat indices over the leading dimensions, ``rows``: their
words, ``shape``: the table's; ``gid_rows``: the same rows of the
parallel gid column) and the loader scatters them into zeros, so the
table comes back bit for bit.  ``frontier.npz`` stores the PACKED rows
(one member ``packed``, ``[n_front, words]``) when the writer hands
them over with its pack manifest; the loader unpacks them with
``PackSpec.from_manifest`` and still returns dense planes, the
interchange form any engine or pack configuration resumes.  Dense
writers and the streamed ``frontier_blocks`` path write dense planes
as before.  **Format 3** (the whole table and dense planes always) is
still read: a job whose worker died before the upgrade resumes.  File
names, CRCs, fsyncs, renames and the ``.old`` fallback are those of
format 3.
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import os
import re
import shutil
import zipfile
import zlib

import numpy as np
from numpy.lib import format as _npformat

from ..obs import spans

FORMAT_VERSION = 4
#: formats ``load_checkpoint`` reads: 3 wrote the whole table and dense
#: frontier planes; a 4 reader takes both member layouts by their names
READ_FORMATS = (3, 4)

#: the payload files of one snapshot directory, in write order
PAYLOADS = ("fpset.npz", "frontier.npz", "trace.npz", "init.npz")


class CheckpointCorrupt(ValueError):
    """A snapshot failed integrity verification (unreadable manifest,
    missing payload, CRC mismatch, undecodable npz, inconsistent row
    counts).  ``load_checkpoint`` falls back to ``.old`` on this."""


def spec_digest(spec) -> str:
    """Stable identity of (module, constants, invariants, view,
    symmetry) for recover-mismatch detection."""
    from ..core.values import fmt
    parts = [spec.module.name]
    for name in sorted(spec.ev.constants):
        parts.append(f"{name}={fmt(spec.ev.constants[name])}")
    parts.append("inv:" + ",".join(sorted(spec.cfg.invariants)))
    parts.append(f"view:{spec.cfg.view}")
    # the full permutation content, not just on/off: resuming under a
    # different SYMMETRY set means a different canonicalization and an
    # incompatible fingerprint space
    perms = sorted(
        ",".join(f"{fmt(a)}>{fmt(b)}" for a, b in sorted(
            p.items(), key=lambda kv: fmt(kv[0])))
        for p in spec.symmetry_perms)
    parts.append("symm:" + ";".join(perms))
    return hashlib.sha256("|".join(parts).encode()).hexdigest()[:16]


def _crc32_file(path):
    crc = 0
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            crc = zlib.crc32(chunk, crc)
    return crc & 0xFFFFFFFF


def _fsync_path(path):
    """fsync a file or directory by path (directory fsync is what makes
    a rename durable on POSIX)."""
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


#: chunked frontier payload member name: "<plane>.<chunk index>"
_CHUNK_RE = re.compile(r"^(.+)\.(\d{6})$")


def _write_frontier_chunks(path, blocks):
    """Stream an iterable of dense plane-dict blocks into one npz
    (ISSUE 13 satellite — the PR 11 residual): each block is written
    as it arrives (member ``<plane>.<i:06d>``) and dropped, so a
    disk-spilled frontier is checkpointed WITHOUT materializing it in
    RAM.  ``load_checkpoint`` reassembles the chunks transparently.
    Returns the number of rows written."""
    rows = 0
    with zipfile.ZipFile(path, "w", zipfile.ZIP_DEFLATED) as zf:
        for i, block in enumerate(blocks):
            n = None
            for k, v in block.items():
                arr = np.ascontiguousarray(np.asarray(v))
                n = arr.shape[0] if n is None else n
                with zf.open(f"{k}.{i:06d}.npy", "w") as f:
                    _npformat.write_array(f, arr, allow_pickle=False)
            rows += int(n or 0)
    return rows


def _dense_frontier(fr, manifest):
    """A loaded frontier payload as dense planes: packed rows (the
    manifest says ``frontier_packed``; one member, ``packed``) are
    unpacked by the spec the manifest's ``pack`` rebuilds; dense
    members, chunked or plain, are assembled."""
    if not manifest.get("frontier_packed"):
        return _assemble_frontier(fr)
    if set(fr) != {"packed"} or not manifest.get("pack"):
        raise CheckpointCorrupt(
            "manifest says the frontier is packed: it needs a pack "
            f"spec and the one member 'packed', not {sorted(fr)}")
    from .pack import PackSpec
    return PackSpec.from_manifest(manifest["pack"]).unpack_np(
        fr["packed"])


def _assemble_frontier(fr):
    """Reassemble a frontier payload dict: plain per-plane arrays pass
    through; chunked members (``<plane>.<i>``) concatenate in chunk
    order."""
    if not any(_CHUNK_RE.match(k) for k in fr):
        return dict(fr)
    chunks = {}
    for k, v in fr.items():
        m = _CHUNK_RE.match(k)
        if m is None:
            raise CheckpointCorrupt(
                f"frontier payload mixes chunked and plain members "
                f"({k!r})")
        chunks.setdefault(m.group(1), []).append((int(m.group(2)), v))
    return {plane: np.concatenate(
        [v for _i, v in sorted(parts)]) if len(parts) > 1
        else sorted(parts)[0][1]
        for plane, parts in chunks.items()}


def _occupied_members(slots, gids):
    """The members of a format-4 ``fpset.npz``: the occupied slots of
    `slots` (``[..., cap, words]``; word 0 is the tag and 0 is the
    empty slot, engine/fpset.py) as flat indices over the leading
    dimensions, their words (claim column included) and the table's
    shape; of `gids`, the parallel column, the same rows."""
    slots = np.asarray(slots)
    flat = slots.reshape(-1, slots.shape[-1])
    index = np.flatnonzero(flat[:, 0])
    arrs = {"shape": np.asarray(slots.shape, np.int64),
            "index": (index.astype(np.uint32)
                      if flat.shape[0] <= 1 << 32 else index),
            "rows": flat[index]}
    if gids is not None:
        arrs["gid_rows"] = np.asarray(gids).reshape(-1)[index]
    return arrs


def _scatter_table(fp):
    """Undo ``_occupied_members``: ``(slots, gids)`` of a loaded
    ``fpset.npz``, bit for bit the arrays that were saved.  A format-3
    payload holds them whole."""
    if "slots" in fp:
        return fp["slots"], fp.get("gids")
    shape = tuple(int(x) for x in fp["shape"])
    index = fp["index"]
    slots = np.zeros(shape, fp["rows"].dtype)
    slots.reshape(-1, shape[-1])[index] = fp["rows"]
    gids = None
    if "gid_rows" in fp:
        gids = np.zeros(shape[:-1], fp["gid_rows"].dtype)
        gids.reshape(-1)[index] = fp["gid_rows"]
    return slots, gids


def _part(obs, name):
    """`name`'s part of the writer's ``checkpoint`` phase; nothing
    without an observer (a conversion, a test)."""
    return contextlib.nullcontext() if obs is None else obs.part(name)


def save_checkpoint(path, *, slots, frontier=None, n_front, h_parent,
                    h_action, h_param, init_dense, level_sizes, depth,
                    fp_count, states_generated, max_msgs, expand_mults,
                    elapsed, digest=None, extra=None, pack=None,
                    canon=None, bounds=None, por=None,
                    frontier_blocks=None, frontier_packed=None,
                    gids=None, edge_blocks=None, graph_blocks=None,
                    obs=None):
    """Write a complete engine snapshot to `path` (atomic + durable);
    returns the bytes staged (payloads + manifest).

    `slots` is the whole table (any leading dimensions: the sharded
    engine passes ``[D, cap, 5]``); its OCCUPIED slots are what
    fpset.npz holds (format 4, module docstring).

    `frontier` rows beyond `n_front` are dropped; `h_*` are the
    concatenated host trace-pointer arrays; `init_dense` is the dense
    encoding of the (deduped) initial states, in gid order.

    `pack` is the packed-frontier spec manifest the writing engine ran
    under (engine/pack.PackSpec.manifest(); None = packing off).  A
    writer that packs hands its rows over as they are,
    `frontier_packed` (``[n, words]``, the first `n_front` kept), in
    place of `frontier`: frontier.npz then holds them packed and
    ``load_checkpoint`` unpacks them by the manifest's spec — it
    returns DENSE planes either way, the interchange form any
    engine/pack configuration can resume — and the manifest's spec
    version makes resuming under a MISMATCHED widths table a loud
    policy error (ISSUE 9 satellite).

    `frontier_blocks` (ISSUE 13 satellite) replaces `frontier` with an
    ITERATOR of dense plane-dict blocks: each block is streamed into
    the staged frontier.npz and released, so a disk-spilled frontier
    (engine/spill.py) checkpoints at page-sized peak residency instead
    of materializing `n_front` dense rows.  The chunked payload is
    read back transparently by ``load_checkpoint``.

    Streamed edge emission (ISSUE 15) adds three OPTIONAL payload
    pieces: `gids` — the FPSet's parallel gid column (fingerprint ->
    graph node id), stored alongside ``slots`` in fpset.npz;
    `edge_blocks` — an iterator of ``{src, aid, dst}`` array blocks
    (the CSR builder's drained rows up to this committed level),
    streamed into edges.npz; `graph_blocks` — an iterator of the
    retained dense level blocks (temporal runs), streamed into
    graph.npz.  All three are restored by ``load_checkpoint``, so a
    SIGTERM'd temporal run resumes to a bit-identical CSR."""
    from ..resilience.faults import fault_point
    tmp = path + ".ckpt-tmp"
    if os.path.isdir(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)
    # the table leaves the device here, whole, and the host finds its
    # occupied slots
    with _part(obs, spans.CHECKPOINT_PULL):
        members = _occupied_members(slots, gids)
    with _part(obs, spans.CHECKPOINT_WRITE):
        # stored, not deflated: fingerprints are hash words, deflate takes
        # a fifth off them at sixteen times the time
        np.savez(os.path.join(tmp, "fpset.npz"), **members)
        extra_payloads = []
        if edge_blocks is not None:
            _write_frontier_chunks(os.path.join(tmp, "edges.npz"),
                                   edge_blocks)
            extra_payloads.append("edges.npz")
        if graph_blocks is not None:
            _write_frontier_chunks(os.path.join(tmp, "graph.npz"),
                                   graph_blocks)
            extra_payloads.append("graph.npz")
        packed = frontier_blocks is None and frontier_packed is not None
        if frontier_blocks is not None:
            rows = _write_frontier_chunks(
                os.path.join(tmp, "frontier.npz"), frontier_blocks)
            if rows != int(n_front):
                raise ValueError(
                    f"frontier_blocks yielded {rows} rows, n_front is "
                    f"{n_front}")
        elif packed:
            if pack is None:
                raise ValueError("frontier_packed needs the writer's pack "
                                 "manifest to be read back")
            np.savez_compressed(
                os.path.join(tmp, "frontier.npz"),
                packed=np.asarray(frontier_packed)[:n_front])
        else:
            np.savez_compressed(
                os.path.join(tmp, "frontier.npz"),
                **{k: np.asarray(v)[:n_front] for k, v in frontier.items()})
        np.savez_compressed(os.path.join(tmp, "trace.npz"),
                            parent=h_parent, action=h_action, param=h_param)
        np.savez_compressed(
            os.path.join(tmp, "init.npz"),
            **{k: np.stack([np.asarray(d[k]) for d in init_dense])
               for k in init_dense[0]})
    with _part(obs, spans.CHECKPOINT_DURABLE):
        # CRCs are computed over the INTENDED payload bytes, before the
        # corrupt-ckpt fault hook below mangles anything — a fault-injected
        # torn write is therefore CRC-detectable, like a real one
        payloads = list(PAYLOADS) + extra_payloads
        crcs = {name: _crc32_file(os.path.join(tmp, name))
                for name in payloads}
        manifest = {
            "format": FORMAT_VERSION,
            "n_front": int(n_front),
            "n_init": len(init_dense),
            "level_sizes": [int(x) for x in level_sizes],
            "depth": int(depth),
            "fp_count": int(fp_count),
            "states_generated": int(states_generated),
            "max_msgs": int(max_msgs),
            "expand_mults": [int(x) for x in expand_mults],
            "elapsed": float(elapsed),
            "spec_digest": digest,
            "payload_crc32": crcs,
            # packed-frontier spec identity (ISSUE 9): version digest +
            # plane table of the writer's packing spec, None when dense
            "pack": pack,
            # frontier.npz holds the writer's packed rows (to be unpacked
            # by `pack`), not dense planes
            "frontier_packed": packed,
            # symmetry canonicalization spec (ISSUE 11): version digest +
            # group order + orbit plane table of the writer's CanonSpec,
            # None when the run stored raw (non-canonical) fingerprints.
            # Resuming under a flipped -symmetry or a changed group is a
            # policy error — the FPSet's fingerprint space would not match
            "canon": canon,
            # bounds-facts identity (ISSUE 13): digest of the speclint
            # bounds pass facts the writer consumed (tightened packing +
            # pruned action ids depend on them), None when bounds off.
            # Resuming under a flipped -bounds or changed cfg constants
            # is a policy error, mirroring the pack/canon rules
            "bounds": bounds,
            # independence-facts identity (ISSUE 16): digest of the
            # speclint independence pass facts the writer's ample-set
            # partial-order reduction consumed (the reduced reachable set
            # depends on them), None when POR off.  Resuming under a
            # flipped -por or changed facts is a policy error, mirroring
            # the pack/canon/bounds rules
            "por": por,
            # engine-specific payload (e.g. the sharded driver's per-shard
            # frontier counts and exchange capacities)
            "extra": extra,
        }
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f)
            f.flush()
            os.fsync(f.fileno())
            staged = f.tell()
        # sized before the fault hook below may truncate one
        staged += sum(os.path.getsize(os.path.join(tmp, name))
                      for name in payloads)
        # fault hook: emulate a corrupted write AND leave the previous
        # snapshot as .old (the crash window between rename-into-place and
        # .old cleanup).  Two flavors (resilience/faults.py): corrupt-ckpt
        # truncates the named payload (torn write — np.load chokes);
        # garble-ckpt XOR-flips a byte span mid-file with the size
        # preserved (bit rot — ONLY the manifest CRC32 catches it)
        corrupt = fault_point("checkpoint", depth=depth, path=path, obs=obs)
        if corrupt:
            victim = os.path.join(tmp, corrupt.payload)
            size = os.path.getsize(victim)
            with open(victim, "r+b") as f:
                if corrupt.kind == "garble-ckpt":
                    span = max(1, min(64, size // 2))
                    f.seek(size // 2)
                    chunk = f.read(span)
                    f.seek(size // 2)
                    f.write(bytes(b ^ 0xFF for b in chunk))
                else:
                    f.truncate(max(1, size // 2))
        for name in payloads:
            _fsync_path(os.path.join(tmp, name))
        _fsync_path(tmp)
        old = path + ".old"
        if os.path.isdir(old):
            shutil.rmtree(old)
        if os.path.isdir(path):
            os.rename(path, old)
        os.rename(tmp, path)
        parent = os.path.dirname(os.path.abspath(path)) or "."
        _fsync_path(parent)
        if os.path.isdir(old) and not corrupt:
            shutil.rmtree(old)
            _fsync_path(parent)
    return staged


def snapshot_info(path):
    """Cheap manifest-only summary of a snapshot directory — the
    checkpoint handoff record the dispatch service attaches to a
    requeued job (ISSUE 6): ``{path, depth, distinct, elapsed}`` or
    None when `path` holds no readable manifest.  Reads no payloads,
    so a worker can stamp a rescue onto the queue without touching
    multi-GB npz files."""
    try:
        with open(os.path.join(path, "manifest.json")) as f:
            mf = json.load(f)
        return {"path": path, "depth": int(mf["depth"]),
                "distinct": int(mf["fp_count"]),
                "elapsed": float(mf["elapsed"])}
    except (OSError, ValueError, KeyError, TypeError):
        return None


def prior_elapsed(path) -> float:
    """Cumulative wall-clock recorded in a snapshot's manifest (0.0
    when absent/unreadable).  Resumable window scripts add this to
    their window budget: a resumed run's elapsed is CUMULATIVE (run()
    rewinds t0 by it), so a bare window budget would no-op."""
    try:
        with open(os.path.join(path, "manifest.json")) as f:
            return float(json.load(f)["elapsed"])
    except (OSError, ValueError, KeyError):
        return 0.0


def _read_snapshot(path, expect_digest):
    """Read + verify one snapshot directory.  Raises CheckpointCorrupt
    on any integrity failure (fallback-eligible) and plain ValueError
    on policy mismatches (format version, spec digest — never masked
    by the .old fallback)."""
    mf = os.path.join(path, "manifest.json")
    try:
        with open(mf) as f:
            manifest = json.load(f)
    except OSError as e:
        raise CheckpointCorrupt(f"{mf}: unreadable manifest ({e})")
    except ValueError as e:
        raise CheckpointCorrupt(f"{mf}: manifest is not valid JSON "
                                f"({e})")
    if manifest.get("format") not in READ_FORMATS:
        raise ValueError(
            f"checkpoint format {manifest.get('format')} unsupported "
            f"(want one of {READ_FORMATS})")
    if expect_digest is not None and manifest.get("spec_digest") and \
            manifest["spec_digest"] != expect_digest:
        raise ValueError(
            "checkpoint was written by a different spec/.cfg "
            f"(digest {manifest['spec_digest']}, this run "
            f"{expect_digest}); refusing to resume")
    crcs = manifest.get("payload_crc32") or {}
    arrs = {}
    # optional payloads (edges.npz / graph.npz, the ISSUE 15 edge
    # stream) are verified iff the manifest recorded a CRC for them —
    # a listed-but-missing optional payload is corruption, not absence
    names = list(PAYLOADS) + sorted(set(crcs) - set(PAYLOADS))
    for name in names:
        p = os.path.join(path, name)
        try:
            want = crcs.get(name)
            if want is not None and _crc32_file(p) != int(want):
                raise CheckpointCorrupt(
                    f"{p}: CRC32 mismatch (payload corrupted after "
                    f"write)")
            with np.load(p) as z:
                arrs[name] = {k: z[k] for k in z.files}
        except CheckpointCorrupt:
            raise
        except Exception as e:  # noqa: BLE001 — np.load raises a zoo
            raise CheckpointCorrupt(
                f"{p}: unreadable payload "
                f"({type(e).__name__}: {e})")
    n_front = int(manifest["n_front"])
    arrs["frontier.npz"] = _dense_frontier(arrs["frontier.npz"],
                                           manifest)
    for k, v in arrs["frontier.npz"].items():
        if v.shape[0] != n_front:
            raise CheckpointCorrupt(
                f"{path}: frontier plane {k!r} has {v.shape[0]} rows, "
                f"manifest says n_front={n_front}")
    return manifest, arrs


def load_checkpoint(path, expect_digest=None, log=None):
    """Read a snapshot; returns a dict mirroring save_checkpoint.

    Falls back to ``<path>.old`` when the primary is missing or fails
    integrity verification at ANY level — absent/garbled manifest, bad
    payload CRC, truncated/missing .npz, inconsistent frontier rows
    (a crash anywhere inside ``save_checkpoint``'s write/rename
    sequence).  The returned dict records which directory actually
    loaded under ``restored_from``."""
    used = path
    try:
        manifest, arrs = _read_snapshot(path, expect_digest)
    except CheckpointCorrupt as e:
        old = path + ".old"
        if not os.path.isdir(old):
            raise
        if log:
            log(f"checkpoint {path} unusable ({e}); "
                f"falling back to {old}")
        manifest, arrs = _read_snapshot(old, expect_digest)
        used = old
    fp = arrs["fpset.npz"]
    fr = arrs["frontier.npz"]
    tr = arrs["trace.npz"]
    ini = arrs["init.npz"]
    n_init = manifest["n_init"]
    init_dense = [{k: ini[k][i] for k in ini}
                  for i in range(n_init)]

    def _opt_chunked(name):
        d = arrs.get(name)
        if d is None:
            return None
        d = _assemble_frontier(d)
        return d or None        # zero-block payload == absent
    slots, gids = _scatter_table(fp)
    return {
        "slots": slots,
        # streamed edge emission (ISSUE 15): the gid column and the
        # drained edge / retained graph rows, when the writer ran
        # with edges on (None otherwise)
        "gids": gids,
        "edges": _opt_chunked("edges.npz"),
        "graph": _opt_chunked("graph.npz"),
        "frontier": dict(fr),
        "n_front": manifest["n_front"],
        "h_parent": tr["parent"],
        "h_action": tr["action"],
        "h_param": tr["param"],
        "init_dense": init_dense,
        "level_sizes": manifest["level_sizes"],
        "depth": manifest["depth"],
        "fp_count": manifest["fp_count"],
        "states_generated": manifest["states_generated"],
        "max_msgs": manifest["max_msgs"],
        "expand_mults": manifest["expand_mults"],
        "elapsed": manifest["elapsed"],
        "extra": manifest.get("extra"),
        "pack": manifest.get("pack"),
        "canon": manifest.get("canon"),
        "bounds": manifest.get("bounds"),
        "por": manifest.get("por"),
        "restored_from": used,
    }
