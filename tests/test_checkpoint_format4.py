"""Snapshot format 4 (ISSUE 36): a snapshot's payloads hold what the
run holds, in the form the device holds it — the occupied slots of the
fingerprint table and the packed frontier rows — and ``load_checkpoint``
gives back, bit for bit, what a format-3 snapshot gave: the whole table
and dense frontier planes.

CPU only.  The round trips and the corruption matrix run on synthetic
tables and the stub kernel; the kill-and-resume cases run the real VSR
kernel from committed files (``small_native``) to its fixpoint.
"""

import json
import os
import shutil

import numpy as np
import pytest

from tpuvsr.engine import checkpoint as ckpt
from tpuvsr.engine.checkpoint import (CheckpointCorrupt, PAYLOADS,
                                      load_checkpoint, save_checkpoint)
from tpuvsr.obs import RunObserver, read_journal
from tpuvsr.resilience import faults
from tpuvsr.resilience.supervisor import (Preempted, PreemptionGuard,
                                          clear_preemption)
from tpuvsr.testing import (STUB_DISTINCT, STUB_LEVELS,
                            stub_device_engine, stub_sharded_engine)


@pytest.fixture(autouse=True)
def _clean_fault_state():
    yield
    faults.clear()
    clear_preemption()


# ---------------------------------------------------------------------
# the table: occupied slots out, the same table back
# ---------------------------------------------------------------------
def _table(shape, occupied, seed):
    """A table of `shape` with `occupied` slots filled (tag never 0,
    every other word free, the claim column included) and a parallel
    gid column that is nonzero only where a slot is."""
    rng = np.random.default_rng(seed)
    slots = np.zeros(shape, np.uint32)
    flat = slots.reshape(-1, shape[-1])
    where = rng.choice(flat.shape[0], occupied, replace=False)
    rows = rng.integers(0, 1 << 32, (occupied, shape[-1]), dtype=np.uint32)
    rows[:, 0] |= 1
    flat[where] = rows
    gids = np.zeros(shape[:-1], np.int32)
    gids.reshape(-1)[where] = rng.integers(
        -5, 1 << 30, occupied, dtype=np.int32)
    return slots, gids


def _frontier(n, seed):
    rng = np.random.default_rng(seed)
    return {"x": rng.integers(0, 9, (n,), dtype=np.int32),
            "bag": rng.integers(-3, 99, (n, 4, 2), dtype=np.int32)}


def _save(path, slots, *, gids=None, n=3, seed=0, **kw):
    args = dict(
        slots=slots, gids=gids, frontier=_frontier(n + 2, seed),
        n_front=n, h_parent=np.arange(7, dtype=np.int64),
        h_action=np.arange(7, dtype=np.int32),
        h_param=np.zeros(7, np.int32),
        init_dense=[{"x": np.int32(0), "bag": np.zeros((4, 2), np.int32)}],
        level_sizes=[1, 3, 3], depth=2,
        fp_count=int(np.count_nonzero(np.asarray(slots)[..., 0])),
        states_generated=11, max_msgs=4, expand_mults=[2, 3],
        elapsed=1.5, digest="d" * 16)
    args.update(kw)
    return save_checkpoint(path, **args)


TABLES = {
    "single": ((1 << 10, 5), 300, False),
    "sharded": ((4, 1 << 8, 5), 333, False),
    "gids": ((1 << 10, 5), 17, True),
    "empty": ((1 << 10, 5), 0, False),
    "empty-gids": ((2, 1 << 6, 5), 0, True),
    "full": ((1 << 6, 5), 1 << 6, True),
}


@pytest.mark.parametrize("case", sorted(TABLES))
def test_table_round_trip_is_bit_identical(tmp_path, case):
    shape, occupied, with_gids = TABLES[case]
    slots, gids = _table(shape, occupied, seed=len(case))
    path = str(tmp_path / "snap")
    _save(path, slots, gids=gids if with_gids else None)
    with np.load(os.path.join(path, "fpset.npz")) as z:
        assert "slots" not in z.files
        assert z["rows"].shape == (occupied, shape[-1])
        assert z["index"].shape == (occupied,)
    ck = load_checkpoint(path, expect_digest="d" * 16)
    assert ck["slots"].dtype == slots.dtype
    assert ck["slots"].shape == slots.shape
    assert np.array_equal(ck["slots"], slots)
    if with_gids:
        assert ck["gids"].dtype == gids.dtype
        assert ck["gids"].shape == gids.shape
        assert np.array_equal(ck["gids"], gids)
    else:
        assert ck["gids"] is None
    want = _frontier(5, 0)
    assert sorted(ck["frontier"]) == sorted(want)
    for k, v in want.items():
        assert np.array_equal(ck["frontier"][k], v[:3])
    assert ck["n_front"] == 3 and ck["level_sizes"] == [1, 3, 3]


@pytest.mark.parametrize("states", [1, 40, 1000])
def test_fpset_bytes_follow_the_states_not_the_capacity(tmp_path,
                                                        states):
    """The same states in a table of 1<<12 and of 1<<20 slots: the
    payload differs by under 2x (format 3 differed by the capacity)."""
    sizes = []
    for cap in (1 << 12, 1 << 20):
        slots, _ = _table((cap, 5), states, seed=states)
        path = str(tmp_path / f"snap{cap}")
        _save(path, slots)
        sizes.append(os.path.getsize(os.path.join(path, "fpset.npz")))
    small, large = sizes
    assert large < 2 * small
    assert large < 2048 + 24 * states


# ---------------------------------------------------------------------
# the frontier: packed rows out, dense planes back
# ---------------------------------------------------------------------
def _packed_frontier(eng, n, seed):
    rng = np.random.default_rng(seed)
    dense = {"x": rng.integers(0, 4, (n,), dtype=np.int32),
             "y": rng.integers(0, 4, (n,), dtype=np.int32)}
    zero = eng.codec.zero_state()
    dense = {k: (dense[k] if k in dense else
                 np.zeros((n,) + np.shape(v), np.int32))
             for k, v in zero.items()}
    return dense, eng._pk.pack_np(dense)


def test_packed_frontier_loads_as_the_dense_planes(tmp_path):
    eng = stub_device_engine()
    dense, packed = _packed_frontier(eng, 9, seed=3)
    slots, _ = _table((1 << 8, 5), 9, seed=1)
    path = str(tmp_path / "snap")
    _save(path, slots, frontier=None, frontier_packed=packed, n=7,
          pack=eng._pack_manifest())
    with np.load(os.path.join(path, "frontier.npz")) as z:
        assert z.files == ["packed"]
        assert z["packed"].shape == (7, eng._pk.words)
    ck = load_checkpoint(path)
    assert sorted(ck["frontier"]) == sorted(dense)
    for k, v in dense.items():
        assert ck["frontier"][k].dtype == np.int32
        assert np.array_equal(ck["frontier"][k], v[:7])
    assert ck["pack"] == json.loads(json.dumps(eng._pack_manifest()))


def test_packed_frontier_needs_its_pack_manifest(tmp_path):
    eng = stub_device_engine()
    _dense, packed = _packed_frontier(eng, 4, seed=4)
    slots, _ = _table((1 << 8, 5), 4, seed=1)
    with pytest.raises(ValueError, match="pack"):
        _save(str(tmp_path / "snap"), slots, frontier=None,
              frontier_packed=packed, n=4)


def test_packed_rows_short_of_n_front_are_corruption(tmp_path):
    eng = stub_device_engine()
    _dense, packed = _packed_frontier(eng, 4, seed=5)
    slots, _ = _table((1 << 8, 5), 4, seed=1)
    path = str(tmp_path / "snap")
    _save(path, slots, frontier=None, frontier_packed=packed, n=6,
          pack=eng._pack_manifest())
    with pytest.raises(CheckpointCorrupt, match="n_front=6"):
        load_checkpoint(path)


@pytest.mark.parametrize("writer", ["device-packed", "device-dense",
                                    "paged-packed", "sharded-packed",
                                    "sharded-dense"])
def test_engines_write_what_they_hold(tmp_path, writer):
    """Which layout each writer stages, the counter and the journal
    keys it brings, and that the stub run resumes to its fixpoint."""
    from tpuvsr.engine.paged_bfs import PagedBFS
    kind, layout = writer.split("-")
    kw = {} if layout == "packed" else {"pack": False}

    def make():
        if kind == "sharded":
            return stub_sharded_engine(n_devices=2, **kw)
        return stub_device_engine(
            cls=PagedBFS if kind == "paged" else None, **kw)

    path = str(tmp_path / "snap")
    jp = str(tmp_path / "j.jsonl")
    res = make().run(max_depth=3, checkpoint_path=path,
                     obs=RunObserver(journal_path=jp))
    assert res.error
    with open(os.path.join(path, "manifest.json")) as f:
        manifest = json.load(f)
    assert manifest["format"] == ckpt.FORMAT_VERSION == 4
    assert manifest["frontier_packed"] == (layout == "packed")
    with np.load(os.path.join(path, "frontier.npz")) as z:
        assert (z.files == ["packed"]) == (layout == "packed")
    # the counter and the journal say what was staged: every payload
    # and the manifest of the last snapshot, to the byte
    staged = sum(os.path.getsize(os.path.join(path, name))
                 for name in os.listdir(path))
    events = [e for e in read_journal(jp) if e["event"] == "checkpoint"]
    assert [e["format"] for e in events] == [4] * len(events)
    assert events[-1]["bytes"] == staged
    counters = res.metrics["counters"]
    assert counters["checkpoints"] == len(events)
    assert counters["checkpoint_bytes"] == sum(e["bytes"] for e in events)
    res2 = make().run(resume_from=path)
    assert res2.ok and res2.distinct_states == STUB_DISTINCT
    assert res2.levels == STUB_LEVELS


# ---------------------------------------------------------------------
# format 3 is still read
# ---------------------------------------------------------------------
def _rewrite_as_format3(path):
    """Rewrite the snapshot at `path` in the layout format 3 wrote: the
    whole table and dense frontier planes, deflated; CRCs to match."""
    ck = load_checkpoint(path)
    fp = {"slots": ck["slots"]}
    if ck["gids"] is not None:
        fp["gids"] = ck["gids"]
    np.savez_compressed(os.path.join(path, "fpset.npz"), **fp)
    np.savez_compressed(os.path.join(path, "frontier.npz"),
                        **ck["frontier"])
    mf = os.path.join(path, "manifest.json")
    with open(mf) as f:
        manifest = json.load(f)
    manifest["format"] = 3
    del manifest["frontier_packed"]
    manifest["payload_crc32"] = {
        name: ckpt._crc32_file(os.path.join(path, name))
        for name in manifest["payload_crc32"]}
    with open(mf, "w") as f:
        json.dump(manifest, f)


def _same(a, b):
    if isinstance(a, dict):
        return sorted(a) == sorted(b) and all(_same(a[k], b[k]) for k in a)
    if isinstance(a, (list, tuple)):
        return len(a) == len(b) and all(map(_same, a, b))
    if isinstance(a, np.ndarray):
        return a.dtype == b.dtype and np.array_equal(a, b)
    return a == b


@pytest.mark.parametrize("writer", ["packed", "dense", "gids"])
def test_format3_snapshot_loads_to_the_same_dict(tmp_path, writer):
    path = str(tmp_path / "snap")
    if writer == "gids":
        slots, gids = _table((1 << 9, 5), 21, seed=2)
        _save(path, slots, gids=gids)
    else:
        kw = {} if writer == "packed" else {"pack": False}
        assert stub_device_engine(**kw).run(
            max_depth=3, checkpoint_path=path).error
    new = load_checkpoint(path)
    _rewrite_as_format3(path)
    with np.load(os.path.join(path, "fpset.npz")) as z:
        assert "slots" in z.files and "index" not in z.files
    old = load_checkpoint(path)
    assert sorted(old) == sorted(new)
    for k in new:
        assert _same(old[k], new[k]), k
    if writer != "gids":
        res = stub_device_engine().run(resume_from=path)
        assert res.ok and res.distinct_states == STUB_DISTINCT
        assert res.levels == STUB_LEVELS


def test_unknown_format_is_a_policy_error(tmp_path):
    path = str(tmp_path / "snap")
    slots, _ = _table((1 << 6, 5), 3, seed=2)
    _save(path, slots)
    shutil.copytree(path, path + ".old")
    mf = os.path.join(path, "manifest.json")
    with open(mf) as f:
        manifest = json.load(f)
    manifest["format"] = 2
    with open(mf, "w") as f:
        json.dump(manifest, f)
    # never masked by the .old fallback
    with pytest.raises(ValueError, match="format 2 unsupported"):
        load_checkpoint(path)


# ---------------------------------------------------------------------
# the fault hooks still name the same payloads
# ---------------------------------------------------------------------
@pytest.mark.parametrize("payload", ["fpset.npz", "frontier.npz"])
@pytest.mark.parametrize("kind", ["corrupt-ckpt", "garble-ckpt"])
def test_faulted_payload_falls_back_to_old(tmp_path, kind, payload):
    path = str(tmp_path / "snap")
    faults.install(f"{kind}:{payload}@level=3")
    res = stub_device_engine().run(max_depth=3, checkpoint_path=path)
    faults.clear()
    assert res.error and os.path.isdir(path + ".old")
    with open(os.path.join(path, "manifest.json")) as f:
        assert set(json.load(f)["payload_crc32"]) == set(PAYLOADS)
    logs = []
    ck = load_checkpoint(path, log=logs.append)
    assert ck["restored_from"] == path + ".old" and ck["depth"] == 2
    assert logs and "falling back" in logs[0]
    shutil.rmtree(path + ".old")
    with pytest.raises(CheckpointCorrupt):
        load_checkpoint(path)


# ---------------------------------------------------------------------
# the real kernel: killed at a level, resumed from a format-4 snapshot
# ---------------------------------------------------------------------
KILL_LEVEL = 12


@pytest.mark.parametrize("writer", ["packed", "dense"])
def test_small_run_killed_and_resumed_reaches_the_pin(
        small_native, small_pin, tmp_path, monkeypatch, writer):
    """vsr-small, SIGTERMed at the start of level 12: the rescue
    snapshot at that level's end is format 4 (packed rows from the
    packing engine, dense planes from the other), the table in it is
    the one the engine held, and the resume ends at 43,941 / 24 with
    the pinned level sizes."""
    from tpuvsr.engine.device_bfs import DeviceBFS
    kw = {} if writer == "packed" else {"pack": False}
    eng = DeviceBFS(small_native, **kw)
    path = str(tmp_path / "snap")
    held = {}
    save = ckpt.save_checkpoint

    def keep(p, **args):
        held["slots"] = np.asarray(args["slots"]).copy()
        held["dense"] = (
            eng._pk.unpack_np(np.asarray(args["frontier_packed"]))
            if "frontier_packed" in args else
            {k: np.asarray(v) for k, v in args["frontier"].items()})
        return save(p, **args)

    faults.install(f"kill@level={KILL_LEVEL}")
    monkeypatch.setattr(ckpt, "save_checkpoint", keep)
    with PreemptionGuard(), pytest.raises(Preempted) as pi:
        # a cadence that never comes: the rescue is the one snapshot
        eng.run(checkpoint_path=path, checkpoint_every=1e9)
    clear_preemption()
    assert pi.value.depth == KILL_LEVEL
    with open(os.path.join(path, "manifest.json")) as f:
        manifest = json.load(f)
    assert manifest["format"] == 4
    assert manifest["frontier_packed"] == (writer == "packed")
    assert manifest["level_sizes"] == small_pin[:KILL_LEVEL + 1]
    ck = load_checkpoint(path)
    assert np.array_equal(ck["slots"], held["slots"])
    assert int(np.count_nonzero(ck["slots"][:, 0])) == sum(
        small_pin[:KILL_LEVEL + 1])
    n = small_pin[KILL_LEVEL]
    assert ck["n_front"] == n
    assert sorted(ck["frontier"]) == sorted(held["dense"])
    for k, v in held["dense"].items():
        assert ck["frontier"][k].dtype == v.dtype
        assert np.array_equal(ck["frontier"][k], v[:n]), k
    # the table is a fiftieth of what format 3 deflated, whole
    assert os.path.getsize(os.path.join(path, "fpset.npz")) < \
        2048 + 24 * sum(small_pin[:KILL_LEVEL + 1])
    res = eng.run(resume_from=path)
    assert res.ok and res.levels == small_pin
    assert (res.distinct_states, res.diameter) == (43941, 24)
