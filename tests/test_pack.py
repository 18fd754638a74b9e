"""Packed bit-planed frontier tests (ISSUE 9).

Three layers, all tier-1 (no reference mount — the codec round-trip
battery builds every registered layout from constants alone, and the
engine oracles drive the REAL device/paged/sharded loops through the
stub harness):

* pack/unpack round-trip property tests across all 8 registered codec
  layouts — random in-range states plus edge rows at each field's
  width boundary, numpy and jnp paths bit-identical;
* the bit-identity oracle: full stub runs packed vs unpacked compare
  distinct/generated/level_sizes/action counters and violation traces
  byte-for-byte, for the chunked, chained (K in {1,2,4}), fused,
  paged (incl. the spill schedule) and sharded engines, and across a
  checkpoint/resume seam;
* the checkpoint policy seam: snapshots record the packing-spec
  version; resume under a mismatched widths table is a TLAError, while
  pack=off on either side stays compatible (snapshots store dense
  planes).

Plus the ISSUE 9 acceptance anchor: the VSR defect layout
(examples/VSR_defect.cfg, MAX_MSGS=48) must pack >= 4x denser than the
dense planes (measured: 10.93x).
"""

import os

import numpy as np
import pytest

from tpuvsr.core.values import ModelValue as MV
from tpuvsr.core.values import TLAError
from tpuvsr.engine.pack import PackSpec, build_pack_spec
from tpuvsr.testing import (STUB_DISTINCT, STUB_LEVELS, counter_spec,
                            stub_device_engine, stub_model_factory,
                            stub_sharded_engine)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ALL_MODULES = ("VSR", "VR_STATE_TRANSFER", "VR_ASSUME_NEWVIEWCHANGE",
               "VR_INC_RESEND", "VR_APP_STATE", "VR_REPLICA_RECOVERY",
               "VR_REPLICA_RECOVERY_ASYNC_LOG",
               "VR_REPLICA_RECOVERY_CP")


def _consts():
    """Constants every registered layout accepts (the drift-test
    recipe: buildable with no reference mount)."""
    consts = {
        "ReplicaCount": 3, "ClientCount": 1,
        "Values": frozenset({MV("v1"), MV("v2")}),
        "StartViewOnTimerLimit": 2, "RestartEmptyLimit": 1,
        "NoProgressChangeLimit": 0, "CrashLimit": 1,
    }
    for n in ("Normal ViewChange StateTransfer Recovering Nil AnyDest "
              "NoOp PrepareMsg PrepareOkMsg StartViewChangeMsg "
              "DoViewChangeMsg StartViewMsg GetStateMsg NewStateMsg "
              "RecoveryMsg RecoveryResponseMsg GetCheckpointMsg "
              "NewCheckpointMsg").split():
        consts[n] = MV(n)
    return consts


# a module's layout at the constants its committed cfg binds, through
# the native door (load_spec by name) instead of the hand-made dict
NATIVE_DOOR = {
    "VR_STATE_TRANSFER:native-door": (
        "VR_STATE_TRANSFER", "benchmark/configs/vr-state-transfer.cfg"),
    "VR_REPLICA_RECOVERY_CP:native-door": (
        "VR_REPLICA_RECOVERY_CP",
        "benchmark/configs/vr-replica-recovery-cp.cfg"),
    # five replicas: replica ids up to 5 in the dest / source columns
    "VR_STATE_TRANSFER:native-door-r5": (
        "VR_STATE_TRANSFER", "benchmark/configs/vr-state-transfer-r5.cfg")}


def _layout_spec(mod, max_msgs=6):
    from tpuvsr.analysis.passes.widths import derive_ranges_from
    from tpuvsr.models import registry
    if mod in NATIVE_DOOR:
        from tpuvsr.engine.spec import load_spec
        name, cfg = NATIVE_DOOR[mod]
        spec = load_spec(name, os.path.join(REPO, cfg))
        codec, _kern, _inv = spec.model(max_msgs)
        return codec, build_pack_spec(codec, spec=spec)
    codec_cls, _ = registry._resolve(mod)
    codec = codec_cls(_consts(), max_msgs=max_msgs)
    pk = build_pack_spec(codec,
                         ranges=derive_ranges_from(_consts(), mod))
    return codec, pk


def _random_rows(pk, n, rng):
    """[n] random rows with every lane uniform inside its declared
    budget, plus the two edge rows (all-lo, all-hi — the width
    boundary of every field at once)."""
    lo = pk._lo.astype(np.int64)
    bits = pk._bits
    hi = np.where(bits >= 32, np.int64(2**31 - 1),
                  lo + (np.int64(1) << bits) - 1)
    lo_edge = np.where(bits >= 32, np.int64(-2**31), lo)
    flat = rng.integers(lo_edge, hi + 1, size=(n, pk.lanes))
    flat = np.concatenate([flat, lo_edge[None], hi[None]])
    out = {}
    for k, s, a, b in pk._splits:
        out[k] = flat[:, a:b].reshape((n + 2,) + s).astype(np.int32)
    return out


# ---------------------------------------------------------------------
# round-trip property battery: all 8 registered layouts
# ---------------------------------------------------------------------
@pytest.mark.parametrize("mod", ALL_MODULES + tuple(NATIVE_DOOR))
def test_roundtrip_all_layouts(mod):
    codec, pk = _layout_spec(mod)
    assert pk is not None and pk.ratio > 2.0, (mod, pk and pk.ratio)
    rng = np.random.default_rng(hash(mod) % 2**32)
    batch = _random_rows(pk, 64, rng)
    rows = pk.pack_np(batch)
    assert rows.shape == (66, pk.words) and rows.dtype == np.uint32
    back = pk.unpack_np(rows)
    for k in batch:
        assert np.array_equal(batch[k], back[k]), (mod, k)
    # zero row (the padding every growth path re-packs) is stable
    zero = {k: np.zeros_like(v[:1]) for k, v in batch.items()}
    zb = pk.unpack_np(pk.pack_np(zero))
    for k in zero:
        assert np.array_equal(zero[k], zb[k]), (mod, k)


@pytest.mark.parametrize("mod", ["VSR", "VR_REPLICA_RECOVERY_CP"])
def test_jnp_np_pack_bit_identical(mod):
    """The jitted/vmapped device path and the numpy host twins produce
    the SAME packed words and the same unpacked planes."""
    import jax
    codec, pk = _layout_spec(mod, max_msgs=4)
    rng = np.random.default_rng(7)
    batch = _random_rows(pk, 6, rng)
    np_rows = pk.pack_np(batch)
    j_rows = np.asarray(jax.jit(jax.vmap(pk.pack))(
        {k: np.asarray(v) for k, v in batch.items()}))
    assert np.array_equal(np_rows, j_rows), mod
    j_back = jax.jit(jax.vmap(pk.unpack))(np_rows)
    for k in batch:
        assert np.array_equal(batch[k], np.asarray(j_back[k])), \
            (mod, k)


def test_unpack_row_np_per_row_shapes():
    """unpack_row_np returns PER-ROW plane shapes (no leading batch
    axis) — the contract _fetch_row/_host_row and the sharded deadlock
    decode rely on for multi-dim planes like VSR's log."""
    _codec, pk = _layout_spec("VSR", max_msgs=4)
    rng = np.random.default_rng(11)
    batch = _random_rows(pk, 1, rng)
    one = pk.unpack_row_np(pk.pack_np(batch)[0])
    for k, s, _a, _b in pk._splits:
        assert one[k].shape == s, (k, one[k].shape, s)
        assert np.array_equal(one[k], batch[k][0]), k


def test_manifest_roundtrip_and_tamper():
    _codec, pk = _layout_spec("VSR", max_msgs=4)
    mf = pk.manifest()
    pk2 = PackSpec.from_manifest(mf)
    assert pk2.version == pk.version and pk2.words == pk.words
    rng = np.random.default_rng(3)
    batch = _random_rows(pk, 4, rng)
    assert np.array_equal(pk.pack_np(batch), pk2.pack_np(batch))
    # a tampered plane table no longer reproduces the recorded digest
    bad = {"version": mf["version"], "words": mf["words"],
           "planes": [list(p) for p in mf["planes"]]}
    bad["planes"][0][2] = [0, 17]          # widened bit budget
    with pytest.raises(TLAError):
        PackSpec.from_manifest(bad)


def test_build_pack_spec_requires_bounds_unless_forced():
    class NoBounds:
        def zero_state(self):
            return {"x": 0, "y": np.zeros((2,), np.int32)}
    assert build_pack_spec(NoBounds()) is None
    pk = build_pack_spec(NoBounds(), force=True)
    assert pk is not None and pk.ratio == 1.0 and pk.words == 3
    batch = {"x": np.asarray([-5, 2**31 - 1], np.int32),
             "y": np.asarray([[1, -2], [3, 4]], np.int32)}
    back = pk.unpack_np(pk.pack_np(batch))
    for k in batch:
        assert np.array_equal(batch[k], back[k])


def test_defect_layout_ratio_acceptance():
    """ISSUE 9 acceptance anchor: >= 4x bytes/state cut on the defect
    layout at MAX_MSGS=48 (CAPACITY.md records the measured 10.93x)."""
    from tpuvsr.analysis.passes.widths import derive_ranges_from
    from tpuvsr.frontend.cfg import parse_cfg_file
    from tpuvsr.models.vsr import VSRCodec
    import os
    cfg = parse_cfg_file(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "examples", "VSR_defect.cfg"))
    pk = build_pack_spec(
        VSRCodec(cfg.constants, max_msgs=48),
        ranges=derive_ranges_from(cfg.constants, "VSR"))
    assert pk.dense_bytes == 7212
    assert pk.ratio >= 4.0, pk.ratio
    assert pk.packed_bytes * 4 <= pk.dense_bytes


# ---------------------------------------------------------------------
# bit-identity oracle: packed vs dense on the real engine loops
# ---------------------------------------------------------------------
def _sig(res):
    return (res.distinct_states, res.states_generated, res.levels,
            res.metrics["gauges"].get("action_expansions"))


def _trace_sig(res):
    return (res.violated_invariant,
            [(e.action_name, e.state) for e in res.trace])


def test_device_packed_vs_dense_bit_identical():
    dense = stub_device_engine(pack=False)
    rd = dense.run()
    assert rd.ok and rd.distinct_states == STUB_DISTINCT
    assert dense._pk is None
    assert rd.metrics["gauges"]["pack_ratio"] == 1.0
    packed = stub_device_engine()
    rp = packed.run()
    assert packed._pk is not None
    assert _sig(rp) == _sig(rd)
    g = rp.metrics["gauges"]
    assert g["pack_ratio"] == 4.0          # 4 planes -> 1 word
    assert g["frontier_bytes_per_state"] == 4


def test_paged_packed_vs_dense_spill_schedule_identical():
    from tpuvsr.engine.paged_bfs import PagedBFS
    dense = stub_device_engine(cls=PagedBFS, chunk_tiles=1, pack=False)
    rd = dense.run()
    packed = stub_device_engine(cls=PagedBFS, chunk_tiles=1)
    rp = packed.run()
    assert rp.ok and _sig(rp) == _sig(rd)
    # the spill SCHEDULE is identical; only the bytes shrink
    assert (packed.spill_count, packed.spill_rows) == \
        (dense.spill_count, dense.spill_rows)
    assert packed._state_row_bytes() * 4 == dense._state_row_bytes()


def test_paged_packed_violation_trace_identical():
    from tpuvsr.engine.paged_bfs import PagedBFS
    rd = stub_device_engine(cls=PagedBFS, chunk_tiles=1, pack=False,
                            inv_bound=4).run()
    rp = stub_device_engine(cls=PagedBFS, chunk_tiles=1,
                            inv_bound=4).run()
    assert not rp.ok and _trace_sig(rp) == _trace_sig(rd)


@pytest.mark.skipif(len(__import__("jax").devices()) < 2,
                    reason="needs 2 virtual devices")
def test_sharded_packed_vs_dense_bit_identical():
    rd = stub_sharded_engine(n_devices=2, pack=False).run()
    eng = stub_sharded_engine(n_devices=2)
    rp = eng.run()
    assert rp.ok and eng._pk is not None and eng.pipe_window == 2
    assert _sig(rp) == _sig(rd)
    # the exchange wire is priced at the packed row size
    assert rp.exchange["row_bytes"] < rd.exchange["row_bytes"]
    assert rp.exchange["useful_rows"] == rd.exchange["useful_rows"]


@pytest.mark.skipif(len(__import__("jax").devices()) < 2,
                    reason="needs 2 virtual devices")
def test_sharded_packed_violation_trace_identical():
    rd = stub_sharded_engine(n_devices=2, inv_x_bound=2,
                             pack=False).run()
    rp = stub_sharded_engine(n_devices=2, inv_x_bound=2).run()
    assert not rp.ok and not rd.ok
    assert _trace_sig(rp) == _trace_sig(rd)


# ---------------------------------------------------------------------
# checkpoint/resume seams
# ---------------------------------------------------------------------
def test_packed_checkpoint_resume_bit_identical(tmp_path):
    """A packed run's snapshot stores DENSE planes: packed AND dense
    engines resume it to the exact uninterrupted result."""
    ck = str(tmp_path / "pack.ckpt")
    oracle = stub_device_engine(pack=False).run()
    r1 = stub_device_engine().run(max_depth=3, checkpoint_path=ck)
    assert r1.error                      # depth-limited, snapshot left
    for kw in ({}, {"pack": False}):
        res = stub_device_engine(**kw).run(resume_from=ck)
        assert res.ok and res.distinct_states == oracle.distinct_states
        assert res.levels == oracle.levels


@pytest.mark.parametrize("engine", ["device", "paged", "sharded"])
def test_pack_version_mismatch_is_policy_error(tmp_path, engine):
    """Resume under a MISMATCHED widths table (different bit budgets
    -> different spec version) is a loud TLAError, not a silent
    re-encode, on every engine and letter for letter.  Run with
    bounds=False on both sides: the ISSUE 13 reachable-interval
    tightening would otherwise intersect BOTH tables down to the same
    (identical, compatible) reachable budgets — this test pins the
    DECLARED-widths policy seam."""
    from tpuvsr.testing import stub_bfs_engine
    ck = str(tmp_path / "mismatch.ckpt")
    r1 = stub_bfs_engine(engine, bounds=False).run(max_depth=3,
                                                   checkpoint_path=ck)
    assert r1.error
    # limit=7 widens x/y to 4-bit budgets: a different packing spec
    eng = stub_bfs_engine(engine, bounds=False,
                          model_factory=stub_model_factory(limit=7))
    assert eng._pk.version != \
        stub_bfs_engine(engine, bounds=False)._pk.version
    with pytest.raises(TLAError, match=(
            r"was written under packing spec \S+ but this engine "
            r"derives \S+ from its widths table; refusing to resume "
            r"\(rebuild with the matching spec/.cfg or pass "
            r"pack=False\)$")):
        eng.run(resume_from=ck)


@pytest.mark.skipif(len(__import__("jax").devices()) < 2,
                    reason="needs 2 virtual devices")
def test_sharded_packed_checkpoint_resume(tmp_path):
    """The sharded rescue seam with packing on: level-boundary
    snapshot, resume packed on the same mesh — exact fixpoint; and the
    sharded resume-side manifest check fires on a drifted table."""
    ck = str(tmp_path / "sh.ckpt")
    oracle = stub_sharded_engine(n_devices=2, pack=False).run()
    # bounds=False on the checkpoint chain: the drifted-table check
    # below pins the DECLARED-widths seam, which the ISSUE 13
    # reachable-interval tightening would otherwise normalize away
    r1 = stub_sharded_engine(n_devices=2, bounds=False).run(
        max_states=6, checkpoint_path=ck, checkpoint_every=0.0)
    assert r1.error
    res = stub_sharded_engine(n_devices=2,
                              bounds=False).run(resume_from=ck)
    assert res.ok and res.distinct_states == oracle.distinct_states
    assert res.levels == oracle.levels
    import jax
    from jax.sharding import Mesh
    from tpuvsr.parallel.sharded_bfs import ShardedBFS
    mesh = Mesh(np.array(jax.devices()[:2]), ("d",))
    drifted = ShardedBFS(counter_spec(), mesh,
                         model_factory=stub_model_factory(limit=7),
                         tile=4, bucket_cap=64, next_capacity=1 << 6,
                         fpset_capacity=1 << 8, bounds=False)
    with pytest.raises(TLAError, match="packing spec"):
        drifted.run(resume_from=ck)


# ---------------------------------------------------------------------
# run_start journal identity
# ---------------------------------------------------------------------
def test_run_start_journal_carries_pack_key(tmp_path):
    from tpuvsr.obs import RunObserver, read_journal
    jp = str(tmp_path / "j.jsonl")
    stub_device_engine().run(obs=RunObserver(journal_path=jp))
    jp2 = str(tmp_path / "j2.jsonl")
    stub_device_engine(pack=False).run(obs=RunObserver(journal_path=jp2))
    (s1,) = [e for e in read_journal(jp) if e["event"] == "run_start"]
    (s2,) = [e for e in read_journal(jp2) if e["event"] == "run_start"]
    assert s1["pack"] is True and s2["pack"] is False
    assert set(s1) == set(s2)            # key-set parity
