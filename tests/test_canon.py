"""Device-native symmetry reduction + disk spill tier (ISSUE 11).

The tier-1 fixture is the SymPair spec (tpuvsr/testing.py): a two-slot
write-once register over the symmetric set {v1, v2, v3} whose 16
reachable states collapse to 5 orbits under the declared
Permutations(Vals) group — small enough that every engine's
symmetry-on-vs-off A/B, the verdict/trace identity oracle, the
checkpoint flip policy, and the paged disk tier all run in seconds
without the reference mount.

The standing contracts:

* verdict identity: symmetry on and off agree on ok/violated (and on
  the violated invariant); traces agree modulo orbit representative
  (same length, replayed final state violates per the interpreter);
* distinct-states(on) <= distinct-states(off) / observed orbit factor,
  and the ``orbit_ratio`` gauge reads the cut off the journal;
* canonicalization runs INSIDE the jitted kernels (the CanonSpec is
  jit/vmap composable — asserted directly);
* resuming a symmetry-on snapshot with -symmetry off (or vice versa)
  is a loud policy error;
* the paged engine completes a fixpoint whose frontier exceeds its
  host-RAM page budget by spilling level files to disk, and resumes
  through a checkpoint back into the tier.
"""

import glob
import os

import numpy as np
import pytest

from tpuvsr.core.values import TLAError
from tpuvsr.testing import (SYMPAIR, SYMPAIR_CFG, SYMPAIR_DISTINCT,
                            SYMPAIR_LEVELS, SYMPAIR_ORBIT_LEVELS,
                            SYMPAIR_ORBITS, stub_sym_engine,
                            stub_sym_factory, stub_sym_sharded,
                            sym_pair_spec)

ORBIT_FACTOR = SYMPAIR_DISTINCT / SYMPAIR_ORBITS        # 3.2


# ---------------------------------------------------------------------
# CanonSpec unit behavior: orbit-mates -> one image, jit/vmap clean
# ---------------------------------------------------------------------
def test_canon_spec_maps_orbit_mates_to_one_image():
    import jax
    import jax.numpy as jnp

    from tpuvsr.engine.canon import build_canon_spec
    spec = sym_pair_spec()
    codec, kern = stub_sym_factory()(spec)
    canon = build_canon_spec(spec, codec, kern, "auto")
    assert canon is not None and canon.perms == 6
    cf = jax.jit(jax.vmap(canon.canonicalize))

    def st(a, b):
        return {"status": jnp.int32(0), "a": jnp.int32(a),
                "b": jnp.int32(b), "err": jnp.int32(0)}
    # the (v, w), v != w orbit has 6 members — all must canonicalize
    # to the SAME image, and the canonical image is a fixpoint
    orbit = [(1, 2), (1, 3), (2, 1), (2, 3), (3, 1), (3, 2)]
    batch = {k: jnp.stack([st(a, b)[k] for a, b in orbit])
             for k in st(0, 0)}
    out = cf(batch)
    images = {(int(out["a"][i]), int(out["b"][i]))
              for i in range(len(orbit))}
    assert len(images) == 1
    again = cf({k: v for k, v in out.items()})
    for k in out:
        assert np.array_equal(np.asarray(out[k]), np.asarray(again[k]))
    # a distinct orbit keeps a distinct image
    other = cf({k: jnp.stack([st(1, 1)[k]]) for k in st(0, 0)})
    assert (int(other["a"][0]), int(other["b"][0])) not in images


def test_canon_requires_declared_symmetry_and_orbit_table():
    from tpuvsr.engine.canon import build_canon_spec, orbit_planes
    spec_off = sym_pair_spec(symmetry=False)
    codec, kern = stub_sym_factory()(spec_off)
    assert build_canon_spec(spec_off, codec, kern, "auto") is None
    with pytest.raises(TLAError, match="no SYMMETRY"):
        build_canon_spec(spec_off, codec, kern, True)
    assert orbit_planes(kern) == {"a": "all", "b": "all"}


def test_folded_kernel_stands_down_and_rejects_off():
    # a custom model_factory may hand the engine a pre-ISSUE-11 FOLDED
    # kernel (fingerprints min-hash over the group): the canon seam
    # stands down (the fold IS the reduction), and symmetry=False is a
    # loud error rather than a silently ineffective flag
    from tpuvsr.engine.device_bfs import DeviceBFS
    spec = sym_pair_spec()
    base = stub_sym_factory()

    def folded(spec_, max_msgs=None):
        codec, kern = base(spec_, max_msgs=max_msgs)
        kern.perms = np.stack([np.arange(4, dtype=np.int32)] * 6)
        return codec, kern
    eng = DeviceBFS(spec, model_factory=folded, hash_mode="full",
                    tile_size=4)
    assert eng._canon is None and eng._symmetry_on()
    with pytest.raises(TLAError, match="FOLDED"):
        DeviceBFS(spec, model_factory=folded, hash_mode="full",
                  tile_size=4, symmetry=False)


# ---------------------------------------------------------------------
# speclint pass 4 device-soundness: closure + the emitted orbit table
# ---------------------------------------------------------------------
def test_lint_rejects_non_closed_symmetry_group():
    from tpuvsr.analysis import run_lint
    from tpuvsr.engine.spec import SpecModel
    from tpuvsr.frontend.cfg import parse_cfg_text
    from tpuvsr.frontend.parser import parse_module_text
    src = SYMPAIR.replace(
        "CONSTANTS Vals", "CONSTANTS Vals, v1, v2, v3").replace(
        "Symm == Permutations(Vals)",
        "Cyc == [v \\in Vals |-> IF v = v1 THEN v2 ELSE "
        "IF v = v2 THEN v3 ELSE v1]\nSymm == {Cyc}")
    cfg = SYMPAIR_CFG.replace("{inv}", "AllOk").replace(
        "Vals = {v1, v2, v3}",
        "Vals = {v1, v2, v3}\n    v1 = v1\n    v2 = v2\n    v3 = v3")
    spec = SpecModel(parse_module_text(src), parse_cfg_text(cfg))
    report = run_lint(spec)
    msgs = [f.message for f in report.findings
            if f.passname == "symmetry" and f.severity == "error"]
    assert any("closed" in m for m in msgs), report.render()
    # the engine refuses independently of the lint gate
    codec, kern = stub_sym_factory()(spec)
    from tpuvsr.engine.canon import build_canon_spec
    with pytest.raises(TLAError, match="closed"):
        build_canon_spec(spec, codec, kern, "auto")


def test_lint_sympair_group_is_clean():
    from tpuvsr.analysis import run_lint
    report = run_lint(sym_pair_spec())
    sym = [f for f in report.findings if f.passname == "symmetry"]
    assert not [f for f in sym if f.severity == "error"], \
        report.render()


# ---------------------------------------------------------------------
# engine A/B: distinct-state cut + orbit_ratio gauge
# ---------------------------------------------------------------------
def test_device_symmetry_on_off_ab():
    ron = stub_sym_engine().run()
    roff = stub_sym_engine(symmetry=False).run()
    assert ron.ok and roff.ok
    assert ron.distinct_states == SYMPAIR_ORBITS
    assert roff.distinct_states == SYMPAIR_DISTINCT
    assert ron.levels == SYMPAIR_ORBIT_LEVELS
    assert roff.levels == SYMPAIR_LEVELS
    # the satellite inequality: on <= off / observed orbit factor
    assert ron.distinct_states <= roff.distinct_states / ORBIT_FACTOR
    gon, goff = ron.metrics["gauges"], roff.metrics["gauges"]
    assert gon["symmetry_perms"] == 6 and goff["symmetry_perms"] == 1
    # orbit_ratio = generated / distinct-after-canon: plain dedup
    # keeps the off run above 1.0, but the canon run folds the orbit
    # factor ON TOP of it — the A/B reads the cut off the gauges
    assert gon["orbit_ratio"] > goff["orbit_ratio"] >= 1


def test_interp_and_device_agree_on_orbit_count():
    from tpuvsr.engine.bfs import bfs_check
    r = bfs_check(sym_pair_spec())
    assert r.ok and r.distinct_states == SYMPAIR_ORBITS
    assert r.levels == SYMPAIR_ORBIT_LEVELS


@pytest.mark.slow
def test_paged_symmetry_on_off_ab(tmp_path):
    from tpuvsr.engine.paged_bfs import PagedBFS
    ron = stub_sym_engine(cls=PagedBFS).run()
    roff = stub_sym_engine(cls=PagedBFS, symmetry=False).run()
    assert ron.distinct_states == SYMPAIR_ORBITS
    assert roff.distinct_states == SYMPAIR_DISTINCT
    # symmetry rides the disk tier unchanged
    r2 = stub_sym_engine(cls=PagedBFS,
                         spill_dir=str(tmp_path / "sp"),
                         spill_ram_rows=1).run()
    assert r2.distinct_states == SYMPAIR_ORBITS


def test_sharded_symmetry_orbit_fixpoint():
    # canonicalize-before-bucketing: orbit-mates route to ONE shard
    # and dedup there, so the global distinct count is orbit-exact
    ron = stub_sym_sharded(n_devices=2).run()
    assert ron.distinct_states == SYMPAIR_ORBITS
    assert ron.levels == SYMPAIR_ORBIT_LEVELS
    assert ron.metrics["gauges"]["symmetry_perms"] == 6


@pytest.mark.slow
def test_sharded_symmetry_off_leg():
    roff = stub_sym_sharded(n_devices=2, symmetry=False).run()
    assert roff.distinct_states == SYMPAIR_DISTINCT
    assert roff.levels == SYMPAIR_LEVELS


# ---------------------------------------------------------------------
# verdict identity: same verdict, trace modulo orbit representative
# ---------------------------------------------------------------------
def _assert_nopair_violation(res, spec):
    assert not res.ok and res.violated_invariant == "NoPair"
    assert len(res.trace) == 3          # init + WriteA/WriteB pair
    assert spec.check_invariants(res.trace[-1].state) == "NoPair"


def test_verdict_identity_device_on_off():
    spec = sym_pair_spec(inv_pair=True)
    _assert_nopair_violation(
        stub_sym_engine(inv_pair=True).run(), spec)
    _assert_nopair_violation(
        stub_sym_engine(inv_pair=True, symmetry=False).run(), spec)


@pytest.mark.slow
def test_verdict_identity_other_engines_and_commit_modes():
    spec = sym_pair_spec(inv_pair=True)
    from tpuvsr.engine.paged_bfs import PagedBFS
    _assert_nopair_violation(
        stub_sym_sharded(n_devices=2, inv_pair=True).run(), spec)
    _assert_nopair_violation(
        stub_sym_engine(cls=PagedBFS, inv_pair=True).run(), spec)
    _assert_nopair_violation(
        stub_sym_engine(inv_pair=True, commit="per-action").run(),
        spec)


# ---------------------------------------------------------------------
# checkpoint/resume policy (ISSUE 11 satellite)
# ---------------------------------------------------------------------
@pytest.mark.parametrize("engine", ["device", "paged", "sharded"])
def test_resume_with_flipped_symmetry_is_policy_error(tmp_path, engine):
    # every engine refuses a snapshot it wrote itself, letter for letter
    from tpuvsr.testing import stub_bfs_engine
    ck = str(tmp_path / "ck")
    r = stub_bfs_engine(engine, sym=True).run(max_depth=1,
                                              checkpoint_path=ck)
    assert r.distinct_states == 3       # init orbit + level-1 orbits
    with pytest.raises(TLAError, match=(
            r"was written with symmetry canonicalization \S+ but this "
            r"engine runs off; the stored fingerprints are not "
            r"comparable — resume with the matching -symmetry "
            r"setting/group$")):
        stub_bfs_engine(engine, sym=True, symmetry=False).run(
            resume_from=ck)
    r2 = stub_bfs_engine(engine, sym=True).run(resume_from=ck)
    assert r2.ok and r2.distinct_states == SYMPAIR_ORBITS


@pytest.mark.slow
def test_resume_flip_mirror_direction(tmp_path):
    # an off-snapshot refuses an on-resume too
    ck2 = str(tmp_path / "ck2")
    stub_sym_engine(symmetry=False).run(max_depth=1,
                                        checkpoint_path=ck2)
    with pytest.raises(TLAError, match="symmetry canonicalization"):
        stub_sym_engine().run(resume_from=ck2)


# ---------------------------------------------------------------------
# disk spill tier (the CAPACITY.md mitigation-2 ladder)
# ---------------------------------------------------------------------
def test_paged_disk_spill_tier_completes_and_cleans_up(tmp_path):
    import json

    from tpuvsr.engine.paged_bfs import PagedBFS
    from tpuvsr.obs import RunObserver
    from tpuvsr.testing import STUB_DISTINCT, STUB_LEVELS, \
        stub_device_engine
    d = str(tmp_path / "spill")
    j = str(tmp_path / "j.jsonl")
    # a 2-row RAM budget forces every level of the 16-state counter
    # space through disk level files
    eng = stub_device_engine(cls=PagedBFS, spill_dir=d,
                             spill_ram_rows=2, chunk_tiles=1)
    r = eng.run(obs=RunObserver(journal_path=j))
    assert r.ok and r.distinct_states == STUB_DISTINCT
    assert r.levels == STUB_LEVELS
    assert r.metrics["gauges"]["spill_tier_bytes"] > 0
    assert not glob.glob(os.path.join(d, "*.npz"))      # dropped
    events = [json.loads(l) for l in open(j)]
    start = [e for e in events if e["event"] == "run_start"][0]
    assert start["symmetry"] is False   # counter declares no SYMMETRY
    disk = [e for e in events
            if e["event"] == "spill" and e.get("tier") == "disk"]
    assert disk and all(e["bytes"] > 0 for e in disk)




@pytest.mark.slow
def test_spill_tier_checkpoint_resume(tmp_path):
    from tpuvsr.engine.paged_bfs import PagedBFS
    from tpuvsr.testing import STUB_DISTINCT, stub_device_engine
    d = str(tmp_path / "spill")
    ck = str(tmp_path / "ck")
    r = stub_device_engine(cls=PagedBFS, spill_dir=d,
                           spill_ram_rows=2,
                           chunk_tiles=1).run(max_depth=3,
                                              checkpoint_path=ck)
    assert r.error and r.distinct_states < STUB_DISTINCT
    # the resumed frontier reloads THROUGH the tier (re-spilling past
    # the budget) and completes bit-identically
    r2 = stub_device_engine(cls=PagedBFS, spill_dir=d,
                            spill_ram_rows=2,
                            chunk_tiles=1).run(resume_from=ck)
    assert r2.ok and r2.distinct_states == STUB_DISTINCT
    oracle = stub_device_engine(cls=PagedBFS).run()
    assert r2.levels == oracle.levels


def test_spill_conflicts_with_retain_levels(tmp_path):
    from tpuvsr.engine.paged_bfs import PagedBFS
    from tpuvsr.testing import stub_device_engine
    with pytest.raises(TLAError, match="retain_levels"):
        stub_device_engine(cls=PagedBFS, retain_levels=True,
                           spill_dir=str(tmp_path / "s"))



