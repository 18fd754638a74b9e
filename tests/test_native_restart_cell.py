"""The cell's cfg of the restart era (benchmark/configs/
vsr-shipped-restart.cfg: the shipped VSR.cfg with RestartEmptyLimit
turned to 1, SYMMETRY on) from committed files:

- the receive-set is a set: two arrival orders of the same two
  records give one row, one fingerprint, one canon key, and a
  symmetry relabel that turns their order leaves them in canonical
  order again (models/vsr.py, layout);
- the three engines, symmetry on, against the plain reference of
  orbit reduction (benchmark/tools/orbit_reference.py), as
  tests/test_native_shipped.py does for the shipped cfg;
- a shape that cannot restart is untouched: the pack manifest, the
  program store's key and the lowered level program of vsr-shipped
  and vsr-defect are what they were before the layout learned K.
"""

import hashlib
import json
import os
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from tpuvsr.core.values import FnVal, mk_record, permute_value
from tpuvsr.engine.spec import load_spec

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark", "tools"))
import orbit_reference  # noqa: E402

CONFIGS = os.path.join(REPO, "benchmark", "configs")
CFG = os.path.join(CONFIGS, "vsr-shipped-restart.cfg")
DEPTH = 5
MAX_MSGS = 32
ORBITS = [1, 6, 27, 113, 446, 1695]


@pytest.fixture(scope="module")
def spec():
    return load_spec("VSR", CFG)


# ---------------------------------------------------------------------
# (c) arrival order does not reach row, fingerprint or canon key
# ---------------------------------------------------------------------
@pytest.fixture(scope="module")
def model(spec):
    """(codec, kernel as the engines build it, canon spec, pack spec)."""
    from tpuvsr.engine.canon import build_canon_spec
    from tpuvsr.engine.pack import build_pack_spec
    from tpuvsr.models.registry import make_model
    codec, kern = make_model(spec, max_msgs=MAX_MSGS, fold_symmetry=False)
    return (codec, kern, build_canon_spec(spec, codec, kern),
            build_pack_spec(codec, spec=spec))


def _two_pending(spec):
    """Init with replica 2, primary of view 2, in a view change, and
    two DoViewChange records of replica 3 for it in the bag: equal in
    all but the value their one log entry holds."""
    c = spec.cfg.constants
    (init,) = spec.init_states()
    v1, v2 = sorted(c["Values"], key=repr)

    def dvc(value):
        return mk_record(
            type=c["DoViewChangeMsg"], view_number=2,
            log=FnVal([(1, mk_record(view_number=1, operation=value,
                                     client_id=1, request_number=1))]),
            last_normal_vn=1, op_number=1, commit_number=0, dest=2,
            source=3)
    state = dict(init)
    state["rep_view_number"] = init["rep_view_number"].updated(2, 2)
    state["rep_status"] = init["rep_status"].updated(2, c["ViewChange"])
    state["messages"] = FnVal([(dvc(v1), 1), (dvc(v2), 1)])
    return state, {v1: v2, v2: v1}


def test_two_arrival_orders_give_one_state(spec, model):
    codec, kern, canon, pk = model
    assert kern.K == 3 and canon.perms == 2
    start, swap = _two_pending(spec)
    dense = {k: jnp.asarray(v) for k, v in codec.encode(start).items()}
    receive = jax.jit(kern.act_receive_matching_dvc)

    def deliver(order):
        st = dense
        for lane in order:
            st, enabled = receive(st, jnp.asarray(lane, jnp.int32))
            assert bool(enabled) and int(st["err"]) == 0
        return st
    a, b = deliver((0, 1)), deliver((1, 0))
    for k in a:
        assert np.array_equal(a[k], b[k]), k
    assert np.array_equal(pk.pack(a), pk.pack(b))
    assert np.array_equal(kern.fingerprint(a), kern.fingerprint(b))
    key = jax.jit(lambda st: canon._key(canon.least(st)[0]))
    assert np.array_equal(key(a), key(b))
    # the set the host sees holds both, whatever the order
    got = codec.decode(a)
    assert len(got["rep_dvc_recv"].apply(2)) == 2
    assert codec.decode(codec.encode(got)) == got

    # a relabel that swaps the two records' values swaps their order:
    # the image is in canonical order again, i.e. the state the codec
    # makes of the permuted host value, and both have one fingerprint
    image = kern._permuted(a, jnp.asarray(canon.group[1]))
    want = codec.encode({k: permute_value(v, swap) for k, v in got.items()})
    # (the replica planes: the bag's slots keep the order they were
    # filled in, and its hash does not read it)
    for k in kern.REP_KEYS:
        assert np.array_equal(image[k], want[k]), k
    # here the swapped set is the set itself; the relabel alone
    # leaves its two records the wrong way round
    assert np.array_equal(image["dvc_log"], a["dvc_log"])
    turned = kern._permuted(a, jnp.asarray(canon.group[1]), resort=False)
    assert not np.array_equal(turned["dvc_log"], image["dvc_log"])
    fingerprint = jax.jit(canon.fingerprint_fn(kern))
    assert np.array_equal(
        fingerprint(a), fingerprint({k: jnp.asarray(v)
                                     for k, v in want.items()}))


# ---------------------------------------------------------------------
# (e) the three engines, symmetry on, against the plain reference
# ---------------------------------------------------------------------
@pytest.fixture(scope="module")
def reference(spec):
    """Per level: the set of least images of a symmetry-off run."""
    eng, _res = orbit_reference.symmetry_off_run(spec, DEPTH,
                                                 max_msgs=MAX_MSGS)
    return [set(orbit_reference.level_images(
        eng.codec, spec.symmetry_perms, b)) for b in eng.level_blocks]


def _device(spec):
    from tpuvsr.engine.device_bfs import DeviceBFS
    return DeviceBFS(spec, max_msgs=MAX_MSGS)


def _paged(spec):
    from tpuvsr.engine.paged_bfs import PagedBFS
    return PagedBFS(spec, max_msgs=MAX_MSGS, retain_levels=True)


def _sharded(spec):
    from tpuvsr.parallel.sharded_bfs import ShardedBFS
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    return ShardedBFS(spec, Mesh(np.array(jax.devices()[:4]), ("d",)),
                      max_msgs=MAX_MSGS, tile=32, bucket_cap=128,
                      next_capacity=1 << 11, fpset_capacity=1 << 13)


def test_reference_counts_the_pinned_orbits(reference):
    assert [len(s) for s in reference] == ORBITS


@pytest.mark.parametrize("build", [_device, _paged, _sharded],
                         ids=["device", "paged", "sharded"])
def test_symmetry_on_levels_equal_the_reference(build, spec, reference):
    eng = build(spec)
    res = eng.run(max_depth=DEPTH)
    assert res.ok and res.error == f"depth limit {DEPTH} reached"
    assert list(eng.level_sizes) == ORBITS
    assert res.distinct_states == sum(ORBITS)
    assert res.metrics["gauges"]["symmetry_perms"] == 2
    if build is _sharded:
        return
    assert res.metrics["counters"]["recovering_states"] > 0
    if build is _paged:
        # the representatives the run kept: one an orbit, and level
        # for level the reference's orbits
        for block, want in zip(eng.level_blocks, reference):
            images = orbit_reference.level_images(
                eng.codec, spec.symmetry_perms, block)
            assert len(set(images)) == len(images)
            assert set(images) == want


# ---------------------------------------------------------------------
# (f) a shape with RestartEmptyLimit = 0 is the object it was
# ---------------------------------------------------------------------
# Read at commit 1d23b93 (the parent of the PR that gave the receive-set
# its K slots), in a process of its own, with this file's `_program`.
# A PR that changes the level program of the restart-free shapes on
# purpose reads them again; this PR had to leave them alone.  PR 48
# (every guard of `VSRKernel` a table of the state) read "lowered"
# again: pack manifest and store key are 1d23b93's, and of the level
# program's jaxpr every equation outside the scope
# `tpuvsr.level.guard_matrix` is the parent's, in order (34,505 of
# them at vsr-defect, 23,384 at vsr-shipped; stage 1 itself 1,723 →
# 1,400).  PR 52 (`hash_mode` defaults to "full") read both keys again,
# since the key's document names the hash, and vsr-defect's "lowered":
# it is what e641fa7 lowers with `hash_mode="full"`, and this tree's
# `hash_mode="incremental"` lowers e641fa7's 87b63039…684cbe681, so the
# two trees differ by which of the two programs is the default's and
# nothing else; vsr-shipped's "lowered" did not move (canon forces the
# full hash whatever `hash_mode` says).  PR 54 changed what "lowered"
# digests and no program: the module with its private functions folded
# (`_private_functions_folded`).  The plain text's sha256 is still
# 56a76fa6…dcb4e6d6a (vsr-shipped) and 9590e9de…a14d5cd2 (vsr-defect)
# in a process of its own, but in one suite run in eight or so
# vsr-shipped read b4260724…17666c1e (PR 49 saw it once, PR 54 once
# and then caught both texts): the same module with ONE more private
# `_where(128xi1, 128xi32, 128xi32)`, a second copy of a function it
# already held, and every numbered name after it one higher.  PR 56
# (stage 2 selects an action's enabled lanes with `enabled_lanes`, not
# `jnp.nonzero`) read "lowered" again, of both shapes: pack manifest
# and store key are as before, and of the level program's jaxpr every
# equation outside the scope `tpuvsr.level.compact` is the parent's,
# in order, by primitive, name stack and result types (14,508 of them
# at vsr-defect, 19,179 at vsr-shipped; inside the scope 5,586 → 5,092
# and 5,605 → 5,111: 26 fewer an action).
BEFORE_K = {
    "vsr-shipped": {
        "pack": "4794ecbbab3f66ae8443bca05016080f448e065ace9c9565337be2002ba5b17c",
        "key": "aa51df70451f4b78784f12dfcb5c630a08d66816430fded995dc27b0b6a14998",
        "lowered": "6ecb339591301d37ee4dd0ddec5f5b97aa7fa3a30572dcad5a5d859b618a4af0"},
    "vsr-defect": {
        "pack": "1730ab9928885a97b25695c893b0321fda8edeb4d2ed3b664416ebd42db6952d",
        "key": "18b401550e71f9e76a6e79b6c8086ac74fa968e0e736c184bd440ec63b97f52a",
        "lowered": "3e64702f0b49012e7d87df554299ad501b587ab62d062261f08a3ca8a9357d88"},
}

_PRIVATE = re.compile(r"^  func\.func private @([\w.$-]+)\(")
_SYMBOL = re.compile(r"@([\w.$-]+)")


def _private_functions_folded(text):
    """A lowered module's text with each private function named by the
    sha256 of its body, one copy of each, in the order of those names.
    JAX lowers an inner jit (`jnp.where`, `cumsum`, `clip`...) to one
    private function a cached jaxpr OBJECT and numbers equal names as
    it meets them: whether two uses share a function, and so every
    number after them, follows what the process's tracing caches hand
    back, which other tests of the process move.  The functions'
    bodies, their callers and `main` are the program."""
    head, funcs, name = [], {}, None
    for line in text.split("\n"):
        found = _PRIVATE.match(line)
        if found:
            name = found.group(1)
            funcs[name] = []
        (head if name is None else funcs[name]).append(line)
        if line == "  }":
            name = None
    folded = {}

    def fold(symbol):
        if symbol in funcs and symbol not in folded:
            body = _SYMBOL.sub(lambda m: "@" + fold(m.group(1)),
                               "\n".join(funcs[symbol][1:]))
            sign = funcs[symbol][0].replace("@" + symbol + "(", "@(", 1)
            folded[symbol] = "f" + hashlib.sha256(
                (sign + "\n" + body).encode()).hexdigest()[:20]
        return folded.get(symbol, symbol)

    def renamed(lines):
        return [_SYMBOL.sub(lambda m: "@" + fold(m.group(1)), line)
                for line in lines]
    bodies = {fold(symbol): renamed(lines)
              for symbol, lines in funcs.items()}
    return "\n".join(renamed(head)
                     + [line for key in sorted(bodies)
                        for line in bodies[key]])


def _program(config):
    """sha256 of the pack manifest, the program store's key for what
    the engine says its trace reads (the process's part, which digests
    the package's source, left out) and the lowered level program."""
    from tpuvsr.engine import program_store
    from tpuvsr.engine.device_bfs import DeviceBFS
    from tpuvsr.engine.fpset import empty_table
    eng = DeviceBFS(load_spec("VSR", os.path.join(CONFIGS, config + ".cfg")),
                    max_msgs=32, tile_size=128, next_capacity=1 << 12,
                    fpset_capacity=1 << 14)
    bufs = eng._alloc_bufs(eng.next_cap)
    i32 = jnp.zeros((), jnp.int32)
    args = ({"slots": empty_table(eng.fpset_capacity)["slots"]}, bufs[0],
            i32, i32, *bufs, i32, jnp.zeros((), bool), None, None, i32)

    def sha(text):
        return hashlib.sha256(text.encode()).hexdigest()
    return {"pack": sha(json.dumps(eng._pack_manifest(), sort_keys=True)),
            "key": program_store.program_key(
                eng._level_key_doc(), program_store._signature(args)),
            "lowered": sha(_private_functions_folded(
                eng._level.lower(*args).as_text()))}


@pytest.mark.parametrize("config", sorted(BEFORE_K))
def test_a_shape_without_restarts_is_untouched(config):
    assert _program(config) == BEFORE_K[config]


# The sharded step of the four-chip defect cell, at the capacities
# `benchmark/configs/vsr-defect-4chip.json` builds it with, read at
# commit bb89680 (the parent of PR 55, which taught the step to carry a
# kernel's `commit_stats` to the owner of each row): a kernel whose
# hook returns None for its shape, the defect cfg's, has the step it
# had, word for word (the plain text's sha256 read c4c15971…18c7f6c5 on
# both trees in a process of its own).  Read again in PR 56, whose
# `enabled_lanes` is stage 2's selection in this step too: every
# equation of the step's jaxpr outside the scope `tpuvsr.level.compact`
# is the parent's, in order (14,206 of them; inside it 5,594 → 5,100);
# the plain text's sha256 reads a4ff5f77…b2632603.
FOUR_CHIP_STEP = \
    "b740a450ebe58105033328416300dccfe4a1816cc3b5cc82c07336416022a707"


def test_the_four_chip_defect_step_is_untouched():
    from tpuvsr.parallel.sharded_bfs import ShardedBFS
    with open(os.path.join(CONFIGS, "vsr-defect-4chip.json")) as f:
        doc = json.load(f)
    D = doc["layout"]["chips"]
    eng = ShardedBFS(
        load_spec(doc["module"], os.path.join(REPO, "benchmark", doc["cfg"])),
        Mesh(np.array(jax.devices()[:D]), ("d",)),
        **doc["assumed"]["engine"]["sharded"])
    assert eng._stat_fn is None

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=eng._sh)
    per_dev = arg((D,), jnp.int32)
    rows = arg((D * eng.N, eng._pk.words), jnp.uint32)
    col = arg((D * eng.N,), jnp.int32)
    lowered = eng._step.lower(
        {"slots": arg((D, eng.fp_cap, 5), jnp.uint32)}, rows, per_dev,
        per_dev, rows, col, col, col, per_dev, per_dev)
    assert hashlib.sha256(_private_functions_folded(
        lowered.as_text()).encode()).hexdigest() == FOUR_CHIP_STEP


_MODULE = """module @jit_level {
  func.func public @main(%arg0: tensor<4xi1>) -> tensor<4xi32> {
    %0 = func.call @_where(%arg0) : (tensor<4xi1>) -> tensor<4xi32>
    %1 = func.call @WHERE_AGAIN(%arg0) : (tensor<4xi1>) -> tensor<4xi32>
    %2 = func.call @clip_7(%1) : (tensor<4xi32>) -> tensor<4xi32>
    return %2 : tensor<4xi32>
  }
  func.func private @_where(%arg0: tensor<4xi1>) -> tensor<4xi32> {
    %0 = stablehlo.convert %arg0 : (tensor<4xi1>) -> tensor<4xi32>
    return %0 : tensor<4xi32>
  }
  func.func private @clip_7(%arg0: tensor<4xi32>) -> tensor<4xi32> {
    %0 = func.call @_where_3(%arg0) : (tensor<4xi32>) -> tensor<4xi32>
    return %0 : tensor<4xi32>
  }
  func.func private @_where_3(%arg0: tensor<4xi32>) -> tensor<4xi32> {
    %0 = stablehlo.CLAMP %arg0 : tensor<4xi32>
    return %0 : tensor<4xi32>
  }
}"""
# the same program as a process whose caches missed once lowers it: a
# second copy of `_where`, and the numbered names after it one higher
_SPLIT = _MODULE.replace("@WHERE_AGAIN", "@_where_2").replace(
    "@clip_7", "@clip_8").replace("@_where_3", "@_where_4").replace(
    "  func.func private @clip_8", """\
  func.func private @_where_2(%arg0: tensor<4xi1>) -> tensor<4xi32> {
    %0 = stablehlo.convert %arg0 : (tensor<4xi1>) -> tensor<4xi32>
    return %0 : tensor<4xi32>
  }
  func.func private @clip_8""")


@pytest.mark.parametrize("case", ["split", "body", "caller", "main"])
def test_the_fold_keeps_the_program_and_drops_the_numbering(case):
    shared = _private_functions_folded(
        _MODULE.replace("@WHERE_AGAIN", "@_where"))
    other = {
        "split": _SPLIT,
        "body": _SPLIT.replace("stablehlo.CLAMP", "stablehlo.negate"),
        "caller": _SPLIT.replace("func.call @_where_4(",
                                 "func.call @_where_2(", 1),
        "main": _SPLIT.replace("return %2", "return %1"),
    }[case]
    assert _SPLIT.count("func.func private") == 4
    assert shared.count("func.func private") == 3
    assert (_private_functions_folded(other) == shared) == (case == "split")
