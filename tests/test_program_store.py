"""The store of traced programs (tpuvsr/engine/program_store.py), on
the committed native small check: no reference mount.

What is held here: the key moves with every input of the level
program's trace; a program read back from the store commits what the
traced one commits, bit for bit, and is found by a second process; a
damaged entry is repaired, never fatal; a kernel the package's source
does not determine never enters the store."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from tests.conftest import REPO, SMALL_CFG
from tests.test_commit import _assert_small_depth6, _small_engine
from tpuvsr.engine import device_bfs, program_store
from tpuvsr.engine.device_bfs import DeviceBFS
from tpuvsr.engine.fpset import empty_table
from tpuvsr.engine.spec import load_spec
from tpuvsr.obs import RunObserver, builds, read_journal
from tpuvsr.testing import subprocess_env

SHIPPED_CFG = os.path.join(REPO, "benchmark", "configs",
                           "vsr-shipped.cfg")


def _level_args(eng):
    import jax.numpy as jnp
    bufs = eng._alloc_bufs(eng.next_cap)
    i32 = jnp.zeros((), jnp.int32)
    return ({"slots": empty_table(eng.fpset_capacity)["slots"]},
            bufs[0], i32, i32, *bufs, i32, jnp.zeros((), bool),
            None, None, i32)


def _key(eng):
    """The key `eng`'s level program is stored under, as
    `StoredProgram._build` makes it."""
    doc = eng._level_key_doc()
    assert doc is not None
    return program_store.program_key(
        [program_store.process_doc(), doc],
        program_store._signature(_level_args(eng)))


def _cfg_with(tmp_path, old, new):
    with open(SMALL_CFG) as f:
        text = f.read()
    assert old in text
    path = str(tmp_path / "changed.cfg")
    with open(path, "w") as f:
        f.write(text.replace(old, new))
    return path


# ---------------------------------------------------------------------
# (1) the key: one input of the trace changed at a time
# ---------------------------------------------------------------------
def _changed_constant(tmp_path, monkeypatch):
    return DeviceBFS(load_spec("VSR", _cfg_with(
        tmp_path, "StartViewOnTimerLimit = 1",
        "StartViewOnTimerLimit = 2")))


def _changed_invariant(tmp_path, monkeypatch):
    return DeviceBFS(load_spec("VSR", _cfg_with(
        tmp_path, "AcknowledgedWriteNotLost", "NoLogDivergence")))


def _changed_tile(tmp_path, monkeypatch):
    return DeviceBFS(load_spec("VSR", SMALL_CFG), tile_size=64)


def _changed_cap(tmp_path, monkeypatch):
    eng = _small_engine()
    eng.expand_caps[3] += 8
    return eng


def _changed_commit_piece(tmp_path, monkeypatch):
    monkeypatch.setattr(device_bfs, "COMMIT_PIECE", 1024)
    return _small_engine()


def _changed_next_capacity(tmp_path, monkeypatch):
    return DeviceBFS(load_spec("VSR", SMALL_CFG), next_capacity=1 << 15)


def _changed_hash_mode(tmp_path, monkeypatch):
    """The hash that is NOT the default's (the full one since ISSUE
    52)."""
    assert _small_engine().hash_mode == "full"
    return DeviceBFS(load_spec("VSR", SMALL_CFG), hash_mode="incremental")


def _changed_commit(tmp_path, monkeypatch):
    return DeviceBFS(load_spec("VSR", SMALL_CFG), commit="per-action")


def _changed_env(tmp_path, monkeypatch):
    monkeypatch.setenv("TPUVSR_FPSET_BARRIER", "1")
    return _small_engine()


def _changed_source(tmp_path, monkeypatch):
    """One byte of a copy of the package's source."""
    copy = str(tmp_path / "tpuvsr")
    shutil.copytree(program_store.PACKAGE_ROOT, copy,
                    ignore=shutil.ignore_patterns("__pycache__"))
    assert (program_store.source_digest(copy)
            == program_store.source_digest(program_store.PACKAGE_ROOT))
    with open(os.path.join(copy, "engine", "fpset.py"), "a") as f:
        f.write("#")
    monkeypatch.setattr(program_store, "PACKAGE_ROOT", copy)
    return _small_engine()


def _changed_x64(tmp_path, monkeypatch):
    import jax
    monkeypatch.setattr(
        program_store, "process_doc",
        lambda doc=program_store.process_doc: dict(
            doc(), x64=not jax.config.jax_enable_x64))
    return _small_engine()


def _changed_module(tmp_path, monkeypatch):
    """The second module through the native door, VR_STATE_TRANSFER,
    at the small check's constants: another kernel class and codec
    under the same engine."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           "vr-state-transfer.cfg")) as f:
        text = f.read()
    cfg = tmp_path / "st03_small.cfg"
    cfg.write_text(text.replace("{v1, v2}", "{v1}").replace(
        "StartViewOnTimerLimit = 2", "StartViewOnTimerLimit = 1"))
    return DeviceBFS(load_spec("VR_STATE_TRANSFER", str(cfg)))


def _changed_module_cp06(tmp_path, monkeypatch):
    """The third module through the native door,
    VR_REPLICA_RECOVERY_CP, at its small cfg: a key of its own, and
    not VR_STATE_TRANSFER's, whose kernel is its base class."""
    eng = DeviceBFS(load_spec("VR_REPLICA_RECOVERY_CP", os.path.join(
        REPO, "examples", "VR_REPLICA_RECOVERY_CP_small.cfg")))
    assert _key(eng) != _key(_changed_module(tmp_path, monkeypatch))
    return eng


CHANGES = {f.__name__[len("_changed_"):]: f for f in (
    _changed_module, _changed_module_cp06,
    _changed_constant, _changed_invariant, _changed_tile, _changed_cap,
    _changed_commit_piece, _changed_next_capacity, _changed_hash_mode,
    _changed_commit, _changed_env, _changed_source, _changed_x64)}


@pytest.mark.parametrize("what", sorted(CHANGES))
def test_key_moves_with_every_input_of_the_trace(what, tmp_path,
                                                 monkeypatch):
    base = _key(_small_engine())
    assert base == _key(_small_engine())        # and with nothing else
    assert _key(CHANGES[what](tmp_path, monkeypatch)) != base


def test_key_moves_with_symmetry():
    """The shipped cfg declares SYMMETRY: canon on and off are two
    programs (and neither is the small check's)."""
    spec = load_spec("VSR", SHIPPED_CFG)
    on, off = DeviceBFS(spec), DeviceBFS(spec, symmetry=False)
    assert on._canon is not None and off._canon is None
    assert len({_key(on), _key(off), _key(_small_engine())}) == 3


def test_key_ignores_what_names_a_run(monkeypatch):
    """A worker exports a new trace triple around every job, and the
    benchmark a profile directory per run: a key that held them would
    never be found again."""
    base = _key(_small_engine())
    for name in sorted(program_store.RUN_SCOPED_ENV):
        monkeypatch.setenv(name, "0123abcd")
    assert _key(_small_engine()) == base


# ---------------------------------------------------------------------
# (2) miss, then hit: the same levels, counts and pointers as the pin
# ---------------------------------------------------------------------
def _run_small(tmp_path, name):
    eng = _small_engine()
    journal = str(tmp_path / f"{name}.jsonl")
    res = eng.run(max_depth=6, obs=RunObserver(journal_path=journal))
    _assert_small_depth6(eng, res)
    level_builds = [e for e in read_journal(journal)
                    if e["event"] == "build" and "level" in e["fun_name"]]
    return res.metrics, [e["export"] for e in level_builds]


def test_miss_then_hit_same_counts(empty_store, tmp_path):
    import jax
    miss, miss_events = _run_small(tmp_path, "miss")
    c = miss["counters"]
    assert (c["build_export_misses"], c["build_export_hits"]) == (1, 0)
    assert miss_events == ["miss"]
    assert miss["gauges"]["build_export_store_s"] > 0
    entries = os.listdir(empty_store)
    assert len(entries) == 1 and entries[0].endswith(".jaxexport")
    # what the first job of a new process finds: nothing traced
    jax.clear_caches()
    hit, hit_events = _run_small(tmp_path, "hit")
    c = hit["counters"]
    assert (c["build_export_misses"], c["build_export_hits"]) == (0, 1)
    assert hit_events == ["hit"]
    assert (c["build_shared_calls"], c["build_shared_traces"]) == (0, 0)
    g = hit["gauges"]
    assert 0 < g["build_export_load_s"] and g["build_export_store_s"] == 0
    assert g["build_trace_s"] * 5 < miss["gauges"]["build_trace_s"]
    assert os.listdir(empty_store) == entries


# ---------------------------------------------------------------------
# (3) a damaged entry is a miss that repairs it
# ---------------------------------------------------------------------
def _stub_program(store_key="k"):
    """A small program through the store, keyed by hand."""
    import jax.numpy as jnp

    def make():
        def double(x):
            return x * 2 + 1
        return double
    return program_store.StoredProgram(make, "double", (), store_key), \
        jnp.arange(8, dtype=jnp.int32)


def _outcome(program, *args):
    meter = builds.BuildMeter()
    previous = builds.attach(meter)
    try:
        out = program(*args)
    finally:
        builds.detach(previous)
    return np.asarray(out).tolist(), (meter.export_hits,
                                      meter.export_misses)


@pytest.mark.parametrize("damage", ["truncated", "garbled", "empty",
                                    "other-avals"])
def test_damaged_entry_is_a_miss_that_repairs_it(empty_store, damage):
    import jax.numpy as jnp
    program, x = _stub_program()
    want = (np.arange(8) * 2 + 1).tolist()
    assert _outcome(program, x) == (want, (0, 1))
    (entry,) = os.listdir(empty_store)
    path = os.path.join(empty_store, entry)
    with open(path, "rb") as f:
        good = f.read()
    if damage == "other-avals":
        # a sound entry of another program's, under this key
        other, y = _stub_program("other")
        _outcome(other, jnp.arange(4, dtype=jnp.int32))
        (theirs,) = set(os.listdir(empty_store)) - {entry}
        os.replace(os.path.join(empty_store, theirs), path)
    else:
        with open(path, "wb") as f:
            f.write({"truncated": good[:len(good) // 2],
                     "garbled": good[:40] + b"\xff" + good[41:],
                     "empty": b""}[damage])
    assert _outcome(_stub_program()[0], x) == (want, (0, 1))
    assert program_store.load(entry[:-len(".jaxexport")],
                              program_store._signature((x,))) is not None
    assert _outcome(_stub_program()[0], x) == (want, (1, 0))


def test_unwritable_store_is_a_miss_every_time(tmp_path, monkeypatch):
    blocked = tmp_path / "file"
    blocked.write_text("not a directory")
    monkeypatch.setattr(program_store, "store_directory",
                        lambda: str(blocked / "store"))
    program, x = _stub_program()
    want = (np.arange(8) * 2 + 1).tolist()
    assert _outcome(program, x) == (want, (0, 1))
    assert _outcome(_stub_program()[0], x) == (want, (0, 1))


# ---------------------------------------------------------------------
# (4) a kernel the source digest does not determine
# ---------------------------------------------------------------------
class OutsideKernel:
    """A kernel class of a test file: nothing under tpuvsr/ says what
    it computes."""

    def __init__(self, kern):
        self._kern = kern

    def __getattr__(self, name):
        return getattr(self.__dict__["_kern"], name)


def _outside_factory(spec, max_msgs=None):
    from tpuvsr.models import registry
    codec, kern = registry.make_model(spec, max_msgs=max_msgs,
                                      fold_symmetry=False)
    return codec, OutsideKernel(kern)


def test_outside_kernel_bypasses_the_store(empty_store, tmp_path,
                                            monkeypatch):
    from tpuvsr.testing import stub_device_engine
    monkeypatch.setattr(builds, "JOURNAL_BUILD_S", 0.0)
    outside = DeviceBFS(load_spec("VSR", SMALL_CFG),
                        model_factory=_outside_factory)
    assert outside._level_key_doc() is None
    # a class made inside a function closes over what nobody can see
    stub = stub_device_engine()
    assert stub._level_key_doc() is None
    journal = str(tmp_path / "j.jsonl")
    res = stub.run(obs=RunObserver(journal_path=journal))
    assert res.ok and res.distinct_states == 16
    c = res.metrics["counters"]
    assert (c["build_export_hits"], c["build_export_misses"]) == (0, 0)
    exports = {e["export"] for e in read_journal(journal)
               if e["event"] == "build"}
    assert exports == {"bypass", "none"}
    assert not os.path.exists(empty_store)


# ---------------------------------------------------------------------
# (5) the wrapper lowers to one text in every process
# ---------------------------------------------------------------------
_CHILD = """
import hashlib, json, sys
sys.path.insert(0, {repo!r})
from tpuvsr.engine import program_store
from tpuvsr.obs import builds
program_store.store_directory = lambda: {store!r}
from tests.test_program_store import _key, _level_args, _small_engine
eng = _small_engine()
args = _level_args(eng)
meter = builds.BuildMeter()
builds.attach(meter)
text = eng._level.lowered_for(program_store._signature(args),
                              args).as_text()
print(json.dumps({{"hits": meter.export_hits, "key": _key(eng),
                  "sha": hashlib.sha256(text.encode()).hexdigest()}}))
"""


def test_wrapper_text_is_stable_across_processes(tmp_path):
    """The first process traces and stores, the second only reads: one
    key, one lowered text, so XLA's persistent cache, which keys on
    the module, gives the second what the first compiled."""
    code = _CHILD.format(repo=REPO, store=str(tmp_path / "store"))
    docs = []
    for hash_seed in ("1", "2"):
        out = subprocess.run(
            [sys.executable, "-c", code], cwd=REPO, text=True,
            capture_output=True, timeout=600,
            env=subprocess_env({"PYTHONHASHSEED": hash_seed}))
        assert out.returncode == 0, out.stderr[-2000:]
        docs.append(json.loads(out.stdout.strip().splitlines()[-1]))
    assert [d["hits"] for d in docs] == [0, 1]
    assert docs[0]["key"] == docs[1]["key"]
    assert docs[0]["sha"] == docs[1]["sha"]


# ---------------------------------------------------------------------
# the benchmark's reader of the two counters
# ---------------------------------------------------------------------
@pytest.mark.parametrize("counters, want", [
    ({"build_export_hits": 1, "build_export_misses": 0}, 100.0),
    ({"build_export_hits": 0, "build_export_misses": 1}, 0.0),
    ({"build_export_hits": 1, "build_export_misses": 3}, 25.0),
    # only bypasses; a program from before the counters; no document
    ({"build_export_hits": 0, "build_export_misses": 0}, None),
    ({"build_programs": 61}, None),
    (None, None),
])
def test_export_hit_share_reader(counters, want):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "engine_export_hit_share", os.path.join(
            REPO, "benchmark", "layer_metrics",
            "engine.export_hit_share.py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    doc = None if counters is None else {"counters": counters,
                                         "gauges": {}}
    assert reader.read({"metrics_doc": doc}, None, None) == want
    assert reader.read({}, None, None) is None
