"""The kernel-native spec (tpuvsr/models/native.py): the main path
built from committed files alone — no reference mount, no AST.

Oracles: the pinned level sizes of the shrunken flagship config
(scripts/pinned_levels_small.json) and the committed 30-state TLC
counterexample (examples/found_violation_trace.txt), which was
replayed step by step through the interpreter against VSR.tla while
the corpus was mounted (tests/test_defect.py).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from tpuvsr.core.values import TLAError
from tpuvsr.engine.spec import load_spec

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMALL_CFG = os.path.join(REPO, "examples", "VSR_small.cfg")
DEFECT_CFG = os.path.join(REPO, "examples", "VSR_defect.cfg")
TRACE = os.path.join(REPO, "examples", "found_violation_trace.txt")


@pytest.fixture(scope="module")
def small():
    return load_spec("VSR", SMALL_CFG)


@pytest.fixture(scope="module")
def defect():
    return load_spec("VSR", DEFECT_CFG)


@pytest.mark.parametrize("cfg", [SMALL_CFG, DEFECT_CFG])
def test_init_round_trips_through_codec(cfg):
    """Entry 1 of the committed trace is the module's one Init state;
    it encodes and decodes to itself under both cfgs (the empty
    aux_client_acked serves |Values|=1 and |Values|=3 alike)."""
    from tpuvsr.models.vsr import VSRCodec
    spec = load_spec("VSR", cfg)
    assert spec.native and spec.module.name == "VSR"
    (st,) = spec.init_states()
    codec = VSRCodec(spec.ev.constants)
    assert codec.decode(codec.encode(st)) == st
    assert st["rep_view_number"].apply(1) == 1
    assert len(st["messages"].items) == 0
    assert spec.check_invariants(st) is None


def test_small_bfs_reproduces_pinned_levels(small, tmp_path):
    """Native VSR_small through DeviceBFS on CPU: the first 7 pinned
    level sizes, and the device identity on run_start."""
    from tpuvsr.engine.device_bfs import DeviceBFS
    from tpuvsr.obs import RunObserver
    with open(os.path.join(REPO, "scripts",
                           "pinned_levels_small.json")) as f:
        pin = json.load(f)["level_sizes"]
    assert pin[:7] == [1, 3, 8, 24, 68, 163, 332]
    journal = tmp_path / "j.jsonl"
    eng = DeviceBFS(small)
    res = eng.run(max_depth=6,
                  obs=RunObserver(journal_path=str(journal)))
    assert res.ok and list(eng.level_sizes) == pin[:7]
    assert res.distinct_states == sum(pin[:7])
    with open(journal) as f:
        start = next(d for d in map(json.loads, f)
                     if d["event"] == "run_start")
    assert (start["platform"], start["device_count"]) == ("cpu", 8)
    assert start["device_kind"] and start["bounds"] is None


def test_trace_walk_on_kernel(defect):
    """chip_smoke.py's phase C on CPU: every recorded step is a
    kernel successor under the recorded action, and the invariant
    fails exactly on state 30."""
    from tpuvsr.models.native import walk_trace
    entries, ok = walk_trace(defect, TRACE)
    assert [e.position for e in entries] == list(range(1, 31))
    assert entries[0].action_name is None
    assert entries[-1].action_name == "ReceiveSV"
    assert ok[:29].all() and not ok[29]
    assert defect.check_invariants(entries[-1].state) == \
        "AcknowledgedWriteNotLost"
    assert defect.check_invariants(entries[-2].state) is None


def test_trace_walk_rejects_a_wrong_step(defect, tmp_path):
    """A trace whose recorded action does not produce the recorded
    state is refused, not walked."""
    from tpuvsr.models.native import walk_trace
    with open(TRACE) as f:
        text = f.read()
    bad = tmp_path / "bad_trace.txt"
    bad.write_text(text.replace('name |-> "ExecuteOp"',
                                'name |-> "SendSV"'))
    with pytest.raises(TLAError, match="no SendSV lane"):
        walk_trace(defect, str(bad))


def test_action_table(small):
    """Kernel action order, with TLC locations where the committed
    trace records them."""
    from tpuvsr.models.vsr_kernel import ACTION_NAMES
    assert [a.name for a in small.actions] == list(ACTION_NAMES)
    loc = {a.name: a.location for a in small.actions}
    assert loc["TimerSendSVC"] == \
        "line 578, col 1 to line 590, col 56 of module VSR"
    assert loc["RestartEmpty"] == "native kernel of module VSR"


def test_unknown_name_still_fails_as_a_missing_file():
    with pytest.raises(FileNotFoundError):
        load_spec("NoSuchModule", SMALL_CFG)
    with pytest.raises(FileNotFoundError):
        load_spec("no/such/dir/VSR.tla", SMALL_CFG)


def test_registered_module_without_init_trace_is_loud():
    """VR_APP_STATE has a kernel and stays shut (VR_STATE_TRANSFER
    went through the door in PR 41: tests/test_native_st03.py)."""
    with pytest.raises(TLAError, match="no committed init trace"):
        load_spec("VR_APP_STATE", SMALL_CFG)


@pytest.mark.parametrize("section", ["SYMMETRY symmReplicas",
                                     "PROPERTY AllReplicasMoveToSameView",
                                     "SPECIFICATION Spec"])
def test_cfg_sections_that_need_the_ast_are_refused(section, tmp_path):
    """PROPERTY, SPECIFICATION and a SYMMETRY definition the committed
    table (native.SYMMETRY_SETS) does not know."""
    with open(SMALL_CFG) as f:
        text = f.read()
    if section.startswith("SPECIFICATION"):
        text = text.replace("INIT Init\nNEXT Next\n", "")
    cfg = tmp_path / "x.cfg"
    cfg.write_text(text + "\n" + section + "\n")
    with pytest.raises(TLAError, match="needs the .tla"):
        load_spec("VSR", str(cfg))


def _with_symmetry(tmp_path, values):
    with open(SMALL_CFG) as f:
        text = f.read()
    cfg = tmp_path / "symm.cfg"
    cfg.write_text(text.replace("Values = {v1}", f"Values = {values}")
                   + "\nSYMMETRY symmValues\n")
    return load_spec("VSR", str(cfg))


@pytest.mark.parametrize("values", ["{v1, v2}", "{v1, v2, v3}"])
def test_symmetry_group_is_permutations_of_values(values, tmp_path):
    """`SYMMETRY symmValues` from the committed table: what
    `SpecModel._symmetry_perms` evaluates `Permutations(Values)` to
    (dicts ModelValue -> ModelValue, fixed points and the identity
    dropped), a closed group."""
    import itertools
    from tpuvsr.engine.canon import group_closed
    spec = _with_symmetry(tmp_path, values)
    elems = sorted(spec.ev.constants["Values"], key=lambda v: v.name)
    want = {frozenset((a, b) for a, b in zip(elems, image) if a is not b)
            for image in itertools.permutations(elems)} - {frozenset()}
    got = [frozenset(p.items()) for p in spec.symmetry_perms]
    assert len(got) == len(set(got)) == len(want)
    assert set(got) == want
    assert group_closed(spec.symmetry_perms)
    # a 3-cycle without its inverse is no group: the check can fail
    cycles = [p for p in spec.symmetry_perms if len(p) == 3]
    assert all(not group_closed([c]) for c in cycles)
    assert len(cycles) == (2 if len(elems) == 3 else 0)


def test_symmetry_of_one_value_is_no_group(tmp_path):
    spec = _with_symmetry(tmp_path, "{v1}")
    assert spec.symmetry_perms == []


def test_shipped_cfg_is_the_one_swap():
    """benchmark/configs/vsr-shipped.cfg: upstream's VSR.cfg."""
    spec = load_spec("VSR", os.path.join(
        REPO, "benchmark", "configs", "vsr-shipped.cfg"))
    (swap,) = spec.symmetry_perms
    assert {k.name: v.name for k, v in swap.items()} == {"v1": "v2",
                                                         "v2": "v1"}
    assert spec.cfg.view == "view"
    assert spec.cfg.invariants == ["AcknowledgedWriteNotLost"]


def test_init_refuses_constants_it_does_not_fit(tmp_path):
    """The committed init state is R=3: another ReplicaCount is a loud
    error, never a silently wrong state space."""
    with open(SMALL_CFG) as f:
        text = f.read()
    cfg = tmp_path / "r5.cfg"
    cfg.write_text(text.replace("ReplicaCount = 3", "ReplicaCount = 5"))
    spec = load_spec("VSR", str(cfg))
    with pytest.raises(TLAError, match="does not fit"):
        list(spec.init_states())


def test_lint_gate_is_off_for_a_native_spec(small):
    """preflight logs one line and returns None; bounds/POR resolve to
    "not consumed" on auto and stay loud when forced."""
    from tpuvsr.analysis import lint_enabled, preflight
    from tpuvsr.engine.bounds import resolve_bounds
    from tpuvsr.engine.por import resolve_por
    lines = []
    assert preflight(small, log=lines.append) is None
    assert len(lines) == 1 and "native spec VSR" in lines[0]
    assert lint_enabled() and not lint_enabled(small)
    assert resolve_bounds(small, "auto") is None
    assert resolve_por(small, "auto") is None
    with pytest.raises(TLAError, match="native spec"):
        resolve_bounds(small, "on")
    with pytest.raises(TLAError, match="native spec"):
        resolve_por(small, "on")


@pytest.mark.parametrize("flags", [["-bounds", "on"], ["-por", "on"],
                                   ["-lint"], ["-lower"],
                                   ["-engine", "interp"]])
def test_cli_flags_that_need_the_ast_exit_2(flags, capsys, monkeypatch):
    from tpuvsr.cli.main import main
    # main() exports -lower to os.environ for the engines: have
    # monkeypatch own the key (an empty value reads as unset), so that
    # it is restored after the test - or every later model this worker
    # builds goes through the lowerer, which a native spec has no AST for
    monkeypatch.setenv("TPUVSR_COMPILED", "")
    with pytest.raises(SystemExit) as e:
        main(["VSR", "-config", SMALL_CFG] + flags)
    assert e.value.code == 2
    assert "needs the module's AST" in capsys.readouterr().err


@pytest.mark.parametrize("env_dir", ["set", "unset"])
def test_compile_cache_directory(env_dir, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set nothing is set in code;
    unset, the cache is <checkout>/.jax_cache.  Fresh interpreter:
    the suite's own process configured its cache long ago."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = os.path.join(REPO, ".jax_cache")
    if env_dir == "set":
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cc")
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax; from tpuvsr.models.registry import "
         "ensure_compile_cache as e; print(e()); print(e()); "
         "print(jax.config.jax_compilation_cache_dir)"],
        env=env, capture_output=True, text=True, check=True)
    assert out.stdout.split() == [want] * 3


def test_device_model_import_error_raises(small, monkeypatch):
    """A registered module whose implementation cannot import is a
    packaging bug: it raises, it does not degrade to the interpreter."""
    from tpuvsr.models import registry

    def broken(name):
        raise ImportError("kernel module went missing")
    monkeypatch.setattr(registry, "_resolve", broken)
    with pytest.raises(ImportError):
        registry.has_device_model(small)


def test_dryrun_multichip_refuses_too_few_devices():
    import __graft_entry__ as g
    with pytest.raises(RuntimeError, match="need 64 devices, have 8"):
        g.dryrun_multichip(64)


def test_graft_entry_builds_from_committed_files():
    import jax

    import __graft_entry__ as g
    fn, args = g.entry()
    fps, en = jax.jit(fn)(*args)
    assert fps.shape[-1] == 4 and fps.shape[0] == en.size
    assert int(np.asarray(en).sum()) == 4 * 3     # 3 timer lanes/state


def test_served_job_journals_the_real_platform(tmp_path):
    """job_started carries the platform the worker process has, not
    the placement advisory's guess."""
    from tpuvsr.service import JobQueue, Worker
    q = JobQueue(str(tmp_path / "spool"))
    job = q.submit("<stub>", kind="check", engine="device",
                   flags={"stub": True})
    Worker(q, devices=1).drain()
    assert q.get(job.job_id).state == "done"
    with open(q.journal_path(job.job_id)) as f:
        started = next(d for d in map(json.loads, f)
                       if d["event"] == "job_started")
    assert started["backend"] == "cpu"
    assert "tpu" in started["placement"]     # the advisory's reason
