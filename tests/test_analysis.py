"""speclint (tpuvsr/analysis) tests.

Two halves:

* reference-corpus greenness — all five passes report zero errors over
  all eight corpus models (gated on the mounted reference, like every
  corpus test);
* seeded-defect fixtures — each pass must FIRE on a deliberately
  broken inline spec: a missing UNCHANGED variable (frames), a
  1-bit-too-narrow packed field (widths), a statically dead guard and
  a vacuous invariant (vacuity), a non-bijective permutation and an
  ordered use of a symmetric value (symmetry), and a kernel with a
  renamed action plus an unhashed plane (drift).

Plus the engine pre-flight contract (abort before dispatch, -lint=off
override) and the CLI flag-conflict validation (argparse exit code 2).
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from tests.conftest import REFERENCE, requires_reference
from tpuvsr.analysis import (LintError, PASS_ORDER, PREFLIGHT_PASSES,
                             preflight, run_lint)
from tpuvsr.analysis.passes.drift import check_drift
from tpuvsr.analysis.report import LintReport
from tpuvsr.engine.bfs import bfs_check
from tpuvsr.engine.spec import SpecModel
from tpuvsr.frontend.cfg import parse_cfg_text
from tpuvsr.frontend.parser import parse_module_text


def _spec(src, cfg):
    return SpecModel(parse_module_text(src), parse_cfg_text(cfg))


def _fired(report, passname, severity=None):
    return [f for f in report.findings if f.passname == passname
            and (severity is None or f.severity == severity)]


# ---------------------------------------------------------------------
# corpus greenness (all five passes x all eight models)
# ---------------------------------------------------------------------
ANALYSIS = f"{REFERENCE}/analysis"

_COMMON = """
    Normal = Normal
    ViewChange = ViewChange
    StateTransfer = StateTransfer
    Recovering = Recovering
    PrepareMsg = PrepareMsg
    PrepareOkMsg = PrepareOkMsg
    StartViewChangeMsg = StartViewChangeMsg
    DoViewChangeMsg = DoViewChangeMsg
    StartViewMsg = StartViewMsg
    GetStateMsg = GetStateMsg
    NewStateMsg = NewStateMsg
    RecoveryMsg = RecoveryMsg
    RecoveryResponseMsg = RecoveryResponseMsg
    Nil = Nil
    AnyDest = AnyDest
"""

RECOVERY_CFG = """CONSTANTS
    ReplicaCount = 3
    Values = {v1}
    StartViewOnTimerLimit = 1
    NoProgressChangeLimit = 0
    CrashLimit = 1
""" + _COMMON + """
INIT Init
NEXT Next
VIEW view
INVARIANT
NoLogDivergence
AcknowledgedWriteNotLost
"""

CP_CFG = RECOVERY_CFG.replace("INIT Init", """    GetCheckpointMsg = GetCheckpointMsg
    NewCheckpointMsg = NewCheckpointMsg
    NoOp = NoOp
INIT Init""")

CORPUS = [
    ("vsr", "VSR.tla", "VSR.cfg", None),
    ("a01", "analysis/01-view-changes/VR_ASSUME_NEWVIEWCHANGE.tla",
     "analysis/01-view-changes/VR_ASSUME_NEWVIEWCHANGE.cfg", None),
    ("i01", "analysis/01-view-changes/VR_INC_RESEND.tla",
     "analysis/01-view-changes/VR_INC_RESEND.cfg", None),
    ("st03", "analysis/03-state-transfer/VR_STATE_TRANSFER.tla",
     "analysis/03-state-transfer/VR_STATE_TRANSFER.cfg", None),
    ("as04", "analysis/04-application-state/VR_APP_STATE.tla",
     "analysis/04-application-state/VR_APP_STATE.cfg", None),
    ("rr05", "analysis/05-replica-recovery/VR_REPLICA_RECOVERY.tla",
     None, RECOVERY_CFG),
    ("al05",
     "analysis/05-replica-recovery/VR_REPLICA_RECOVERY_ASYNC_LOG.tla",
     None, RECOVERY_CFG),
    ("cp06",
     "analysis/06-replica-recovery-cp/VR_REPLICA_RECOVERY_CP.tla",
     None, CP_CFG),
]


@requires_reference
@pytest.mark.parametrize("stem,tla,cfg,cfg_text",
                         CORPUS, ids=[c[0] for c in CORPUS])
def test_corpus_model_lints_clean(stem, tla, cfg, cfg_text):
    import time
    from tpuvsr.frontend.cfg import parse_cfg_file
    from tpuvsr.frontend.parser import parse_module_file
    mod = parse_module_file(f"{REFERENCE}/{tla}")
    model = parse_cfg_file(f"{REFERENCE}/{cfg}") if cfg \
        else parse_cfg_text(cfg_text)
    spec = SpecModel(mod, model)
    t0 = time.time()
    report = run_lint(spec)
    elapsed = time.time() - t0
    assert list(report.passes_run) == list(PASS_ORDER)
    assert report.ok, "\n" + report.render()
    assert elapsed < 5.0, f"lint took {elapsed:.1f}s (budget 5s)"


# ---------------------------------------------------------------------
# pass 1: frames — fires on a missing UNCHANGED variable
# ---------------------------------------------------------------------
def test_frames_fires_on_missing_unchanged():
    spec = _spec("""---- MODULE BF ----
EXTENDS Naturals
VARIABLES x, y
Init == x = 0 /\\ y = 0
Step == x' = x + 1
Next == Step
====
""", "INIT Init\nNEXT Next\n")
    errs = _fired(run_lint(spec, passes=("frames",)), "frames", "error")
    assert errs and "'y'" in errs[0].message


def test_frames_fires_on_double_prime_and_partial_frame():
    spec = _spec("""---- MODULE DP ----
EXTENDS Naturals
VARIABLES x, y
Init == x = 0 /\\ y = 0
Step == /\\ x'' = x
        /\\ IF x = 0 THEN y' = 1 ELSE TRUE
Next == Step
====
""", "INIT Init\nNEXT Next\n")
    rep = run_lint(spec, passes=("frames",))
    assert any("double prime" in f.message for f in rep.errors)
    # y is primed on the THEN path only: partial-frame warning
    assert any("some paths" in f.message and f.subject == "Step"
               for f in rep.warnings)


def test_frames_clean_on_fully_framed_action():
    spec = _spec("""---- MODULE OK ----
EXTENDS Naturals
VARIABLES x, y
vars == <<x, y>>
Init == x = 0 /\\ y = 0
Step == x' = x + 1 /\\ UNCHANGED y
Reset == x' = 0 /\\ UNCHANGED << y >>
Next == Step \\/ Reset
====
""", "INIT Init\nNEXT Next\n")
    rep = run_lint(spec, passes=("frames",))
    assert rep.ok and not rep.warnings


# ---------------------------------------------------------------------
# pass 2: widths — fires on a 1-bit-too-narrow packed field
# ---------------------------------------------------------------------
WIDTH_MOD = """---- MODULE VR_REPLICA_RECOVERY ----
EXTENDS Naturals
CONSTANTS ReplicaCount, Values, StartViewOnTimerLimit, CrashLimit
VARIABLES x
Init == x = 0
Step == x' = x
Next == Step
====
"""


def _width_cfg(timer):
    return (f"CONSTANTS\n ReplicaCount = 3\n Values = {{v1}}\n"
            f" StartViewOnTimerLimit = {timer}\n CrashLimit = 1\n"
            f"INIT Init\nNEXT Next\n")


def test_widths_fires_one_past_the_packed_budget():
    # MAX_VIEW = 1 + timer; ENTRY_VIEW_BITS = 8 -> 255 is the last
    # representable view: timer=254 fits exactly, timer=255 overflows
    ok = run_lint(_spec(WIDTH_MOD, _width_cfg(254)), passes=("widths",))
    assert ok.ok
    bad = run_lint(_spec(WIDTH_MOD, _width_cfg(255)), passes=("widths",))
    errs = _fired(bad, "widths", "error")
    assert errs and errs[0].subject == "view_number"
    assert "overflow" in errs[0].message


def test_widths_reports_headroom_info():
    rep = run_lint(_spec(WIDTH_MOD, _width_cfg(1)), passes=("widths",))
    assert rep.ok
    infos = _fired(rep, "widths", "info")
    assert any("headroom" in f.message for f in infos)


# -- AL05 suffix-log / CP06 checkpoint-plane dedicated fields (ISSUE 4)
def _family_mod(name):
    return WIDTH_MOD.replace("VR_REPLICA_RECOVERY ", f"{name} ")


def _family_cfg(values="{v1}", timer=1):
    return (f"CONSTANTS\n ReplicaCount = 3\n Values = {values}\n"
            f" StartViewOnTimerLimit = {timer}\n CrashLimit = 1\n"
            f"INIT Init\nNEXT Next\n")


def test_widths_al05_suffix_log_dedicated_field():
    mod = _family_mod("VR_REPLICA_RECOVERY_ASYNC_LOG")
    # derivable bound: the dedicated suffix_log field reports the
    # re-based plane fit (the suffix consumes the full MAX_OPS plane
    # exactly, by construction)
    rep = run_lint(_spec(mod, _family_cfg()), passes=("widths",))
    assert rep.ok
    infos = _fired(rep, "widths", "info")
    assert any(f.subject == "suffix_log" and "re-based" in f.message
               for f in infos)
    # Values bound to a non-set: the suffix-log bound is underivable
    # and the dedicated field FIRES as a warning
    rep = run_lint(_spec(mod, _family_cfg(values="v1")),
                   passes=("widths",))
    warns = _fired(rep, "widths", "warning")
    assert any(f.subject == "suffix_log" and "unverified" in f.message
               for f in warns)
    # AL05 entries are plain value ids — the old packed-entry
    # "operation << 8" attribution must be gone; the view bound stays
    # (inherited RR05Codec construction guard) and still fires
    assert not any(f.subject == "operation" for f in rep.findings)
    bad = run_lint(_spec(mod, _family_cfg(timer=255)),
                   passes=("widths",))
    errs = _fired(bad, "widths", "error")
    assert errs and errs[0].subject == "view_number"
    assert "RR05Codec" in errs[0].message


def test_widths_cp06_checkpoint_plane_and_entry_code():
    mod = _family_mod("VR_REPLICA_RECOVERY_CP")
    # dedicated checkpoint-plane field reports the fit
    rep = run_lint(_spec(mod, _family_cfg()), passes=("widths",))
    assert rep.ok
    assert any(f.subject == "checkpoint_plane"
               and "m_cp" in f.message
               for f in _fired(rep, "widths", "info"))
    # underivable Values: the dedicated field fires as a warning
    rep = run_lint(_spec(mod, _family_cfg(values="v1")),
                   passes=("widths",))
    assert any(f.subject == "checkpoint_plane"
               for f in _fired(rep, "widths", "warning"))
    # the WinningDVC suffix sort key packs entries into a 64-wide
    # field: NoOp id = |Values|+1, so 62 values is the last fit and
    # 63 overflows (one past the budget, the classic silent mis-sort)
    v62 = "{" + ", ".join(f"v{i}" for i in range(1, 63)) + "}"
    v63 = "{" + ", ".join(f"v{i}" for i in range(1, 64)) + "}"
    ok = run_lint(_spec(mod, _family_cfg(values=v62)),
                  passes=("widths",))
    assert ok.ok
    bad = run_lint(_spec(mod, _family_cfg(values=v63)),
                   passes=("widths",))
    errs = _fired(bad, "widths", "error")
    assert errs and errs[0].subject == "entry_code"
    assert "_winning_dvc" in errs[0].message


# ---------------------------------------------------------------------
# pass 3: vacuity — dead guard, vacuous invariant
# ---------------------------------------------------------------------
def test_vacuity_fires_on_dead_action_and_vacuous_invariant():
    spec = _spec("""---- MODULE DG ----
EXTENDS Naturals
CONSTANTS Limit
VARIABLES aux_svc
Init == aux_svc = 0
Tick == /\\ aux_svc < Limit
        /\\ aux_svc' = aux_svc + 1
Noop == aux_svc' = aux_svc
Next == Tick \\/ Noop
AlwaysTrue == Limit >= 0
====
""", "CONSTANTS\n Limit = 0\nINIT Init\nNEXT Next\n"
         "INVARIANT AlwaysTrue\n")
    rep = run_lint(spec, passes=("vacuity",))
    warns = _fired(rep, "vacuity", "warning")
    assert any(f.subject == "Tick" and "dead action" in f.message
               for f in warns)
    assert any(f.subject == "AlwaysTrue" and "vacuous" in f.message
               for f in warns)
    # with a positive limit neither fires
    live = _spec("""---- MODULE DG ----
EXTENDS Naturals
CONSTANTS Limit
VARIABLES aux_svc
Init == aux_svc = 0
Tick == /\\ aux_svc < Limit
        /\\ aux_svc' = aux_svc + 1
Next == Tick
====
""", "CONSTANTS\n Limit = 2\nINIT Init\nNEXT Next\n")
    assert not _fired(run_lint(live, passes=("vacuity",)), "vacuity",
                      "warning")


def test_vacuity_statically_false_invariant_is_error():
    spec = _spec("""---- MODULE FI ----
EXTENDS Naturals
CONSTANTS Limit
VARIABLES x
Init == x = 0
Step == x' = x
Next == Step
Broken == Limit > Limit
====
""", "CONSTANTS\n Limit = 1\nINIT Init\nNEXT Next\nINVARIANT Broken\n")
    errs = _fired(run_lint(spec, passes=("vacuity",)), "vacuity",
                  "error")
    assert errs and errs[0].subject == "Broken"


# ---------------------------------------------------------------------
# pass 4: symmetry — asymmetric perm, ordered use
# ---------------------------------------------------------------------
def test_symmetry_fires_on_non_bijective_perm():
    spec = _spec("""---- MODULE BS ----
EXTENDS Naturals, TLC
CONSTANTS Values
VARIABLES s
BadSym == {[v \\in Values |-> CHOOSE w \\in Values : TRUE]}
Init == s = 0
Step == s' = s
Next == Step
====
""", "CONSTANTS\n Values = {v1, v2}\nINIT Init\nNEXT Next\n"
         "SYMMETRY BadSym\n")
    errs = _fired(run_lint(spec, passes=("symmetry",)), "symmetry",
                  "error")
    assert errs and "bijection" in errs[0].message


def test_symmetry_fires_on_ordered_use_of_symmetric_value():
    spec = _spec("""---- MODULE OS ----
EXTENDS Naturals, TLC
CONSTANTS Values
VARIABLES s
Sym == Permutations(Values)
Init == s = 0
Step == \\E v \\in Values : /\\ v < v \\/ TRUE
                           /\\ s' = s
Next == Step
====
""", "CONSTANTS\n Values = {v1, v2}\nINIT Init\nNEXT Next\n"
         "SYMMETRY Sym\n")
    errs = _fired(run_lint(spec, passes=("symmetry",)), "symmetry",
                  "error")
    assert errs and "order/arithmetic" in errs[0].message


def test_symmetry_clean_on_sound_permutations():
    spec = _spec("""---- MODULE GS ----
EXTENDS Naturals, TLC
CONSTANTS Values, Nil
VARIABLES slot
Sym == Permutations(Values)
Init == slot = Nil
Assign == \\E v \\in Values : slot' = v
Next == Assign
====
""", "CONSTANTS\n Values = {v1, v2}\n Nil = Nil\n"
         "INIT Init\nNEXT Next\nSYMMETRY Sym\n")
    assert run_lint(spec, passes=("symmetry",)).ok


# ---------------------------------------------------------------------
# pass 5: drift — renamed action, unhashed plane
# ---------------------------------------------------------------------
TOY = """---- MODULE Toy ----
EXTENDS Naturals
VARIABLES x
Init == x = 0
A == x' = x + 1
B == x' = x
Next == A \\/ B
====
"""


class _StubShape:
    R, V, MAX_MSGS, MAX_OPS = 3, 1, 8, 1


class _StubCodec:
    shape = _StubShape()

    def zero_state(self):
        return {"x": 0, "ghost": 0}


class _StubKern:
    action_names = ("A", "B")
    REP_KEYS = ("x", "ghost")
    MSG_KEYS = ()
    AUX_KEYS = ()

    def _lane_count(self, name):
        return 1


def test_drift_fires_on_renamed_action():
    spec = _spec(TOY, "INIT Init\nNEXT Next\n")
    kern = _StubKern()
    kern.action_names = ("A", "Bx")       # renamed in the kernel
    rep = LintReport(module="Toy")
    check_drift(spec, _StubCodec(), kern, rep)
    errs = _fired(rep, "drift", "error")
    assert any(f.subject == "B" for f in errs)     # spec-only action
    assert any(f.subject == "Bx" for f in errs)    # kernel-only action


def test_drift_fires_on_unhashed_plane():
    spec = _spec(TOY, "INIT Init\nNEXT Next\n")
    kern = _StubKern()
    kern.REP_KEYS = ("x",)                # ghost plane not hashed
    rep = LintReport(module="Toy")
    check_drift(spec, _StubCodec(), kern, rep)
    errs = _fired(rep, "drift", "error")
    assert any(f.subject == "ghost" for f in errs)


def test_drift_clean_on_matching_stub():
    spec = _spec(TOY, "INIT Init\nNEXT Next\n")
    rep = LintReport(module="Toy")
    check_drift(spec, _StubCodec(), _StubKern(), rep)
    assert not rep.findings, [str(f) for f in rep.findings]


class _PackCodec(_StubCodec):
    """Stub codec with packed-frontier bounds (ISSUE 9): `x` claims a
    3-bit budget, `ghost` a 1-bit one.  TOY's init state (x = 0)
    encodes in range."""

    def plane_bounds(self, ranges):
        return {"x": (0, 7), "ghost": (0, 1)}

    def encode(self, st):
        return {"x": np.int32(int(st["x"])), "ghost": np.int32(0)}


def test_pack_drift_clean_on_matching_bounds():
    from tpuvsr.analysis.passes.drift import check_pack_drift
    spec = _spec(TOY, "INIT Init\nNEXT Next\n")
    rep = LintReport(module="Toy")
    check_pack_drift(spec, _PackCodec(), rep)
    assert not _fired(rep, "drift", "error"), \
        [str(f) for f in rep.findings]
    # the pass reports the packed sizing as an INFO line
    assert any("round-trip" in f.message
               for f in _fired(rep, "drift"))


def test_pack_drift_fires_on_codec_width_edit():
    """ISSUE 9 satellite fixture: a codec width/encoding edit WITHOUT
    a widths-table/bounds edit fails speclint.  Here the codec starts
    encoding x with a +10 offset (a layout change) while plane_bounds
    still claims the old 3-bit budget — the init state no longer
    round-trips the packed format and the drift pass errors instead
    of letting the engines wrap silently."""
    from tpuvsr.analysis.passes.drift import check_pack_drift

    class Edited(_PackCodec):
        def encode(self, st):
            return {"x": np.int32(int(st["x"]) + 10),
                    "ghost": np.int32(0)}
    spec = _spec(TOY, "INIT Init\nNEXT Next\n")
    rep = LintReport(module="Toy")
    check_pack_drift(spec, Edited(), rep)
    errs = _fired(rep, "drift", "error")
    assert any(f.subject == "x" and "round-trip" in f.message
               for f in errs), [str(f) for f in rep.findings]


def test_pack_drift_fires_on_stale_bound_key_and_bad_arity():
    from tpuvsr.analysis.passes.drift import check_pack_drift

    class StaleKey(_PackCodec):
        def plane_bounds(self, ranges):
            return {"x": (0, 7), "gone": (0, 1)}   # renamed plane
    spec = _spec(TOY, "INIT Init\nNEXT Next\n")
    rep = LintReport(module="Toy")
    check_pack_drift(spec, StaleKey(), rep)
    assert any(f.subject == "gone"
               for f in _fired(rep, "drift", "error"))

    class BadArity(_PackCodec):
        def zero_state(self):
            return {"x": 0, "ghost": np.zeros((2, 3), np.int32)}

        def plane_bounds(self, ranges):
            # per-column list with the wrong arity for ghost's last
            # axis (2 entries vs 3 columns)
            return {"x": (0, 7), "ghost": [(0, 1), (0, 1)]}
    rep2 = LintReport(module="Toy")
    check_pack_drift(spec, BadArity(), rep2)
    assert any("drifted" in f.message
               for f in _fired(rep2, "drift", "error"))


def test_pack_drift_fires_on_zero_row_exclusion():
    """Bounds whose lower end excludes 0 break the all-zero padding
    row every growth path re-packs — the pass must catch it."""
    from tpuvsr.analysis.passes.drift import check_pack_drift

    class NoZero(_PackCodec):
        def plane_bounds(self, ranges):
            return {"x": (1, 8), "ghost": (0, 1)}  # 0 not encodable
    spec = _spec(TOY, "INIT Init\nNEXT Next\n")
    rep = LintReport(module="Toy")
    check_pack_drift(spec, NoZero(), rep)
    assert any(f.subject == "x" and "zero row" in f.message
               for f in _fired(rep, "drift", "error")), \
        [str(f) for f in rep.findings]


def test_drift_kernel_key_tables_cover_all_registered_layouts():
    """Every registered kernel's class key tables exactly cover its
    codec's zero_state planes (the invariant the drift layout check
    relies on) — buildable from constants alone, no reference needed."""
    from tpuvsr.core.values import ModelValue as MV
    from tpuvsr.models import registry
    consts = {
        "ReplicaCount": 3, "ClientCount": 1,
        "Values": frozenset({MV("v1")}),
        "StartViewOnTimerLimit": 1, "RestartEmptyLimit": 0,
        "NoProgressChangeLimit": 0, "CrashLimit": 1,
    }
    for n in ("Normal ViewChange StateTransfer Recovering Nil AnyDest "
              "NoOp PrepareMsg PrepareOkMsg StartViewChangeMsg "
              "DoViewChangeMsg StartViewMsg GetStateMsg NewStateMsg "
              "RecoveryMsg RecoveryResponseMsg GetCheckpointMsg "
              "NewCheckpointMsg").split():
        consts[n] = MV(n)
    for mod in ("VSR", "VR_STATE_TRANSFER", "VR_ASSUME_NEWVIEWCHANGE",
                "VR_INC_RESEND", "VR_APP_STATE", "VR_REPLICA_RECOVERY",
                "VR_REPLICA_RECOVERY_ASYNC_LOG",
                "VR_REPLICA_RECOVERY_CP"):
        codec_cls, kern_cls = registry._resolve(mod)
        codec = codec_cls(consts)
        kern = kern_cls(codec)
        keys = set()
        for attr in ("REP_KEYS", "MSG_KEYS", "AUX_KEYS", "GLOBAL_KEYS"):
            keys.update(getattr(kern, attr, ()))
        planes = set(codec.zero_state().keys())
        assert keys == planes, (
            f"{mod}: missing={sorted(planes - keys)} "
            f"stale={sorted(keys - planes)}")


# ---------------------------------------------------------------------
# engine pre-flight gate
# ---------------------------------------------------------------------
BROKEN_FRAME = """---- MODULE BF ----
EXTENDS Naturals
VARIABLES x, y
Init == x = 0 /\\ y = 0
Step == x' = x + 1
Next == Step
====
"""


def test_preflight_aborts_interpreter_bfs():
    spec = _spec(BROKEN_FRAME, "INIT Init\nNEXT Next\n")
    with pytest.raises(LintError) as ei:
        bfs_check(spec)
    assert "speclint pre-flight failed" in str(ei.value)


def test_preflight_aborts_device_engine_without_dispatch():
    # injected width-overflow defect: the device engine must refuse at
    # run() entry, before any level kernel is built or dispatched
    spec = _spec(WIDTH_MOD, _width_cfg(255))
    from tpuvsr.engine.device_bfs import DeviceBFS

    class NoDispatch(DeviceBFS):
        def _build(self, max_msgs):     # no kernel for module "VR_..."
            self.codec = self.kern = None

        def _register_init(self, res, obs):
            raise AssertionError("dispatch reached despite lint errors")

    eng = NoDispatch(spec)
    with pytest.raises(LintError):
        eng.run()


def test_preflight_override_and_cache(monkeypatch):
    spec = _spec(BROKEN_FRAME, "INIT Init\nNEXT Next\n")
    monkeypatch.setenv("TPUVSR_LINT", "off")
    assert preflight(spec) is None           # disabled -> no gate
    monkeypatch.delenv("TPUVSR_LINT")
    with pytest.raises(LintError):
        preflight(spec)
    with pytest.raises(LintError):           # cached report re-raises
        preflight(spec)
    clean = _spec(TOY, "INIT Init\nNEXT Next\n")
    rep = preflight(clean)
    assert rep.ok and list(rep.passes_run) == list(PREFLIGHT_PASSES)
    assert preflight(clean) is rep           # cache hit


# ---------------------------------------------------------------------
# CLI: -lint mode, flag-conflict validation (exit code 2), -lint=off
# ---------------------------------------------------------------------
def _cli(*argv, cwd=None):
    return subprocess.run(
        [sys.executable, "-m", "tpuvsr", *argv],
        capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "PYTHONPATH": os.path.dirname(os.path.dirname(
                 os.path.abspath(__file__))),
             "HOME": os.path.expanduser("~")})


@pytest.mark.parametrize("argv", [
    ("spec.tla", "-fpset", "host", "-engine", "device"),
    ("spec.tla", "-fpset", "hbm", "-engine", "interp"),
    ("spec.tla", "-fpset", "paged", "-engine", "interp"),
], ids=["host-device", "hbm-interp", "paged-interp"])
def test_cli_flag_conflicts_exit_2(argv):
    # conflicts are argparse errors BEFORE the spec file is touched:
    # the path does not exist, yet the exit is a usage error
    r = _cli(*argv)
    assert r.returncode == 2, (r.returncode, r.stderr)
    assert "usage" in r.stderr.lower() or "error" in r.stderr.lower()


def test_cli_lint_mode_json(tmp_path):
    import json
    (tmp_path / "BF.tla").write_text(BROKEN_FRAME)
    (tmp_path / "BF.cfg").write_text("INIT Init\nNEXT Next\n")
    r = _cli(str(tmp_path / "BF.tla"), "-lint", "-json")
    assert r.returncode == 1
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["ok"] is False and out["errors"] >= 1
    assert any(f["pass"] == "frames" and f["severity"] == "error"
               for f in out["findings"])

    (tmp_path / "OK.tla").write_text(TOY)
    (tmp_path / "OK.cfg").write_text("INIT Init\nNEXT Next\n")
    r = _cli(str(tmp_path / "OK.tla"), "-lint", "-json")
    assert r.returncode == 0, r.stderr
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["ok"] is True and out["passes"] == list(PASS_ORDER)


def test_cli_preflight_gate_and_lint_off(tmp_path):
    (tmp_path / "BF.tla").write_text(BROKEN_FRAME)
    (tmp_path / "BF.cfg").write_text("INIT Init\nNEXT Next\n")
    # default: the pre-flight gate refuses the run (exit 1, no engine)
    r = _cli(str(tmp_path / "BF.tla"), "-engine", "interp", "-json")
    assert r.returncode == 1
    assert "speclint pre-flight failed" in r.stderr
    # -lint=off bypasses the gate; the interpreter then fails at the
    # first enabled step with its own runtime error (nonzero, but NOT
    # the lint gate)
    r = _cli(str(tmp_path / "BF.tla"), "-engine", "interp",
             "-lint=off", "-json")
    assert "speclint pre-flight failed" not in r.stderr
