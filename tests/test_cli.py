"""CLI tests: the reference specs + cfgs run unchanged through the
TLC-compatible entry point, and the flag contract (documented mutual
exclusions -> argparse exit 2) holds without any spec being loaded.

The flag-contract tests run under tier-1 (no reference mount: the
conflicts fail at parse time, before the spec path is touched); the
end-to-end runs are reference-gated per test.
"""

import json
import subprocess
import sys

import pytest

from tests.conftest import (REFERENCE, REPO, SMALL_CFG,
                            requires_reference)


def _run(*argv, timeout=420):
    return subprocess.run(
        [sys.executable, "-m", "tpuvsr", *argv],
        capture_output=True, text=True, timeout=timeout,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "PYTHONPATH": REPO,
             "HOME": "/root"})


@requires_reference
def test_cli_bfs_interp_maxstates():
    r = _run(f"{REFERENCE}/VSR.tla", "-engine", "interp",
             "-maxstates", "500", "-json")
    assert r.returncode == 0, r.stderr
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["mode"] == "bfs" and out["distinct_states"] >= 500


@requires_reference
def test_cli_simulate_interp():
    r = _run(f"{REFERENCE}/VSR.tla", "-engine", "interp", "-simulate",
             "-num", "5", "-depth", "10", "-json")
    assert r.returncode == 0, r.stderr
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["mode"] == "simulate" and out["walks"] == 5


@requires_reference
def test_cli_checks_temporal_properties(tmp_path):
    # a cfg with PROPERTY must run the liveness checker after safety;
    # fairness-free spec -> stuttering violation, nonzero exit
    spec = """---- MODULE Tk ----
EXTENDS Naturals
VARIABLES x
Init == x = 0
Incr == x' = (x + 1) % 3
Next == Incr
vars == <<x>>
AtZero == x = 0
Prop == []<>AtZero
Spec == Init /\\ [][Next]_vars
FairSpec == Init /\\ [][Next]_vars /\\ WF_vars(Incr)
====
"""
    (tmp_path / "Tk.tla").write_text(spec)
    (tmp_path / "Tk.cfg").write_text("SPECIFICATION Spec\nPROPERTY Prop\n")
    r = _run(str(tmp_path / "Tk.tla"), "-json")
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert r.returncode != 0
    assert out["properties_ok"] is False and out["violated"] == "Prop"

    (tmp_path / "Tk.cfg").write_text(
        "SPECIFICATION FairSpec\nPROPERTY Prop\n")
    r2 = _run(str(tmp_path / "Tk.tla"), "-json")
    out2 = json.loads(r2.stdout.strip().splitlines()[-1])
    assert r2.returncode == 0 and out2["properties_ok"] is True


@requires_reference
def test_cli_analysis_spec_with_shipped_cfg():
    r = _run(f"{REFERENCE}/analysis/03-state-transfer/VR_STATE_TRANSFER.tla",
             "-maxstates", "300", "-json")
    assert r.returncode == 0, r.stderr
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["distinct_states"] >= 300


# ---------------------------------------------------------------------
# flag contract (ISSUE 5 satellite): -engine sharded is first-class —
# -supervise -engine sharded parses, invalid sharded combos are clean
# argparse errors (exit 2) before any spec is loaded.  No reference
# mount needed: the conflicts fire at parse time.
# ---------------------------------------------------------------------
@pytest.mark.parametrize("bad", [
    ["-engine", "sharded", "-simulate"],
    ["-engine", "sharded", "-fpset", "host"],
    ["-engine", "sharded", "-fpset", "hbm"],
    ["-engine", "sharded", "-fpset", "paged"],
    ["-engine", "sharded", "-supervise", "-inject", "kill@level="],
    ["-engine", "sharded", "-inject", "exchange-drop:0@shard=0"],
    ["-engine", "sharded", "-pipeline", "0"],
], ids=["simulate", "fpset-host", "fpset-hbm", "fpset-paged",
        "bad-kill-spec", "zero-drop-count", "bad-pipeline"])
def test_cli_sharded_flag_conflicts_exit_2(bad):
    r = _run("X.tla", *bad)
    assert r.returncode == 2, (r.stdout, r.stderr)
    assert "usage" in r.stderr or "error" in r.stderr


@pytest.mark.parametrize("bad", [
    ["-commit", "fused", "-engine", "interp"],
], ids=["commit-interp"])
def test_cli_commit_flag_conflicts_exit_2(bad):
    """ISSUE 10: -commit configures the BFS level kernel; its
    documented conflicts are argparse errors (exit 2) before any spec
    is loaded."""
    r = _run("X.tla", *bad)
    assert r.returncode == 2, (r.stdout, r.stderr)
    assert "usage" in r.stderr or "error" in r.stderr


@pytest.mark.parametrize("engine", [["-engine", "device"],
                                    ["-fpset", "paged"]],
                         ids=["device", "paged"])
def test_cli_native_check_exact_count(engine, small_pin):
    """`python -m tpuvsr VSR -config examples/VSR_small.cfg` from
    committed files under each one-chip engine: the state limit is
    tested between levels, so the run ends after the level that
    passes 500 states, depth 6, with the pinned count."""
    r = _run("VSR", "-config", SMALL_CFG, *engine,
             "-maxstates", "500", "-json")
    assert r.returncode == 0, r.stderr
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["mode"] == "bfs" and out["ok"] is True
    assert out["distinct_states"] == sum(small_pin[:7]) == 599
    assert out["diameter"] == 6


@pytest.mark.parametrize("flag", ["-fused", "-chained"])
def test_cli_removed_driver_flags_exit_2(flag):
    """`DeviceBFS` has one driver, `run`: the flags that selected the
    other two are unknown arguments, refused before any spec is
    loaded (the path does not exist)."""
    r = _run("X.tla", flag)
    assert r.returncode == 2, (r.stdout, r.stderr)
    assert "unrecognized arguments: " + flag in r.stderr


@pytest.mark.parametrize("bad", [
    ["-pack", "on", "-engine", "interp"],
    ["-pack", "on", "-fpset", "host"],
    ["-pack", "maybe"],
], ids=["interp", "fpset-host", "bad-mode"])
def test_cli_pack_flag_conflicts_exit_2(bad):
    """ISSUE 9 satellite: explicit -pack on needs a device engine (the
    packed frontier is the device engines' interchange format); the
    conflicts are argparse errors before any spec is loaded."""
    r = _run("X.tla", *bad)
    assert r.returncode == 2, (r.stdout, r.stderr)
    assert "usage" in r.stderr or "error" in r.stderr


@pytest.mark.parametrize("bad", [
    ["-symmetry", "on", "-engine", "interp"],
    ["-symmetry", "off", "-fpset", "host"],
    ["-symmetry", "on", "-validate", "t.jsonl"],
    ["-symmetry", "maybe"],
    ["-spill", "/tmp/sp", "-engine", "device"],
    ["-spill", "/tmp/sp", "-engine", "sharded"],
    ["-spill", "/tmp/sp", "-fpset", "hbm"],
    ["-spill", "/tmp/sp", "-fpset", "host"],
    ["-spill", "/tmp/sp", "-simulate"],
    ["-spill", "/tmp/sp", "-supervise"],
], ids=["symmetry-interp", "symmetry-fpset-host",
        "symmetry-validate", "symmetry-bad-mode", "spill-device",
        "spill-sharded", "spill-fpset-hbm", "spill-fpset-host",
        "spill-simulate", "spill-supervise"])
def test_cli_symmetry_spill_flag_conflicts_exit_2(bad):
    """ISSUE 11 satellite: -symmetry configures the device
    canonicalization kernel and -spill the paged engine's disk tier;
    their documented conflicts are argparse errors (exit 2) before
    any spec is loaded."""
    r = _run("X.tla", *bad)
    assert r.returncode == 2, (r.stdout, r.stderr)
    assert "usage" in r.stderr or "error" in r.stderr


@pytest.mark.parametrize("bad", [
    ["-bounds", "on", "-lint=off"],
    ["-bounds", "on", "-engine", "interp"],
    ["-bounds", "on", "-fpset", "host"],
    ["-bounds", "on", "-simulate"],
    ["-bounds", "on", "-validate", "t.jsonl"],
    ["-bounds", "maybe"],
], ids=["lint-off", "interp", "fpset-host", "simulate", "validate",
        "bad-mode"])
def test_cli_bounds_flag_conflicts_exit_2(bad):
    """ISSUE 13 satellite: -bounds on consumes the speclint bounds
    pass, so combining it with -lint=off (untrusted facts) or the
    interpreter engine (no pack/lane tables to tighten) is an
    argparse error (exit 2) before any spec is loaded."""
    r = _run("X.tla", *bad)
    assert r.returncode == 2, (r.stdout, r.stderr)
    assert "usage" in r.stderr or "error" in r.stderr


@pytest.mark.parametrize("bad", [
    ["-por", "on", "-lint=off"],
    ["-por", "on", "-engine", "interp"],
    ["-por", "on", "-fpset", "host"],
    ["-por", "on", "-simulate"],
    ["-por", "on", "-validate", "t.jsonl"],
    ["-por", "on", "-edges", "on"],
    ["-por", "on", "-commit", "per-action"],
    ["-por", "maybe"],
], ids=["lint-off", "interp", "fpset-host", "simulate", "validate",
        "edges-on", "per-action", "bad-mode"])
def test_cli_por_flag_conflicts_exit_2(bad):
    """ISSUE 16 satellite: -por on consumes the speclint independence
    pass inside the fused device commit, so -lint=off (untrusted
    facts), the interpreter engine, the non-BFS modes, -edges on (the
    behavior graph must cover the full relation) and -commit
    per-action are argparse errors (exit 2) before any spec is
    loaded."""
    r = _run("X.tla", *bad)
    assert r.returncode == 2, (r.stdout, r.stderr)
    assert "usage" in r.stderr or "error" in r.stderr


def test_cli_por_on_spec_level_refusals_exit_2(tmp_path):
    """The two refusals that need the spec: -por on with a PROPERTY
    cfg (the reduction preserves invariant/deadlock verdicts, not the
    liveness graph) and -por on resolving to the interpreter (a
    forced flag must not be silently inert) — both exit 2."""
    spec = """---- MODULE Po ----
EXTENDS Naturals
VARIABLES x
Init == x = 0
Incr == x' = (x + 1) % 3
Next == Incr
vars == <<x>>
AtZero == x = 0
Prop == []<>AtZero
Spec == Init /\\ [][Next]_vars
====
"""
    (tmp_path / "Po.tla").write_text(spec)
    (tmp_path / "Po.cfg").write_text(
        "SPECIFICATION Spec\nPROPERTY Prop\n")
    r = _run(str(tmp_path / "Po.tla"), "-por", "on")
    assert r.returncode == 2, (r.stdout, r.stderr)
    assert "temporal" in r.stderr
    # no PROPERTY, but the module has no compiled device kernel: the
    # auto-resolved interpreter cannot host the ample filter
    (tmp_path / "Po.cfg").write_text("INIT Init\nNEXT Next\n")
    r2 = _run(str(tmp_path / "Po.tla"), "-por", "on")
    assert r2.returncode == 2, (r2.stdout, r2.stderr)
    assert "interpreter" in r2.stderr
    # -por off is inert everywhere — parses and runs
    r3 = _run(str(tmp_path / "Po.tla"), "-por", "off",
              "-engine", "interp")
    assert r3.returncode == 0, (r3.stdout, r3.stderr)


@pytest.mark.parametrize("bad", [
    ["-edges", "on", "-simulate"],
    ["-edges", "on", "-validate", "t.jsonl"],
    ["-edges", "on", "-symmetry", "on"],
    ["-edges", "on", "-engine", "interp"],
    ["-edges", "on", "-fpset", "host"],
    ["-edges", "maybe"],
], ids=["simulate", "validate", "symmetry-on", "interp",
        "fpset-host", "bad-mode"])
def test_cli_edges_flag_conflicts_exit_2(bad):
    """ISSUE 15 satellite: -edges on streams the BFS behavior graph,
    so combining it with -simulate/-validate (no graph), -symmetry on
    (orbit-folded fingerprints would merge graph nodes) or the
    interpreter engine is an argparse error (exit 2) before any spec
    is loaded."""
    r = _run("X.tla", *bad)
    assert r.returncode == 2, (r.stdout, r.stderr)
    assert "usage" in r.stderr or "error" in r.stderr


def test_cli_edges_on_without_property_cfg_exit_2(tmp_path):
    """-edges on against a cfg with no PROPERTY is rejected right
    after the cfg loads (there is no temporal check to consume the
    stream), still exit 2 — no engine is ever built."""
    spec = """---- MODULE Ed ----
EXTENDS Naturals
VARIABLES x
Init == x = 0
Incr == x' = (x + 1) % 3
Next == Incr
vars == <<x>>
====
"""
    (tmp_path / "Ed.tla").write_text(spec)
    (tmp_path / "Ed.cfg").write_text("INIT Init\nNEXT Next\n")
    r = _run(str(tmp_path / "Ed.tla"), "-edges", "on")
    assert r.returncode == 2, (r.stdout, r.stderr)
    assert "PROPERTY" in r.stderr
    # -edges off is inert without temporal properties — parses fine,
    # the run proceeds (and fails later only if the spec is bogus)
    r2 = _run(str(tmp_path / "Ed.tla"), "-edges", "off",
              "-engine", "interp")
    assert r2.returncode != 2, (r2.stdout, r2.stderr)


def test_cli_symmetry_on_with_liveness_spec_exit_2(tmp_path):
    """-symmetry on with a PROPERTY cfg is the liveness conflict the
    reference cfg comments insist on — checked right after the cfg
    loads, still exit 2 (no engine is ever built)."""
    spec = """---- MODULE Sy ----
EXTENDS Naturals
VARIABLES x
Init == x = 0
Incr == x' = (x + 1) % 3
Next == Incr
vars == <<x>>
AtZero == x = 0
Prop == []<>AtZero
Spec == Init /\\ [][Next]_vars
====
"""
    (tmp_path / "Sy.tla").write_text(spec)
    (tmp_path / "Sy.cfg").write_text(
        "SPECIFICATION Spec\nPROPERTY Prop\n")
    r = _run(str(tmp_path / "Sy.tla"), "-symmetry", "on")
    assert r.returncode == 2, (r.stdout, r.stderr)
    assert "temporal" in r.stderr
    # and -symmetry on against a cfg with no SYMMETRY at all
    (tmp_path / "Sy.cfg").write_text("INIT Init\nNEXT Next\n")
    r2 = _run(str(tmp_path / "Sy.tla"), "-symmetry", "on")
    assert r2.returncode == 2, (r2.stdout, r2.stderr)
    assert "SYMMETRY" in r2.stderr


@pytest.mark.parametrize("good", [
    ["-supervise", "-engine", "sharded"],
    ["-engine", "sharded", "-supervise", "-inject", "oom@shard=0"],
    ["-engine", "sharded", "-inject", "exchange-drop:3@shard=0"],
    ["-engine", "sharded", "-recover", "/nonexistent-ckpt"],
    ["-pack", "on", "-engine", "sharded"],
    ["-pack", "off", "-engine", "interp"],
    ["-pack", "off", "-fpset", "host"],
    ["-symmetry", "off", "-engine", "sharded"],
    ["-spill", "/tmp/sp", "-fpset", "paged"],
    ["-spill", "/tmp/sp"],
], ids=["supervise", "supervise-oom-shard", "drop-count", "recover",
        "pack-sharded", "pack-off-interp", "pack-off-fpset-host",
        "symmetry-off-sharded", "spill-paged", "spill-auto"])
def test_cli_sharded_valid_combos_pass_parsing(good):
    """Valid sharded combinations get past flag validation: the run
    fails on the nonexistent spec path (not exit 2)."""
    r = _run("/nonexistent-spec-dir/X.tla", *good)
    assert r.returncode != 2, (r.stdout, r.stderr)
