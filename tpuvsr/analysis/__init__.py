"""speclint — static analysis over the frontend AST, the guarded-
command IR, and the dense kernel layouts, gating every checking run.

The reference corpus's only "type system" is TLC failing hours into a
run; the TPU port adds a second hazard the reference never had: packed
narrow-dtype layouts and hand-written kernels that can silently drift
from the lowered spec semantics.  This package proves the structural
properties that are provable BEFORE dispatch:

  frames    every state variable framed in every action (pass 1)
  widths    cfg-derived value ranges fit the packed bit-widths (pass 2)
  vacuity   dead actions / vacuous invariants under the cfg (pass 3)
  symmetry  SYMMETRY perms are structural automorphisms (pass 4)
  drift     hand kernel vs lowerer-derived ActionIR divergence (pass 5)
  bounds    symbolic interval pre-pass (pass 6, ISSUE 13): reachable
            per-variable intervals, statically dead actions, fanout
            and state-space upper bounds — FACTS the engines consume
            (tightened packing, pruned lane tables, exact expansion
            caps, service admission), not just properties they check
  independence  static action-independence relation (pass 7, ISSUE
            16): column-refined read/write access sets, the n x n
            independence matrix, invariant visibility and monotone
            progress witnesses — the facts behind the engines'
            ample-set partial-order reduction (``-por``,
            engine/por.py); unattributable actions poison to
            dependent-with-all, mirroring bounds' refusal discipline

Entry points:

* ``run_lint(spec)`` — full report (CLI ``-lint``,
  scripts/lint_corpus.py);
* ``preflight(spec)`` — the engine gate: all seven passes (the drift
  kernel cross-check became cheap once the key tables moved to class
  attributes; the bounds fixpoint and independence matrix are
  pure-AST and cached), raises ``LintError`` on error-severity
  findings, caches per spec object, honors ``TPUVSR_LINT=off`` (the
  CLI's ``-lint=off``); a kernel-native spec (no AST) logs one line
  and passes through.
"""

from __future__ import annotations

import os

from .passes import PASS_ORDER, PASSES, PREFLIGHT_PASSES
from .report import (Finding, LintError, LintReport, SEV_ERROR, SEV_INFO,
                     SEV_WARN)

__all__ = ["run_lint", "preflight", "lint_enabled", "Finding",
           "LintError", "LintReport", "SEV_ERROR", "SEV_WARN",
           "SEV_INFO", "PASS_ORDER", "PREFLIGHT_PASSES"]


def run_lint(spec, passes=None) -> LintReport:
    """Run the requested passes (default: all seven, in canonical
    order) over a bound spec and return the report."""
    report = LintReport(module=spec.module.name)
    for name in (passes if passes is not None else PASS_ORDER):
        PASSES[name](spec, report)
        report.passes_run.append(name)
    return report


def lint_enabled(spec=None) -> bool:
    """The speclint gate: off under ``TPUVSR_LINT=off``, and for a
    kernel-native spec (models/native.py), which has no AST for the
    passes to read."""
    if getattr(spec, "native", False):
        return False
    return os.environ.get("TPUVSR_LINT", "").lower() not in (
        "off", "0", "false", "no")


def preflight(spec, log=None):
    """Fail-fast gate the engines call before dispatch.

    Runs all seven passes (including the kernel drift cross-check) once
    per spec object; raises ``LintError`` if any error-severity finding
    survives.  Returns the report (or None when disabled via
    TPUVSR_LINT=off)."""
    if not lint_enabled(spec):
        if getattr(spec, "native", False) and log is not None:
            log(f"speclint: skipped — native spec {spec.module.name} "
                f"has no AST (bounds/POR facts not consumed)")
        return None
    cached = getattr(spec, "_speclint_report", None)
    if cached is not None:
        if not cached.ok:
            raise LintError(cached)
        return cached
    report = run_lint(spec, passes=PREFLIGHT_PASSES)
    spec._speclint_report = report
    if log is not None:
        for f in report.warnings:
            log(f"speclint: {f}")
    if not report.ok:
        raise LintError(report)
    return report
