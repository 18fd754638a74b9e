"""Run the speclint static analyzer (tpuvsr/analysis) over the FULL
reference corpus — all eight registered models under their shipped (or,
for the 05/06 recovery-era specs that ship without one, synthesized)
cfgs — and report per-model findings.

This is the tier-1 lint gate: fast, CPU-only, no jit dispatch (the
drift pass instantiates codecs/kernels but never compiles a level
kernel).  Exit code 0 when every model is clean of error-severity
findings, 1 otherwise, 3 when the reference corpus is not mounted.

Usage:
    python scripts/lint_corpus.py [--json] [--bounds] [--independence]
                                  [only_stem_substr]

--json emits one JSON object: {model: report_dict, ...} plus an "ok"
summary key, mirroring the CLI's `-lint -json` per-spec shape.
--bounds adds a per-model bounds-pass column (ISSUE 13): tightened?,
dead-action count and the static state bound — the facts the engines
consume, read straight off each report's extras["bounds"] section.
--independence adds the pass-7 column (ISSUE 16): independent-pair
count, poisoned/invisible action tallies and monotone-witness count —
how much ample-set reduction each corpus model statically admits.
"""

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tpuvsr.analysis import run_lint  # noqa: E402
from tpuvsr.engine.spec import SpecModel  # noqa: E402
from tpuvsr.frontend.cfg import parse_cfg_file, parse_cfg_text  # noqa: E402
from tpuvsr.frontend.parser import parse_module_file  # noqa: E402

REFERENCE = os.environ.get(
    "TPUVSR_REFERENCE", "/root/reference/vsr-revisited/paper")
ANALYSIS = f"{REFERENCE}/analysis"

# shipped-cfg models: stem -> (tla path, cfg path)
SHIPPED = {
    "vsr": (f"{REFERENCE}/VSR.tla", f"{REFERENCE}/VSR.cfg"),
    "a01": (f"{ANALYSIS}/01-view-changes/VR_ASSUME_NEWVIEWCHANGE.tla",
            f"{ANALYSIS}/01-view-changes/VR_ASSUME_NEWVIEWCHANGE.cfg"),
    "i01": (f"{ANALYSIS}/01-view-changes/VR_INC_RESEND.tla",
            f"{ANALYSIS}/01-view-changes/VR_INC_RESEND.cfg"),
    "st03": (f"{ANALYSIS}/03-state-transfer/VR_STATE_TRANSFER.tla",
             f"{ANALYSIS}/03-state-transfer/VR_STATE_TRANSFER.cfg"),
    "as04": (f"{ANALYSIS}/04-application-state/VR_APP_STATE.tla",
             f"{ANALYSIS}/04-application-state/VR_APP_STATE.cfg"),
}

# 05/06 ship without cfgs; synthesize minimal ones (same bindings as
# tests/test_corpus.py)
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
NoAppStateDivergence
AcknowledgedWriteNotLost
CommitNumberNeverHigherThanOpNumber
"""

CP_CFG = """CONSTANTS
    ReplicaCount = 3
    Values = {v1}
    StartViewOnTimerLimit = 1
    NoProgressChangeLimit = 0
    CrashLimit = 1
""" + _COMMON + """
    GetCheckpointMsg = GetCheckpointMsg
    NewCheckpointMsg = NewCheckpointMsg
    NoOp = NoOp
INIT Init
NEXT Next
VIEW view
INVARIANT
NoLogDivergence
NoAppStateDivergence
AcknowledgedWriteNotLost
CommitNumberNeverHigherThanOpNumber
CommitNumberMatchesAppState
"""

SYNTHESIZED = {
    "rr05": (f"{ANALYSIS}/05-replica-recovery/VR_REPLICA_RECOVERY.tla",
             RECOVERY_CFG),
    "al05": (f"{ANALYSIS}/05-replica-recovery/"
             f"VR_REPLICA_RECOVERY_ASYNC_LOG.tla", RECOVERY_CFG),
    "cp06": (f"{ANALYSIS}/06-replica-recovery-cp/"
             f"VR_REPLICA_RECOVERY_CP.tla", CP_CFG),
}


def load_all(only=""):
    specs = {}
    for stem, (tla, cfg) in SHIPPED.items():
        if only in stem:
            specs[stem] = SpecModel(parse_module_file(tla),
                                    parse_cfg_file(cfg))
    for stem, (tla, cfg_text) in SYNTHESIZED.items():
        if only in stem:
            specs[stem] = SpecModel(parse_module_file(tla),
                                    parse_cfg_text(cfg_text))
    return specs


def _bounds_col(report):
    """One-line bounds summary column from a report's extras."""
    b = report.extras.get("bounds") or {}
    if not b:
        return "bounds: (pass did not run)"
    sb = b.get("state_bound")
    return (f"bounds: tightened={b.get('tightened')} "
            f"dead={len(b.get('dead_actions') or [])} "
            f"state_bound={'unbounded' if sb is None else sb}")


def _indep_col(report):
    """One-line independence summary column (ISSUE 16): the pairs the
    ample-set filter could consume plus the refusal tallies (poisoned
    actions, invariant-visible actions, monotone witnesses — the
    sharded proviso's currency)."""
    d = report.extras.get("independence") or {}
    if not d:
        return "independence: (pass did not run)"
    vis = d.get("visible") or {}
    mono = d.get("monotone") or {}
    return (f"independence: pairs={d.get('independent_pairs')} "
            f"actions={len(d.get('actions') or [])} "
            f"poisoned={len(d.get('poisoned') or {})} "
            f"invisible={sum(1 for v in vis.values() if not v)} "
            f"witnesses={sum(1 for v in mono.values() if v)} "
            f"digest={d.get('digest')}")


def main(argv):
    as_json = "--json" in argv
    with_bounds = "--bounds" in argv
    with_indep = "--independence" in argv
    rest = [a for a in argv if not a.startswith("--")]
    only = rest[0] if rest else ""

    if not os.path.isdir(REFERENCE):
        print(f"reference corpus not mounted at {REFERENCE} "
              f"(set TPUVSR_REFERENCE)", file=sys.stderr)
        return 3

    t0 = time.time()
    reports = {}
    for stem, spec in sorted(load_all(only).items()):
        ts = time.time()
        reports[stem] = (run_lint(spec), time.time() - ts)

    ok = all(r.ok for r, _ in reports.values())
    if as_json:
        out = {stem: dict(r.to_dict(), elapsed_s=round(dt, 3))
               for stem, (r, dt) in reports.items()}
        out["ok"] = ok
        print(json.dumps(out))
    else:
        for stem, (r, dt) in reports.items():
            print(f"==== {stem} ({dt:.2f}s)")
            if with_bounds:
                print(_bounds_col(r))
            if with_indep:
                print(_indep_col(r))
            print(r.render())
        print(f"==== corpus {'CLEAN' if ok else 'HAS ERRORS'} "
              f"({time.time() - t0:.2f}s total)")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
