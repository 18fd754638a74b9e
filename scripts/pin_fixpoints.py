"""Measure exact interpreter-BFS fixpoints for all six corpus specs at
pinned small constants — the standing differential oracle (SURVEY.md
§4.7).  TLC is not available in this image, so the interpreter engine
(collision-free dedup on exact canonical view values) is the oracle;
the device engines are differentially held to these counts.

Writes scripts/fixpoints.json: stem -> {constants, distinct, generated,
diameter, elapsed_s}.

Usage: python scripts/pin_fixpoints.py [max_states] [only_stem_substr]
"""

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tpuvsr.engine.bfs import bfs_check
from tpuvsr.engine.spec import SpecModel
from tpuvsr.frontend.cfg import parse_cfg_file, parse_cfg_text
from tpuvsr.frontend.parser import parse_module_file

REFERENCE = os.environ.get(
    "TPUVSR_REFERENCE", "/root/reference/vsr-revisited/paper")
ANALYSIS = f"{REFERENCE}/analysis"
OUT = os.environ.get("TPUVSR_FIXPOINT_OUT",
                     os.path.join(REPO, "scripts", "fixpoints.json"))

max_states = int(sys.argv[1]) if len(sys.argv) > 1 else 3_000_000
only = sys.argv[2] if len(sys.argv) > 2 else ""

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

SMALL = {
    "ReplicaCount": "3",
    "Values": "{v1}",
    "StartViewOnTimerLimit": "1",
}


def load(stem, cfg_text=None, overrides=None):
    mod = parse_module_file(f"{ANALYSIS}/{stem}.tla"
                            if "/" in stem else f"{REFERENCE}/{stem}.tla")
    if cfg_text is None:
        cfg = parse_cfg_file(f"{ANALYSIS}/{stem}.cfg"
                             if "/" in stem else f"{REFERENCE}/{stem}.cfg")
    else:
        cfg = parse_cfg_text(cfg_text)
    from tpuvsr.frontend.cfg import _parse_value
    for k, v in {**SMALL, **(overrides or {})}.items():
        if k in cfg.constants:
            cfg.constants[k] = _parse_value(v)
    cfg.symmetry = None
    return SpecModel(mod, cfg)


RECOVERY_CFG = ("CONSTANTS\n    ReplicaCount = 3\n    Values = {v1}\n"
                "    StartViewOnTimerLimit = 1\n"
                "    NoProgressChangeLimit = 0\n    CrashLimit = 1\n"
                + _COMMON +
                "\nINIT Init\nNEXT Next\nVIEW view\nINVARIANT\n"
                "NoLogDivergence\nNoAppStateDivergence\n"
                "AcknowledgedWriteNotLost\n"
                "CommitNumberNeverHigherThanOpNumber\n")

CP_CFG = ("CONSTANTS\n    ReplicaCount = 3\n    Values = {v1}\n"
          "    StartViewOnTimerLimit = 1\n"
          "    NoProgressChangeLimit = 0\n    CrashLimit = 1\n"
          + _COMMON +
          "    GetCheckpointMsg = GetCheckpointMsg\n"
          "    NewCheckpointMsg = NewCheckpointMsg\n    NoOp = NoOp\n"
          "INIT Init\nNEXT Next\nVIEW view\nINVARIANT\n"
          "NoLogDivergence\nNoAppStateDivergence\n"
          "AcknowledgedWriteNotLost\n"
          "CommitNumberNeverHigherThanOpNumber\n"
          "CommitNumberMatchesAppState\n")

JOBS = [
    ("VSR", None, {"RestartEmptyLimit": "0"}),
    ("01-view-changes/VR_ASSUME_NEWVIEWCHANGE", None, None),
    ("01-view-changes/VR_INC_RESEND", None, None),
    ("03-state-transfer/VR_STATE_TRANSFER", None, None),
    ("04-application-state/VR_APP_STATE", None, None),
    ("05-replica-recovery/VR_REPLICA_RECOVERY", RECOVERY_CFG, None),
    ("05-replica-recovery/VR_REPLICA_RECOVERY_ASYNC_LOG", RECOVERY_CFG,
     None),
    ("06-replica-recovery-cp/VR_REPLICA_RECOVERY_CP", CP_CFG, None),
]

def main():
    results = {}
    if os.path.exists(OUT):
        with open(OUT) as f:
            results = json.load(f)

    for stem, cfg_text, overrides in JOBS:
        if only and only not in stem:
            continue
        print(f"=== {stem}", flush=True)
        spec = load(stem, cfg_text, overrides)
        t0 = time.time()
        res = bfs_check(spec, max_states=max_states,
                        log=lambda m: print(f"  {m}", flush=True))
        el = time.time() - t0
        entry = {
            "constants": {k: repr(v) for k, v in sorted(
                spec.ev.constants.items())
                if k in ("ReplicaCount", "Values",
                         "StartViewOnTimerLimit", "RestartEmptyLimit",
                         "CrashLimit", "NoProgressChangeLimit",
                         "ClientCount")},
            "ok": res.ok,
            "fixpoint": res.error is None,
            "distinct": res.distinct_states,
            "generated": res.states_generated,
            "diameter": res.diameter,
            "elapsed_s": round(el, 1),
            "violated": res.violated_invariant,
            "error": res.error,
        }
        results[stem] = entry
        print(f"  -> {entry}", flush=True)
        with open(OUT, "w") as f:
            json.dump(results, f, indent=1, sort_keys=True)
    print("done")


if __name__ == "__main__":
    main()
