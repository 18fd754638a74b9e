#!/usr/bin/env python3
"""What the quorum counters of `ST03Kernel.commit_stats` count, on the
plain reference's own states (`state_transfer_reference.py`, imported
and not copied: `State` tuples, `Msg` records, frozensets; nothing of
`tpuvsr`, `jax` or `numpy` here either).

    python3 benchmark/tools/quorum_counts.py CFG --depth N

prints one JSON object: the reference's level sizes, its sixteen
per-action expansion counts, its bag peak, and `committed`, the
counters summed over every state but Init, which is what the device
counts (`benchmark/oracles/state_transfer_r5_levels.json` is written
from it by `scripts/st03_r5_oracle.py`).

A replica WAITS on a quorum when it is in ViewChange with the quorum's
send still to make, has processed at least one record that counts
toward it (a delivered record of its own view addressed to it, at
count 0 in the bag: ST03:595-600, 669-674) and fewer than the quorum
needs: f = ReplicaCount \\div 2 StartViewChanges before SendDVC (the
sender is implicit), f + 1 DoViewChanges before SendSV (the new
primary's own, born delivered, among them).

* `quorum_waiting_states`: states in which some replica waits on
  either quorum;
* `svc_quorum_waiting_states`: on a StartViewChange quorum.  At
  ReplicaCount = 3 f is 1, one record IS the quorum, and this is 0 by
  construction: the test that the counter means what it says.

**What holds the reference at ReplicaCount = 5.**  At R = 3 it
reproduces the two records this repository has of the real
`VR_STATE_TRANSFER.tla` (42,753 distinct / 106,794 generated / 24
levels at |Values| = 1, timer 1: `tests/test_native_st03.py`), and
every quorum in it is written in `replicas // 2` (`f` in `successors`:
SendDVC, SendSV, ExecuteOp, NoProgressChange), every broadcast over
`range(1, R + 1)`; nothing in it names 3.  **What does not**: the
`.tla` is not in this repository, so at R = 5 the kernel and the
reference are two readings of the same cited lines (ST03:595-600, 703;
SURVEY 2.2-2.3), independent in layout and code, not in source
(PERF.md 7, the ST03 cell (a)).
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import state_transfer_reference as reference  # noqa: E402

COUNTERS = ("quorum_waiting_states", "svc_quorum_waiting_states",
            "state_transfer_states", "bag_slots", "bag_tombstones")


def counted(state, replica, mtype):
    """The records of `mtype` that `replica` has processed in its own
    view (delivered, at count 0 in the bag, addressed to it): what its
    quorum of that type counts."""
    view = state.rep_view_number[replica - 1]
    return sum(count == 0 and m.type == mtype and m.dest == replica
               and m.view_number == view for m, count in state.messages)


def waiting(state, c):
    """(on a StartViewChange quorum, on a DoViewChange quorum): whether
    some replica of `state` waits on each."""
    f = c.replicas // 2
    svc_waits = dvc_waits = False
    for r in range(1, c.replicas + 1):
        if state.rep_status[r - 1] != reference.VIEW_CHANGE:
            continue
        svc_waits |= not state.rep_sent_dvc[r - 1] \
            and 0 < counted(state, r, "StartViewChangeMsg") < f
        dvc_waits |= not state.rep_sent_sv[r - 1] \
            and 0 < counted(state, r, "DoViewChangeMsg") < f + 1
    return svc_waits, dvc_waits


def commit_stats(state, c):
    """One state's entry of every counter in `COUNTERS`."""
    svc_waits, dvc_waits = waiting(state, c)
    return {
        "quorum_waiting_states": svc_waits or dvc_waits,
        "svc_quorum_waiting_states": svc_waits,
        "state_transfer_states":
            reference.STATE_TRANSFER in state.rep_status,
        "bag_slots": len(state.messages),
        "bag_tombstones": sum(n == 0 for _m, n in state.messages)}


def committed(levels, c):
    """`COUNTERS` summed over the states of `levels[1:]`: every state
    a run commits (Init is given, not committed)."""
    total = dict.fromkeys(COUNTERS, 0)
    for level in levels[1:]:
        for state in level:
            for name, value in commit_stats(state, c).items():
                total[name] += int(value)
    return total


def run(c, invariants=(), max_depth=None, log=None):
    """The reference's own breadth-first run with its levels kept, and
    the counters over them; the levels themselves are dropped."""
    res = reference.bfs(c, invariants, max_depth=max_depth,
                        keep_levels=True, log=log)
    res["committed"] = committed(res.pop("levels"), c)
    return res


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cfg")
    ap.add_argument("--depth", type=int, default=None)
    args = ap.parse_args(argv)
    c, invariants = reference.read_cfg(args.cfg)
    res = run(c, invariants, max_depth=args.depth,
              log=lambda s: print(s, file=sys.stderr, flush=True))
    if res["violation"]:
        res["violation"] = [res["violation"][0], res["violation"][2]]
    print(json.dumps(dict(res, constants=c._asdict())))
    return 0


if __name__ == "__main__":
    sys.exit(main())
