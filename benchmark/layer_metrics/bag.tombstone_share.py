"""Share of the bag's slots, summed over the states a run committed,
that hold a delivered record (count 0): counters `bag_tombstones` /
`bag_slots` (`ST03Kernel.commit_stats`).  The tombstones are what the
quorum guards of SendDVC and SendSV scan and count (ST03:595-600, 703).
None on a program without the counters: the parent's, and every `VSR`
cell (its quorums read per-replica receive-sets)."""


def read(obs, trace, cell):
    counters = (obs.get("metrics_doc") or {}).get("counters", {})
    slots = counters.get("bag_slots")
    if not slots or "bag_tombstones" not in counters:
        return None
    return 100.0 * counters["bag_tombstones"] / slots
