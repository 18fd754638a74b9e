"""Share of the states a run committed in which some replica is
Recovering with an op number above 0, so that it came back from its
crash holding a non-empty PREFIX of its log: counter
`prefix_survivor_states` (counted on the device over the committed
states, `AL05Kernel.commit_stats`) / states committed.  Those are the
states only VR_REPLICA_RECOVERY_ASYNC_LOG has: a recovering replica of
VR_REPLICA_RECOVERY holds nothing, one of VR_REPLICA_RECOVERY_CP a
checkpoint.  None on a program without the counter: the parent's, and
every cell of another module."""


def read(obs, trace, cell):
    doc = obs.get("metrics_doc") or {}
    survivors = doc.get("counters", {}).get("prefix_survivor_states")
    if survivors is None or not obs.get("distinct"):
        return None
    return 100.0 * survivors / obs["distinct"]
