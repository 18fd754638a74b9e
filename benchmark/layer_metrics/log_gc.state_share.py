"""Share of the states a run committed in which some replica's log has
a garbage-collected prefix (a NoOp entry, HighestGCedOp > 0): counter
`gc_states` (counted on the device over the committed states,
`CP06Kernel.commit_stats`) / states committed.  Those are the states in
which the invariants read the application state through OpOf and a
reply may have to carry a checkpoint.  None on a program without the
counter: the parent's, and every cell of another module."""


def read(obs, trace, cell):
    doc = obs.get("metrics_doc") or {}
    collected = doc.get("counters", {}).get("gc_states")
    if collected is None or not obs.get("distinct"):
        return None
    return 100.0 * collected / obs["distinct"]
