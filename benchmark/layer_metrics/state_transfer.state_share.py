"""Share of the states a run committed in which some replica is in
status StateTransfer: counter `state_transfer_states` (counted on the
device over the committed states, `ST03Kernel.commit_stats`) / states
committed.  None on a program without the counter: the parent's, and
every `VSR` cell."""


def read(obs, trace, cell):
    doc = obs.get("metrics_doc") or {}
    waiting = doc.get("counters", {}).get("state_transfer_states")
    if waiting is None or not obs.get("distinct"):
        return None
    return 100.0 * waiting / obs["distinct"]
