"""Share of the states a run committed in which some replica waits on
a partly filled StartViewChange or DoViewChange quorum (in ViewChange,
the quorum's send still to make, at least one record counted toward it
and fewer than it needs): counter `quorum_waiting_states` (counted on
the device over the committed states, `ST03Kernel.commit_stats`, from
what the guards of SendDVC and SendSV count) / states committed.  None
on a program without the counter: the parent's, and every `VSR`
cell."""


def read(obs, trace, cell):
    doc = obs.get("metrics_doc") or {}
    waiting = doc.get("counters", {}).get("quorum_waiting_states")
    if waiting is None or not obs.get("distinct"):
        return None
    return 100.0 * waiting / obs["distinct"]
