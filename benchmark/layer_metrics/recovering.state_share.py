"""Share of the states a run committed in which some replica is
Recovering: counter `recovering_states` (counted on the device over
the committed states, only by a program whose cfg allows a restart) /
states committed.  None on a program without the counter: the parent's,
and every cfg with RestartEmptyLimit = 0."""


def read(obs, trace, cell):
    doc = obs.get("metrics_doc") or {}
    recovering = doc.get("counters", {}).get("recovering_states")
    if recovering is None or not obs.get("distinct"):
        return None
    return 100.0 * recovering / obs["distinct"]
