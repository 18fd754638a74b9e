"""Share of the states a run committed in which some replica waits on
a partly filled StartViewChange quorum (it has processed at least one
StartViewChange of its view and fewer than the f = ReplicaCount \\div 2
that SendDVC needs): counter `svc_quorum_waiting_states`
(`ST03Kernel.commit_stats`) / states committed.  0 at three replicas by
construction, where one record is the quorum: what `st03-bfs-timed`
reads.  None on a program without the counter: the parent's, and every
`VSR` cell."""


def read(obs, trace, cell):
    doc = obs.get("metrics_doc") or {}
    waiting = doc.get("counters", {}).get("svc_quorum_waiting_states")
    if waiting is None or not obs.get("distinct"):
        return None
    return 100.0 * waiting / obs["distinct"]
