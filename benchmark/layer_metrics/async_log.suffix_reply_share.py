"""Share of the states a run committed in which a RecoveryResponse with
`prefix_ceil` above 0 is pending in the bag or held in a receive-set:
counter `suffix_reply_states` (counted on the device over the committed
states, `AL05Kernel.commit_stats`) / states committed.  Those are the
states in which CompleteRecovery's splice has a prefix of the
replica's own to keep under the primary's suffix (AL05:947-977); the
crashed replica has to have committed an entry and kept it.  None on a
program without the counter: the parent's, and every cell of another
module."""


def read(obs, trace, cell):
    doc = obs.get("metrics_doc") or {}
    replies = doc.get("counters", {}).get("suffix_reply_states")
    if replies is None or not obs.get("distinct"):
        return None
    return 100.0 * replies / obs["distinct"]
