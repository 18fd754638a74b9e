"""Share of a run's expansions that the six crash / checkpoint /
recovery actions of VR_REPLICA_RECOVERY_CP made (Crash,
ReceiveGetCheckpointMsg, ReceiveNewCheckpointMsg, ReceiveRecoveryMsg,
ReceiveRecoveryResponseMsg, CompleteRecovery): the `action_expansions`
gauge, counted on the device action by action.  A third of a window of
`cp06-bfs-timed`, and the lanes with the extra `last_cp` dimension
among them: a lane pruning or a guard table that is right for the
sixteen inherited actions and wrong for these moves it.  None on a
program without the gauge; 0 on one that has none of the six."""

CHAIN = ("Crash", "ReceiveGetCheckpointMsg", "ReceiveNewCheckpointMsg",
         "ReceiveRecoveryMsg", "ReceiveRecoveryResponseMsg",
         "CompleteRecovery")


def read(obs, trace, cell):
    doc = obs.get("metrics_doc") or {}
    fired = doc.get("gauges", {}).get("action_expansions")
    if not fired or not sum(fired.values()):
        return None
    return 100.0 * sum(fired.get(a, 0) for a in CHAIN) / sum(fired.values())
