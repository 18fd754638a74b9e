"""Share of a run's expansions that the four recovery actions made
(RestartEmpty, ReceivesRecoveryMsg, ReceivesRecoveryResponseMsg,
CompleteRecovery): the `action_expansions` gauge, counted on the device
action by action.  0 in every cell whose cfg binds RestartEmptyLimit =
0; a lane pruning that is right there and wrong at 1 moves it."""

RECOVERY = ("RestartEmpty", "ReceivesRecoveryMsg",
            "ReceivesRecoveryResponseMsg", "CompleteRecovery")


def read(obs, trace, cell):
    doc = obs.get("metrics_doc") or {}
    fired = doc.get("gauges", {}).get("action_expansions")
    if not fired or not sum(fired.values()):
        return None
    return (100.0 * sum(fired.get(a, 0) for a in RECOVERY)
            / sum(fired.values()))
