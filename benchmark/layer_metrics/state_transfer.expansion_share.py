"""Share of a run's expansions that the three state-transfer actions
made (SendGetState, ReceiveGetState, ReceiveNewState): the
`action_expansions` gauge, counted on the device action by action.
Near 0.005 % over a window of `st03-bfs-timed` (breadth-first order
reaches the first SendGetState in level 10) and 0 on a slice that ends
before it: it says whether a window reaches the trio at all, and a lane
pruning or a guard table that is right for the thirteen common actions
and wrong for these three moves it."""

TRIO = ("SendGetState", "ReceiveGetState", "ReceiveNewState")


def read(obs, trace, cell):
    doc = obs.get("metrics_doc") or {}
    fired = doc.get("gauges", {}).get("action_expansions")
    if not fired or not sum(fired.values()):
        return None
    return 100.0 * sum(fired.get(a, 0) for a in TRIO) / sum(fired.values())
