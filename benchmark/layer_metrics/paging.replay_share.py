"""Share of the window's dispatches that a drain dropped: counters
`pipeline_replays` / `dispatches`.  A dropped dispatch ran no tile
but did run the guard stage over a whole page, so on the paged engine
this is device time that commits nothing.  Both counters are older
than the metric: an engine that replays every page reads about 50.
Read only where the engine counts its page shapes."""


def read(obs, trace, cell):
    doc = obs.get("metrics_doc")
    if not doc or "page_shapes" not in doc["counters"]:
        return None
    counters = doc["counters"]
    if not counters.get("dispatches"):
        return None
    return 100.0 * counters.get("pipeline_replays", 0) \
        / counters["dispatches"]
