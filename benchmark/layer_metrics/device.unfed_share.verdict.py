"""Share of the engine's run in which the device had nothing queued, on
the program's own clock: the sum of `phases_unfed` (the dispatch
window's unfed clock, charged to the phase the host was in) / elapsed.
No profiler: it reads in a timed run too, for the whole window, and
stands beside the trace's `device.idle_share.*`.  A program without the
clock reads nothing."""


def read(obs, trace, cell):
    doc = obs.get("metrics_doc")
    if not doc or not doc.get("elapsed_s") or "phases_unfed" not in doc:
        return None
    return 100.0 * sum(doc["phases_unfed"].values()) / doc["elapsed_s"]
