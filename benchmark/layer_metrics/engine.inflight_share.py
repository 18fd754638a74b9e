"""Share of the engine's run in which the host was blocked on the
oldest dispatch in flight (`inflight` phase timer / elapsed)."""


def read(obs, trace, cell):
    doc = obs.get("metrics_doc")
    if not doc or not doc.get("elapsed_s"):
        return None
    return 100.0 * doc["phases"].get("inflight", 0.0) / doc["elapsed_s"]
