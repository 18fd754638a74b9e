"""Share of the engine's run under no named phase: the exclusive
seconds of the root frame (`check`, span tpuvsr.engine.check) /
elapsed.  What it holds is time the program cannot put a name to."""


def read(obs, trace, cell):
    doc = obs.get("metrics_doc")
    if not doc or not doc.get("elapsed_s"):
        return None
    return 100.0 * doc["phases"].get("check", 0.0) / doc["elapsed_s"]
