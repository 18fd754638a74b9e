"""Share of the engine's run spent moving pages: the exclusive
`page_in` and `page_out` phase timers (spans tpuvsr.engine.page_in /
.page_out; the device waits through both) / elapsed."""


def read(obs, trace, cell):
    doc = obs.get("metrics_doc")
    if not doc or not doc.get("elapsed_s"):
        return None
    phases = doc["phases"]
    if "page_in" not in phases and "page_out" not in phases:
        return None
    return 100.0 * (phases.get("page_in", 0.0)
                    + phases.get("page_out", 0.0)) / doc["elapsed_s"]
