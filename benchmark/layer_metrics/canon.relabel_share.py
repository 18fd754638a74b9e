"""Share of the canonicalized lanes whose least image was not the
identity's: counters `canon_relabelled` / `canon_lanes`.  Near 0 the
cell's states are mostly value-free and canon idles."""


def read(obs, trace, cell):
    doc = obs.get("metrics_doc")
    counters = doc["counters"] if doc else {}
    if not counters.get("canon_lanes"):
        return None
    return (100.0 * counters.get("canon_relabelled", 0)
            / counters["canon_lanes"])
