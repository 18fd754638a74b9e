"""Bytes of frontier rows paged in and out (`page_in_bytes` +
`spill_bytes`: the rows a page holds, not its padding) per second of
the `page_in` + `page_out` phases."""


def read(obs, trace, cell):
    doc = obs.get("metrics_doc")
    if not doc:
        return None
    counters, phases = doc["counters"], doc["phases"]
    if "page_in_bytes" not in counters:
        return None
    secs = phases.get("page_in", 0.0) + phases.get("page_out", 0.0)
    if not secs:
        return None
    return (counters["page_in_bytes"]
            + counters.get("spill_bytes", 0)) / secs / 1e9
