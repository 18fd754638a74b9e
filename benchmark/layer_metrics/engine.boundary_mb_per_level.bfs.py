"""Megabytes the host moves at a level boundary, a level: counters
`boundary_put_bytes` (host -> device: the zero buffers and control
arrays a sharded level starts with) + `boundary_pull_bytes` (device ->
host: whole pointer planes) / 1e6 / level rows.  Pages are not in it
(`paging.*`).  A program without the counters reads nothing."""


def read(obs, trace, cell):
    doc = obs.get("metrics_doc")
    if not doc or not doc.get("levels"):
        return None
    counters = doc["counters"]
    if "boundary_put_bytes" not in counters \
            and "boundary_pull_bytes" not in counters:
        return None
    moved = (counters.get("boundary_put_bytes", 0)
             + counters.get("boundary_pull_bytes", 0))
    return moved / 1e6 / len(doc["levels"])
