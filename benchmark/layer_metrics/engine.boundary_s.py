"""Seconds the engine's run spent at level boundaries: host code between
the last collect of one unit of device work (a level; a chunk of the
paged engine) and the first launch of the next (the exclusive
`boundary` phase, span tpuvsr.engine.boundary; snapshots, page moves
and scalar pulls inside it are their own phases).  A program without
the span reads nothing."""


def read(obs, trace, cell):
    doc = obs.get("metrics_doc")
    return doc["phases"].get("boundary") if doc else None
