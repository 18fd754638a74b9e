"""Seconds the job's engine run spent writing level-boundary snapshots
(the exclusive `checkpoint` phase, span tpuvsr.engine.checkpoint)."""


def read(obs, trace, cell):
    doc = obs.get("metrics_doc")
    return doc["phases"].get("checkpoint") if doc else None
