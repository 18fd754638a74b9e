"""Megabytes one level-boundary snapshot stages, payloads and manifest:
counters `checkpoint_bytes` / `checkpoints` / 1e6 of the measured job
(`RunObserver.checkpoint` adds what `save_checkpoint` staged).  Beside
`engine.snapshot_s` it says whether a snapshot costs what the run found
or what its table could hold.  Nothing to read in a program without the
counter, or in a job that wrote no snapshot."""


def read(obs, trace, cell):
    doc = obs.get("metrics_doc")
    counters = doc["counters"] if doc else {}
    written = counters.get("checkpoints", 0)
    if "checkpoint_bytes" not in counters or not written:
        return None
    return counters["checkpoint_bytes"] / written / 1e6
