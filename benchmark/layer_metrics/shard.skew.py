"""The fullest shard's distinct states over the mean (`shard_skew`
gauge): every shard runs the fullest shard's number of tiles, so the
others idle by this factor."""


def read(obs, trace, cell):
    doc = obs.get("metrics_doc")
    return doc["gauges"].get("shard_skew") if doc else None
