"""Useful lanes / dispatched expand lanes (`occupancy` gauge)."""


def read(obs, trace, cell):
    doc = obs.get("metrics_doc")
    value = doc["gauges"].get("occupancy") if doc else None
    return None if value is None else 100.0 * value
