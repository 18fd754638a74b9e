"""Useful rows / rows on the wire of the sharded step's all_to_all
(`exchange_useful_rows`, `exchange_wire_rows` gauges)."""


def read(obs, trace, cell):
    doc = obs.get("metrics_doc")
    gauges = doc["gauges"] if doc else {}
    wire = gauges.get("exchange_wire_rows")
    if not wire:
        return None
    return 100.0 * gauges.get("exchange_useful_rows", 0) / wire
