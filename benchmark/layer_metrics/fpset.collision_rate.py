"""Share of FPSet entries not in their home slot (`fpset_collision_rate`
gauge, engine/fpset.table_stats)."""


def read(obs, trace, cell):
    doc = obs.get("metrics_doc")
    return doc["gauges"].get("fpset_collision_rate") if doc else None
