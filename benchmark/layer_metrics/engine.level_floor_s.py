"""What a level costs before it holds a tile: the median `wall_s` of the
level rows whose frontier is at most 128 states (one tile at the
configurations' tile size), boundary and snapshot included.  Rows
without `wall_s` (a program before the per-level costs) and runs with
no such level read nothing."""

import statistics

SMALL = 128


def read(obs, trace, cell):
    doc = obs.get("metrics_doc")
    if not doc:
        return None
    walls = [row["wall_s"] for row in doc.get("levels", ())
             if "wall_s" in row and row["frontier"] <= SMALL]
    return statistics.median(walls) if walls else None
