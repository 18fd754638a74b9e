"""Share of the measured job's level programs that came out of the
store of traced programs (tpuvsr/engine/program_store.py) instead of
being traced and lowered again: counters `build_export_hits` /
(`build_export_hits` + `build_export_misses`), counted inside the
program by tpuvsr/obs/builds.  100 on a warm store, 0 in the job that
fills it; nothing to read in a program without the counters, or in a
job none of whose programs went to the store."""


def read(obs, trace, cell):
    doc = obs.get("metrics_doc")
    counters = doc["counters"] if doc else {}
    hits = counters.get("build_export_hits", 0)
    went = hits + counters.get("build_export_misses", 0)
    return 100.0 * hits / went if went else None
