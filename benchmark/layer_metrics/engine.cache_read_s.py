"""Seconds the job's engine run spent reading the persistent cache's
files: the cache's `get` for every program the run read back (span
`tpuvsr.engine.build.cache_read`; gauge `build_cache_read_s`).
The three read-back metrics sum to `build_cache_load_s`; timed inside
the program by tpuvsr/obs/builds.  `None` on a program without the
gauge (the parent's), and where the installed JAX gave no seam."""


def read(obs, trace, cell):
    doc = obs.get("metrics_doc")
    return doc["gauges"].get("build_cache_read_s") if doc else None
