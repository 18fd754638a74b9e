"""Seconds the job's engine run spent in the runtime's deserialize +
load of the programs it read back: JAX's `cache_retrieval_time_sec`
less the read and the decompression (span
`tpuvsr.engine.build.executable_load`; gauge
`build_executable_load_s`).
The three read-back metrics sum to `build_cache_load_s`; timed inside
the program by tpuvsr/obs/builds.  `None` on a program without the
gauge (the parent's), and where the installed JAX gave no seam."""


def read(obs, trace, cell):
    doc = obs.get("metrics_doc")
    return doc["gauges"].get("build_executable_load_s") if doc else None
