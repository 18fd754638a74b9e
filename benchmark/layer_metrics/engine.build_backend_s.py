"""Seconds the job's engine run spent in the backend stage of its
builds: XLA compiling a program, or reading it back from the persistent
cache (`build_backend_s` gauge: JAX's backend_compile_duration events,
counted inside the program by tpuvsr/obs/builds)."""


def read(obs, trace, cell):
    doc = obs.get("metrics_doc")
    return doc["gauges"].get("build_backend_s") if doc else None
