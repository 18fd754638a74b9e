"""Seconds the job's engine run spent tracing its programs in Python
(`build_trace_s` gauge: JAX's jaxpr_trace_duration events, nested
traces counted once, counted inside the program by tpuvsr/obs/builds)."""


def read(obs, trace, cell):
    doc = obs.get("metrics_doc")
    return doc["gauges"].get("build_trace_s") if doc else None
