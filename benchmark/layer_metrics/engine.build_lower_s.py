"""Seconds the job's engine run spent lowering traced programs to MLIR
in Python (`build_lower_s` gauge: JAX's jaxpr_to_mlir_module_duration
events, counted inside the program by tpuvsr/obs/builds)."""


def read(obs, trace, cell):
    doc = obs.get("metrics_doc")
    return doc["gauges"].get("build_lower_s") if doc else None
