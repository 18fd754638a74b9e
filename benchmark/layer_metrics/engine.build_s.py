"""The engine's exclusive `compile` phase timer of the job: tracing,
lowering, and loading the program from the cache or compiling it."""


def read(obs, trace, cell):
    doc = obs.get("metrics_doc")
    return doc["phases"].get("compile", 0.0) if doc else None
