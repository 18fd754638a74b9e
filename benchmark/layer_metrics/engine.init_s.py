"""Seconds of the window's run before its first dispatch (the exclusive
`init` phase, span tpuvsr.engine.init): registering Init, building and
placing the first frontier.  It lies inside `CheckResult.elapsed`, so
inside `distinct_per_s`."""


def read(obs, trace, cell):
    doc = obs.get("metrics_doc")
    return doc["phases"].get("init") if doc else None
