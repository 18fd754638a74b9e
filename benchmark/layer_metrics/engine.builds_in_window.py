"""Programs built after the window opened: JAX's backend-compile events
of a second or more (a compile, or a load from the persistent cache, of
a program of the level loop) plus the engine's growth events, each of
which re-jits the level program.  A defect window must read 0.  The
millisecond jits of a new slice shape, which the host loop makes at
every level, are not builds; the run's record counts them."""


def read(obs, trace, cell):
    doc = obs.get("metrics_doc")
    if not doc:
        return None
    return obs["window_builds"]["slow_builds"] + doc["counters"].get("grows", 0)
