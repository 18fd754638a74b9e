"""Every backend compile after the window opened, however short
(`window_builds.compiles`, JAX's own event): an engine that slices a
page by its length builds three to five programs for every page of a
new length.  Read only where the engine counts its page shapes."""


def read(obs, trace, cell):
    doc = obs.get("metrics_doc")
    if not doc or "page_shapes" not in doc["counters"]:
        return None
    builds = obs.get("window_builds")
    return None if builds is None else builds["compiles"]
