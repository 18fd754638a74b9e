"""Device-busy microseconds of the traced slice per distinct state it
committed."""


def read(obs, trace, cell):
    if not trace or not obs.get("distinct"):
        return None
    return trace["busy_s"] * 1e6 / obs["distinct"]
