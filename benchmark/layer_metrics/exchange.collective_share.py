"""Share of the traced slice's device-busy seconds spent in the
collectives of the sharded step: the all_to_all of the exchange and
the all-reduces of its votes (`device_opcodes` of the reduced trace:
self seconds by opcode, a mean over the chips).  The reducer keeps the
ten opcodes with most seconds: where no collective is among them the
metric is left out."""

COLLECTIVES = ("all-to-all", "all-reduce", "all-gather")


def collective_seconds(trace):
    """Self seconds of the collective opcodes, a mean over the chips
    (an async pair `all-reduce-start` / `-done` counts both halves);
    None where the reduced trace names none."""
    if not trace or not trace.get("device_opcodes"):
        return None
    hits = [secs for name, secs in trace["device_opcodes"]
            if name.startswith(COLLECTIVES)]
    return sum(hits) if hits else None


def read(obs, trace, cell):
    secs = collective_seconds(trace)
    if secs is None or not trace.get("busy_s"):
        return None
    return 100.0 * secs / trace["busy_s"]
