"""Seconds of the interpreter's share of the `init` phase:
`init_states()`, the encoding, the host dedup and the invariants on
Init (part `tpuvsr.engine.init.states`).
Inside the exclusive `init` phase, which is timed as without
it: `phase_parts.init.states` of the metrics document.  `None` on a
document without the section (the parent's)."""


def read(obs, trace, cell):
    doc = obs.get("metrics_doc")
    if not doc:
        return None
    return doc.get("phase_parts", {}).get("init", {}).get("states")
