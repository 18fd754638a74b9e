"""Seconds of the host's share of `init`'s device work: the empty
table, the insert, the buffers and the first frontier enqueued, with
whatever pull that code already makes (part
`tpuvsr.engine.init.device`; no synchronisation is added, so these are
not the device's seconds).
Inside the exclusive `init` phase, which is timed as without
it: `phase_parts.init.device` of the metrics document.  `None` on a
document without the section (the parent's)."""


def read(obs, trace, cell):
    doc = obs.get("metrics_doc")
    if not doc:
        return None
    return doc.get("phase_parts", {}).get("init", {}).get("device")
