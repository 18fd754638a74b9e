"""Seconds the job's snapshots spent writing their `.npz` payloads
into the staging directory (part `tpuvsr.engine.checkpoint.write`).
Inside the exclusive `checkpoint` phase, which is timed as without
it: `phase_parts.checkpoint.write` of the metrics document.  `None` on a
document without the section (the parent's)."""


def read(obs, trace, cell):
    doc = obs.get("metrics_doc")
    if not doc:
        return None
    return doc.get("phase_parts", {}).get("checkpoint", {}).get("write")
