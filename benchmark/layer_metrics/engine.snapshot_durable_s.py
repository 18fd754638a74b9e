"""Seconds the job's snapshots spent becoming durable: CRCs, the
manifest, fsyncs and renames (part
`tpuvsr.engine.checkpoint.durable`).
Inside the exclusive `checkpoint` phase, which is timed as without
it: `phase_parts.checkpoint.durable` of the metrics document.  `None` on a
document without the section (the parent's)."""


def read(obs, trace, cell):
    doc = obs.get("metrics_doc")
    if not doc:
        return None
    return doc.get("phase_parts", {}).get("checkpoint", {}).get("durable")
