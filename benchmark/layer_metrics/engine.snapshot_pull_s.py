"""Seconds the job's snapshots spent bringing their data to the host:
the pointer pages, the frontier's rows, the table whole and its
occupied slots found (part `tpuvsr.engine.checkpoint.pull`).
Inside the exclusive `checkpoint` phase, which is timed as without
it: `phase_parts.checkpoint.pull` of the metrics document.  `None` on a
document without the section (the parent's)."""


def read(obs, trace, cell):
    doc = obs.get("metrics_doc")
    if not doc:
        return None
    return doc.get("phase_parts", {}).get("checkpoint", {}).get("pull")
