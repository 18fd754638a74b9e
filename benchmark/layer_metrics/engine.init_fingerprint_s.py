"""Seconds `init` spent fingerprinting the Init batch, up to the pull
of the fingerprints: `CheckedModel.fp_batch`, eager over the group
where symmetry is on (part `tpuvsr.engine.init.fingerprint`).
Inside the exclusive `init` phase, which is timed as without
it: `phase_parts.init.fingerprint` of the metrics document.  `None` on a
document without the section (the parent's)."""


def read(obs, trace, cell):
    doc = obs.get("metrics_doc")
    if not doc:
        return None
    return doc.get("phase_parts", {}).get("init", {}).get("fingerprint")
