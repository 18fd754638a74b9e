"""Images the canon stage built per distinct state of the run: counter
`canon_lanes` (real lanes canonicalized) x gauge `symmetry_perms` (the
order of the symmetry group) / states committed.  Dedup before the
hash, or a skip of value-free states, would lower it."""


def read(obs, trace, cell):
    doc = obs.get("metrics_doc") or {}
    lanes = doc.get("counters", {}).get("canon_lanes")
    perms = doc.get("gauges", {}).get("symmetry_perms")
    if lanes is None or perms is None or not obs.get("distinct"):
        return None
    return lanes * perms / obs["distinct"]
