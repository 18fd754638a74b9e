"""Liveness verdicts at (or toward) the SHIPPED analysis-cfg constants
(VERDICT r4 item 5).

The reference's shipped cfgs run ConvergenceToView /
OpEventuallyAllOrNothing at R=3, |Values|=2, StartViewOnTimerLimit=2
(analysis/01-view-changes/*.cfg).  The r5 size probe
(scripts/a01_shipped_probe.json) measured that space past 4.2M
distinct at depth 14 with the frontier still growing 1.9x/level —
projected well past 1e8 states, beyond a resident behavior graph on
this host.  So this script supports BOTH: the shipped cfg unchanged
(an honest bounded attempt, reported as such when the cap trips) and
intermediate constant ladders (|V|=2/timer=1, |V|=1/timer=2 — each
strictly larger than the r4 toy |V|=1/timer=1 verdicts) that complete
to real verdicts.  Pipeline: paged-BFS enumeration -> device-built
behavior graph (CSR edges, gid-valued FPSet) -> device-compiled
property leaves (lower/compile) -> host fair-SCC.

Writes/merges scripts/liveness_shipped.json.

Usage: [JAX_PLATFORMS=cpu] python scripts/liveness_shipped.py [a01|i01]
           [max_states] [tile] [chunk_tiles] [values] [timer]
(values/timer override the shipped constants when given)
"""

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402

backend = jax.default_backend()

from tpuvsr.engine.device_liveness import DeviceGraph   # noqa: E402
from tpuvsr.engine.liveness import liveness_check       # noqa: E402
from tpuvsr.engine.spec import load_spec                # noqa: E402

MODS = {
    "a01": "VR_ASSUME_NEWVIEWCHANGE",
    "i01": "VR_INC_RESEND",
}

which = sys.argv[1] if len(sys.argv) > 1 else "a01"
max_states = int(sys.argv[2]) if len(sys.argv) > 2 else 30_000_000
tile = int(sys.argv[3]) if len(sys.argv) > 3 else 512
chunk_tiles = int(sys.argv[4]) if len(sys.argv) > 4 else 16
values = int(sys.argv[5]) if len(sys.argv) > 5 else None
timer = int(sys.argv[6]) if len(sys.argv) > 6 else None

REF = os.environ.get(
    "TPUVSR_REFERENCE", "/root/reference/vsr-revisited/paper")
stem = f"{REF}/analysis/01-view-changes/{MODS[which]}"
spec = load_spec(f"{stem}.tla", f"{stem}.cfg")
key = which
desc = f"{MODS[which]}.cfg UNCHANGED (R=3, |Values|=2, timer=2)"
if values is not None or timer is not None:
    from tpuvsr.core.values import ModelValue
    from tpuvsr.engine.spec import SpecModel
    from tpuvsr.frontend.cfg import parse_cfg_file
    from tpuvsr.frontend.parser import parse_module_file
    mod = parse_module_file(f"{stem}.tla")
    cfg = parse_cfg_file(f"{stem}.cfg")
    if values is not None:
        cfg.constants["Values"] = frozenset(
            ModelValue(f"v{i + 1}") for i in range(values))
    if timer is not None:
        cfg.constants["StartViewOnTimerLimit"] = timer
    spec = SpecModel(mod, cfg)
    v = values if values is not None else 2
    t = timer if timer is not None else 2
    key = f"{which}-v{v}t{t}"
    desc = (f"{MODS[which]}.cfg with |Values|={v}, timer={t} "
            f"(intermediate ladder toward the shipped constants)")

OUT = os.path.join(REPO, "scripts", "liveness_shipped.json")
results = {}
if os.path.exists(OUT):
    with open(OUT) as f:
        results = json.load(f)

entry = {
    "module": MODS[which],
    "config": desc + " — SPECIFICATION LivenessSpec",
    "backend": backend,
    "tile": tile,
    "properties": list(spec.temporal_props),
}
t0 = time.time()
try:
    g = DeviceGraph(spec, tile_size=tile, chunk_tiles=chunk_tiles,
                    max_states=max_states,
                    fpset_capacity=1 << 24, next_capacity=1 << 17,
                    # pre-sized expansion caps: the timer=2 SVC storm
                    # overflows the default x2 caps and every growth
                    # is a multi-minute recompile
                    expand_mult=4,
                    log=lambda m: print(f"[liveness] {m}", flush=True))
    entry.update({
        "states": g.n,
        "edges": int(g.csr[1].shape[0]),
        "graph_build_s": round(g.build_elapsed, 1),
        "bfs_s": round(g.bfs_elapsed, 1),
    })
    res = liveness_check(spec, graph=g,
                         log=lambda m: print(f"[liveness] {m}",
                                             flush=True))
    entry.update({
        "ok": res.ok,
        "violated_property": res.property_name,
        "check_s": round(res.elapsed, 1),
        "error": res.error,
        "verdict": ("all temporal properties hold" if res.ok
                    else f"violated: {res.property_name}"),
    })
except Exception as e:  # noqa: BLE001
    entry["error"] = f"{type(e).__name__}: {e}"
entry["total_s"] = round(time.time() - t0, 1)
results[key] = entry
with open(OUT, "w") as f:
    json.dump(results, f, indent=1)
print(json.dumps(entry))
