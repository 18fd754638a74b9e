"""BASELINE configs[2] scale simulation: 1e6 random walks x depth 100
(TLC-uniform successor sampling, invariants checked every step) — on
the SHARDED WALKER FLEET (tpuvsr/sim, ISSUE 7; previously the
single-device DeviceSimulator scan loop, BENCH_r03: 17.7 walks/s).

Runs as many walks of the target shape as the time budget allows and
records measured walks/s + the projected wall clock for the full 1e6
— honest about backend and completion.  The fleet's per-(seed,
walk-id) determinism means the walk population is identical at any
walker count, so rounds at 131072 walkers measure the same workload
BENCH_r03 measured at 4096.  Writes scripts/<out> (arg 4, default
sim_scale.json).

Usage: python scripts/sim_scale.py [walkers] [max_seconds] [num_walks] [out.json]
"""

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

walkers = int(sys.argv[1]) if len(sys.argv) > 1 else 1 << 17
max_seconds = float(sys.argv[2]) if len(sys.argv) > 2 else 900
num = int(sys.argv[3]) if len(sys.argv) > 3 else 10**6
out_name = sys.argv[4] if len(sys.argv) > 4 else "sim_scale.json"

from tpuvsr.engine.spec import SpecModel
from tpuvsr.frontend.cfg import parse_cfg_file
from tpuvsr.frontend.parser import parse_module_file
from tpuvsr.sim.fleet import FleetSimulator

REFERENCE = os.environ.get(
    "TPUVSR_REFERENCE", "/root/reference/vsr-revisited/paper")

mod = parse_module_file(f"{REFERENCE}/VSR.tla")
cfg = parse_cfg_file(f"{REPO}/examples/VSR_defect.cfg")
spec = SpecModel(mod, cfg)

import jax
backend = jax.default_backend()
print(f"backend: {backend} ({len(jax.devices())} device(s))",
      file=sys.stderr, flush=True)

# reuse the previous run's calibrated dispatch-group caps (same walker
# count) so the measurement starts at steady state instead of paying
# the cap-growth recompiles inside the budget
prev_caps = None
prev_path = os.path.join(REPO, "scripts", "sim_scale.json")
if os.path.exists(prev_path):
    try:
        with open(prev_path) as f:
            prev = json.load(f)
        if prev.get("walkers") == walkers and prev.get("group_caps"):
            prev_caps = list(prev["group_caps"])
    except ValueError:
        pass

sim = FleetSimulator(spec, walkers=walkers, chunk_steps=25,
                     max_msgs=64, group_caps=prev_caps)
t0 = time.time()
res = sim.run(num=num, depth=100, seed=0, max_seconds=max_seconds,
              log=lambda m: print(f"sim: {m} ({time.time()-t0:.0f}s)",
                                  file=sys.stderr, flush=True))
el = res.elapsed
walks_per_s = res.walks / el if el > 0 else 0.0
out = {
    "target": {"num_walks": num, "depth": 100,
               "config": "VSR defect fixture (R=3, |Values|=3, timer=3)"},
    "engine": "fleet-sim",
    "walkers": walkers,
    "mesh_devices": sim.D,
    "split_enabled": False,
    "walks_completed": res.walks,
    "steps": res.steps,
    "elapsed_s": round(el, 1),
    "walks_per_s": round(walks_per_s, 2),
    "steps_per_s": round(res.steps / el, 1) if el > 0 else 0.0,
    "projected_s_for_1e6_walks": round(10**6 / walks_per_s, 1)
    if walks_per_s else None,
    "completed_target": res.walks >= num,
    "vs_bench_r03_17_7": round(walks_per_s / 17.7, 2)
    if walks_per_s else None,
    "ok": res.ok,
    "violated": res.violated_invariant,
    "backend": backend,
    "dispatch": sim.dispatch,
    "group_caps": list(sim.group_caps),
}
print(json.dumps(out))
with open(os.path.join(REPO, "scripts", out_name), "w") as f:
    json.dump(out, f, indent=1)
