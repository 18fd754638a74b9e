"""Defect-reproduction hunt: find the state-transfer data-loss
violation (reference README:11-18, state_transfer_violation_trace.txt)
with the SHARDED WALKER FLEET (tpuvsr/sim, ISSUE 7) on the defect
fixture config.

Uses weighted two-stage action sampling + swarm scheduler noise
(uniform-over-successors walks are dominated by message-delivery lanes
and essentially never thread the SendGetState truncation window), and
— in guided mode — fingerprint-novelty importance splitting with the
VSR kernel's ``hunt_score`` blended in (``tpuvsr/sim/splitting.py``).

Usage: python scripts/defect_hunt.py [walkers] [depth] [max_seconds]
       [seed] [swarm_sigma] [mode]

Modes (the r4 ablation axis, VERDICT item 6):
  uniform  — TLC's uniform-over-successors draw (no action weighting)
  flat     — two-stage sampling, uniform over enabled ACTIONS
  weighted — two-stage sampling with real weights biased toward the
             defect path (SendGetState truncation + view changes)
  guided   — weighted + importance splitting (novelty + hunt_score
             kill/clone resampling)
"""

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

walkers = int(sys.argv[1]) if len(sys.argv) > 1 else 4096
depth = int(sys.argv[2]) if len(sys.argv) > 2 else 48
max_seconds = float(sys.argv[3]) if len(sys.argv) > 3 else 600
seed = int(sys.argv[4]) if len(sys.argv) > 4 else 0
sigma = float(sys.argv[5]) if len(sys.argv) > 5 else 1.0
mode = sys.argv[6] if len(sys.argv) > 6 else os.environ.get(
    "TPUVSR_HUNT_MODE",
    "guided" if os.environ.get("TPUVSR_HUNT_GUIDED", "1") == "1"
    else "flat")

# Real action weights biased toward the defect path: the violation
# needs view changes interleaved with the SendGetState truncation
# (VSR.tla:491-516) and the final ReceiveSV log wipe (TRACE:554-577);
# unlisted actions weigh 1.
WEIGHTS = {
    "TimerSendSVC": 3.0,
    "SendGetState": 6.0,
    "SendDVC": 2.0,
    "SendSV": 2.0,
    "ReceiveSV": 2.0,
    "ReceiveClientRequest": 2.0,
}

MODES = {
    "uniform": dict(action_weights=None, split=False, swarm=0.0),
    "flat": dict(action_weights={}, split=False, swarm=sigma),
    "weighted": dict(action_weights=WEIGHTS, split=False, swarm=sigma),
    "guided": dict(action_weights=WEIGHTS, split=True, swarm=sigma),
}
mcfg = MODES[mode]

from tpuvsr.engine.spec import SpecModel
from tpuvsr.frontend.cfg import parse_cfg_file
from tpuvsr.frontend.parser import parse_module_file
from tpuvsr.sim.fleet import FleetSimulator
from tpuvsr.sim.splitting import NoveltySplitter

REFERENCE = os.environ.get(
    "TPUVSR_REFERENCE", "/root/reference/vsr-revisited/paper")

mod = parse_module_file(f"{REFERENCE}/VSR.tla")
cfg = parse_cfg_file(f"{REPO}/examples/VSR_defect.cfg")
spec = SpecModel(mod, cfg)

import jax
print(f"backend: {jax.default_backend()}", file=sys.stderr)

split = (NoveltySplitter(frac=0.25, decay=0.5, hunt_beta=1.5)
         if mcfg["split"] else None)
t0 = time.time()
sim = FleetSimulator(spec, walkers=walkers, chunk_steps=8, max_msgs=48,
                     action_weights=mcfg["action_weights"],
                     swarm_sigma=mcfg["swarm"], split=split)
print(f"build: {time.time()-t0:.1f}s mode={mode} walkers={walkers} "
      f"mesh={sim.D} (compile on first chunk)",
      file=sys.stderr, flush=True)

t0 = time.time()
res = sim.run(num=10**9, depth=depth, seed=seed,
              max_seconds=max_seconds,
              log=lambda m: print(f"hunt: {m} ({time.time()-t0:.0f}s)",
                                  file=sys.stderr))
ttv = time.time() - t0
print(f"\nelapsed {res.elapsed:.1f}s, walks {res.walks}, steps {res.steps}")
print(f"ok={res.ok} violated={res.violated_invariant}")
if res.trace:
    print(f"trace length {len(res.trace)}")
    for te in res.trace:
        print(f"  {te.position}: {te.action_name}")
    last = res.trace[-1].state
    print("final logs:", last["rep_log"])
    print("acked:", last["aux_client_acked"])
    result = {"time_to_violation_s": round(ttv, 1),
              "violated": res.violated_invariant,
              "engine": "fleet-sim",
              "walkers": walkers, "mesh_devices": sim.D,
              "depth": depth, "seed": seed,
              "swarm_sigma": mcfg["swarm"],
              "split_enabled": bool(mcfg["split"]),
              "mode": mode,
              "walks": res.walks, "steps": res.steps,
              "trace_len": len(res.trace),
              "final_action": res.trace[-1].action_name,
              "backend": jax.default_backend()}
    print(json.dumps(result))
    with open(os.path.join(REPO, "scripts", "hunt_result.json"), "w") as f:
        json.dump(result, f, indent=1)
    from tpuvsr.engine.trace import format_trace, format_trace_te
    with open(os.path.join(REPO, "scripts", "hunt_trace.txt"), "w") as f:
        f.write(format_trace(res.trace))
    # replayable artifact (frontend.trace_parse format).  Written to
    # scripts/ — the committed golden at examples/found_violation_trace
    # .txt is promoted manually after replay validation, so a later
    # hunt with a different witness shape can't silently clobber it
    with open(os.path.join(REPO, "scripts",
                           "found_violation_trace.txt"), "w") as f:
        f.write(format_trace_te(res.trace))
