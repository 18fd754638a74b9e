"""Pin the SHIPPED VSR.cfg safety fixpoint (VERDICT r4 item 5).

Every exact pin so far used shrunken constants; the reference's shipped
flagship config — R=3, C=1, |Values|=2, StartViewOnTimerLimit=2,
RestartEmptyLimit=0, SYMMETRY symmValues ON, INVARIANT
AcknowledgedWriteNotLost (VSR.cfg:4-8,29-37; re-typed as
benchmark/configs/vsr-shipped.cfg and loaded through the kernel-native
spec, no .tla) — has never been run to fixpoint.  This script runs it
through the paged engine in resumable wall-clock windows (checkpoint
scripts/shipped_ckpt) and records the fixpoint when reached, or an
honest bounded pin with EVERY level size: the next pin past depth 15
(benchmark/oracles/shipped_levels.json) is this one command.

Writes scripts/shipped_pin_levels.json.  scripts/shipped_pin.json is
the record of the one run an earlier round made with the spec loaded
from VSR.tla (its last eight level sizes); this script leaves it alone.

Usage: [JAX_PLATFORMS=cpu] python scripts/shipped_pin.py [seconds] [tile]
           [chunk_tiles]
"""

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402

backend = jax.default_backend()

from tpuvsr.engine.paged_bfs import PagedBFS          # noqa: E402
from tpuvsr.engine.spec import load_spec              # noqa: E402

seconds = float(sys.argv[1]) if len(sys.argv) > 1 else 1500.0
tile = int(sys.argv[2]) if len(sys.argv) > 2 else 512
chunk_tiles = int(sys.argv[3]) if len(sys.argv) > 3 else 32

CKPT = os.path.join(REPO, "scripts", "shipped_ckpt")
OUT = os.path.join(REPO, "scripts", "shipped_pin_levels.json")

spec = load_spec("VSR", os.path.join(REPO, "benchmark", "configs",
                                     "vsr-shipped.cfg"))
assert spec.symmetry_perms, "shipped VSR.cfg declares SYMMETRY"

t0 = time.time()
eng = PagedBFS(spec, tile_size=tile, chunk_tiles=chunk_tiles,
               next_capacity=1 << 17, fpset_capacity=1 << 24)
from tpuvsr.engine.checkpoint import prior_elapsed  # noqa: E402

resume = CKPT if os.path.isdir(CKPT) else None
prev_elapsed = prior_elapsed(CKPT) if resume else 0.0
if resume:
    print(f"[shipped] resuming from {CKPT}", flush=True)
res = eng.run(max_seconds=prev_elapsed + seconds, resume_from=resume,
              checkpoint_path=CKPT, checkpoint_every=120.0,
              log=lambda m: print(f"[shipped] {m}", flush=True))
elapsed = res.elapsed
out = {
    "config": "benchmark/configs/vsr-shipped.cfg (R=3, C=1, |Values|=2, "
              "timer=2, restarts=0, SYMMETRY ON, "
              "AcknowledgedWriteNotLost)",
    "engine": "paged",
    "backend": backend,
    "symmetry_perms": len(spec.symmetry_perms) + 1,
    "window_s": seconds,
    "tile": tile,
    "elapsed_s": round(elapsed, 1),
    "depth_reached": res.diameter,
    "distinct_states": res.distinct_states,
    "states_generated": res.states_generated,
    "distinct_per_s": round(res.distinct_states / max(elapsed, 1e-9),
                            1),
    "fixpoint": res.error is None,
    # the last one is partial unless `fixpoint`
    "level_sizes": [int(x) for x in eng.level_sizes],
    "violated": res.violated_invariant,
    "error": res.error,
    "ok": res.ok,
}
with open(OUT, "w") as f:
    json.dump(out, f, indent=1)
print(json.dumps(out))
