"""Deepen the RR05 bounded pin with TPU throughput (VERDICT r4 item 8).

r4 pinned VR_REPLICA_RECOVERY (CrashLimit=1, |Values|=1, timer=1) to a
BOUNDED oracle: 12,749,898 distinct at depth 189, frontier still
growing ~130k/level at the cutoff (~6h of 1-core CPU) — the one corpus
module with neither a fixpoint nor a full-space differential
(scripts/recovery_fixpoints.json).  This script re-runs the space
through the PAGED engine in resumable wall-clock windows: each run
extends the previous one via the level-boundary checkpoint
(scripts/rr05_ckpt), records the exact per-level prefix, and asserts it
matches the r4 prefix where they overlap (the levels are an exact
oracle; any divergence is an engine regression, not progress).

Writes scripts/rr05_deep.json.

Usage: [JAX_PLATFORMS=cpu] python scripts/rr05_deep.py [seconds] [tile] [chunk_tiles]
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

seconds = float(sys.argv[1]) if len(sys.argv) > 1 else 1500.0
tile = int(sys.argv[2]) if len(sys.argv) > 2 else 512
chunk_tiles = int(sys.argv[3]) if len(sys.argv) > 3 else 32

CKPT = os.path.join(REPO, "scripts", "rr05_ckpt")
OUT = os.path.join(REPO, "scripts", "rr05_deep.json")

sys.path.insert(0, os.path.join(REPO, "scripts"))
_argv, sys.argv = sys.argv, sys.argv[:1]
from pin_fixpoints import RECOVERY_CFG, load          # noqa: E402
sys.argv = _argv

spec = load("05-replica-recovery/VR_REPLICA_RECOVERY", RECOVERY_CFG,
            None)

t0 = time.time()
eng = PagedBFS(spec, tile_size=tile, chunk_tiles=chunk_tiles,
               next_capacity=1 << 17, fpset_capacity=1 << 24)
from tpuvsr.engine.checkpoint import prior_elapsed  # noqa: E402

resume = CKPT if os.path.isdir(CKPT) else None
prev_elapsed = prior_elapsed(CKPT) if resume else 0.0
if resume:
    print(f"[rr05] resuming from {CKPT}", flush=True)
res = eng.run(max_seconds=prev_elapsed + seconds, resume_from=resume,
              checkpoint_path=CKPT, checkpoint_every=120.0,
              log=lambda m: print(f"[rr05] {m}", flush=True))
elapsed = res.elapsed

# cross-check the completed-level prefix against the r4 bounded pin
prefix_ok = None
try:
    with open(os.path.join(REPO, "scripts",
                           "recovery_fixpoints.json")) as f:
        r4 = json.load(f)["VR_REPLICA_RECOVERY"]["single_bounded"]
    want = r4.get("level_sizes")
    if want:
        done = eng.level_sizes[:-1]  # last level may be partial
        overlap = min(len(done), len(want))
        prefix_ok = done[:overlap] == [int(x) for x in want[:overlap]]
except (OSError, KeyError, ValueError):
    pass

out = {
    "module": "VR_REPLICA_RECOVERY (RR05), CrashLimit=1, |Values|=1, "
              "timer=1",
    "engine": "paged",
    "backend": backend,
    "window_s": seconds,
    "tile": tile,
    "chunk_tiles": chunk_tiles,
    "elapsed_s": round(elapsed, 1),
    "depth_reached": res.diameter,
    "distinct_states": res.distinct_states,
    "states_generated": res.states_generated,
    "distinct_per_s": round(res.distinct_states / max(elapsed, 1e-9),
                            1),
    "fixpoint": res.error is None,
    "r4_bounded_pin": {"distinct": 12749898, "depth": 189},
    "beats_r4_pin": res.distinct_states > 12749898
    or res.error is None,
    "prefix_matches_r4": prefix_ok,
    "level_sizes_tail": eng.level_sizes[-10:],
    "n_levels": len(eng.level_sizes),
    "violated": res.violated_invariant,
    "error": res.error,
    "ok": res.ok,
}
with open(OUT, "w") as f:
    json.dump(out, f, indent=1)
print(json.dumps(out))
