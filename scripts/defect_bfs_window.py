"""Bounded-window BFS of the defect fixture through the paged engine.

The reference's flagship run — exhaustive BFS of VSR.tla at R=3,
|Values|=3, timer=3 — took "multiple days" and >=500 GB of disk
(/root/reference/README.md:20).  This script runs the same fixture
(examples/VSR_defect.cfg) through the host-paged BFS engine for a fixed
wall-clock window and records sustained throughput, memory behavior,
spill statistics, frontier occupancy, and a measured time-to-depth-24
projection (the violation depth: TRACE:556) — the single-chip version
of the reference's headline workload.

Checkpoint/resume: the run snapshots at level boundaries
(scripts/defect_window_ckpt) and RESUMES from the snapshot when one
exists — a lost machine mid-window costs only the partial level, and
re-running the job goes deeper instead of starting over.  Delete the
checkpoint dir to start fresh.

Writes scripts/defect_window.json (cumulative across resumed windows).

Usage: python scripts/defect_bfs_window.py [seconds] [tile] [chunk_tiles]
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
from tpuvsr.obs import RunObserver                    # noqa: E402

seconds = float(sys.argv[1]) if len(sys.argv) > 1 else 600.0
tile = int(sys.argv[2]) if len(sys.argv) > 2 else 256
chunk_tiles = int(sys.argv[3]) if len(sys.argv) > 3 else 16

CKPT = os.path.join(REPO, "scripts", "defect_window_ckpt")
OUT = os.path.join(REPO, "scripts", "defect_window.json")
# round-artifact trajectories (ISSUE 3 satellite / ROADMAP follow-up):
# the journal appends across resumed windows — one continuous event
# stream for the whole checkpoint/recover chain — and the metrics file
# carries the last window's per-level rows + phase timers
JOURNAL = os.path.join(REPO, "scripts", "defect_window.jsonl")
METRICS = os.path.join(REPO, "scripts", "defect_window_metrics.json")

REFERENCE = os.environ.get(
    "TPUVSR_REFERENCE", "/root/reference/vsr-revisited/paper")
spec = load_spec(f"{REFERENCE}/VSR.tla",
                 f"{REPO}/examples/VSR_defect.cfg")

t0 = time.time()
eng = PagedBFS(spec, tile_size=tile, chunk_tiles=chunk_tiles,
               next_capacity=1 << 17, fpset_capacity=1 << 24,
               max_msgs=32)
from tpuvsr.engine.checkpoint import prior_elapsed  # noqa: E402

resume = CKPT if os.path.isdir(CKPT) else None
prev_elapsed = prior_elapsed(CKPT) if resume else 0.0
if resume:
    print(f"[defect_window] resuming from {CKPT}", flush=True)
res = eng.run(max_seconds=prev_elapsed + seconds, resume_from=resume,
              checkpoint_path=CKPT, checkpoint_every=120.0,
              obs=RunObserver(journal_path=JOURNAL, metrics_path=METRICS),
              log=lambda m: print(f"[defect_window] {m}", flush=True))
window_elapsed = time.time() - t0          # this window's wall clock
elapsed = res.elapsed                      # cumulative across resumes


def depth24_projection(level_sizes, distinct_per_s):
    """Fit the tail growth ratio of the level sizes and project the
    cumulative states through depth 24 (the violation depth), then
    divide by the sustained distinct/s.  Crude but measured."""
    full = [s for s in level_sizes if s > 0]
    if len(full) < 4 or distinct_per_s <= 0:
        return None
    # fit on the last 3 COMPLETED levels (the final entry is partial
    # whenever the window cut mid-level) and seed the extrapolation
    # from the last completed level too — seeding from the partial one
    # would understate the projection by its completion fraction
    tail = full[-4:-1]
    ratios = [tail[i + 1] / tail[i] for i in range(len(tail) - 1)
              if tail[i] > 0]
    if not ratios:
        return None
    r = sum(ratios) / len(ratios)
    total = sum(full[:-1])
    cur = full[-2]
    for _ in range(len(full) - 2, 24):
        cur *= r
        total += cur
    return {"tail_growth_ratio": round(r, 2),
            "projected_cumulative_states_depth24": int(total),
            "projected_seconds_at_current_rate":
                int(total / distinct_per_s)}


distinct_per_s = res.distinct_states / max(elapsed, 1e-9)
out = {
    "config": "examples/VSR_defect.cfg (R=3, |Values|=3, timer=3)",
    "engine": "paged (host-RAM frontier, HBM fingerprints)",
    "backend": backend,
    "window_s": seconds,
    "tile": tile,
    "chunk_tiles": chunk_tiles,
    "elapsed_s": round(elapsed, 1),
    "window_elapsed_s": round(window_elapsed, 1),
    "resumed": bool(resume),
    "depth_reached": res.diameter,
    "distinct_states": res.distinct_states,
    "states_generated": res.states_generated,
    "distinct_per_s": round(distinct_per_s, 1),
    "generated_per_s": round(res.states_generated / max(elapsed, 1e-9),
                             1),
    "vs_cpu_window_1160": round(distinct_per_s / 1160.3, 2),
    "level_sizes": eng.level_sizes,
    "frontier_final": eng.level_sizes[-1] if eng.level_sizes else 0,
    "avg_tile_occupancy": round(
        sum(eng.level_sizes) / max(1, len(eng.level_sizes)) / tile, 1),
    "spill_count": eng.spill_count,
    "spill_rows": eng.spill_rows,
    "max_msgs_final": eng.codec.shape.MAX_MSGS,
    # the packed row when -pack is on (ISSUE 9) — the bytes the paged
    # tier ACTUALLY moves per state; pack_ratio records the cut
    "frontier_bytes_per_state": eng._state_row_bytes(),
    "pack_ratio": round(
        sum(v.nbytes for v in eng.codec.zero_state().values())
        / eng._state_row_bytes(), 2),
    "device_bytes_per_s": round(
        (res.states_generated + res.distinct_states)
        * eng._state_row_bytes() / max(elapsed, 1e-9) / 1e6, 1),
    "depth24_projection": depth24_projection(
        eng.level_sizes, distinct_per_s),
    "violated": res.violated_invariant,
    "error": res.error,
    "ok": res.ok,
    "journal": "scripts/defect_window.jsonl",
    "metrics_file": "scripts/defect_window_metrics.json",
    "phases": (res.metrics or {}).get("phases"),
    "counters": (res.metrics or {}).get("counters"),
    # ISSUE 10 acceptance surface: the occupancy-packed fused commit's
    # real-work fraction (one insert per tile is what "fused" means)
    "commit": (res.metrics or {}).get("gauges", {}).get("commit_mode"),
    "occupancy": (res.metrics or {}).get("gauges", {}).get("occupancy"),
}
with open(OUT, "w") as f:
    json.dump(out, f, indent=1)
print(json.dumps(out))
