"""Pin exact full fixpoints for the recovery-era specs (RR05/AL05/CP06)
with the DEVICE engine — the interpreter oracle could not reach them
(scripts/fixpoints.json: RR05/AL05 hit the 300k-state limit at ~75
states/s; CP06 did finish at 137,524, which doubles as the cross-check
that the device fixpoint machinery agrees with the interpreter on a
recovery-era spec before we trust its RR05/AL05 numbers).

CP06 is run through BOTH the single-device engine and the sharded
engine (8-way virtual CPU mesh) — two independently-written dedup/
frontier paths; agreement on (distinct, generated, diameter) plus the
interpreter's 137,524 is the evidence standard.  RR05/AL05 proved far
larger than the interpreter bound suggested (RR05 passed 2M distinct
at depth 44), so they are pinned as BOUNDED oracles: single-device
engine to a state cap, exact level-size prefix recorded.  Device dedup
is on 128-bit fingerprints (collision odds at 1e6 states ~ 1e-26), vs
the interpreter's exact canonical views.

Writes scripts/recovery_fixpoints.json.

Usage: python scripts/recovery_fixpoints.py [only_stem_substr]
"""

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
OUT = os.path.join(REPO, "scripts", "recovery_fixpoints.json")
only = sys.argv[1] if len(sys.argv) > 1 else ""
# pin_fixpoints parses sys.argv at import time (its own max_states arg)
sys.argv = sys.argv[:1]

sys.path.insert(0, os.path.join(REPO, "scripts"))
from pin_fixpoints import RECOVERY_CFG, CP_CFG, load  # noqa: E402

from tpuvsr.engine.device_bfs import DeviceBFS  # noqa: E402

# CP06 first: its interpreter fixpoint (137,524) is the cross-check
# that the device fixpoint machinery agrees with the oracle on a
# recovery-era spec, and it is small enough for BOTH engines.  The
# RR05/AL05 spaces turned out to be far larger (RR05 passed 2M distinct
# at depth 44 on the first attempt), so they get the single-device
# engine only, with a state cap as the bounded pinning fallback.
CAP = 6_000_000
JOBS = [
    ("06-replica-recovery-cp/VR_REPLICA_RECOVERY_CP", CP_CFG,
     ("single", "sharded"), None),
    ("05-replica-recovery/VR_REPLICA_RECOVERY", RECOVERY_CFG,
     ("single",), CAP),
    ("05-replica-recovery/VR_REPLICA_RECOVERY_ASYNC_LOG", RECOVERY_CFG,
     ("single",), CAP),
]

results = {}
if os.path.exists(OUT):
    with open(OUT) as f:
        results = json.load(f)


def _obs(key, engine):
    """Per-job observer (ISSUE 3 satellite / ROADMAP follow-up): round
    artifacts carry the journal + metrics trajectory of every pinning
    run, not just its headline counts."""
    from tpuvsr.obs import RunObserver
    stem = os.path.join(REPO, "scripts",
                        f"recovery_{key.lower()}_{engine}")
    return RunObserver(journal_path=stem + ".jsonl",
                       metrics_path=stem + "_metrics.json")


def run_single(spec, max_states=None, key=""):
    eng = DeviceBFS(spec, tile_size=512)
    res = eng.run(max_states=max_states, obs=_obs(key, "single"),
                  log=lambda m: print(f"  [single] {m}", flush=True))
    return res, eng.level_sizes


def run_sharded(spec, max_states=None, key=""):
    import numpy as np
    import jax
    from jax.sharding import Mesh
    from tpuvsr.parallel.sharded_bfs import ShardedBFS
    mesh = Mesh(np.array(jax.devices()[:8]), ("d",))
    eng = ShardedBFS(spec, mesh, tile=64, bucket_cap=4096,
                     next_capacity=1 << 15, fpset_capacity=1 << 17)
    res = eng.run(max_states=max_states, obs=_obs(key, "sharded"),
                  log=lambda m: print(f"  [sharded] {m}", flush=True))
    return res, eng.level_sizes


RUNNERS = {"single": run_single, "sharded": run_sharded}

for stem, cfg_text, engines, cap in JOBS:
    if only and only not in stem:
        continue
    key = stem.split("/")[-1]
    print(f"=== {stem}", flush=True)
    entry = results.get(key, {})
    for engine in engines:
        done = entry.get(engine, {})
        if done.get("fixpoint") or (cap and done.get("distinct")):
            print(f"  {engine}: already pinned, skipping", flush=True)
            continue
        spec = load(stem, cfg_text, None)
        t0 = time.time()
        try:
            res, levels = RUNNERS[engine](spec, max_states=cap, key=key)
        except Exception as e:  # noqa: BLE001
            entry[engine] = {"error": f"{type(e).__name__}: {e}"}
            results[key] = entry
            with open(OUT, "w") as f:
                json.dump(results, f, indent=1, sort_keys=True)
            print(f"  {engine} FAILED: {e}", flush=True)
            continue
        entry[engine] = {
            "ok": res.ok,
            "fixpoint": res.error is None,
            "distinct": res.distinct_states,
            "generated": res.states_generated,
            "diameter": res.diameter,
            "elapsed_s": round(time.time() - t0, 1),
            "violated": res.violated_invariant,
            "error": res.error,
            "level_sizes": levels,
            "journal": f"scripts/recovery_{key.lower()}_{engine}.jsonl",
            "metrics_file": (f"scripts/recovery_{key.lower()}_{engine}"
                             f"_metrics.json"),
            "phases": (res.metrics or {}).get("phases"),
        }
        results[key] = entry
        with open(OUT, "w") as f:
            json.dump(results, f, indent=1, sort_keys=True)
        print(f"  {engine} -> distinct={res.distinct_states} "
              f"generated={res.states_generated} diam={res.diameter} "
              f"({entry[engine]['elapsed_s']}s)", flush=True)
    s, sh = entry.get("single", {}), entry.get("sharded", {})
    if s.get("fixpoint") and sh.get("fixpoint"):
        agree = all(s.get(k) == sh.get(k) for k in
                    ("distinct", "generated", "diameter", "level_sizes"))
        entry["engines_agree"] = agree
        if key == "VR_REPLICA_RECOVERY_CP":
            entry["matches_interpreter_137524"] = (
                s.get("distinct") == 137524)
        results[key] = entry
        with open(OUT, "w") as f:
            json.dump(results, f, indent=1, sort_keys=True)
        print(f"  engines_agree={agree}", flush=True)

print("done")
