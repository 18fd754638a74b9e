"""On-hardware repro ladder for the tile-1024 TPU mis-exploration
(ROADMAP S4).

A round-4 tile sweep found DeviceBFS at tile=1024 on a v5e produces
58,957 distinct states on the flagship small config vs the pinned
43,941 — duplicate states entering the frontier — while tile<=512
matched exactly (the same engine is exact at every width on CPU).
This script isolates WHERE the TPU lowering diverges, cheapest
hypothesis first, writing partial results to
scripts/miscompile_repro.json after every stage so a lost machine
never loses evidence (completed stages are skipped on re-run):

  insert       synthetic duplicate-heavy batches through insert_core
               chained in a fori_loop (the level kernel's composition):
               fresh-count must equal the distinct count, the table
               must hold exactly the expected fingerprints (a torn
               claim scatter leaves garbage slots).
  insert_barrier  same, in a subprocess with TPUVSR_FPSET_BARRIER=1
               (an optimization_barrier between the claim scatter and
               the verify gather) — only when `insert` failed.
  fingerprint  width-determinism of the canonical fingerprint: the
               same reachable states fingerprinted at batch widths
               1024/2048 must match the width-256 values (width-
               dependent vectorization would make one state hash two
               ways, which also duplicates frontier entries).
  levels       DeviceBFS tile=1024 chunked run vs the pinned per-level
               sizes (scripts/pinned_levels_small.json): the first
               divergent BFS level localizes the failure in time.
  levels_full  same at hash_mode="full" — if full-state hashing is
               exact where incremental diverges, the incremental
               fingerprint path is the culprit.
  levels_barrier  tile=1024 with the claim barrier — if exact, the
               insert claim race is the culprit and the barrier is the
               fix.

Usage: [JAX_PLATFORMS=cpu] python scripts/tpu_miscompile_repro.py [stage ...]
"""

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# diagnosis runs need the unvalidated width the guard refuses
os.environ.setdefault("TPUVSR_UNSAFE_TILE", "1")

import jax  # noqa: E402

backend = jax.default_backend()

OUT = os.environ.get(
    "TPUVSR_REPRO_OUT", os.path.join(REPO, "scripts",
                                     "miscompile_repro.json"))
BUDGET = float(os.environ.get("TPUVSR_REPRO_BUDGET", "3300"))
T0 = time.time()

RESULTS = {}
if os.path.exists(OUT):
    try:
        with open(OUT) as f:
            RESULTS = json.load(f)
    except ValueError:
        RESULTS = {}
RESULTS["backend"] = backend


def save():
    with open(OUT, "w") as f:
        json.dump(RESULTS, f, indent=1)


def left():
    return BUDGET - (time.time() - T0)


def log(msg):
    print(f"[repro] {msg}", flush=True)


# ----------------------------------------------------------------------
def stage_insert(widths=(512, 1024, 2048, 4096), rounds=8, seed=0):
    import numpy as np
    import jax
    import jax.numpy as jnp
    from tpuvsr.engine.fpset import empty_table, insert_core

    rows = []
    for B in widths:
        rng = np.random.default_rng(seed + B)
        P = max(64, rounds * B // 2)
        pool = rng.integers(1, 2**32, size=(P, 4), dtype=np.uint32)
        pool[:, 3] = np.arange(P, dtype=np.uint32)   # rows distinct
        idx = rng.integers(0, P, size=(rounds, B))
        batches = jnp.asarray(pool[idx])
        n_unique = int(np.unique(idx).size)
        cap = 1 << max(12, int(np.ceil(np.log2(P * 4))))
        slots0 = empty_table(cap)["slots"]

        @jax.jit
        def run(slots, batches):
            def body(i, carry):
                slots, fresh, ovf = carry
                tbl, fr, o = insert_core(
                    {"slots": slots}, batches[i],
                    jnp.ones((batches.shape[1],), bool))
                return (tbl["slots"],
                        fresh + fr.sum(dtype=jnp.int32), ovf | o)
            return jax.lax.fori_loop(
                0, batches.shape[0], body,
                (slots, jnp.asarray(0, jnp.int32), jnp.asarray(False)))

        t0 = time.time()
        slots, fresh, ovf = jax.device_get(run(slots0, batches))
        occ = slots[slots[:, 0] != 0]
        keyed = pool.copy()
        keyed[keyed[:, 0] == 0, 0] = 1
        expect = set(map(tuple, keyed[np.unique(idx)]))
        got = set(map(tuple, occ[:, :4].astype(np.uint32)))
        row = {
            "width": B, "rounds": rounds, "unique": n_unique,
            "fresh": int(fresh), "occupied": int(occ.shape[0]),
            "overflow": bool(ovf),
            "garbage_slots": len(got - expect),
            "missing_fps": len(expect - got),
            "elapsed_s": round(time.time() - t0, 1),
        }
        row["ok"] = (row["fresh"] == n_unique and not row["overflow"]
                     and row["garbage_slots"] == 0
                     and row["missing_fps"] == 0)
        rows.append(row)
        log(f"insert width={B}: fresh={row['fresh']} want={n_unique} "
            f"garbage={row['garbage_slots']} ok={row['ok']}")
    return rows


# ----------------------------------------------------------------------
def _collect_states(n_target=3072):
    """Reachable dense states of the flagship small config, enumerated
    through the kernel's own step_batch (width 256 — a validated
    width)."""
    import numpy as np
    from __graft_entry__ import _small_spec
    from tpuvsr.models import registry

    spec = _small_spec()
    codec, kern = registry.make_model(spec)
    init = [codec.encode(st) for st in spec.init_states()]
    states = [{k: np.asarray(v) for k, v in init[0].items()}]
    seen = set()
    frontier = list(states)
    W = 256
    while len(states) < n_target and frontier:
        chunk = frontier[:W]
        frontier = frontier[W:]
        cs = chunk + [chunk[-1]] * (W - len(chunk))
        batch = {k: np.stack([d[k] for d in cs]) for k in cs[0]}
        succs, en = kern.step_batch(batch)
        en = np.asarray(en)
        succs = {k: np.asarray(v) for k, v in succs.items()
                 if not k.startswith("_")}
        for i in range(len(chunk)):
            for lane in np.nonzero(en[i])[0]:
                d = {k: succs[k][i, lane] for k in succs}
                if int(d["err"]) != 0:
                    continue
                key = b"".join(np.ascontiguousarray(d[k]).tobytes()
                               for k in sorted(d))
                if key in seen:
                    continue
                seen.add(key)
                states.append(d)
                frontier.append(d)
                if len(states) >= n_target:
                    break
            if len(states) >= n_target:
                break
    return kern, states


def stage_fingerprint(widths=(1024, 2048), ref_width=256):
    import numpy as np
    kern, states = _collect_states()
    log(f"fingerprint: {len(states)} reachable states collected")

    def fps_at(width):
        out = []
        for off in range(0, len(states), width):
            chunk = states[off:off + width]
            cs = chunk + [chunk[-1]] * (width - len(chunk))
            batch = {k: np.stack([d[k] for d in cs]) for k in cs[0]}
            f = np.asarray(kern.fingerprint_batch(batch))
            out.append(f[:len(chunk)])
        return np.concatenate(out)

    ref = fps_at(ref_width)
    rows = []
    for w in widths:
        got = fps_at(w)
        bad = np.nonzero((got != ref).any(axis=1))[0]
        rows.append({"width": w, "states": len(states),
                     "mismatches": int(bad.size),
                     "first_bad_index": int(bad[0]) if bad.size else None,
                     "ok": bad.size == 0})
        log(f"fingerprint width={w}: {bad.size} mismatches vs "
            f"width-{ref_width}")
    return {"ref_width": ref_width, "rows": rows}


# ----------------------------------------------------------------------
def stage_levels(tile=1024, hash_mode="incremental"):
    from __graft_entry__ import _small_spec
    from tpuvsr.engine.device_bfs import DeviceBFS

    with open(os.path.join(REPO, "scripts",
                           "pinned_levels_small.json")) as f:
        pinned = json.load(f)
    want = pinned["level_sizes"]
    spec = _small_spec()
    eng = DeviceBFS(spec, tile_size=tile, fpset_capacity=1 << 21,
                    next_capacity=1 << 15, expand_mult=2,
                    hash_mode=hash_mode,
                    expand_mults={"ReceiveMatchingSVC": 4, "SendDVC": 4})
    t0 = time.time()
    res = eng.run()
    lv = [int(x) for x in eng.level_sizes]
    first_div = next((i for i, (a, b) in enumerate(zip(lv, want))
                      if a != b), None)
    if first_div is None and len(lv) != len(want):
        first_div = min(len(lv), len(want))
    row = {
        "tile": tile, "hash_mode": hash_mode,
        "distinct": res.distinct_states,
        "generated": res.states_generated,
        "pinned_distinct": pinned["distinct"],
        "elapsed_s": round(time.time() - t0, 1),
        "level_sizes": lv,
        "first_divergent_level": first_div,
        "ok": res.distinct_states == pinned["distinct"]
        and first_div is None,
    }
    log(f"levels tile={tile} hash={hash_mode}: distinct="
        f"{res.distinct_states} (pinned {pinned['distinct']}), first "
        f"divergent level {first_div}")
    return row


# ----------------------------------------------------------------------
def run_subprocess(stage, out_suffix, extra_env):
    sub_out = OUT.replace(".json", f"_{out_suffix}.json")
    if os.path.exists(sub_out):
        os.unlink(sub_out)
    env = dict(os.environ)
    env.update(extra_env)
    env["TPUVSR_REPRO_OUT"] = sub_out
    env["TPUVSR_REPRO_BUDGET"] = str(max(60, int(left()) - 30))
    try:
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__), stage],
            env=env, cwd=REPO, timeout=max(120, left()))
        rc = r.returncode
    except subprocess.TimeoutExpired:
        rc = "timeout"
    if os.path.exists(sub_out):
        with open(sub_out) as f:
            rec = json.load(f).get(stage)
        if rec is not None:
            return rec
    # an error dict (never None): _errored() then re-attempts the
    # stage on the next queue run instead of suppressing it forever
    return {"error": f"subprocess rc={rc}, no stage output"}


def _errored(rec):
    return isinstance(rec, dict) and "error" in rec


def main():
    stages = sys.argv[1:] or ["insert", "fingerprint", "levels"]
    for st in stages:
        if st in RESULTS and not _errored(RESULTS[st]):
            log(f"stage {st}: already recorded, skipping")
            continue
        if left() < 120:
            log(f"stage {st}: budget exhausted, stopping")
            break
        log(f"=== stage {st} (budget left {left():.0f}s)")
        try:
            if st == "insert":
                RESULTS[st] = stage_insert()
            elif st == "fingerprint":
                RESULTS[st] = stage_fingerprint()
            elif st == "levels":
                RESULTS[st] = stage_levels()
            elif st == "levels_full":
                RESULTS[st] = stage_levels(hash_mode="full")
            else:
                log(f"unknown stage {st}")
                continue
        except Exception as e:  # noqa: BLE001
            RESULTS[st] = {"error": f"{type(e).__name__}: {e}"}
        save()

    # conditional follow-ups (skipped when already recorded)
    def _want(key):
        return key not in RESULTS or _errored(RESULTS[key])

    ins = RESULTS.get("insert")
    insert_bad = isinstance(ins, list) and any(not r["ok"] for r in ins)
    if insert_bad and _want("insert_barrier") and left() > 300:
        log("=== stage insert_barrier (insert failed; testing the "
            "claim-barrier hypothesis)")
        RESULTS["insert_barrier"] = run_subprocess(
            "insert", "barrier", {"TPUVSR_FPSET_BARRIER": "1"})
        save()

    lv = RESULTS.get("levels")
    levels_bad = isinstance(lv, dict) and not lv.get("ok", True)
    if levels_bad and _want("levels_full") and left() > 900:
        log("=== stage levels_full (incremental diverged; "
            "discriminating the fingerprint path)")
        try:
            RESULTS["levels_full"] = stage_levels(hash_mode="full")
        except Exception as e:  # noqa: BLE001
            RESULTS["levels_full"] = {"error": f"{type(e).__name__}: {e}"}
        save()
    if levels_bad and _want("levels_barrier") and left() > 900:
        log("=== stage levels_barrier (end-to-end with the claim "
            "barrier)")
        RESULTS["levels_barrier"] = run_subprocess(
            "levels", "barrier2", {"TPUVSR_FPSET_BARRIER": "1"})
        save()

    save()
    print(json.dumps(RESULTS))


if __name__ == "__main__":
    main()
