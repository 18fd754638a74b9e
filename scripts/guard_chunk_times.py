"""Stage 1 of the level program by guard: ms a chunk, on whatever
device JAX gives (the chip tool for a number worth writing down).

    python scripts/guard_chunk_times.py MODULE CFG --max-msgs 24 \
        [--states rows.npz] [--rows 8192] [--out times.json]

Every guard of ``kern._guard_fns()`` is jitted alone under the two
vmaps ``DeviceBFS._guard_matrix`` puts it under (rows of a chunk, the
action's lanes), and all of them as one program, which is what stage 1
runs (XLA shares what two guards both compute, so the sum of the
guards alone is an upper bound, and a guard alone reads no lower than
the call's own floor: 0.6-0.7 ms on the v5e with CP06's 40 planes
passed).  Of the kernel it reads the attributes the engines read, so
a parent tree's copy of this file times that tree's guards the same
way: run each from its own checkout.

``--states``: an ``.npz`` of dense planes ``[N, ...]`` (a snapshot's
frontier, say); without it the codec's init state, tiled.  The guards
are dense arithmetic with no data-dependent control flow, so the
states move the times little; they decide what ``true_lanes`` and
``lane_sum`` read, the two numbers that say a parent and a change
computed the same matrix.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from tpuvsr.engine.spec import load_spec  # noqa: E402
from tpuvsr.models.guard_tables import table_lanes  # noqa: E402

REPEATS = 20


def _batch(spec, codec, path, rows):
    if path:
        with np.load(path) as z:
            planes = {k: z[k] for k in z.files}
    else:
        (init,) = spec.init_states()
        planes = {k: np.asarray(v)[None]
                  for k, v in codec.encode(init).items()}
    n = next(iter(planes.values())).shape[0]
    take = np.arange(rows) % n
    return {k: jnp.asarray(v[take], jnp.int32) for k, v in planes.items()}


def _ms(fn, batch):
    out = jax.block_until_ready(fn(batch))          # compiles
    times = []
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(batch))
        times.append(time.perf_counter() - t0)
    return float(np.median(times)) * 1e3, out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("module")
    ap.add_argument("cfg")
    ap.add_argument("--max-msgs", type=int, required=True)
    ap.add_argument("--states")
    ap.add_argument("--rows", type=int, default=8192)
    ap.add_argument("--out")
    args = ap.parse_args()

    spec = load_spec(args.module, args.cfg)
    codec, kern, _inv = spec.model(args.max_msgs)
    batch = _batch(spec, codec, args.states, args.rows)
    names = list(kern.action_names)
    guards = kern._guard_fns()
    lanes = [jnp.arange(kern._lane_count(n), dtype=jnp.int32)
             for n in names]

    def matrix(g, ln):
        return jax.vmap(lambda st: jax.vmap(lambda x: g(st, x))(ln))

    doc = {"device": jax.devices()[0].device_kind, "module": args.module,
           "rows": args.rows, "max_msgs": args.max_msgs,
           "lanes": int(sum(x.size for x in lanes)),
           "table_lanes": table_lanes(kern),
           "guards": {}}
    for name, g, ln in zip(names, guards, lanes):
        ms, out = _ms(jax.jit(matrix(g, ln)), batch)
        out = np.asarray(out)
        doc["guards"][name] = {
            "lanes": int(ln.size), "ms": round(ms, 4),
            "ns_per_lane_row": round(ms * 1e6 / (ln.size * args.rows), 3),
            "true_lanes": int(out.sum()),
            "lane_sum": int((out * np.arange(1, ln.size + 1)).sum())}
    ms, _out = _ms(jax.jit(lambda b: [matrix(g, ln)(b) for g, ln
                                      in zip(guards, lanes)]), batch)
    doc["all_ms"] = round(ms, 4)
    doc["sum_ms"] = round(sum(g["ms"] for g in doc["guards"].values()), 4)
    doc["all_ns_per_lane_row"] = round(
        ms * 1e6 / (doc["lanes"] * args.rows), 3)
    text = json.dumps(doc, indent=1)
    print(text)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text + "\n")


if __name__ == "__main__":
    main()
