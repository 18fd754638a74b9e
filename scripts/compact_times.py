"""Stage 2's selection of an action's enabled lanes, by shape: ms a
chunk of 64 tiles, ``jnp.nonzero`` (what ``Stage2.tile_pass`` called
until PR 56) against ``enabled_lanes`` (what it calls since), on
whatever device JAX gives (the chip tool for a number worth writing
down).

    python scripts/compact_times.py [--density 0.01] [--out times.json]

The shapes ``(T, L_a, E_a)`` are read off the benchmark's
configurations: every action of every engine entry of
``benchmark/configs/*.json``, T the entry's tile (or the engine's
default: 128, sharded 32), L_a the kernel's ``_lane_count`` and E_a
the cap the entry starts with (``static_cap``, or its ``expand_mults``
where that is more; the sharded engine takes none).  Each form runs
alone under the loop the engines run it in: a ``fori_loop`` over the 64
tiles of a chunk that cuts a tile's bits out of the chunk's and writes
the three arrays it selects into buffers (``loop_alone``: that loop
with nothing selected, the floor both forms stand on).  The bits are
random at ``--density`` (a committed state of the defect window
enables 3.8 of its 475 lanes); both forms are dense arithmetic with no
data-dependent control flow, and both are held to each other here on
every shape, element for element.

Not on any cell's path.
"""

import argparse
import glob
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from tpuvsr.engine.device_bfs import (_align8, enabled_lanes,  # noqa: E402
                                      static_cap)
from tpuvsr.engine.spec import load_spec  # noqa: E402

I32 = jnp.int32
TILES = 64
REPEATS = 20


def nonzero_lanes(en, slots):
    """The selection as `Stage2.tile_pass` made it until PR 56."""
    T, L = en.shape
    (sel,) = jnp.nonzero(en.reshape(T * L), size=slots, fill_value=T * L)
    return (jnp.clip(sel // L, 0, T - 1).astype(I32),
            (sel % L).astype(I32), sel < T * L)


def no_lanes(en, slots):
    """No selection: what the loop costs alone (a tile's bits cut out,
    one of them read so that the cut stays, three rows written)."""
    none = jnp.zeros((slots,), I32) + en[0, 0]
    return none, none, none > 1


FORMS = {"nonzero": nonzero_lanes, "enabled_lanes": enabled_lanes,
         "loop_alone": no_lanes}


def cell_shapes():
    """``{(T, L_a, E_a): [(configuration, engine, action), ...]}`` over
    the benchmark's configurations."""
    shapes = {}
    for path in sorted(glob.glob(os.path.join(
            ROOT, "benchmark", "configs", "*.json"))):
        with open(path) as f:
            doc = json.load(f)
        spec = load_spec(doc["module"],
                         os.path.join(ROOT, "benchmark", doc["cfg"]))
        for engine, opts in doc["assumed"]["engine"].items():
            _codec, kern, _inv = spec.model(opts.get("max_msgs"))
            T = opts.get("tile_size", opts.get(
                "tile", 32 if engine == "sharded" else 128))
            mults = opts.get("expand_mults") or {}
            for name in kern.action_names:
                L = kern._lane_count(name)
                cap = static_cap(T, T * L)
                if name in mults:
                    cap = max(cap, min(T * L, _align8(T * mults[name])))
                shapes.setdefault((T, L, cap), []).append(
                    (os.path.basename(path)[:-5], engine, name))
    return shapes


def chunk_program(select, T, slots):
    """`select` over every tile of a chunk, as the level program's tile
    loop runs it: ``bits [TILES * T, L] -> (pidx, lane, ok)`` of
    ``[TILES, slots]`` each."""
    def run(bits):
        def tile(t, out):
            en = jax.lax.dynamic_slice_in_dim(bits, t * T, T)
            return tuple(
                jax.lax.dynamic_update_slice_in_dim(buf, v[None], t, 0)
                for buf, v in zip(out, select(en, slots)))
        return jax.lax.fori_loop(0, TILES, tile, (
            jnp.zeros((TILES, slots), I32), jnp.zeros((TILES, slots), I32),
            jnp.zeros((TILES, slots), bool)))
    return jax.jit(run)


def ms_a_chunk(fn, bits):
    out = jax.block_until_ready(fn(bits))          # compiles
    times = []
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(bits))
        times.append(time.perf_counter() - t0)
    return float(np.median(times)) * 1e3, out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--density", type=float, default=0.01)
    ap.add_argument("--out")
    args = ap.parse_args()

    rng = np.random.default_rng(56)
    doc = {"device": jax.devices()[0].device_kind, "tiles": TILES,
           "density": args.density, "shapes": [], "cells": {}}
    for (T, L, slots), users in sorted(cell_shapes().items()):
        bits = jnp.asarray(rng.random((TILES * T, L)) < args.density)
        row = {"T": T, "L": L, "slots": slots, "actions": len(users),
               "configs": sorted({u[0] for u in users})}
        outs = {}
        for form, select in FORMS.items():
            row[form + "_ms"], outs[form] = ms_a_chunk(
                chunk_program(select, T, slots), bits)
        row["equal"] = all(
            np.array_equal(a, b)
            for a, b in zip(outs["nonzero"], outs["enabled_lanes"]))
        doc["shapes"].append(row)
        print(json.dumps(row), flush=True)
        for config, engine, _action in users:
            cell = doc["cells"].setdefault(
                f"{config}/{engine}", dict.fromkeys(
                    [form + "_ms" for form in FORMS], 0.0))
            for form in FORMS:
                cell[form + "_ms"] += row[form + "_ms"]
    print(json.dumps(doc["cells"], indent=1))
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=1)
            f.write("\n")
    return 0 if all(r["equal"] for r in doc["shapes"]) else 1


if __name__ == "__main__":
    sys.exit(main())
