#!/usr/bin/env python3
"""The plain reference of symmetry reduction: orbit counts, level by
level, from the states a symmetry-OFF run committed.

A value permutation fixes Init and commutes with Next, so the members
of an orbit share a depth: the number of distinct orbits among the
states of level d of a symmetry-off run is the size of level d of a
symmetry-on run, whichever member an engine happened to keep.

Each state is decoded with the codec, projected on the variables of
the cfg VIEW (VSR.tla:149-150: every variable but the auxiliary ones,
the codec's `aux_*` planes), and mapped to the least of
`value_key(permute_value(v, p))` over the group: the interpreter's own
`view_value` rule (engine/spec.py), on host values.  Nothing of the
code under test is called: not `engine/canon.py`, not the kernel's
permuted planes, not its hash.

    JAX_PLATFORMS=cpu python3 benchmark/tools/orbit_reference.py \
        --config vsr-shipped --depth 8

runs `PagedBFS(symmetry=False, retain_levels=True)` to that depth and
prints the level sizes of the symmetry-off run beside their orbit
counts (about 1.4 ms a state on the host).
"""

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))

from tpuvsr.core.values import FnVal, permute_value, value_key  # noqa: E402


def least_image(state, perms):
    """`value_key` of the least image of one decoded state's VIEW
    projection under {identity} + perms."""
    view = FnVal(sorted((k, v) for k, v in state.items()
                        if not k.startswith("aux_")))
    return min([value_key(view)]
               + [value_key(permute_value(view, p)) for p in perms])


def level_images(codec, perms, block):
    """The least image of every row of one level's dense block (a
    dict plane -> [n, ...] array), in row order."""
    n = len(next(iter(block.values())))
    return [least_image(codec.decode({k: v[i] for k, v in block.items()}),
                        perms) for i in range(n)]


def orbit_level_sizes(codec, perms, level_blocks):
    """Distinct orbits per level of a symmetry-off run's levels."""
    return [len(set(level_images(codec, perms, block)))
            for block in level_blocks]


def symmetry_off_run(spec, depth, **engine_kw):
    """(engine, result) of a symmetry-off `PagedBFS` run whose
    `engine.level_blocks` are the dense blocks of levels 0..depth: the
    engine keeps the levels it expands, so it expands one level more
    than it is asked for."""
    from tpuvsr.engine.paged_bfs import PagedBFS
    eng = PagedBFS(spec, symmetry=False, retain_levels=True, **engine_kw)
    res = eng.run(max_depth=depth + 1)
    if not res.ok or len(eng.level_blocks) != depth + 1:
        raise SystemExit(f"orbit_reference: the symmetry-off run "
                         f"failed: {res.violated_invariant or res.error}")
    return eng, res


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True,
                    help="a file name under benchmark/configs, no .json")
    ap.add_argument("--depth", type=int, required=True)
    args = ap.parse_args(argv)
    from tpuvsr.engine.spec import load_spec
    with open(os.path.join(BENCH, "configs", args.config + ".json")) as f:
        config = json.load(f)
    spec = load_spec(config["module"], os.path.join(BENCH, config["cfg"]))
    if not spec.symmetry_perms:
        raise SystemExit(f"orbit_reference: {config['cfg']} declares no "
                         f"SYMMETRY")
    eng, _res = symmetry_off_run(
        spec, args.depth,
        max_msgs=config["assumed"]["engine"]["device"]["max_msgs"])
    print(json.dumps({
        "config": args.config,
        "symmetry_off_level_sizes": list(eng.level_sizes[:args.depth + 1]),
        "orbit_level_sizes": orbit_level_sizes(
            eng.codec, spec.symmetry_perms, eng.level_blocks)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
