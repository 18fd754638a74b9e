"""What a four-chip configuration's start caps and `bucket_cap` rest
on: `ShardedBFS` over D devices of whatever backend JAX has (D
virtual CPU devices here, the chips there), from Init to the
configuration's pinned depth, with every dispatch's `need` output kept
(the most lanes of each action one tile of one shard enabled: what a
cap has to hold) and the bucket left to find its own size.

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python scripts/sharded_needs.py benchmark/configs/NAME.json \\
        [--depth N] [--as-configured]

Without `--as-configured` the run starts from the static caps and a
bucket of 64 and grows both: the last line gives `need_seen`, the
actions whose need passes the static cap `ShardedBFS` starts them at
(`over_static`: none in either four-chip configuration, which is why
the engine takes no `expand_mults`; ROADMAP R-l), the bucket the run
ended with and what grew.  With it the engine is built as the benchmark
builds it (`assumed.engine.sharded`), and `grows` has to read 0.  A count, which
holds on any backend: which states share a tile follows from the
fingerprint's owner and the order of insertion, not from the device.
It also holds the run to the configuration's oracle: level sizes,
per-action counts and the kernel's commit stats where the oracle has
them.
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
BENCH = os.path.join(REPO, "benchmark")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config")
    ap.add_argument("--depth", type=int)
    ap.add_argument("--as-configured", action="store_true")
    args = ap.parse_args(argv)
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from tpuvsr.engine.device_bfs import static_cap
    from tpuvsr.engine.spec import load_spec
    from tpuvsr.parallel.sharded_bfs import ShardedBFS

    with open(args.config) as f:
        config = json.load(f)
    kw = dict(config["assumed"]["engine"]["sharded"])
    if not args.as_configured:
        kw["bucket_cap"] = None
    spec = load_spec(config["module"], os.path.join(BENCH, config["cfg"]))
    with open(os.path.join(BENCH, config["oracle"]["levels"]["file"])) as f:
        oracle = json.load(f)
    depth = args.depth or config["oracle"]["levels"]["complete_through_depth"]
    devices = jax.devices()
    eng = ShardedBFS(spec, Mesh(np.array(devices), ("d",)), **kw)
    needs = []

    def recording(step):
        def recorded(*a):
            out = step(*a)
            needs.append(out[13])
            return out
        return recorded

    # a growth makes a new step: wrap each as it is made
    make_step = eng._make_step

    def make_and_wrap():
        make_step()
        eng._step = recording(eng._step)
    eng._make_step = make_and_wrap
    eng._step = recording(eng._step)

    t0 = time.time()
    res = eng.run(max_depth=depth, log=lambda s: print(
        f"[{time.time() - t0:7.1f}] {s}", file=sys.stderr, flush=True))
    assert res.ok and res.error == f"depth limit {depth} reached", res.error
    names = eng.kern.action_names
    need = np.max([np.asarray(n) for n in needs], axis=(0, 1)).astype(int)
    tile = eng.tile
    static = [static_cap(tile, tile * eng.kern._lane_count(n))
              for n in names]
    over_static = {n: int(x) for n, x, s in zip(names, need, static)
                   if x > s}
    counters, gauges = res.metrics["counters"], res.metrics["gauges"]
    # held to the oracle, as deep as it goes
    levels = [int(x) for x in eng.level_sizes]
    assert levels == oracle["level_sizes"][:depth + 1], levels
    through = oracle.get("through")
    want = (through[depth - 1] if through else oracle
            if oracle.get("complete_through_depth") == depth else None)
    checked = []
    if want:
        assert gauges["action_expansions"] == want["action_expansions"]
        checked.append("action_expansions")
        for name, how in getattr(eng.kern, "COMMIT_STATS", ()):
            got = (counters if how == "sum" else gauges).get(name, 0)
            if eng._stat_fn and name in want.get("committed", {}):
                assert got == want["committed"][name], (name, got)
                checked.append(name)
    print(json.dumps({
        "backend": jax.default_backend(), "devices": len(devices),
        "engine": kw, "depth": depth, "distinct": res.distinct_states,
        "seconds": round(time.time() - t0, 1),
        "need_seen": dict(zip(names, (int(x) for x in need))),
        "static_caps": dict(zip(names, static)),
        "caps_at_end": dict(zip(names, eng._caps())),
        "over_static": over_static, "bucket_cap_at_end": eng.bucket_cap,
        "grows": int(counters.get("grows", 0)),
        "grown": {k: v for k, v in counters.items()
                  if k.startswith("grow_")},
        "shard_skew": gauges.get("shard_skew"),
        "equal_to_oracle": ["level_sizes"] + checked,
        "level_elapsed_s": [row["elapsed_s"]
                            for row in res.metrics["levels"]]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
