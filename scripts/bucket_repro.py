"""Repro: sharded-engine level divergence when exchange buckets
overflow/grow mid-run (found via the depth-14 multihost artifact:
518,843 'distinct' > the whole 43,941-state space, generated < distinct
— dedup collapse beyond the level where bucket overflows begin).
Forces tiny buckets on the flagship small config and compares exact
level sizes to the interpreter."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_f = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in _f:
    os.environ["XLA_FLAGS"] = (
        _f + " --xla_force_host_platform_device_count=8").strip()

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests"))

import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
from jax.sharding import Mesh

from conftest import vsr_spec, interp_level_sizes
from tpuvsr.parallel.sharded_bfs import ShardedBFS

depth = int(sys.argv[1]) if len(sys.argv) > 1 else 10
bucket = int(sys.argv[2]) if len(sys.argv) > 2 else 128

spec = vsr_spec()
want = interp_level_sizes(spec, depth)
print("interp levels:", want, flush=True)
eng = ShardedBFS(spec, Mesh(np.array(jax.devices()[:8]), ("d",)),
                 tile=64, bucket_cap=bucket,
                 next_capacity=1 << 14, fpset_capacity=1 << 16)
res = eng.run(max_depth=depth,
              log=lambda m: print(" ", m, flush=True))
print("sharded levels:", eng.level_sizes, flush=True)
print("match:", eng.level_sizes == want,
      "distinct:", res.distinct_states,
      "gen:", res.states_generated, flush=True)
