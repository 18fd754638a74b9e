"""Diagnose the CP06 device/interpreter invariant divergence hit by
recovery_fixpoints (device flags a violation after
ReceiveNewCheckpointMsg at parent gid ~1446; interpreter accepts).

Mended since (`CP06Kernel._op_of`, CP06:1219-1222;
scripts/recovery_fixpoints.json: `engines_agree` true), so this script
now ends with "no divergence reproduced"; it needs the `.tla`.  Without
it the mended behaviour is held by tests/test_native_cp06.py: the plain
reference (benchmark/tools/checkpoint_recovery_reference.py) meets the
first such parent in the same level (6, numbers 705-1583 of its own
breadth-first order), its ReceiveNewCheckpointMsg successor is flagged
by a raw-log NoLogDivergence and accepted through OpOf by reference and
kernel alike, and that parent is a root of the crafted subtree."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "scripts"))
sys.argv = sys.argv[:1]

import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")

from pin_fixpoints import CP_CFG, load
from tpuvsr.core.values import TLAError
from tpuvsr.engine.device_bfs import DeviceBFS

spec = load("06-replica-recovery-cp/VR_REPLICA_RECOVERY_CP", CP_CFG,
            None)
eng = DeviceBFS(spec, tile_size=256)
err = None
try:
    eng.run(log=lambda m: print(" ", m, flush=True))
except TLAError as e:
    err = e
print("error:", err, flush=True)
if err is None:
    sys.exit("no divergence reproduced")

# the reported gid
import re
m = re.search(r"parent gid (\d+)", str(err))
gid = int(m.group(1))
aname = re.search(r"action (\w+)", str(err)).group(1)
print("parent gid", gid, "action", aname, flush=True)

parent = eng._trace(gid)[-1].state
codec, kern = eng.codec, eng.kern

# interpreter successors for this action + per-invariant verdicts
inv_names = list(spec.cfg.invariants)
print("invariants:", inv_names, flush=True)
interp_succs = [s for a, s in spec.successors(parent)
                if a.name == aname]
print(f"interp has {len(interp_succs)} {aname} successors; "
      f"interp verdicts:", flush=True)
for i, s in enumerate(interp_succs):
    print(f"  succ {i}: violated={spec.check_invariants(s)}",
          flush=True)

# kernel successors for this action with per-invariant device verdicts
dense = codec.encode(parent)
succs, enabled = kern.step_batch(
    {k: np.asarray(v)[None] for k, v in dense.items()})
enabled = np.asarray(enabled)[0]
succs = {k: np.asarray(v)[0] for k, v in succs.items()}
aid = kern.action_names.index(aname)
per_inv = {n: kern.invariant_fn([n]) for n in inv_names}
import jax.numpy as jnp
for lane in np.nonzero(enabled)[0]:
    if kern.lane_action[lane] != aid:
        continue
    d = {k: v[lane] for k, v in succs.items()}
    verdicts = {n: bool(np.asarray(fn(
        {k: jnp.asarray(v) for k, v in d.items()})))
        for n, fn in per_inv.items()}
    bad = [n for n, ok in verdicts.items() if not ok]
    st = codec.decode({k: v for k, v in d.items()
                       if not k.startswith("_")})
    ibad = spec.check_invariants(st)
    print(f"lane {lane}: device-bad={bad} interp-bad={ibad}",
          flush=True)
    if bad and not ibad:
        print("DIVERGENT lane; decoded successor state:", flush=True)
        for k in sorted(st):
            print("   ", k, "=", st[k], flush=True)
        break
