"""Defect-config BFS capacity analysis (SURVEY.md §7.3.8; VERDICT r2
missing #6): measure bytes/state and FPSet cost from the actual dense
layout, project HBM needs at defect scale, and write CAPACITY.md.

Usage: python scripts/capacity.py
"""

import json
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tpuvsr.engine.spec import SpecModel
from tpuvsr.frontend.cfg import parse_cfg_file
from tpuvsr.frontend.parser import parse_module_file
from tpuvsr.models.vsr import VSRCodec

REFERENCE = os.environ.get(
    "TPUVSR_REFERENCE", "/root/reference/vsr-revisited/paper")

mod = parse_module_file(f"{REFERENCE}/VSR.tla")
cfg = parse_cfg_file(f"{REPO}/examples/VSR_defect.cfg")
spec = SpecModel(mod, cfg)


def state_bytes(max_msgs):
    codec = VSRCodec(spec.ev.constants, max_msgs=max_msgs)
    z = codec.zero_state()
    per = {k: int(np.prod(np.shape(v)) or 1) * 4 for k, v in z.items()}
    # packed bit-planed row (ISSUE 9): the at-rest/wire format the
    # device engines default to, sized by the widths-pass ranges
    from tpuvsr.analysis.passes.widths import derive_ranges_from
    from tpuvsr.engine.pack import build_pack_spec
    pk = build_pack_spec(
        codec, ranges=derive_ranges_from(spec.ev.constants, "VSR"))
    return sum(per.values()), per, codec.shape, pk


HBM_PER_CHIP = 16 << 30          # v5e
CHIPS = 8
FP_SLOT_BYTES = 20               # [cap, 5] uint32 FPSet slot
LOAD = 0.5                       # max healthy FPSet load factor

rows = []
for M in (48, 64, 96, 128):
    sb, per, shape, pk = state_bytes(M)
    rows.append((M, sb, pk.packed_bytes, pk.ratio))

sb48, per48, shape48, pk48 = state_bytes(48)
pb48 = pk48.packed_bytes

fp_cap_total = int(CHIPS * HBM_PER_CHIP * 0.5 / FP_SLOT_BYTES * LOAD)

out = f"""# CAPACITY — defect-config BFS sizing (VSR.tla, R=3, |Values|=3, timer=3)

Derived from the actual dense layout (`models/vsr.py` `zero_state`)
for the defect fixture (`examples/VSR_defect.cfg`); reference baseline:
multiple days + >=500 GB disk on a large CPU box
(/root/reference/README.md:20).

## Bytes per state: dense planes vs the packed bit-planed row

(dense = int32 struct-of-arrays, one word per field; packed = the
`engine/pack.py` interchange format the device engines default to —
per-field bit budgets from the speclint widths pass, `-pack off`
restores dense)

| MAX_MSGS | dense bytes/state | packed bytes/state | ratio |
|---|---|---|---|
""" + "\n".join(f"| {m} | {b:,} | {p:,} | {r:.2f}x |"
                for m, b, p, r in rows) + f"""

Top contributors at MAX_MSGS=48 (bytes):
""" + "\n".join(f"- `{k}`: {v:,}"
                for k, v in sorted(per48.items(), key=lambda kv: -kv[1])[:6])
out += f"""

Shapes: R={shape48.R}, V={shape48.V}, MAX_OPS={shape48.MAX_OPS},
MAX_VIEW={shape48.MAX_VIEW}.

## HBM budget on a v5e-8 (16 GB/chip x 8)

- **Fingerprints**: 20 B/slot (claim word + 128-bit fp).  At <= {LOAD:.0%}
  load with half of HBM given to the FPSet, the 8-chip mesh holds
  ~**{fp_cap_total / 1e9:.1f} B distinct states** — fingerprint capacity is
  NOT the binding constraint at defect scale (TLC burned 500 GB of disk
  largely on queue/state storage, not fingerprints).
- **Frontier**: the binding constraint — now measured at the PACKED
  row size ({pb48} B/state at MAX_MSGS=48, {pk48.ratio:.1f}x denser
  than the {sb48 / 1024:.1f} KiB dense row): one chip's spare ~6 GB
  holds ~**{6e9 / pb48 / 1e6:.1f} M frontier states**
  ({CHIPS * 6e9 / pb48 / 1e6:.0f} M mesh-wide) vs
  {6e9 / sb48 / 1e6:.1f} M dense; the same factor multiplies paged
  spill bandwidth and the sharded exchange.  Remaining mitigations:
  1. **BUILT (r4)**: `engine/paged_bfs.py` pages the frontier through
     host RAM — with packing the 125 GB host holds ~{125e9 / pb48 / 1e6:.0f} M
     states ({125e9 / sb48 / 1e6:.0f} M dense);
  2. bag-slot compression, RE-SCOPED: packing already shrinks the log
     planes ~16x (an entry packs to 8 bits vs 128 dense), so a
     content-addressed side table of distinct logs now buys only the
     residual duplicate-content factor, not the raw
     {per48['m_log'] / sb48:.0%} the dense m_log plane suggested —
     it drops below the DCN tier in priority;
  3. sharding the frontier over more hosts (DCN tier).
- **Trace pointers**: 10 B/state on host; 1e9 states = 10 GB host RAM
  (the 125 GB host holds ~12 B states).

## Measured throughput anchors

(From `scripts/hunt_result.json` where available — a CPU-backend
anchor.  No device throughput has been measured: the first chip run,
`chip_smoke.py`, checks counts, not rates; a benchmark is ROADMAP S0.)
"""

hunt_path = os.path.join(REPO, "scripts", "hunt_result.json")
if os.path.exists(hunt_path):
    with open(hunt_path) as f:
        h = json.load(f)
    out += (f"- guided-simulation time-to-violation on the defect "
            f"fixture: {h.get('time_to_violation_s')} s "
            f"({h.get('backend')}, {h.get('walkers')} walkers, "
            f"seed {h.get('seed')}).\n")

out += """
## Projection to the <1 h north star (v5e-8)

The exhaustive-BFS route needs ~1e9-1e10 distinct states (unmeasured —
TLC's 500 GB disk / multi-day run bounds it loosely from above) at
>=3 M distinct/s sustained to finish inside an hour; fingerprint
capacity supports it, frontier paging is the engineering risk.  The
simulation route (the reference's own recommendation, README:22) needs
no FPSet at all and parallelizes perfectly: the guided
importance-splitting hunt already reproduces the violation on CPU (see
anchor above when present); on a v5e-8 the same walker program scales
~linearly with lane count x clock, putting time-to-violation well
under the hour target.
"""

with open(os.path.join(REPO, "CAPACITY.md"), "w") as f:
    f.write(out)
print(out)
