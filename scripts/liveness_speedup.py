"""Streamed-vs-two-pass-vs-interp behavior-graph A/B (ISSUE 15).

Measures the three graph-construction paths on the A01 liveness
ladder pins — the streamed single pass (edges flowing out of the
fused commit, ``DeviceGraph(mode="stream")``), the historical
two-pass retained-levels + re-expansion body (``mode="two-pass"``)
and the interpreter reference — and checks the bit-identity contract
between them (identical CSR modulo edge order within a source's
segment, identical verdicts).

Ladder (the v2t1 ladder, largest pin = BENCH_r05's `i01-v2t1`
bottleneck config): |Values|=1/timer=0 -> |Values|=1/timer=1 ->
|Values|=2/timer=1.  Pass ``--pin N`` to run only ladder rung N,
``--skip-interp`` to drop the interpreter leg (it is the slow one),
``--skip-two-pass`` to drop the re-expansion leg, ``--stub`` to run
the reference-free stub-harness proxy (the tier-1 acceptance proxy
for ``graph_overhead_ratio``).

Headline keys (scripts/compare_bench.py's ``gate_liveness`` gates on
them; the repo's benchmark is
``python3 benchmark/run.py --workload W --seed N --seconds S --trace 0|1``,
which has no liveness cell yet):
``mode``, ``edges``, ``edges_per_s``, ``graph_overhead_ratio``,
``check_s``.

Writes scripts/liveness_speedup.json.

Usage: python scripts/liveness_speedup.py [--pin N] [--skip-interp]
       [--skip-two-pass] [--stub]
"""

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

STUB = "--stub" in sys.argv
if STUB:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

backend = jax.default_backend()

from tpuvsr.engine.device_liveness import DeviceGraph  # noqa: E402
from tpuvsr.engine.liveness import build_graph, liveness_check  # noqa: E402

REFERENCE = os.environ.get(
    "TPUVSR_REFERENCE", "/root/reference/vsr-revisited/paper")
PATH = f"{REFERENCE}/analysis/01-view-changes/VR_ASSUME_NEWVIEWCHANGE"

#: the v2t1 ladder: (|Values|, StartViewOnTimerLimit)
LADDER = [(1, 0), (1, 1), (2, 1)]

skip_interp = "--skip-interp" in sys.argv
skip_two_pass = "--skip-two-pass" in sys.argv
pin_only = None
if "--pin" in sys.argv:
    pin_only = int(sys.argv[sys.argv.index("--pin") + 1])


def _log(m):
    print(f"[liveness] {m}", flush=True)


def _ref_spec(values, timer, spec_formula=None):
    from tpuvsr.core.values import ModelValue
    from tpuvsr.engine.spec import SpecModel
    from tpuvsr.frontend.cfg import parse_cfg_file
    from tpuvsr.frontend.parser import parse_module_file
    mod = parse_module_file(f"{PATH}.tla")
    cfg = parse_cfg_file(f"{PATH}.cfg")
    cfg.constants["Values"] = frozenset(
        ModelValue(f"v{i + 1}") for i in range(values))
    cfg.constants["StartViewOnTimerLimit"] = timer
    if spec_formula:
        cfg.specification = spec_formula
    return SpecModel(mod, cfg)


def _graph_leg(make_graph, spec, label):
    t0 = time.time()
    g = make_graph()
    graph_s = round(time.time() - t0, 2)
    t0 = time.time()
    res = liveness_check(spec, graph=g)
    check_s = round(time.time() - t0, 2)
    leg = {"graph_s": graph_s, "check_s": check_s,
           "bfs_s": round(g.bfs_elapsed, 2),
           "graph_overhead_ratio": g.graph_overhead_ratio,
           "edges": int(g.csr[1].shape[0]),
           "edges_per_s": g.edges_per_s,
           "states": g.n,
           "verdict": {"ok": res.ok, "property": res.property_name}}
    _log(f"{label}: {g.n} states, {leg['edges']} edges, graph "
         f"{graph_s}s (overhead {g.graph_overhead_ratio}), check "
         f"{check_s}s -> ok={res.ok}")
    return g, leg


def run_pin(values, timer, spec_builder, graph_kw):
    pin = {"config": f"|Values|={values}, timer={timer}"}
    spec = spec_builder()
    gs, pin["streamed"] = _graph_leg(
        lambda: DeviceGraph(spec, mode="stream", **graph_kw),
        spec, "streamed")
    if not skip_two_pass:
        from tpuvsr.testing import canon_csr
        gt, pin["two_pass"] = _graph_leg(
            lambda: DeviceGraph(spec, mode="two-pass", **graph_kw),
            spec, "two-pass")
        pin["csr_identical"] = canon_csr(gs) == canon_csr(gt)
        pin["verdicts_match"] = (pin["streamed"]["verdict"]
                                 == pin["two_pass"]["verdict"])
    if not skip_interp:
        t0 = time.time()
        graph = build_graph(spec)
        pin["interp_graph_s"] = round(time.time() - t0, 1)
        ires = liveness_check(spec, graph=graph)
        pin["interp_verdict"] = {"ok": ires.ok,
                                 "property": ires.property_name}
        pin["interp_edges"] = sum(len(e) for e in graph[1])
        pin["interp_match"] = (
            pin["interp_verdict"] == pin["streamed"]["verdict"]
            and pin["interp_edges"] == pin["streamed"]["edges"])
        pin["graph_speedup_vs_interp"] = round(
            pin["interp_graph_s"]
            / max(pin["streamed"]["graph_s"], 1e-9), 1)
    return pin


# mode uses the DeviceGraph vocabulary ("stream" / "two-pass") so
# gate_liveness compares like with like across doc forms
out = {"backend": backend, "mode": "stream", "pins": []}

if STUB:
    # reference-free proxy: the Ticker liveness fixture through the
    # REAL engines (the tier-1 graph_overhead_ratio acceptance proxy)
    from tpuvsr.testing import stub_ticker_factory, ticker_spec
    out["config"] = "stub Ticker proxy (no reference mount)"
    pin = run_pin(
        0, 0, lambda: ticker_spec(modulus=12),
        dict(tile_size=4, chunk_tiles=2, hash_mode="full",
             fpset_capacity=1 << 8, next_capacity=1 << 6,
             model_factory=stub_ticker_factory(modulus=12)))
    pin["config"] = "stub Ticker, modulus=12"
    out["pins"].append(pin)
else:
    for i, (values, timer) in enumerate(LADDER):
        if pin_only is not None and i != pin_only:
            continue
        out["pins"].append(run_pin(
            values, timer,
            lambda v=values, t=timer: _ref_spec(v, t),
            dict(tile_size=128)))

# headline = the largest pin that ran
if out["pins"]:
    head = out["pins"][-1]
    out["edges"] = head["streamed"]["edges"]
    out["edges_per_s"] = head["streamed"]["edges_per_s"]
    out["graph_overhead_ratio"] = \
        head["streamed"]["graph_overhead_ratio"]
    out["check_s"] = head["streamed"]["check_s"]
    out["csr_identical"] = head.get("csr_identical")

with open(os.path.join(REPO, "scripts", "liveness_speedup.json"),
          "w") as f:
    json.dump(out, f, indent=1)
print(json.dumps(out))
