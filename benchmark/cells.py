"""Cells, configurations, traffic mixes and plug-in modules, found by
the names BENCHMARK.json gives them.

A later PR adds a cell by adding files and BENCHMARK.json entries, and
edits nothing here:

    workloads[].config   -> configs/<config>.json   (BENCHMARK.json configs[].file)
    workloads[].traffic  -> traffic/<traffic>.json  (its "kind" names the module)
    traffic kind         -> traffic_kinds/<kind>.py
    per_layer[].name     -> layer_metrics/<name>.py
"""

import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load_json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def benchmark_doc():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_plugin(folder, name):
    """The module benchmark/<folder>/<name>.py, loaded by path: names
    keep the dots and hyphens BENCHMARK.json gives them."""
    path = os.path.join(BENCH, folder, name + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"benchmark: no {folder}/{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{folder}_{name}".replace("-", "_").replace(".", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of BENCHMARK.json's workloads with its files read."""

    def __init__(self, workload, rehearse=False):
        doc = benchmark_doc()
        by_name = {w["name"]: w for w in doc["workloads"]}
        if workload not in by_name:
            raise SystemExit(
                f"benchmark: no workload {workload!r} in BENCHMARK.json "
                f"(have {sorted(by_name)})")
        self.doc = doc
        self.entry = by_name[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        self.traffic = load_json("traffic", self.entry["traffic"] + ".json")
        config = self.entry["config"]
        if rehearse and self.traffic.get("rehearse_config"):
            config = self.traffic["rehearse_config"]
        files = {c["name"]: c["file"] for c in doc["configs"]}
        with open(os.path.join(ROOT, files[config])) as f:
            self.config = json.load(f)
        # the depth a traced slice ends at: run.py sets it in a
        # --trace 1 run from the configuration's `assumed.trace_depth`;
        # None in every timed run and for a configuration without one
        self.trace_depth = None

    def path(self, rel):
        """A file the configuration names, relative to benchmark/."""
        return os.path.join(BENCH, rel)

    def metrics_for(self, section):
        """The section's metrics this cell reports: those with no
        `workloads` key, and those whose key lists the cell."""
        return [m for m in self.doc[section]
                if "workloads" not in m or self.name in m["workloads"]]

    def oracle_levels(self):
        spec = self.config["oracle"]["levels"]
        sizes = load_json(spec["file"])[spec["key"]]
        return [int(x) for x in sizes[:spec["complete_through_depth"] + 1]]
