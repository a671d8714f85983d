"""Find a cell's files by the names in BENCHMARK.json.

A cell `<config>.<traffic>` names a configuration file
`bench/configs/<config>.json` and a traffic mix `bench/traffic/<traffic>.json`;
each per-layer metric `<name>` is read by `bench/metrics/<name>.py`. Adding a
cell, a mix or a metric is adding files and entries, never editing one.
"""
from __future__ import annotations

import importlib.util
import json
import os
from typing import Callable, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        return json.load(fh)


def find_cell(name: str, root: str = ROOT) -> dict:
    """-> {"workload", "config", "traffic", "end_to_end", "per_layer"} for
    the cell `name`: its entry, its two data files and the metrics it
    reports (a metric with a `workloads` list only in those cells)."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(os.path.join(root, configs[cell["config"]]["file"])) as fh:
        config = json.load(fh)
    with open(os.path.join(root, "bench", "traffic",
                           cell["traffic"] + ".json")) as fh:
        traffic = json.load(fh)

    def mine(metrics: List[dict]) -> List[dict]:
        return [m for m in metrics if name in m.get("workloads", [name])]

    return {"workload": cell, "config": config, "traffic": traffic,
            "end_to_end": mine(bench["end_to_end"]),
            "per_layer": mine(bench["per_layer"])}


def metric_reader(name: str, root: str = ROOT) -> Callable[[dict], object]:
    """The `read(ctx)` function of `bench/metrics/<name>.py`."""
    path = os.path.join(root, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_json(*parts: str) -> Dict:
    with open(os.path.join(BENCH_DIR, *parts)) as fh:
        return json.load(fh)
