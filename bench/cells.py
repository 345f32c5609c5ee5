"""Find a cell's files by the names in `BENCHMARK.json`.

A cell (an entry of `workloads`) names a configuration and a traffic
mix.  Everything that belongs to one of them, or to one per-layer
metric, sits in a file of its own:

    bench/configs/<config>.json      sizes, precision, source, cuts
    bench/traffic/<traffic>.json     generator, its parameters, engine
    bench/generators/<name>.py       named by the traffic file
    bench/metrics/<metric>.py        one reader per per-layer metric
    bench/limits/<cell>.json         the limit of each compared number

so a cell, a configuration or a metric is added by adding files and
entries, and no file here changes.
"""
from __future__ import annotations

import importlib.util
import json
import os
from typing import Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> Dict:
    return _json(os.path.join(ROOT, "BENCHMARK.json"))


def workload(name: str, bench: Dict = None) -> Dict:
    bench = bench or benchmark()
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str) -> Dict:
    return _json(os.path.join(HERE, "configs", f"{name}.json"))


def traffic(name: str) -> Dict:
    return _json(os.path.join(HERE, "traffic", f"{name}.json"))


def limits(cell: str) -> Dict:
    return _json(os.path.join(HERE, "limits", f"{cell}.json"))


def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def generator(name: str):
    return _module(os.path.join(HERE, "generators", f"{name}.py"),
                   f"bench_generator_{name}")


def metric_reader(name: str):
    return _module(os.path.join(HERE, "metrics", f"{name}.py"),
                   "bench_metric_" + name.replace(".", "_"))


def end_to_end(cell: str, bench: Dict = None) -> List[Dict]:
    """The end-to-end metrics this cell reports."""
    bench = bench or benchmark()
    return [m for m in bench["end_to_end"]
            if cell in m.get("workloads", [cell])]


def per_layer(cell: str, bench: Dict = None) -> List[Dict]:
    """The per-layer metrics this cell reports: those that list it, and
    those without a list whose end-to-end metric the cell reports."""
    bench = bench or benchmark()
    mine = {m["name"] for m in end_to_end(cell, bench)}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in mine)]
