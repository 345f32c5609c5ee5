"""Find a cell's files by the names in `BENCHMARK.json`.

A cell (an entry of `workloads`) names a configuration and a traffic
mix.  Everything that belongs to one of them, or to one per-layer
metric, sits in a file of its own:

    bench/configs/<config>.json      sizes, precision, source, cuts,
                                     and the family
    bench/families/<family>.py       named by the configuration: its
                                     weights, parameter tree, decode
                                     work and reference
    bench/reference/<module>.py      named by the family: the plain
                                     reference that judges `correct`
    bench/traffic/<traffic>.json     generator, its parameters, engine
    bench/generators/<name>.py       named by the traffic file
    bench/metrics/<metric>.py        one reader per per-layer metric
    bench/limits/<cell>.json         the limit of each compared number

so a cell, a configuration, an architecture or a metric is added by
adding files and entries, and no file here changes.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
from typing import Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
FAMILIES = os.path.join(HERE, "families")
REFERENCES = os.path.join(HERE, "reference")


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
    """The module of the file at `path`, loaded once: every caller of a
    run shares one module object, and its compiled functions."""
    mod = sys.modules.get(name)
    if mod is not None and getattr(mod, "__file__", None) == path:
        return mod
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    sys.modules[name] = mod
    return mod


def family(cfg: Dict):
    """The module of the configuration's model family,
    `bench/families/<family>.py` (the contract is in
    `families/dense_gqa.py`).  A configuration that names none, or a
    family with no file, is an error."""
    name = cfg.get("family")
    if not name:
        raise KeyError("the configuration names no family")
    path = os.path.join(FAMILIES, f"{name}.py")
    if not os.path.isfile(path):
        raise KeyError(f"no family {name!r}: no file {path}")
    return _module(path, f"bench_family_{name}")


def reference(fam):
    """The plain reference that the family names (`fam.REFERENCE`)."""
    return _module(os.path.join(REFERENCES, f"{fam.REFERENCE}.py"),
                   f"bench_reference_{fam.REFERENCE}")


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
