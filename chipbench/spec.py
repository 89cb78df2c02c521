"""BENCHMARK.json and the files it names.

Everything that belongs to one configuration, traffic mix, metric or cell
sits in a file of its own, found by its name:

    configs/<config>.json     the configuration's sizes, as run
    configs/<config>.py       its weights from the seed, the program's
                              export of them, and its plain reference
    traffic/<traffic>.json    the mix's parameters, read by traffic.py
    metrics/<metric>.py       the metric's reducer: reduce(ctx) -> number
                              or None (nothing to read in this cell)
    limits/<workload>.json    the cell's correctness numbers and limits

so a new configuration, mix, metric or cell is new files and entries in
BENCHMARK.json, never an edit of the harness.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_benchmark() -> Dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _path(kind: str, name: str, ext: str) -> str:
    path = os.path.join(HERE, kind, name + ext)
    if not os.path.isfile(path):
        raise FileNotFoundError(f"{kind} {name!r}: no file {path}")
    return path


def _json(kind: str, name: str) -> Dict:
    with open(_path(kind, name, ".json")) as f:
        return json.load(f)


def _module(kind: str, name: str):
    path = _path(kind, name, ".py")
    mod_name = f"chipbench_{kind}_" + name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def workload(bench: Dict, name: str) -> Dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in bench['workloads']]}")


def config(name: str) -> Dict:
    return _json("configs", name)


def config_module(name: str):
    return _module("configs", name)


def traffic(name: str) -> Dict:
    return _json("traffic", name)


def limits(workload_name: str) -> Dict:
    return _json("limits", workload_name)


def reducer(metric: str):
    return _module("metrics", metric)


def metrics_for(bench: Dict, workload_name: str, trace: bool) -> List[Dict]:
    """The metrics a run of the cell reports: its end-to-end metrics with
    ``trace`` off, its per-layer metrics with it on. A metric without a
    ``workloads`` key belongs to every cell."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if workload_name in m.get("workloads", [workload_name])]
