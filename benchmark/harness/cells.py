"""Finds the data and code files of a cell by name.

``workloads/<cell>.json`` names its configuration, chips, driver and
traffic; ``configs/<config>.json`` names its family (builder
``configs/<family>.py``, plain reference ``references/<family>.py``);
``layer_metrics/<metric>.json`` names its reader (``readers/<reader>.py``).
A later PR adds files and touches none that is here.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_DIR = os.path.dirname(BENCH_DIR)


def load_json(*parts: str) -> dict:
    with open(os.path.join(BENCH_DIR, *parts), encoding="utf-8") as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """Import ``<kind>/<name>.py`` of the benchmark as ``bench_<kind>_<name>``."""
    modname = f"bench_{kind}_{name}"
    if modname in sys.modules:
        return sys.modules[modname]
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(modname, path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


def load_workload(name: str) -> dict:
    w = load_json("workloads", name + ".json")
    if w["name"] != name:
        raise ValueError(f"workloads/{name}.json names itself {w['name']!r}")
    return w


def load_config(name: str) -> dict:
    c = load_json("configs", name + ".json")
    if c["name"] != name:
        raise ValueError(f"configs/{name}.json names itself {c['name']!r}")
    return c


def layer_metrics_for(workload: dict) -> list:
    """The per-layer metric files read in this cell: those that move one
    of the cell's end-to-end metrics, unless the file lists its cells."""
    reported = set(workload["end_to_end"])
    out = []
    folder = os.path.join(BENCH_DIR, "layer_metrics")
    for fn in sorted(os.listdir(folder)):
        if not fn.endswith(".json"):
            continue
        m = load_json("layer_metrics", fn)
        cells = m.get("workloads")
        if cells is not None and workload["name"] not in cells:
            continue
        if m["moves"] in reported:
            out.append(m)
    return out
