"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; each lives in a file of its
own (``bench/configs/<config>.json``, ``bench/traffic/<traffic>.json``), and
each per-layer metric has a reader ``bench/metrics/<metric>.py``.  Model
families, wire stages and kernels are found the same way
(``bench/models/<family>.py``, ``bench/wire/<stage>.py``,
``bench/kernels/<kernel>.py``), so that a new cell, configuration or metric
is new files and new entries and no edit.
"""
from __future__ import annotations

import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def benchmark(root=ROOT):
    return load_json(os.path.join(root, "BENCHMARK.json"))


def module(kind: str, name: str):
    """The module ``bench/<kind>/<name>.py``, or None when there is none."""
    path = os.path.join(BENCH, kind, name + ".py")
    if not os.path.isfile(path):
        return None
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def required_module(kind: str, name: str):
    mod = module(kind, name)
    if mod is None:
        raise FileNotFoundError(f"no bench/{kind}/{name}.py")
    return mod


def resolve(workload: str, root=ROOT) -> dict:
    """Everything a run of ``workload`` needs, read from files."""
    bench = benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(os.path.join(root, configs[cell["config"]]["file"]))
    traffic = load_json(os.path.join(BENCH, "traffic",
                                     cell["traffic"] + ".json"))
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    per_layer = [m for m in bench["per_layer"]
                 if workload in m.get("workloads", [workload])]
    return dict(name=workload, chips=cell["chips"], config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer,
                seconds=bench["run_seconds"])
