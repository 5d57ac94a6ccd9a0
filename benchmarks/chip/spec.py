"""Finding a cell's files by name: ``BENCHMARK.json`` names each cell's
configuration and traffic mix, and each lives in a file of its own
(``configs/<config>.json``, ``traffic/<traffic>.json``); each per-layer
metric is a reader ``metrics/<name>.py`` with a ``read(ctx)`` function.
Adding a cell or a metric adds files and entries; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
import os
from typing import Callable, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))


def repo_root() -> str:
    """The checkout: the directory that holds ``BENCHMARK.json``."""
    d = HERE
    while True:
        if os.path.isfile(os.path.join(d, "BENCHMARK.json")):
            return d
        parent = os.path.dirname(d)
        if parent == d:
            raise FileNotFoundError("no BENCHMARK.json above " + HERE)
        d = parent


def load_benchmark(root: Optional[str] = None) -> dict:
    with open(os.path.join(root or repo_root(), "BENCHMARK.json")) as f:
        return json.load(f)


def _json(kind: str, name: str) -> dict:
    path = os.path.join(HERE, kind, name + ".json")
    with open(path) as f:
        return json.load(f)


def config(name: str) -> dict:
    return _json("configs", name)


def traffic(name: str) -> dict:
    return _json("traffic", name)


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in bench['workloads']]}")


def cell_metrics(bench: dict, cell: str, section: str) -> List[dict]:
    """The metrics of ``section`` (end_to_end or per_layer) this cell
    reports: those without a ``workloads`` list, and those that list it."""
    return [m for m in bench[section]
            if "workloads" not in m or cell in m["workloads"]]


def reader(name: str) -> Callable[[dict], Optional[float]]:
    """``metrics/<name>.py``'s ``read``."""
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
