"""Finding a cell's files by name: ``BENCHMARK.json`` names each cell's
configuration and traffic mix, and each lives in a file of its own
(``configs/<config>.json``, ``traffic/<traffic>.json``); each per-layer
metric is a reader ``metrics/<name>.py`` with a ``read(ctx)`` function; a
configuration's model family (its ``"family"`` key, ``"dense"`` where the
file has none) is a module ``families/<family>.py``.  Adding a cell, a
metric or a model family adds files and entries; nothing here changes.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import os
from types import ModuleType
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


@functools.lru_cache(maxsize=None)
def _load(path: str, modname: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _module(kind: str, name: str) -> ModuleType:
    """``<kind>/<name>.py``, loaded once a process."""
    return _load(os.path.join(HERE, kind, name + ".py"),
                 f"bench_{kind}_" + name.replace(".", "_").replace("-", "_"))


def reader(name: str) -> Callable[[dict], Optional[float]]:
    """``metrics/<name>.py``'s ``read``."""
    return _module("metrics", name).read


def family(name: str) -> ModuleType:
    """``families/<name>.py``: a model family's ``program_config``,
    ``shapes``, ``axis_sizes``, ``loss`` and ``train_flops``."""
    return _module("families", name)


def family_of(cfg: dict) -> ModuleType:
    """The family a configuration names (``"dense"`` where it names
    none)."""
    return family(cfg.get("family", "dense"))
