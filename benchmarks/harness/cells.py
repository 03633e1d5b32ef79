"""Finds a cell's files by the names in ``BENCHMARK.json``.

A cell ``<config>.<traffic>`` is ``configs/<config>.json`` plus
``traffic/<traffic>.json``; a per-layer metric ``<name>`` is
``metrics/<name>.json``, which names a reader module ``readers/<reader>.py``;
a configuration names its runner ``runners/<runner>.py`` and its plain
reference ``reference/<reference>.py``. A later PR adds files and entries and
edits none.
"""

from __future__ import annotations

import importlib
import json
import os
from typing import Dict

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(ROOT)


def load_json(*parts: str) -> Dict:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def load_spec() -> Dict:
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_cell(spec: Dict, workload: str) -> Dict:
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"BENCHMARK.json has no workload {workload!r}; it has "
                       f"{sorted(cells)}")
    cell = dict(cells[workload])
    cell["config_file"] = load_json("configs", cell["config"] + ".json")
    cell["traffic_file"] = load_json("traffic", cell["traffic"] + ".json")
    return cell


def load_module(kind: str, name: str):
    """``runners``/``readers``/``reference`` module ``name``."""
    return importlib.import_module(f"{kind}.{name}")


def cache_dir(*parts: str) -> str:
    """A fixed directory inside the checkout for what a run leaves behind
    (data files, traces); listed in ``benchmarks/.gitignore``."""
    path = os.path.join(ROOT, ".cache", *parts)
    os.makedirs(path, exist_ok=True)
    return path
