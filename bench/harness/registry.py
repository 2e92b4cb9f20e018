"""Finds a cell's configuration, traffic mix, driver, generator and
metrics by name.

A configuration is ``bench/configs/<name>.json`` and names its instance
generator (``"gen"``: ``bench/gens/<gen>.py``, with ``instance(config)``);
a traffic mix is ``bench/traffic/<name>.json`` and names its driver
(``"driver"``: ``bench/drivers/<driver>.py``, with a class ``Driver``);
a metric is ``bench/metrics/<name>.py`` (with ``read(ctx)``, returning a
number or None).  A cell, a traffic kind, a generator or a metric is
added by adding files and `BENCHMARK.json` entries only.
"""

from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str) -> dict:
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def traffic(name: str) -> dict:
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def _module(kind: str, name: str):
    """The module ``bench/<kind>/<name>.py`` (names may hold dots)."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no {kind} file {path}")
    key = f"bench_{kind}_{name}"
    if key not in sys.modules:  # dataclasses look their module up there
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
    return sys.modules[key]


def metric(name: str):
    """The reader module of one metric."""
    return _module("metrics", name)


def driver(name: str):
    """The driver class of one traffic kind."""
    return _module("drivers", name).Driver


def generator(name: str):
    """The instance generator module named by a configuration."""
    return _module("gens", name)


def cell_metrics(bench: dict, cell: str, section: str) -> list[dict]:
    """Entries of ``section`` (end_to_end or per_layer) that this cell
    reports: those listing it, and those without a ``workloads`` key."""
    return [m for m in bench[section]
            if cell in m.get("workloads", [cell])]
