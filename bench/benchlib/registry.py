"""Finds a cell's pieces by name, each in a file of its own.

    BENCHMARK.json                 the cells, the metrics, the bounds
    bench/configs/<config>.json    a deployment's sizes and guarantees
    bench/limits/<config>.json     the limits that decide ``correct``
    bench/traffic/<mix>.json       a traffic mix's parameters
    bench/signals/<model>.py       a signal model: ``sources(rng, N, n, T, params)``
    bench/metrics/<metric>.py      a per-layer metric's reader: ``read(run)``

A new cell, configuration, mix or per-layer metric is a new file plus an
entry in ``BENCHMARK.json``; no file that is already here changes.
"""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path
from typing import Dict, List

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _checked(name: str) -> str:
    if not NAME.match(name):
        raise ValueError(f"bad name {name!r}")
    return name


def _json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path) -> Dict:
    return _json(Path(root) / "BENCHMARK.json")


def workload(root: Path, name: str) -> Dict:
    for wl in benchmark(root)["workloads"]:
        if wl["name"] == name:
            return wl
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(root: Path, name: str) -> Dict:
    return _json(Path(root) / "bench" / "configs" / f"{_checked(name)}.json")


def limits(root: Path, name: str) -> Dict:
    return _json(Path(root) / "bench" / "limits" / f"{_checked(name)}.json")


def traffic(root: Path, name: str) -> Dict:
    return _json(Path(root) / "bench" / "traffic" / f"{_checked(name)}.json")


def metrics_of(root: Path, wl_name: str, section: str) -> List[Dict]:
    """The ``end_to_end`` or ``per_layer`` metrics that cell ``wl_name``
    reports: those without a ``workloads`` key, and those that list it."""
    return [
        m for m in benchmark(root)[section]
        if wl_name in m.get("workloads", [wl_name])
    ]


def _module(root: Path, kind: str, name: str):
    path = Path(root) / "bench" / kind / f"{_checked(name)}.py"
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(root: Path, metric: str):
    """The ``read(run)`` function of ``bench/metrics/<metric>.py``."""
    return _module(root, "metrics", metric).read


def signal_model(root: Path, model: str):
    """The ``sources(rng, N, n, T, params)`` function of
    ``bench/signals/<model>.py``."""
    return _module(root, "signals", model).sources
