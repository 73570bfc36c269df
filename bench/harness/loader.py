"""Finds a cell's pieces by the names in BENCHMARK.json: configuration
files, traffic mixes, limits, entry drivers and per-layer metric readers.
Adding a cell, a mix or a metric is adding files; nothing here changes."""
from __future__ import annotations

import importlib.util
import json
import os

from bench.harness.manifest import BENCH_DIR, ROOT  # noqa: F401

TRAFFIC_DIR = os.path.join(BENCH_DIR, "traffic")
LIMITS_DIR = os.path.join(BENCH_DIR, "limits")
DRIVERS_DIR = os.path.join(BENCH_DIR, "drivers")
METRICS_DIR = os.path.join(BENCH_DIR, "metrics")


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def config(entry: dict) -> dict:
    """The configuration file named by a ``configs`` entry."""
    return _json(os.path.join(ROOT, entry["file"]))


def traffic(name: str) -> dict:
    return _json(os.path.join(TRAFFIC_DIR, f"{name}.json"))


def limits(workload: str) -> dict:
    """Correctness limits of one cell (``limits/<workload>.json``)."""
    return _json(os.path.join(LIMITS_DIR, f"{workload}.json"))


def _module(path: str, name: str):
    if not os.path.exists(path):
        raise SystemExit(f"no file {os.path.relpath(path, ROOT)} for {name!r}")
    spec = importlib.util.spec_from_file_location(
        "bench_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(name: str):
    """Entry driver module ``drivers/<name>.py`` (one per kind of entry)."""
    return _module(os.path.join(DRIVERS_DIR, f"{name}.py"), name)


def metric_reader(name: str):
    """Per-layer metric reader ``metrics/<name>.py``; its ``read(r)``
    returns the value, or None where the run gave it nothing to read."""
    return _module(os.path.join(METRICS_DIR, f"{name}.py"), name)
