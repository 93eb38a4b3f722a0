"""Finds every piece of a cell by its name.

``BENCHMARK.json`` at the checkout root names the cells, configurations
and metrics.  Each piece lives in a file of its own, so a later change
adds a cell by adding files and entries, never by editing a file:

* a configuration: the ``file`` its entry names (``chipbench/configs/``);
* a traffic mix: ``chipbench/traffic/<traffic>.json``;
* the limits that decide a cell's ``correct``:
  ``chipbench/limits/<workload>.json``;
* a per-layer metric: its reader ``chipbench/metrics/<metric>.py``, a
  module with ``read(run) -> float | None``.
"""
from __future__ import annotations

import importlib.util
import json
import os
from typing import Any, List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = "chipbench"


class CatalogError(Exception):
    pass


def _load_json(path: str) -> Any:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise CatalogError(f"missing file {path}") from None


def benchmark(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def _by_name(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise CatalogError(f"no {what} named {name!r} in BENCHMARK.json; "
                       f"known: {[e['name'] for e in entries]}")


def workload(bench: dict, name: str) -> dict:
    return _by_name(bench["workloads"], name, "workload")


def config(bench: dict, name: str, root: str = ROOT) -> dict:
    entry = _by_name(bench["configs"], name, "configuration")
    return _load_json(os.path.join(root, entry["file"]))


def traffic(name: str, root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, PKG, "traffic", f"{name}.json"))


def limits(workload_name: str, root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, PKG, "limits", f"{workload_name}.json"))


def _applies(metric: dict, workload_name: str, reported: List[str]) -> bool:
    if "workloads" in metric:
        return workload_name in metric["workloads"]
    return metric.get("moves") is None or metric["moves"] in reported


def end_to_end(bench: dict, workload_name: str) -> List[dict]:
    """The end-to-end metrics this cell reports."""
    return [m for m in bench["end_to_end"]
            if "workloads" not in m or workload_name in m["workloads"]]


def per_layer(bench: dict, workload_name: str) -> List[dict]:
    """The per-layer metrics this cell reports: those that list it, and
    those without a list whose end-to-end metric it reports."""
    reported = [m["name"] for m in end_to_end(bench, workload_name)]
    return [m for m in bench["per_layer"]
            if _applies(m, workload_name, reported)]


def metric_reader(name: str, root: str = ROOT):
    """The ``read`` function of ``chipbench/metrics/<name>.py``."""
    path = os.path.join(root, PKG, "metrics", f"{name}.py")
    if not os.path.exists(path):
        raise CatalogError(f"no reader for metric {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"{PKG}.metrics.{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
