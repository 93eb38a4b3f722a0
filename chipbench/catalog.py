"""Finds every piece of a cell by its name.

``BENCHMARK.json`` at the checkout root names the cells, configurations
and metrics.  Each piece lives in a file of its own, so a later change
adds a cell by adding files and entries, never by editing a file:

* a configuration: the ``file`` its entry names (``chipbench/configs/``);
* a traffic mix: ``chipbench/traffic/<traffic>.json``;
* the limits that decide a cell's ``correct``:
  ``chipbench/limits/<workload>.json``;
* a per-layer metric: its reader ``chipbench/metrics/<metric>.py``, a
  module with ``read(run) -> float | None``;
* a model family: ``chipbench/families/<family>.py``, named by the
  ``family`` key of a configuration file.  It owns everything the
  harness does not know of a model (``family`` below says what).
"""
from __future__ import annotations

import importlib.util
import json
import os
from typing import Any, Dict, List

from chipbench.harness import BenchError

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = "chipbench"


class CatalogError(BenchError):
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


_MODULES: Dict[str, Any] = {}


def _module(path: str, name: str, what: str):
    """The module at ``path``, loaded once by path."""
    if path not in _MODULES:
        if not os.path.exists(path):
            raise CatalogError(f"no {what} at {path}")
        spec = importlib.util.spec_from_file_location(
            name.replace("-", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


def metric_reader(name: str, root: str = ROOT):
    """The ``read`` function of ``chipbench/metrics/<name>.py``."""
    path = os.path.join(root, PKG, "metrics", f"{name}.py")
    return _module(path, f"{PKG}.metrics.{name.replace('.', '_')}",
                   f"reader for metric {name!r}").read


def family(cfg: dict, root: str = ROOT):
    """The family module of configuration ``cfg``:
    ``chipbench/families/<cfg["family"]>.py``.  It provides

    * ``program_config(cfg)``: the program's configuration for the file,
      with the family's own guards;
    * ``CELLS``: ``{traffic mode: entry(ctx) -> result}``.  An entry makes
      the weights and inputs from ``ctx["seed"]``, builds and warms the
      program's compiled entry, sets ``ctx["setup_s"]``, ``ctx["plans"]``
      and ``ctx["memory_peak_bytes"]``, drives ``ctx["window"]`` and
      returns ``images``, ``window_s``, ``calls``, ``failed``, ``program``
      (what the check compares) and ``inputs`` (what the reference needs);
    * ``msda_calls(cfg, mode, plans)``: the step's ``work.MsdaCalls``;
    * ``flops_per_image(cfg, mode)``: model FLOPs of one image (or sample);
    * ``compare(mode, cfg, traffic, program, inputs, hooks)``: ``(numbers,
      readings)`` against the family's plain reference.
    """
    name = cfg.get("family")
    if not name:
        raise CatalogError(f"configuration {cfg.get('name')!r} names no "
                           f"family")
    path = os.path.join(root, PKG, "families", f"{name}.py")
    return _module(path, f"{PKG}.families.{name}", f"family {name!r}")
