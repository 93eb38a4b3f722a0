"""The one writer behind every ``BENCH_*.json`` trajectory file.

Common schema (``"schema": 1``) shared by ``BENCH_sparsity.json``,
``BENCH_train.json`` and ``BENCH_resilience.json``:

    {
      "bench":        str,      # benchmark id ("sparsity_ablation", ...)
      "schema":       1,
      "config":       {...},    # geometry / run config the numbers depend on
      "note":         str,
      "results":      {...},    # numeric leaves — what bench_gate diffs
      "trajectory":   [...],    # optional per-step / per-point series
      "events":       [...],    # optional discrete-event log
      "gate":         [...],    # optional regression-gate rules (below)
      "history":      [...],    # appended by `bench_gate --update`
      "created_unix": float,
    }

``gate`` tells ``tools/bench_gate.py`` which ``results`` leaves are
comparable across machines and in which direction:

    {"pattern": "*.launches_per_call", "direction": "lower", "tolerance": 0.0}

``pattern`` is an fnmatch over the flattened dotted result key,
``direction`` is ``"lower"`` or ``"higher"`` (which way is better), and
``tolerance`` is the relative slack before a worse value counts as a
regression (0.0 = structural, must not move at all).  Leaves matched by
no rule are informational only — raw timings from different machines
never gate the build unless a rule says so.
"""
from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, List, Optional

SCHEMA_VERSION = 1


def repo_root() -> str:
    """The checkout root (this file lives at src/repro/obs/bench.py)."""
    here = os.path.abspath(__file__)
    return os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(here))))


def bench_path(name: str) -> str:
    """Canonical root-level path for trajectory ``name`` ("kernels", ...)."""
    return os.path.join(repo_root(), f"BENCH_{name}.json")


def gate_rule(pattern: str, direction: str, tolerance: float) -> Dict[str, Any]:
    if direction not in ("lower", "higher"):
        raise ValueError(f"direction {direction!r}: use 'lower' or 'higher'")
    return {"pattern": str(pattern), "direction": direction,
            "tolerance": float(tolerance)}


def write_bench(
    path: str,
    *,
    bench: str,
    results: Dict[str, Any],
    config: Optional[Dict[str, Any]] = None,
    note: str = "",
    trajectory: Optional[List[Any]] = None,
    events: Optional[List[Any]] = None,
    gate: Optional[List[Dict[str, Any]]] = None,
    created_unix: Optional[float] = None,
    extra: Optional[Dict[str, Any]] = None,
) -> str:
    """Atomic (tmp + rename) dump of one trajectory payload."""
    payload: Dict[str, Any] = {
        "bench": str(bench),
        "schema": SCHEMA_VERSION,
        "config": dict(config or {}),
        "note": str(note),
        "results": results,
        "created_unix": (time.time() if created_unix is None
                         else float(created_unix)),
    }
    if trajectory is not None:
        payload["trajectory"] = list(trajectory)
    if events is not None:
        payload["events"] = list(events)
    if gate is not None:
        payload["gate"] = list(gate)
    if extra:
        for k, v in extra.items():
            payload.setdefault(k, v)
    path = str(path)
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
    os.replace(tmp, path)
    return path


def read_bench(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)
