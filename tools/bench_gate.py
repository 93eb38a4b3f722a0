#!/usr/bin/env python
"""CI perf-regression gate over the ``BENCH_*.json`` trajectory files.

Diffs a freshly emitted trajectory against the committed baseline:

    python tools/bench_gate.py --baseline BENCH_sparsity.json \
        --fresh fresh/BENCH_sparsity.json

    # several files at once (missing fresh files fail):
    python tools/bench_gate.py --baseline-dir . --fresh-dir fresh \
        --files BENCH_sparsity.json BENCH_train.json

Exit codes: 0 = no regressions, 2 = regression(s), 1 = usage/IO error.

Which ``results`` leaves are compared — and in which direction — comes
from the baseline payload's ``gate`` rules (see ``obs.bench.gate_rule``
and ``docs/observability.md``): each rule is an fnmatch pattern over the
flattened dotted key, a direction (``lower``/``higher`` = which way is
better) and a relative tolerance (0.0 = structural, must not move).
Leaves matched by no rule are informational only.  Payloads without a
``gate`` block fall back to a conservative name heuristic: count-like
keys (``launches``, ``gathers``, ``recoveries``, ...) gate structurally,
everything else is informational.

A fresh value *better* than baseline beyond its tolerance is an
improvement; ``--update`` then rewrites the baseline in place — fresh
``results`` become current, the previous results are appended to the
payload's ``history`` list — so the committed trajectory ratchets
forward instead of rotting.
"""
from __future__ import annotations

import argparse
import fnmatch
import json
import os
import sys
from typing import Any, Dict, List, Optional, Tuple

# heuristic fallback for payloads written before gate rules existed:
# keys whose leaf name contains one of these gate structurally (lower is
# better); nothing else gates
_STRUCTURAL_HINTS = ("launches", "gathers", "recoveries", "replan",
                     "retrace", "compiles")


def flatten(results: Any, prefix: str = "") -> Dict[str, float]:
    """Dotted-key map of every numeric leaf (bools excluded)."""
    out: Dict[str, float] = {}
    if isinstance(results, dict):
        for k, v in results.items():
            out.update(flatten(v, f"{prefix}{k}."))
    elif isinstance(results, (int, float)) and not isinstance(results, bool):
        out[prefix[:-1]] = float(results)
    return out


def _heuristic_rules() -> List[Dict[str, Any]]:
    return [{"pattern": f"*{h}*", "direction": "lower", "tolerance": 0.0}
            for h in _STRUCTURAL_HINTS]


def rule_for(key: str, rules: List[Dict[str, Any]]) -> Optional[Dict[str, Any]]:
    leaf = key.rsplit(".", 1)[-1]
    for r in rules:
        if fnmatch.fnmatch(key, r["pattern"]) or fnmatch.fnmatch(leaf, r["pattern"]):
            return r
    return None


def compare(baseline: Dict[str, Any], fresh: Dict[str, Any]
            ) -> Tuple[List[str], List[str], List[str]]:
    """-> (regressions, improvements, notes), each a list of messages."""
    rules = baseline.get("gate") or _heuristic_rules()
    base = flatten(baseline.get("results", {}))
    new = flatten(fresh.get("results", {}))
    regressions, improvements, notes = [], [], []
    for key, b in sorted(base.items()):
        r = rule_for(key, rules)
        if r is None:
            continue
        if key not in new:
            regressions.append(f"{key}: gated metric missing from fresh run "
                               f"(baseline {b:g})")
            continue
        f = new[key]
        tol = float(r.get("tolerance", 0.0))
        lower_better = r.get("direction", "lower") == "lower"
        # relative slack around the baseline; structural rules (tol 0)
        # use a tiny epsilon so float round-trips never false-positive
        eps = abs(b) * 1e-9 + 1e-12
        if lower_better:
            worst, best = b * (1.0 + tol) + eps, b * (1.0 - tol) - eps
            if f > worst:
                regressions.append(
                    f"{key}: {f:g} > {b:g} (+{_pct(f, b)}, tol {tol:g})")
            elif f < best and tol > 0:
                improvements.append(f"{key}: {f:g} < {b:g} (-{_pct(b, f)})")
            elif f < b - eps and tol == 0:
                improvements.append(f"{key}: {f:g} < {b:g} (structural win)")
        else:
            worst, best = b * (1.0 - tol) - eps, b * (1.0 + tol) + eps
            if f < worst:
                regressions.append(
                    f"{key}: {f:g} < {b:g} (-{_pct(b, f)}, tol {tol:g})")
            elif f > best and tol > 0:
                improvements.append(f"{key}: {f:g} > {b:g} (+{_pct(f, b)})")
            elif f > b + eps and tol == 0:
                improvements.append(f"{key}: {f:g} > {b:g} (structural win)")
    for key in sorted(set(new) - set(base)):
        notes.append(f"{key}: new metric ({new[key]:g}), not in baseline")
    return regressions, improvements, notes


def _pct(hi: float, lo: float) -> str:
    if lo == 0:
        return "inf%"
    return f"{100.0 * (hi - lo) / abs(lo):.1f}%"


def update_baseline(baseline_path: str, baseline: Dict[str, Any],
                    fresh: Dict[str, Any]) -> None:
    """Ratchet: fresh results become current, old ones go to history."""
    hist = list(baseline.get("history", []))
    hist.append({"results": baseline.get("results", {}),
                 "created_unix": baseline.get("created_unix")})
    updated = dict(baseline)
    updated["results"] = fresh.get("results", {})
    updated["created_unix"] = fresh.get("created_unix")
    updated["history"] = hist
    tmp = baseline_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(updated, f, indent=1, sort_keys=True)
    os.replace(tmp, baseline_path)


def gate_pair(baseline_path: str, fresh_path: str, *, update: bool = False
              ) -> int:
    try:
        with open(baseline_path) as f:
            baseline = json.load(f)
        with open(fresh_path) as f:
            fresh = json.load(f)
    except (OSError, ValueError) as e:
        print(f"[bench-gate] ERROR reading {baseline_path} / {fresh_path}: {e}")
        return 1
    name = os.path.basename(baseline_path)
    if baseline.get("bench") != fresh.get("bench"):
        print(f"[bench-gate] ERROR {name}: bench id mismatch "
              f"({baseline.get('bench')} vs {fresh.get('bench')})")
        return 1
    regressions, improvements, notes = compare(baseline, fresh)
    for m in regressions:
        print(f"[bench-gate] REGRESSION {name}: {m}")
    for m in improvements:
        print(f"[bench-gate] improved {name}: {m}")
    for m in notes:
        print(f"[bench-gate] note {name}: {m}")
    if regressions:
        return 2
    if improvements and update:
        update_baseline(baseline_path, baseline, fresh)
        print(f"[bench-gate] baseline updated: {baseline_path} "
              f"(previous results appended to history)")
    if not improvements:
        print(f"[bench-gate] OK {name}: within tolerance")
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--baseline", help="committed trajectory file")
    ap.add_argument("--fresh", help="freshly emitted trajectory file")
    ap.add_argument("--baseline-dir", help="directory of committed trajectories")
    ap.add_argument("--fresh-dir", help="directory of fresh trajectories")
    ap.add_argument("--files", nargs="+", default=None,
                    help="file names to gate under --baseline-dir/--fresh-dir")
    ap.add_argument("--update", action="store_true",
                    help="on improvement, ratchet the baseline forward "
                         "(old results appended to its history)")
    args = ap.parse_args(argv)

    pairs: List[Tuple[str, str]] = []
    if args.baseline and args.fresh:
        pairs.append((args.baseline, args.fresh))
    elif args.baseline_dir and args.fresh_dir and args.files:
        for name in args.files:
            pairs.append((os.path.join(args.baseline_dir, name),
                          os.path.join(args.fresh_dir, name)))
    else:
        ap.error("use --baseline + --fresh, or "
                 "--baseline-dir + --fresh-dir + --files")

    rc = 0
    for baseline_path, fresh_path in pairs:
        rc = max(rc, gate_pair(baseline_path, fresh_path, update=args.update))
    return rc


if __name__ == "__main__":
    sys.exit(main())
