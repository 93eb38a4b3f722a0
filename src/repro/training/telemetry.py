"""Step-time telemetry -> ``BENCH_train.json``.

The training counterpart of ``benchmarks/paper_benchmarks.py``'s
``BENCH_sparsity.json``: one recorder object rides the harness, collects
the per-step wall-time trajectory plus the runtime's discrete events
(recoveries, re-plans), and writes a single JSON payload in the same
``{"bench", "config", "note", "results", ...}`` shape, so the CI
artifact tooling treats both files identically.

``results`` carries the headline scalars (mean/p50 step time,
tokens/sec, re-plan count, recovery count + latencies); ``trajectory``
the full per-step series the step-time plot is drawn from; ``events``
the recovery/replan log with latencies.
"""
from __future__ import annotations

import time
from typing import Any, Dict, Optional

from repro.obs import bench as _bench
from repro.obs import registry as _obs


class StepTimeRecorder:
    """Accumulates the step-time trajectory + runtime events.

    ``tokens_per_step``: global tokens (or queries, for detection
    workloads) consumed per optimizer step — the tokens/sec headline is
    derived from it; 0 disables that row.
    """

    def __init__(self, *, tokens_per_step: int = 0,
                 config: Optional[Dict[str, Any]] = None,
                 window: int = 4096):
        self.tokens_per_step = int(tokens_per_step)
        self.config = dict(config or {})
        # bounded rings (arbitrarily long runs must not grow host
        # memory): raw step/event rows are windowed to the last
        # ``window`` entries — headline scalars stay EXACT via the
        # running aggregates below; the p50 and the trajectory/events
        # blocks of the payload are windowed views
        self.steps: _obs.EventWindow = _obs.EventWindow(window)
        self.events: _obs.EventWindow = _obs.EventWindow(window)
        self._wall = _obs.NumericWindow(window)
        self._event_counts: Dict[str, int] = {}
        self._created = time.time()
        # registry mirror (process-wide obs substrate)
        self._step_hist = _obs.histogram(
            "train.step_wall_s", help="per-step wall time (seconds)")
        self._event_ctr = _obs.counter(
            "train.events", help="harness runtime events by kind")

    # -- recording --------------------------------------------------------
    def record_step(self, step: int, wall_s: float,
                    loss: Optional[float] = None) -> None:
        row: Dict[str, Any] = {"step": int(step), "wall_s": float(wall_s)}
        if loss is not None:
            row["loss"] = float(loss)
        self.steps.append(row)
        self._wall.append(float(wall_s))
        self._step_hist.observe(float(wall_s))

    def record_event(self, kind: str, *, step: int, latency_s: float = 0.0,
                     detail: str = "", **extra: Any) -> None:
        """``kind``: 'recovery' | 'replan' | anything the harness emits.

        ``extra`` keys ride into the event row verbatim — the harness
        uses this to promote its ``recovery_log`` fields (failed step,
        resume point, skipped checkpoints) to first-class event fields.
        """
        row = {"kind": str(kind), "step": int(step),
               "latency_s": float(latency_s), "detail": str(detail)}
        for k, v in extra.items():
            row.setdefault(k, v)
        self.events.append(row)
        self._event_counts[str(kind)] = self._event_counts.get(str(kind), 0) + 1
        self._event_ctr.inc(kind=str(kind))

    # -- reporting --------------------------------------------------------
    def summary(self) -> Dict[str, Any]:
        # counts/total/mean/max are exact over the full run; p50 and
        # recovery_latency_s come from the bounded window (the last
        # ``window`` steps/events)
        n = self._wall.count
        total = self._wall.total
        recoveries = [e for e in self.events if e["kind"] == "recovery"]
        out: Dict[str, Any] = {
            "steps": n,
            "total_step_wall_s": total,
            "mean_step_s": self._wall.mean,
            "p50_step_s": self._wall.p50,
            "max_step_s": self._wall.max,
            "recoveries": self._event_counts.get("recovery", 0),
            "recovery_latency_s": [e["latency_s"] for e in recoveries],
            "replan_count": self._event_counts.get("replan", 0),
        }
        if self.tokens_per_step and total > 0:
            out["tokens_per_sec"] = self.tokens_per_step * n / total
        from repro.kernels import plan as plan_mod

        out["plan_execution"] = plan_mod.execution_telemetry()
        return out

    def payload(self, *, note: str = "") -> Dict[str, Any]:
        return {
            "bench": "train_runtime",
            "config": self.config,
            "note": note or (
                "step wall-time trajectory + recovery/replan events from "
                "the elastic training harness (repro.training)"),
            "results": self.summary(),
            "trajectory": list(self.steps),
            "events": list(self.events),
            "created_unix": self._created,
        }

    # regression-gate rules for BENCH_train.json: step timings and
    # tokens/sec are machine-relative, so only very generous slack;
    # steps/recoveries depend on the run config and are not gated
    GATE = [
        _bench.gate_rule("mean_step_s", "lower", 4.0),
        _bench.gate_rule("p50_step_s", "lower", 4.0),
        _bench.gate_rule("tokens_per_sec", "higher", 0.8),
    ]

    def write(self, path: str, *, note: str = "") -> str:
        """Atomic JSON dump (tmp + rename) via ``obs.bench.write_bench``."""
        p = self.payload(note=note)
        return _bench.write_bench(
            path, bench=p["bench"], results=p["results"], config=p["config"],
            note=p["note"], trajectory=p["trajectory"], events=p["events"],
            gate=self.GATE, created_unix=p["created_unix"])
