"""What a family's cell entry takes from the harness: the clock that
``setup_s`` runs from, the run's error, its log, and the generic reports
on the compiled entry and the device.

A module of its own because ``chipbench.run`` runs as ``__main__``: a
family that imported it would run a second copy of it, with a clock and
an error class of its own.
"""
from __future__ import annotations

import json
import time

T_START = time.perf_counter()


class BenchError(Exception):
    pass


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", flush=True)


def setup_s() -> float:
    """Seconds since the harness was first imported: set-up, when read
    just before the window."""
    return time.perf_counter() - T_START


def report_program(compiled, plans) -> None:
    """Earlier output lines: plans, memory analysis, Pallas launches."""
    for name, plan in plans.items():
        log(f"plan {name}:\n{plan.describe()}")
    ma = compiled.memory_analysis()
    mem = {k: int(getattr(ma, k)) for k in (
        "temp_size_in_bytes", "argument_size_in_bytes", "output_size_in_bytes",
        "alias_size_in_bytes", "generated_code_size_in_bytes")}
    log(f"memory_analysis: {json.dumps(mem)}")
    text = compiled.as_text()
    n_custom = text.count('custom_call_target="tpu_custom_call"')
    log(f"Pallas launch sites in the compiled step (tpu_custom_call ops in "
        f"its HLO, each inside the layer loop where it runs): {n_custom}")
    for name, plan in plans.items():
        log(f"plan {name} launches per call: {plan.launches_per_call()}")


def memory_peak(devices) -> int:
    """``peak_bytes_in_use`` of the fullest of ``devices``."""
    vals = []
    for d in devices:
        stats = d.memory_stats() or {}
        vals.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(vals) if vals else 0
