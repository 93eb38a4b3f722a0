"""Reduction from a profiler trace to the numbers the metrics read.

The JAX profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it.  Device planes are named ``/device:TPU:<n>``; their ops line
holds one event per operation that ran on the device (XLA fusions and
Pallas kernels alike).  The benchmark's own host spans
(``TraceAnnotation``, names starting ``chipbench.``) are on the host
plane, on a clock that the device's can lead by about a millisecond.

An op event is named by its HLO instruction (``%fusion.3 = f32[...]
fusion(...)``).  Control-flow ops (``while``, ``conditional``, ``call``)
span the ops they run and are left out.  A Pallas kernel is a
``tpu_custom_call``, named after the kernel where the program names it
(``chipbench/device_scopes.py`` finds the MSDA kernels so); the
breakdown labels a kernel event with its operand count as well.

Everything here is plain arithmetic on (name, start, end) triples, so it
is tested on a small trace recorded on the chip.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

SPAN_PREFIX = "chipbench."
WINDOW_SPAN = "chipbench.window"
OPS_LINE = "XLA Ops"

CONTAINERS = ("while", "conditional", "call")
PALLAS = 'custom_call_target="tpu_custom_call"'

Event = Tuple[str, float, float]  # name, start_ns, end_ns
_OP = re.compile(r"^(%[\w.-]+) = .*?\b([a-z][\w-]*)\(")


def op_kind(name: str) -> Tuple[str, str]:
    """(instruction, opcode) of an HLO op event's name."""
    m = _OP.match(name)
    return (m.group(1), m.group(2)) if m else (name, "")


def short_name(name: str) -> str:
    inst, kind = op_kind(name)
    if PALLAS in name:
        return f"{inst} pallas kernel, {pallas_operands(name)} operands"
    return f"{inst} {kind}".strip()


def pallas_operands(name: str) -> int:
    """Operand count of a Pallas kernel's custom call (0 if not one)."""
    if PALLAS not in name:
        return 0
    args = name.split("custom-call(", 1)[1].split("), custom_call_target")[0]
    return len(re.findall(r"(?:^|\s)%[\w.-]+", args))


@dataclass
class Trace:
    device_ops: Dict[str, List[Event]] = field(default_factory=dict)
    host_spans: List[Event] = field(default_factory=list)

    def window(self) -> Tuple[float, float]:
        """The traced steady window: the benchmark's window span."""
        spans = [s for s in self.host_spans if s[0] == WINDOW_SPAN]
        if not spans:
            raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
        return spans[0][1], spans[0][2]


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    tr = Trace()
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            evs = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events
                            if op_kind(e.name)[1] not in CONTAINERS]
            if evs:
                tr.device_ops[plane.name] = sorted(evs, key=lambda e: e[1])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                tr.host_spans += [
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events if e.name.startswith(SPAN_PREFIX)]
    return tr


def clip(events: Sequence[Event], lo: float, hi: float) -> List[Event]:
    out = []
    for n, s, e in events:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((n, s, e))
    return out


def union(events: Sequence[Event]) -> List[Tuple[float, float]]:
    """Merged busy intervals of ``events``."""
    merged: List[List[float]] = []
    for _, s, e in sorted(events, key=lambda x: x[1]):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_ns(events: Sequence[Event], lo: float, hi: float) -> float:
    return sum(e - s for s, e in union(clip(events, lo, hi)))


def gaps(events: Sequence[Event], lo: float, hi: float) -> List[Tuple[float, float]]:
    """Idle intervals of the device inside [lo, hi]."""
    out, t = [], lo
    for s, e in union(clip(events, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def host_activity(spans: Sequence[Event], t0: float, t1: float) -> str:
    """The innermost benchmark span (other than the window) that covers
    most of [t0, t1]: what the host was doing while the device idled."""
    best, best_cover, best_len = "no span", 0.0, float("inf")
    for n, s, e in spans:
        if n == WINDOW_SPAN:
            continue
        cover = min(e, t1) - max(s, t0)
        if cover <= 0:
            continue
        if cover > best_cover or (cover == best_cover and e - s < best_len):
            best, best_cover, best_len = n, cover, e - s
    return best


def op_seconds(events: Sequence[Event]) -> Dict[str, float]:
    tot: Dict[str, float] = {}
    for n, s, e in events:
        tot[n] = tot.get(n, 0.0) + (e - s) * 1e-9
    return tot


def breakdown(tr: Trace, lo: float, hi: float, top: int = 10) -> dict:
    """Top device ops by time, and the longest idle gaps by what the host
    was doing, over the first device plane."""
    plane = sorted(tr.device_ops)[0]
    evs = clip(tr.device_ops[plane], lo, hi)
    ops = sorted(op_seconds([(short_name(n), s, e) for n, s, e in evs]).items(),
                 key=lambda kv: -kv[1])[:top]
    gs = sorted(gaps(evs, lo, hi), key=lambda g: g[0] - g[1])[:top]
    return {
        "device_ops": [[n, s] for n, s in ops],
        "idle_gaps": [[host_activity(tr.host_spans, s, e), (e - s) * 1e-9]
                      for s, e in gs],
    }


def device_busy_s(tr: Trace) -> float:
    """Busy seconds averaged over the traced devices: the union of their
    op intervals.  The trace starts and stops with the device idle, so
    every op in it belongs to the window; the device clock is not clipped
    to the host's window span, whose clock it can lead by about a
    millisecond."""
    vals = [sum(e - s for s, e in union(evs)) * 1e-9
            for evs in tr.device_ops.values()]
    return sum(vals) / len(vals) if vals else 0.0


def first_device(tr: Trace) -> Optional[str]:
    return sorted(tr.device_ops)[0] if tr.device_ops else None
