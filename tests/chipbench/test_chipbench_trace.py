"""The reduction from a profiler trace to metrics: by hand on a few
intervals, and on small traces recorded on a TPU v5e
(``data/msda_small.xplane.pb`` and, once the program named its kernels,
``data/msda_small_named.xplane.pb``, written by ``record_trace.py``:
three forward+VJP calls of a small MSDA Pallas plan under the
benchmark's own window and spans)."""
import os

import pytest

import chipbench_tiny
from chipbench import catalog, device_scopes, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "msda_small.xplane.pb")
NAMED = os.path.join(os.path.dirname(DATA), "msda_small_named.xplane.pb")
DETR = catalog.family(chipbench_tiny.TINY_CONFIG, chipbench_tiny.REPO)
EVENTS = [("a", 0, 10), ("b", 5, 20), ("a", 30, 40), ("c", 38, 45),
          ("d", 60, 61)]


def test_union_and_gaps_by_hand():
    assert trace.union(EVENTS) == [(0, 20), (30, 45), (60, 61)]
    assert trace.busy_ns(EVENTS, 0, 100) == 20 + 15 + 1
    assert trace.gaps(EVENTS, 0, 100) == [(20, 30), (45, 60), (61, 100)]
    # clipped to a window that cuts events
    assert trace.busy_ns(EVENTS, 8, 35) == 12 + 5
    assert trace.gaps(EVENTS, 8, 35) == [(20, 30)]


GATHER = ('%op.16 = f32[2,2,8,128]{3,2,1,0:T(8,128)} custom-call(s32[64]{0:T(1024)} '
          '%bitcast.7, f32[256]{0:T(1024)} %reshape.9, f32[2,2,9,128]{3,2,1,0} '
          '%pad_fusion.2), custom_call_target="tpu_custom_call", '
          'frontend_attributes={kernel_metadata={}}')
SCATTER = ('%op.55 = (f32[2,2,9,128]{3,2,1,0}, f32[8]{0}) custom-call(s32[64]{0} '
           '%b.1, f32[256]{0} %b.2, f32[2,2,8,128]{3,2,1,0} %g.3, f32[16]{0} '
           '%s.4), custom_call_target="tpu_custom_call"')


def test_op_names_and_kernel_seconds():
    assert trace.op_seconds(EVENTS)["a"] == pytest.approx(20e-9)
    assert trace.op_kind(GATHER) == ("%op.16", "custom-call")
    assert trace.op_kind("%while.9 = (s32[]) while(%t), body=%b") == ("%while.9", "while")
    assert trace.pallas_operands(GATHER) == 3
    assert trace.pallas_operands(SCATTER) == 4
    assert trace.pallas_operands("%fusion.1 = f32[2] fusion(%a), kind=kLoop") == 0
    assert trace.short_name(SCATTER) == "%op.55 pallas kernel, 4 operands"
    gather = GATHER.replace("%op.16", "%msda_gather.16")
    scatter = SCATTER.replace("%op.55", "%msda_scatter.55")
    evs = [(gather, 0, 5), (scatter, 5, 20), (gather, 20, 24), (GATHER, 24, 30)]
    assert device_scopes.trace_kernel_seconds(evs, "msda_gather") == (
        pytest.approx(9e-9), 2)
    assert device_scopes.trace_kernel_seconds(evs, "msda_scatter") == (
        pytest.approx(15e-9), 1)


def test_host_activity_labels_gaps():
    spans = [("chipbench.window", 0, 100), ("chipbench.dispatch", 18, 32),
             ("chipbench.wait", 44, 62)]
    assert trace.host_activity(spans, 20, 30) == "chipbench.dispatch"
    assert trace.host_activity(spans, 45, 60) == "chipbench.wait"
    assert trace.host_activity(spans, 70, 90) == "no span"


def test_breakdown_by_hand():
    tr = trace.Trace(device_ops={"/device:TPU:0": EVENTS},
                     host_spans=[("chipbench.window", 0, 100),
                                 ("chipbench.wait", 40, 100)])
    bd = trace.breakdown(tr, *tr.window())
    assert bd["device_ops"][0][0] == "a"
    assert bd["idle_gaps"][0] == ["chipbench.wait", pytest.approx(39e-9)]
    assert len(bd["idle_gaps"]) == 3


@pytest.fixture(scope="module")
def chip_trace():
    return trace.load(DATA)


@pytest.fixture(scope="module")
def named_trace():
    return trace.load(NAMED)


def _by_operands(events, operands):
    """Seconds and count of the Pallas kernel events with ``operands``
    operands: the gather takes three, the scatter four."""
    sel = [(s, e) for n, s, e in events if trace.pallas_operands(n) == operands]
    return sum(e - s for s, e in sel) * 1e-9, len(sel)


def test_chip_trace_planes_and_window(chip_trace):
    assert trace.first_device(chip_trace) == "/device:TPU:0"
    lo, hi = chip_trace.window()
    assert hi > lo
    names = {n for n, _, _ in chip_trace.host_spans}
    assert {"chipbench.window", "chipbench.dispatch", "chipbench.wait"} <= names
    kinds = {trace.op_kind(n)[1] for n, _, _ in
             chip_trace.device_ops["/device:TPU:0"]}
    assert not kinds & set(trace.CONTAINERS)


def test_chip_trace_kernels_one_per_call(chip_trace):
    events = chip_trace.device_ops["/device:TPU:0"]
    calls = sum(1 for n, _, _ in chip_trace.host_spans
                if n == "chipbench.dispatch")
    fwd_s, n_fwd = _by_operands(events, 3)
    bwd_s, n_bwd = _by_operands(events, 4)
    assert n_fwd == n_bwd == calls
    assert 0 < fwd_s < bwd_s


def test_chip_trace_busy_and_breakdown(chip_trace):
    lo, hi = chip_trace.window()
    busy = trace.device_busy_s(chip_trace)
    assert 0 < busy <= (hi - lo) * 1e-9
    bd = trace.breakdown(chip_trace, lo, hi)
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    secs = [s for _, s in bd["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    assert "pallas kernel, 4 operands" in bd["device_ops"][0][0]
    assert all(label.startswith("chipbench.") or label == "no span"
               for label, _ in bd["idle_gaps"])


class _Plan:
    def __init__(self, fwd, bwd):
        self.launches = {"fwd": fwd, "bwd": bwd}

    def launches_per_call(self):
        return dict(self.launches)


def _traced_run(chip_trace, mode, encoder_layers):
    from chipbench.run import TraceRun

    calls = sum(1 for n, _, _ in chip_trace.host_spans
                if n == "chipbench.dispatch")
    lo, hi = chip_trace.window()
    cfg = {"encoder_layers": encoder_layers, "decoder_layers": 1}
    plans = {"encoder": _Plan(1, 1), "decoder": _Plan(0, 0)}
    return TraceRun(chip_trace, cfg, {"mode": mode, "batch": 1},
                    DETR.msda_calls(cfg, mode, plans), None,
                    calls, calls, (hi - lo) * 1e-9, lo, hi)


def test_msda_launches_follow_the_plans_and_remat(chip_trace):
    run = _traced_run(chip_trace, "train", encoder_layers=6)
    # the encoder's forward runs twice per layer in training (remat)
    assert run.msda_launches("fwd") == 12
    assert run.msda_launches("bwd") == 6
    assert _traced_run(chip_trace, "infer", 6).msda_launches("fwd") == 6


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_msda_kernel_seconds_when_the_count_matches(named_trace, direction):
    # the recorded trace: one plan call per window call, one launch each
    # way; the kernels found by name are those with the kernel's operands
    run = _traced_run(named_trace, "infer", encoder_layers=1)
    operands = 3 if direction == "fwd" else 4
    seconds = device_scopes.named_kernel_seconds(run, direction)
    assert seconds == _by_operands(run.device_events(), operands)[0] > 0


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_msda_kernel_seconds_fail_on_another_count(named_trace, direction):
    from chipbench.run import BenchError

    run = _traced_run(named_trace, "infer", encoder_layers=2)
    with pytest.raises(BenchError, match="committed plans launch"):
        device_scopes.named_kernel_seconds(run, direction)
