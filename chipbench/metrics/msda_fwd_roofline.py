"""Share of the MSDA forward kernels' roofline: the least time the chip
could take for every forward MSDA call of the traced steps (the larger of
FLOPs over peak FLOP/s and bytes over peak bytes/s, ``chipbench/work.py``)
over the device time of the forward Pallas kernel's events (the
three-operand gather, as many as the committed plans launch,
``TraceRun.msda_kernel_seconds``).  The XLA ops that build its corner
tables are not told apart in the trace and are not counted."""
from chipbench import work


def read(run):
    if run.peak is None or run.msda_launches("fwd") == 0:
        return None
    seconds = run.msda_kernel_seconds("fwd")
    least, _ = work.least_seconds(*run.msda_work("fwd"), run.peak)
    return 100.0 * least / seconds
