"""Share of the MSDA forward kernels' roofline: the least time the chip
could take for every forward MSDA call of the traced steps (the larger of
FLOPs over peak FLOP/s and bytes over peak bytes/s, ``chipbench/work.py``)
over the device time of the forward Pallas kernel's events, found by the
kernel's name (``msda_gather``), as many as the committed plans launch
(``device_scopes.named_kernel_seconds``).  The XLA ops that build its
corner tables are not counted; ``msda_fwd_op_roofline`` counts them."""
from chipbench import device_scopes, work


def read(run):
    if (run.peak is None or device_scopes.vocabulary() is None
            or run.msda_launches("fwd") == 0):
        return None
    seconds = device_scopes.named_kernel_seconds(run, "fwd")
    least, _ = work.least_seconds(*run.msda_work("fwd"), run.peak)
    return 100.0 * least / seconds
