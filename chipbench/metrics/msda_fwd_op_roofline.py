"""Share of the whole MSDA forward op's roofline: the least time the chip
could take for every forward MSDA call of the traced steps (the same
least time as ``msda_fwd_roofline``) over the device time of every event
the program puts in its ``msda_fwd`` scope, forward direction, encoder
and decoder: the corner tables, the value slabs, the kernels and the
reduction of their outputs (``chipbench/device_scopes.py``).  It reads
the same work whatever implements it, kernel or XLA."""
from chipbench import device_scopes, work


def read(run):
    scopes = device_scopes.vocabulary()
    if (run.peak is None or not run.device_events() or scopes is None
            or run.msda_launches("fwd") == 0):
        return None
    seconds = device_scopes.scope_seconds(run, scopes.MSDA_FWD, "fwd")
    least, _ = work.least_seconds(*run.msda_work("fwd"), run.peak)
    return 100.0 * least / seconds
