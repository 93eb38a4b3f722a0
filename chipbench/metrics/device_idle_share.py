"""Share of the traced steady window in which no operation ran on the
device: 1 - (union of device-op intervals / window)."""


def read(run):
    if not run.device_events():
        return None
    return 100.0 * (1.0 - run.busy_s / run.trace_window_s)
