"""Share of the traced window, in percent, in which no operation ran on
the device (1 minus the union of the device's op intervals)."""


def read(run):
    if run.trace is None or run.trace_window_s <= 0 or run.trace.n_devices == 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace_window_s)
