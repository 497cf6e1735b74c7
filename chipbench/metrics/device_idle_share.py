"""Share of the traced window in which no operation ran on the device:
1 less the union of the device's op intervals over the window."""


def read(data):
    if data.trace is None or data.trace.busy_s is None:
        return None
    return 100.0 * (1.0 - data.trace.busy_s / data.served.window_s)
