"""Device time of one decode step: the decode program's module events in
the traced window, summed and divided by their count."""


def read(data):
    if data.trace is None:
        return None
    durs = data.trace.module_durations("decode")
    if not durs:
        return None
    return sum(durs) / len(durs) / 1e6
