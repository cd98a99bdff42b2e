"""1 - the union of device op intervals over the traced window, averaged
over the cell's chips (device trace)."""


def read(run, metric):
    if run.trace is None:
        return None
    ids = range(run.chips)
    busy = sum(run.trace.busy_s(i) for i in ids) / run.chips
    return 100.0 * (1.0 - busy / run.trace.window_s)
