"""The device's idle share of the traced window, in percent: 1 minus the
union of device-operation intervals over the window (device trace).  One
reader for each ``idle_share.<part>``: the cells that report latency and
those that report throughput each name their own."""


def read(run):
    t = run.trace
    if t is None or t.busy_s <= 0:
        return None
    return (1.0 - t.busy_s / t.window_s) * 100
