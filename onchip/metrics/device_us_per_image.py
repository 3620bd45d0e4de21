"""Summed duration of the device operations in the traced window over the
images completed in it (device trace)."""


def read(run):
    if run.trace is None or not run.completed or run.trace.device_op_s <= 0:
        return None
    return run.trace.device_op_s / run.completed * 1e6
