"""Median wall time of the benchmark's ``step`` spans around
``Deployment.step()``, in the traced window (host clock)."""

import numpy as np


def read(run):
    steps = [t1 - t0 for name, t0, t1 in run.spans if name == "step"]
    if not steps:
        return None
    return float(np.median(steps) * 1e3)
