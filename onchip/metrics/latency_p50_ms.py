"""Median request latency over every request the window completed, from
the client's send to its scores on the host (host clock)."""

import numpy as np


def read(run):
    if not run.latency_s:
        return None
    return float(np.percentile(np.asarray(run.latency_s) * 1e3, 50))
