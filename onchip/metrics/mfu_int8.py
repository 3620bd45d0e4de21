"""The traced window's images per second, times two operations per binary
MAC of each image, over the chip's int8 peak: the share of the peak that
the same work would reach as a +-1 int8 matrix product.  The MACs come
from ``ops.binary_macs_per_image``, per tenant, and the peak from
``peaks.json``, by the device's kind; a kind missing from the table is an
error."""

import ops


def read(run):
    if not run.completed:
        return None
    peak = ops.peaks(run.device_kind)["int8_ops_per_s"] * run.cell.chips
    ops_done = sum(2 * run.macs[t] * n for t, n in run.completed_by.items())
    return ops_done / run.window_s / peak * 100
