"""The same operations as ``mfu_int8_all`` over the device's summed
operation time in the traced window, against the chip's int8 peak
(device trace): the served node's share of its roofline.  Its roof is
the int8 peak, not the bandwidth: at Bi-Real-Net-18's sizes the bytes a
node cannot avoid (200 KB of input words an image, 11.7 MB of weights a
micro-batch of 64, 4 KB of scores an image) take about 5% of the time
its 3.63 G operations an image take at the peak (0.47 of 9.2 us)."""

import harness
import ops


def read(run):
    sibling = harness.reader_path("mfu_int8_all")
    per_image = harness._load_module(sibling).ops_per_image(run)
    t = run.trace
    if per_image is None or t is None or t.device_op_s <= 0:
        return None
    peak = ops.peaks(run.device_kind)["int8_ops_per_s"] * run.cell.chips
    done = sum(per_image[n] * k for n, k in run.completed_by.items())
    return done / t.device_op_s / peak * 100
