"""Operation counts of a configuration and the chip peaks they are held
against.

A binary MAC is one product of two {-1, +1} values added into a sum.  A
3x3 convolution of an H x W map from Cin to Cout channels makes
H * W * 9 * Cin * Cout of them and a fully connected layer Din * Dout.
Pooling, sign and flatten are not counted.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def binary_macs_per_image(layer_shapes) -> int:
    """MACs of one image, from ``(kind, in_shape, out_shape)`` per layer."""
    total = 0
    for kind, in_shape, out_shape in layer_shapes:
        if kind == "conv":
            h, w, cin = in_shape
            total += h * w * 9 * cin * out_shape[-1]
        elif kind == "fc":
            total += int(np.prod(in_shape)) * out_shape[0]
    return int(total)


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of `device_kind`.  A device that is
    not in the table is an error, not a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in {PEAKS_FILE.name}"
        )
    return table[device_kind]
