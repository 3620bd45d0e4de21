"""The window's images per second, times two operations per MAC of each
image, binary and int8 alike, over the chip's int8 peak (host clock).
The MACs come from the configuration's plain reference
(``macs_per_image``), per tenant: unlike ``mfu_int8`` they count strided
convs at their output positions and the int8 stem, shortcuts and fc.
Nothing is read for a configuration whose reference does not count
them."""

import harness
import ops


def ops_per_image(run) -> dict | None:
    """Tenant -> operations of one image, or None where a tenant's
    reference has no ``macs_per_image``."""
    out = {}
    for name, cfg in run.cell.tenants.items():
        count = getattr(harness.reference(cfg), "macs_per_image", None)
        if count is None:
            return None
        out[name] = 2 * sum(count(cfg).values())
    return out


def read(run):
    per_image = ops_per_image(run)
    if per_image is None or not run.completed:
        return None
    peak = ops.peaks(run.device_kind)["int8_ops_per_s"] * run.cell.chips
    done = sum(per_image[t] * n for t, n in run.completed_by.items())
    return done / run.window_s / peak * 100
