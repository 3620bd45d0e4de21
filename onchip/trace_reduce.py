"""Reduction of a ``jax.profiler`` trace to the numbers the benchmark
reports: device busy time, idle share, device time per operation, and the
idle gaps attributed to what the host was doing.

The trace is the ``.xplane.pb`` file the profiler writes.  Device
operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane.  Host spans are the benchmark's own
``TraceAnnotation`` events on the host plane, named in `SPAN_NAMES`, and
the ``window`` span that bounds the measured window.  All are on one clock
in nanoseconds.
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

import numpy as np

SPAN_NAMES = ("generate", "submit", "step", "collect", "wait")
WINDOW_SPAN = "window"
DEVICE_PLANE = re.compile(r"/device:TPU:\d+$")
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class TraceEvents:
    """Events as ``(name, start_ns, end_ns)``: per device, and on the host."""

    device: dict          # plane name -> list of events
    host: list


def load(path) -> TraceEvents:
    """Read the device operations and the benchmark's host spans."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    device, host = {}, []
    wanted = set(SPAN_NAMES) | {WINDOW_SPAN}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device[plane.name] = [
                        (op_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events
                    ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events
                    if e.name in wanted
                )
    return TraceEvents(device=device, host=sorted(host, key=lambda e: e[1]))


def op_name(hlo: str) -> str:
    """``fusion.1`` of ``%fusion.1 = s32[...] fusion(...)``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def merge(intervals) -> np.ndarray:
    """Union of ``(start, end)`` intervals, as sorted disjoint rows."""
    iv = np.asarray(intervals, dtype=np.float64).reshape(-1, 2)
    if len(iv) == 0:
        return iv
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    reach = np.maximum.accumulate(iv[:, 1])
    # an interval opens a new group where it starts after everything
    # before it has ended
    first = np.concatenate([[True], iv[1:, 0] > reach[:-1]])
    last = np.concatenate([first[1:], [True]])
    return np.stack([iv[first, 0], reach[last]], axis=1)


def busy_before(merged: np.ndarray, t) -> np.ndarray:
    """Busy time in ``merged`` before each time in `t`."""
    t = np.atleast_1d(np.asarray(t, dtype=np.float64))
    if len(merged) == 0:
        return np.zeros_like(t)
    starts, ends = merged[:, 0], merged[:, 1]
    cum = np.concatenate([[0.0], np.cumsum(ends - starts)])
    k = np.searchsorted(starts, t, side="right")   # intervals begun by t
    within = np.zeros_like(t)
    has = k > 0
    kk = k[has] - 1
    within[has] = np.clip(t[has] - starts[kk], 0.0, ends[kk] - starts[kk])
    return np.where(has, cum[np.maximum(k - 1, 0)] + within, 0.0)


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                 # union of device operations, mean over chips
    device_op_s: float            # sum of device operation durations, all chips
    device_ops: list              # [[name, seconds], ...], longest first
    idle_gaps: list               # [[host span, seconds], ...], longest first


def reduce(events: TraceEvents, *, top: int = 10) -> Summary | None:
    """Reduce `events` over the measured window.  None where the trace
    holds no window span or no device operation."""
    windows = [e for e in events.host if e[0] == WINDOW_SPAN]
    if not windows or not events.device:
        return None
    _, w0, w1 = windows[-1]
    window_ns = w1 - w0
    spans = [e for e in events.host if e[0] in SPAN_NAMES]
    per_op = defaultdict(float)
    busy_ns, gap_ns = 0.0, defaultdict(float)
    for evs in events.device.values():
        clipped = [
            (max(s, w0), min(e, w1)) for _, s, e in evs if e > w0 and s < w1
        ]
        for (name, s, e) in evs:
            if e > w0 and s < w1:
                per_op[name] += min(e, w1) - max(s, w0)
        merged = merge(clipped)
        busy = float((merged[:, 1] - merged[:, 0]).sum()) if len(merged) else 0.0
        busy_ns += busy
        # idle time inside each host span; the rest of the idle time fell
        # outside every span
        in_spans = 0.0
        if spans:
            s0 = np.clip([s for _, s, _ in spans], w0, w1)
            s1 = np.clip([e for _, _, e in spans], w0, w1)
            idle = (s1 - s0) - (busy_before(merged, s1) - busy_before(merged, s0))
            for (name, _, _), v in zip(spans, idle):
                gap_ns[name] += float(v)
            in_spans = float(idle.sum())
        gap_ns["other"] += (window_ns - busy) - in_spans
    n = len(events.device)
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(gap_ns.items(), key=lambda kv: -kv[1])[:top]
    return Summary(
        window_s=window_ns * 1e-9,
        busy_s=busy_ns / n * 1e-9,
        device_op_s=sum(per_op.values()) * 1e-9,
        device_ops=[[k, v * 1e-9] for k, v in ops],
        idle_gaps=[[k, v / n * 1e-9] for k, v in gaps],
    )
