"""The program's own spans in a profiler trace, set against the
benchmark's ``step`` spans and the device's time.

The serving engine writes its spans into the profiler's trace while a
session runs (``repro.tracing``): ``engine.step`` around one
``Deployment.step``, and below it ``batcher.form``, ``pipeline.h2d``,
``pipeline.dispatch``, ``pipeline.d2h``, ``engine.complete`` and
``python.gc``, each with its counts as event stats.  Each executable
of a plan node is jitted under the node's stable name, so the device's
``XLA Modules`` line gives device time per node.

This reads them beside what ``trace_reduce`` reads, from one pass over
the ``.xplane.pb``, and reduces the measured window to:

* ``step_split``: for each benchmark ``step`` span, the summed duration
  of each program span name inside it;
* ``self_by_program_span`` and ``idle_by_program_span``: the time inside
  the ``step`` spans, and the device idle in it, put down to the
  innermost program span open at each instant (the one begun last);
  time under none is ``step:unspanned``.  The idle parts sum to
  ``trace_reduce``'s ``idle_gaps["step"]``;
* ``gc_idle_s``: device idle while a ``python.gc`` span is open;
* ``device_modules``: device time per module name, hash stripped.

``readings`` gives the per-layer numbers these feed; ``report`` all of
it as JSON-ready numbers (``trace_cell.py`` prints it).
"""

from __future__ import annotations

import dataclasses
import re
import statistics
from collections import defaultdict

import numpy as np

import trace_reduce

PROGRAM_SPANS = ("engine.step", "batcher.form", "pipeline.h2d",
                 "pipeline.dispatch", "pipeline.d2h", "engine.complete",
                 "python.gc")
STEP_SPAN = "step"            # the benchmark's span around Deployment.step
UNSPANNED = "step:unspanned"
GC_SPAN = "python.gc"
MODULES_LINE = "XLA Modules"
_HASH = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class ProgramEvents:
    events: trace_reduce.TraceEvents   # what trace_reduce.load reads
    program: list     # [(name, start_ns, end_ns, {stat: value})], by start
    modules: dict     # device plane -> [(module name, start_ns, end_ns)]


def load(path) -> ProgramEvents:
    """One pass over the trace: the device operations and the
    benchmark's spans as ``trace_reduce.load`` keeps them, the program's
    spans with their stats, and the device's modules."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    device, host, program, modules = {}, [], [], {}
    bench = set(trace_reduce.SPAN_NAMES) | {trace_reduce.WINDOW_SPAN}
    mine = set(PROGRAM_SPANS)
    for plane in data.planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == trace_reduce.OPS_LINE:
                    device[plane.name] = [
                        (trace_reduce.op_name(e.name), e.start_ns,
                         e.start_ns + e.duration_ns)
                        for e in line.events
                    ]
                elif line.name == MODULES_LINE:
                    modules[plane.name] = [
                        (_HASH.sub("", e.name), e.start_ns,
                         e.start_ns + e.duration_ns)
                        for e in line.events
                    ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in bench:
                        host.append((e.name, e.start_ns,
                                     e.start_ns + e.duration_ns))
                    elif e.name in mine:
                        program.append((e.name, e.start_ns,
                                        e.start_ns + e.duration_ns,
                                        dict(e.stats)))
    return ProgramEvents(
        events=trace_reduce.TraceEvents(
            device=device, host=sorted(host, key=lambda e: e[1])),
        program=sorted(program, key=lambda e: e[1]),
        modules=modules,
    )


@dataclasses.dataclass
class ProgramSummary:
    step_s: float                  # the window's step spans, summed
    images: int                    # ``images`` of its engine.step spans
    step_split: dict               # span name -> seconds in each step span
    self_by_program_span: dict     # innermost span -> seconds of step time
    idle_by_program_span: dict     # innermost span -> device idle seconds
    gc_idle_s: float
    device_modules: list           # [[module, seconds]], longest first

    @property
    def covered(self) -> float:
        """Share of the step spans' time under a span below
        ``engine.step``."""
        bare = (self.self_by_program_span.get("engine.step", 0.0)
                + self.self_by_program_span.get(UNSPANNED, 0.0))
        return 1.0 - bare / self.step_s if self.step_s else 0.0


def _clip(intervals, w0, w1) -> np.ndarray:
    iv = np.asarray(intervals, dtype=np.float64).reshape(-1, 2)
    iv = iv[(iv[:, 1] > w0) & (iv[:, 0] < w1)]
    return np.clip(iv, w0, w1)


def _innermost(program, steps):
    """The time inside `steps` cut at every program span's ends:
    ``(starts, ends, labels)``, each piece labelled with the innermost
    program span open over it, or `UNSPANNED`."""
    program = [p for p in program if p[2] > p[1]]
    points = [(s, 1, j) for j, (_, s, _e) in enumerate(program)]
    points += [(e, 0, j) for j, (_, _s, e) in enumerate(program)]
    points += [(s, 1, -1) for s, _ in steps] + [(e, 0, -1) for _, e in steps]
    points.sort()                 # at one instant, ends before starts
    a, b, labels = [], [], []
    stack, in_step, t_prev = [], 0, None
    for t, opens, j in points:
        if in_step and t > t_prev:
            a.append(t_prev)
            b.append(t)
            labels.append(program[stack[-1]][0] if stack else UNSPANNED)
        t_prev = t
        if j < 0:
            in_step += 1 if opens else -1
        elif opens:
            stack.append(j)       # in start order: the last is innermost
        else:
            stack.remove(j)
    return np.asarray(a), np.asarray(b), labels


def reduce(pe: ProgramEvents, *, top: int = 10) -> ProgramSummary | None:
    """Reduce the measured window.  None where the trace holds no
    window span or no device operation."""
    ev = pe.events
    windows = [e for e in ev.host if e[0] == trace_reduce.WINDOW_SPAN]
    if not windows or not ev.device:
        return None
    _, w0, w1 = windows[-1]
    steps = _clip([(s, e) for n, s, e in ev.host if n == STEP_SPAN], w0, w1)
    program = [(n, max(s, w0), min(e, w1)) for n, s, e, _ in pe.program
               if e > w0 and s < w1]

    split = {}
    for name in PROGRAM_SPANS:
        per_step = np.zeros(len(steps))
        iv = np.asarray([(s, e) for n, s, e in program if n == name],
                        dtype=np.float64).reshape(-1, 2)
        if len(iv) and len(steps):
            k = np.searchsorted(steps[:, 0], iv[:, 0], side="right") - 1
            inside = k >= 0
            k = np.maximum(k, 0)
            overlap = np.minimum(iv[:, 1], steps[k, 1]) - \
                np.maximum(iv[:, 0], steps[k, 0])
            np.add.at(per_step, k, np.where(inside, np.maximum(overlap, 0), 0))
        split[name] = per_step * 1e-9

    a, b, labels = _innermost(program, steps)
    names = sorted(set(labels))
    index = np.asarray([names.index(x) for x in labels], dtype=np.int64)
    self_ns = np.bincount(index, weights=b - a, minlength=len(names)) \
        if len(index) else np.zeros(0)
    idle_ns = np.zeros(len(names))
    gc_ns = 0.0
    gc_iv = trace_reduce.merge(
        [(s, e) for n, s, e in program if n == GC_SPAN])
    for evs in ev.device.values():
        merged = trace_reduce.merge(_clip([(s, e) for _, s, e in evs], w0, w1))
        if len(index):
            busy = trace_reduce.busy_before(merged, b) - \
                trace_reduce.busy_before(merged, a)
            idle_ns += np.bincount(index, weights=(b - a) - busy,
                                   minlength=len(names))
        if len(gc_iv):
            gc_ns += float(
                (gc_iv[:, 1] - gc_iv[:, 0]).sum()
                - (trace_reduce.busy_before(merged, gc_iv[:, 1])
                   - trace_reduce.busy_before(merged, gc_iv[:, 0])).sum()
            )
    n_dev = len(ev.device)

    per_module = defaultdict(float)
    for evs in pe.modules.values():
        for name, s, e in evs:
            if e > w0 and s < w1:
                per_module[name] += min(e, w1) - max(s, w0)
    mods = sorted(per_module.items(), key=lambda kv: -kv[1])[:top]
    images = sum(
        int(args.get("images", 0)) for n, s, e, args in pe.program
        if n == "engine.step" and e > w0 and s < w1
    )
    return ProgramSummary(
        step_s=float((steps[:, 1] - steps[:, 0]).sum()) * 1e-9,
        images=images,
        step_split=split,
        self_by_program_span={
            k: float(v) * 1e-9 for k, v in zip(names, self_ns)},
        idle_by_program_span={
            k: float(v) / n_dev * 1e-9 for k, v in zip(names, idle_ns)},
        gc_idle_s=gc_ns / n_dev * 1e-9,
        device_modules=[[k, v * 1e-9] for k, v in mods],
    )


def readings(s: ProgramSummary, *, images: int, window_s: float) -> dict:
    """The per-layer numbers the program's spans feed, for a window
    that completed `images` images in `window_s` seconds."""
    split = s.step_split
    out = {"gc_idle_share": s.gc_idle_s / window_s * 100.0}
    if len(split["pipeline.dispatch"]):
        out["dispatch_ms.latency"] = \
            statistics.median(split["pipeline.dispatch"]) * 1e3
        out["d2h_ms.latency"] = statistics.median(split["pipeline.d2h"]) * 1e3
    if images:
        out["batch_form_us_per_image"] = \
            split["batcher.form"].sum() / images * 1e6
        out["complete_us_per_image"] = \
            split["engine.complete"].sum() / images * 1e6
    return out


def report(s: ProgramSummary, window_s: float) -> dict:
    """The summary as JSON-ready numbers: per-step medians and totals
    in place of the per-step arrays."""
    return {
        "step_s": s.step_s,
        "steps": len(next(iter(s.step_split.values()))),
        "images": s.images,
        "covered": s.covered,
        "step_split_median_ms": {
            k: statistics.median(v) * 1e3 for k, v in s.step_split.items()
            if len(v)},
        "step_split_total_s": {
            k: float(v.sum()) for k, v in s.step_split.items()},
        "self_by_program_span": s.self_by_program_span,
        "idle_by_program_span": s.idle_by_program_span,
        "gc_idle_s": s.gc_idle_s,
        "device_modules": s.device_modules,
        "readings": readings(s, images=s.images, window_s=window_s),
    }
