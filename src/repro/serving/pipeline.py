"""Segment-pipelined execution of a mapped BNN.

The mapper's :meth:`EfficientConfiguration.segments` splits the layer
sequence into maximal same-placement runs; adjacent segments alternate
host <-> device, so execution is a chain

    [host seg] -> H2D -> [device seg] -> D2H -> [host seg] -> ...

:class:`SegmentPipeline` runs a *stream* of micro-batches through that
chain as a software pipeline: micro-batch ``i`` enters at wave ``i``
and advances one segment per wave, so in any wave at most one
micro-batch occupies each segment.  Within a wave, device segments are
dispatched first (JAX async dispatch returns immediately) and host
segments run afterwards on the Python thread — overlapping the host
work of micro-batch *i+1* with the in-flight device work of
micro-batch *i*.  H2D uploads are double-buffered: micro-batch
*i+1*'s input is staged with :func:`jax.device_put` while wave *i* is
still executing, and the D2H sync for a device segment's output is
deferred one full wave, so the download price is paid only after the
device had a wave's worth of time to finish.

Placement is modeled the same way as the faithful
``mapped_model`` driver: "host" activations are materialized
``numpy`` arrays, "device" activations are JAX arrays left to XLA's
asynchronous runtime.  On a CPU-only container both ultimately
execute on the XLA host device, but the sync structure — where the
Python thread blocks, where transfers are staged — is exactly the one
the cost model prices, and it is the structure that generalizes to a
real accelerator backend.

All arithmetic is int32/bool, so pipelined, serial, and fused
execution are bit-exact for the same inputs.

**Telemetry hook.**  Both drivers accept an ``observer`` — a callable
``observer(seg_index, segment, seconds, batch)`` fired once per
(micro-batch, segment) execution with the segment's wall time for a
``batch``-row micro-batch.  With ``observer=None`` (the default) the
drivers are exactly the un-instrumented code paths — zero overhead.
When observing, the pipelined driver must block on each device
segment's output to read a true wall time, which serializes that
wave's device/host overlap; the adaptive runtime
(``repro.adapt.SegmentTelemetry``) therefore *samples* — it hands an
observer to only every k-th step — so steady-state throughput keeps
the overlap.
"""

from __future__ import annotations

import time
from typing import Callable, Sequence

import jax
import numpy as np

from repro.bnn.models import BNNModel
from repro.core.mapped_model import (
    build_node_fns,
    dispatch_span,
    node_name,
    node_stream,
    to_host,
)
from repro.core.mapper import EfficientConfiguration
from repro.core.parallel_config import CPU, FULL_GPU
from repro.core.plan import SegmentPlan, build_plan
from repro.tracing import span


def canonical_mixed_mapping(model: BNNModel) -> tuple:
    """The canonical mixed host/device split for serving experiments:
    GEMM layers (conv/fc) on the device, elementwise layers on the
    host — guarantees alternating segments so the two-stage pipeline
    has work to overlap.  Shared by benchmarks and tests so they
    exercise the same schedule."""
    return tuple(
        FULL_GPU if s.kind in ("conv", "fc") else CPU
        for s in model.specs
    )


class SegmentPipeline:
    """Compiled executables for a ``"segments"``-mode
    :class:`~repro.core.plan.SegmentPlan`, plus serial and pipelined
    drivers over its nodes.

    The pipeline schedules **plan nodes**: the plan (built once from
    the configuration, or passed in pre-built) fixes each node's
    placement, boundary transfers and fused-variant choice; the
    drivers below only decide *when* each node runs and where the
    Python thread blocks.  Plan nodes duck-type ``mapper.Segment``,
    so observers and telemetry consumers see the same interface as
    before the IR existed.
    """

    def __init__(
        self,
        model: BNNModel,
        packed_params: list,
        config: EfficientConfiguration,
        *,
        device=None,
        plan: SegmentPlan | None = None,
        registry=None,
    ):
        self.config = config
        if plan is None:
            plan = build_plan(config, mode="segments")
        elif plan.mode != "segments":
            raise ValueError(
                f"SegmentPipeline schedules 'segments'-mode plans, "
                f"got mode {plan.mode!r}"
            )
        self.plan = plan
        self.segment_fns = build_node_fns(
            model, packed_params, config, plan, registry
        )
        self.node_names = [
            node_name(k, node) for k, (node, _) in enumerate(self.segment_fns)
        ]
        self.node_streams = [
            node_stream(model.specs[node.start:node.stop],
                        node.fused_variant)
            for node, _ in self.segment_fns
        ]
        self.device = device if device is not None else jax.devices()[0]

    @property
    def segments(self) -> tuple:
        return tuple(seg for seg, _ in self.segment_fns)

    # -- serial reference: one micro-batch at a time, Python thread
    #    blocks at every segment boundary (no overlap) ---------------
    def run_serial(self, x_words, *, observer: Callable | None = None):
        x = np.asarray(x_words)
        batch = x.shape[0]
        for s, (seg, fn) in enumerate(self.segment_fns):
            name = self.node_names[s]
            t0 = time.perf_counter() if observer is not None else 0.0
            if seg.on_device:
                with span("pipeline.h2d", batch=batch):
                    x = jax.device_put(x, self.device)
            with dispatch_span(name, batch, self.node_streams[s]):
                out = fn(x)
            jax.block_until_ready(out)
            # D2H after a device segment, before the next one
            x = to_host(out, name, batch) if seg.on_device else out
            if observer is not None:
                observer(s, seg, time.perf_counter() - t0, batch)
        return to_host(x, self.node_names[-1], batch)

    # -- pipelined driver over a micro-batch stream ------------------
    def run_pipelined(
        self,
        inputs: Sequence,
        *,
        on_complete: Callable | None = None,
        observer: Callable | None = None,
    ) -> list:
        """Run `inputs` (a list of micro-batch word arrays) through the
        segment chain with a one-segment-per-wave skew.

        ``on_complete(i, out)`` fires as soon as micro-batch ``i``'s
        output is materialized on the host — the per-micro-batch
        completion point for latency measurement.  Returns outputs in
        input order.

        ``observer(seg_index, segment, seconds, batch)`` fires per
        (micro-batch, segment) with the segment's wall time.  Observing
        blocks on device-segment outputs (a true wall time needs a
        sync), trading that wave's overlap for measurement — pass an
        observer only on sampled steps (module docstring).
        """
        segs = self.segment_fns
        k, n = len(segs), len(inputs)
        if n == 0:
            return []
        first_on_device = segs[0][0].on_device
        names = self.node_names
        state: list = [None] * n
        staged: list = [None] * n
        outputs: list = [None] * n

        def stage(i):
            # double-buffered H2D: the upload is issued a wave before
            # micro-batch i first executes
            x = np.asarray(inputs[i])
            if first_on_device:
                with span("pipeline.h2d", batch=x.shape[0]):
                    x = jax.device_put(x, self.device)
            staged[i] = x

        stage(0)
        for w in range(n + k - 1):
            active = [
                (i, w - i)
                for i in range(max(0, w - k + 1), min(n - 1, w) + 1)
            ]
            if w + 1 < n:
                stage(w + 1)
            # device advances first: async dispatch keeps the device
            # busy while this wave's host segments run below
            for i, s in active:
                seg, fn = segs[s]
                if seg.on_device:
                    x = staged[i] if s == 0 else state[i]
                    staged[i] = None        # keep only ~2 live buffers
                    batch = x.shape[0]
                    if not isinstance(x, jax.Array):
                        with span("pipeline.h2d", batch=batch):
                            x = jax.device_put(x, self.device)
                    t0 = time.perf_counter() if observer is not None else 0.0
                    with dispatch_span(names[s], batch,
                                       self.node_streams[s]):
                        out = fn(x)
                    if observer is not None:
                        jax.block_until_ready(out)
                        observer(s, seg, time.perf_counter() - t0, batch)
                    state[i] = out
            # host advances: np.asarray is the deferred D2H sync on the
            # previous wave's device output
            for i, s in active:
                seg, fn = segs[s]
                if not seg.on_device:
                    x = staged[i] if s == 0 else state[i]
                    staged[i] = None
                    batch = x.shape[0]
                    # with an observer the timing includes the deferred
                    # D2H sync of the upstream device output: the host
                    # stage pays it in the un-instrumented driver too
                    t0 = time.perf_counter() if observer is not None else 0.0
                    if s > 0:
                        x = to_host(x, names[s - 1], batch)
                    with dispatch_span(names[s], batch,
                                       self.node_streams[s]):
                        out = fn(x)
                    if observer is not None:
                        jax.block_until_ready(out)
                        observer(s, seg, time.perf_counter() - t0, batch)
                    state[i] = out
            # completions: micro-batch i leaves the pipeline
            for i, s in active:
                if s == k - 1:
                    outputs[i] = to_host(state[i], names[s],
                                         state[i].shape[0])
                    state[i] = None
                    if on_complete is not None:
                        on_complete(i, outputs[i])
        return outputs
