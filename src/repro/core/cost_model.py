"""Analytic TPU v5e cost model for the xnor/popcount kernels.

Used when the mapping target is real TPU hardware this container cannot
time (``time_source='analytic'`` in the profiler), and for per-layer
roofline terms. Mirrors the roofline constants used in
EXPERIMENTS.md §Roofline.

The aspect configuration enters through the *grid order*: aspect
(parallel) dims are outermost, non-aspect dims innermost (exactly how
the Pallas kernel builds its grid). HBM traffic per operand follows the
classic loop-nest reuse model: a block is (re)loaded once per iteration
of every grid dim at or outside the innermost dim its index depends on.
The parallel-vs-sequential split also sets the core-parallelism factor:
grid iterations on parallel dims spread across ``TENSOR_CORES``.
"""

from __future__ import annotations

import dataclasses
import math

from repro.bnn.layers import LayerSpec
from repro.core.parallel_config import CONFIGS, CPU, aspects_of

# --- TPU v5e hardware constants (per chip) --------------------------------
PEAK_BF16_FLOPS = 197e12          # MXU
PEAK_INT8_OPS = 393e12            # MXU, int8 x int8 -> int32
HBM_BW = 819e9                    # bytes/s
ICI_BW_PER_LINK = 50e9            # bytes/s
VPU_INT_OPS = 4e12                # int32 vector ops/s (VPU, est.)
VMEM_BYTES = 128 * 1024 * 1024    # ~128 MiB v5e VMEM
TENSOR_CORES = 1                  # v5e: single core per chip
DISPATCH_OVERHEAD = 3e-6          # per kernel launch, seconds
HOST_LINK_BW = 16e9               # host<->HBM (PCIe-ish), bytes/s
HOST_LATENCY = 20e-6              # per host<->device boundary crossing
# host CPU executing the layer itself (the paper's CPU device)
CPU_BW = 50e9
CPU_INT_OPS = 2e11

P_BLK = 128
N_BLK = 128


@dataclasses.dataclass(frozen=True)
class GemmDims:
    b: int      # batch (X axis)
    p: int      # windows per image (Y axis)
    n: int      # output neurons (Z axis)
    kw: int     # packed reduction words

    @property
    def a_bytes(self):
        return self.b * self.p * self.kw * 4

    @property
    def w_bytes(self):
        return self.n * self.kw * 4

    @property
    def o_bytes(self):
        return self.b * self.p * self.n * 4

    @property
    def vpu_ops(self):
        # xor + not + popcount + add per word pair
        return 4 * self.b * self.p * self.n * self.kw


def gemm_dims_for(spec: LayerSpec, batch: int) -> GemmDims | None:
    """The packed xnor GEMM of a conv, fc or residual block (its binary
    conv, one window per output position); None for other kinds."""
    if spec.kind in ("conv", "res"):
        h, w, _ = spec.out_shape
        cin = spec.in_shape[-1]
        return GemmDims(
            b=batch, p=h * w, n=spec.units, kw=9 * math.ceil(cin / 32)
        )
    if spec.kind == "fc":
        return GemmDims(
            b=batch, p=1, n=spec.units, kw=math.ceil(spec.in_shape[0] / 32)
        )
    return None


def variant_analytics(config: str, registry=None) -> tuple:
    """(p_blk, n_blk, kind) pricing metadata for `config`.

    Fixed-8 names price under the model-default blocks; registered
    kernel variants (``repro.kernels.registry``) carry their own tile
    sizes and traffic kind (``"tiled"`` loop-nest reuse, ``"fused"``
    single pass, ``"host"`` CPU-side).  `registry` overrides the
    default registry for custom profiling sweeps.
    """
    if config == CPU:
        return P_BLK, N_BLK, "host"
    if config in CONFIGS:
        return P_BLK, N_BLK, "tiled"
    if registry is None:
        from repro.kernels.registry import DEFAULT_REGISTRY

        registry = DEFAULT_REGISTRY
    v = registry.get(config)
    return v.p_blk or P_BLK, v.n_blk or N_BLK, v.analytic


def _aspects_of(config: str, registry=None) -> tuple:
    if registry is not None and config not in CONFIGS and config in registry:
        return tuple(registry.get(config).aspects)
    return aspects_of(config)


def _grid(dims: GemmDims, config: str, registry=None):
    """(ordered axis names, sizes, parallel flags) as the kernel builds
    them: aspects outermost; block sizes from the variant's metadata."""
    aspects = set(_aspects_of(config, registry))
    p_blk, n_blk, _ = variant_analytics(config, registry)
    sizes = {
        "X": dims.b,
        "Y": math.ceil(dims.p / min(p_blk, dims.p)),
        "Z": math.ceil(dims.n / min(n_blk, dims.n)),
    }
    order = [a for a in ("X", "Y", "Z") if a in aspects] + [
        a for a in ("X", "Y", "Z") if a not in aspects
    ]
    return order, sizes, aspects


def gemm_hbm_traffic(dims: GemmDims, config: str, registry=None) -> float:
    """Bytes moved HBM<->VMEM under the loop-nest reuse model."""
    order, sizes, _ = _grid(dims, config, registry)
    blk_p, blk_n, _ = variant_analytics(config, registry)
    p_blk, n_blk = min(blk_p, dims.p), min(blk_n, dims.n)
    deps = {"a": {"X", "Y"}, "w": {"Z"}, "o": {"X", "Y", "Z"}}
    block_bytes = {
        "a": p_blk * dims.kw * 4,
        "w": n_blk * dims.kw * 4,
        "o": p_blk * n_blk * 4,
    }
    total = 0.0
    for t, dep in deps.items():
        depth = max(order.index(d) for d in dep)
        loads = 1
        for d in order[: depth + 1]:
            loads *= sizes[d]
        total += loads * block_bytes[t]
    return total


def gemm_kernel_time_tpu(dims: GemmDims, config: str, registry=None) -> float:
    """Kernel-only seconds for one xnor-GEMM dispatch under `config` —
    no host<->device transfer term.

    compute and memory terms overlap (max), parallel aspect dims spread
    over TENSOR_CORES, sequential dims serialize dispatch-free.
    """
    _, _, kind = variant_analytics(config, registry)
    if kind == "host":
        bytes_ = dims.a_bytes + dims.w_bytes + dims.o_bytes
        return max(bytes_ / CPU_BW, dims.vpu_ops / CPU_INT_OPS)
    order, sizes, aspects = _grid(dims, config, registry)
    par = 1
    for a in aspects:
        par *= sizes[a]
    core_par = min(TENSOR_CORES, max(par, 1))
    compute = dims.vpu_ops / (VPU_INT_OPS * core_par)
    if kind == "fused":
        # single fused dispatch: each operand crosses HBM exactly once
        traffic = dims.a_bytes + dims.w_bytes + dims.o_bytes
    else:
        traffic = gemm_hbm_traffic(dims, config, registry)
    memory = traffic / HBM_BW
    return max(compute, memory) + DISPATCH_OVERHEAD


def gemm_transfer_times_tpu(dims: GemmDims) -> tuple:
    """(h2d, d2h) boundary seconds: operand upload / result download."""
    h2d = HOST_LATENCY + dims.a_bytes / HOST_LINK_BW
    d2h = HOST_LATENCY + dims.o_bytes / HOST_LINK_BW
    return h2d, d2h


def _is_host(config: str, registry=None) -> bool:
    from repro.core.parallel_config import is_host_config

    return is_host_config(config, registry)


def _split(kernel: float, transfers: tuple, config: str, registry=None) -> tuple:
    """The single placement-charging rule: host placements have no
    boundary cost, device placements carry the layer's (h2d, d2h)."""
    if _is_host(config, registry):
        return kernel, 0.0, 0.0
    h2d, d2h = transfers
    return kernel, h2d, d2h


def gemm_time_tpu(dims: GemmDims, config: str) -> float:
    """Paper-faithful per-dispatch seconds: kernel plus the full
    per-layer H2D+D2H boundary for device placements (§IV-A)."""
    return sum(
        _split(
            gemm_kernel_time_tpu(dims, config),
            gemm_transfer_times_tpu(dims),
            config,
        )
    )


def elementwise_kernel_time_tpu(
    spec: LayerSpec, config: str, batch: int, registry=None
) -> float:
    """mp / step / flat layers: pure memory-bound, kernel term only."""
    import numpy as np

    elems = batch * int(np.prod(spec.in_shape))
    bytes_ = elems * 4 * 2
    if _is_host(config, registry):
        return bytes_ / CPU_BW
    return bytes_ / HBM_BW + DISPATCH_OVERHEAD


def elementwise_transfer_times_tpu(spec: LayerSpec, batch: int) -> tuple:
    """(h2d, d2h) for an elementwise layer (operand in, result out)."""
    import numpy as np

    elems = batch * int(np.prod(spec.in_shape))
    h2d = HOST_LATENCY + elems * 4 / HOST_LINK_BW
    d2h = HOST_LATENCY + elems * 4 / HOST_LINK_BW
    return h2d, d2h


def elementwise_time_tpu(spec: LayerSpec, config: str, batch: int) -> float:
    return sum(
        _split(
            elementwise_kernel_time_tpu(spec, config, batch),
            elementwise_transfer_times_tpu(spec, batch),
            config,
        )
    )


def int8_macs(spec: LayerSpec) -> int:
    """int8 x int8 MACs of one example: the stem, a downsampling
    block's 1x1 shortcut, the int8 fc; 0 for other kinds."""
    out = spec.out_shape
    cin = spec.in_shape[-1]
    if spec.kind == "stem":
        return out[0] * out[1] * 49 * cin * out[2]
    if spec.kind == "res" and spec.stride != 1:
        return out[0] * out[1] * cin * out[2]
    if spec.kind == "fcq":
        return cin * out[0]
    return 0


def int32_stream_time_tpu(
    spec: LayerSpec, config: str, batch: int, registry=None
) -> float:
    """Seconds of a Bi-Real layer's work beside any packed GEMM: its
    int8 products on the MXU and its int32 input and output crossing
    HBM (or, host-placed, the host's memory and integer units)."""
    import numpy as np

    ops = 2 * batch * int8_macs(spec)
    bytes_ = batch * 4 * (
        int(np.prod(spec.in_shape)) + int(np.prod(spec.out_shape))
    )
    if _is_host(config, registry):
        return max(bytes_ / CPU_BW, ops / CPU_INT_OPS)
    return max(bytes_ / HBM_BW, ops / PEAK_INT8_OPS)


def layer_time_split_tpu(
    spec: LayerSpec, config: str, batch: int, registry=None
) -> tuple:
    """(kernel_s, h2d_s, d2h_s) for one layer at `batch`.

    The transfer terms are placement costs of the layer's operand and
    result, independent of which aspect config runs the kernel; they are
    charged (or elided) by the mapper, not folded into the kernel time.
    CPU placement reports zero transfer.
    """
    dims = gemm_dims_for(spec, batch)
    if spec.kind in ("stem", "res", "fcq"):
        kern = int32_stream_time_tpu(spec, config, batch, registry)
        if dims is not None:
            kern += gemm_kernel_time_tpu(dims, config, registry)
        elif not _is_host(config, registry):
            kern += DISPATCH_OVERHEAD
        return _split(
            kern, elementwise_transfer_times_tpu(spec, batch), config,
            registry,
        )
    if dims is None:
        return _split(
            elementwise_kernel_time_tpu(spec, config, batch, registry),
            elementwise_transfer_times_tpu(spec, batch),
            config,
            registry,
        )
    return _split(
        gemm_kernel_time_tpu(dims, config, registry),
        gemm_transfer_times_tpu(dims),
        config,
        registry,
    )


def layer_time_tpu(spec: LayerSpec, config: str, batch: int) -> float:
    kern, h2d, d2h = layer_time_split_tpu(spec, config, batch)
    return kern + h2d + d2h


def fused_segment_kernel_time_tpu(specs, batch: int) -> float:
    """Kernel-only seconds for a whole device segment executed as
    **one** fused dispatch (``kernels.segment_fused.seg_pallas``-style):
    interior activations live in VMEM, so HBM traffic is a single pass
    over the segment's edge activations (in their edge encodings) plus
    every parameter array — intermediate results contribute compute
    but zero HBM bytes — and exactly one dispatch overhead.

    Compared with the per-layer sum this drops (a) each interior
    layer's unpacked activation write + read, (b) all but one dispatch
    overhead; compute is unchanged.  The fused price is therefore
    <= the per-layer kernel sum by construction, which is what lets
    the DP/selector prefer fused execution wherever it is applicable.
    """
    from repro.kernels.segment_fused import (
        encoded_shape,
        infer_in_encoding,
        segment_out_encoding,
    )

    specs = tuple(specs)
    in_enc = infer_in_encoding(specs)
    out_enc = segment_out_encoding(specs, in_enc)

    compute_ops = 0.0
    param_bytes = 0.0
    for spec in specs:
        dims = gemm_dims_for(spec, batch)
        if dims is None:
            # elementwise work still runs on the VPU, just without the
            # HBM round-trip
            import numpy as np

            compute_ops += 2 * batch * int(np.prod(spec.in_shape))
            if spec.kind == "step":
                param_bytes += spec.units * 4 * 2    # thresh + flip
        else:
            compute_ops += dims.vpu_ops
            param_bytes += dims.w_bytes

    def _edge_bytes(shape, enc) -> float:
        n = 1
        for d in encoded_shape(shape, enc):
            n *= d
        return batch * n * 4

    traffic = (
        _edge_bytes(specs[0].in_shape, in_enc)
        + _edge_bytes(specs[-1].out_shape, out_enc)
        + param_bytes
    )
    core_par = min(TENSOR_CORES, max(batch, 1))
    compute = compute_ops / (VPU_INT_OPS * core_par)
    return max(compute, traffic / HBM_BW) + DISPATCH_OVERHEAD


def xla_segment_kernel_time_tpu(specs, batch: int, registry=None) -> float:
    """Kernel-only seconds for a segment jitted as one XLA executable
    (``seg_xla``): per-layer single-pass traffic (XLA materializes the
    GEMM outputs but fuses the elementwise tails) with one dispatch
    for the whole chain.  Sits between the per-layer sum and the fully
    fused price — elementwise layers fuse into their producers (no
    separate traffic term), GEMM activations still cross HBM."""
    total = 0.0
    for spec in specs:
        if spec.kind in ("stem", "res", "fcq"):
            total += int32_stream_time_tpu(spec, "xla_fused", batch, registry)
        dims = gemm_dims_for(spec, batch)
        if dims is None:
            continue                    # fused into the producer GEMM
        total += gemm_kernel_time_tpu(dims, "xla_fused", registry)
        total -= DISPATCH_OVERHEAD
    return total + DISPATCH_OVERHEAD


def plan_node_times(plan) -> tuple:
    """Seconds per plan node — the IR's own kernel/boundary
    annotations (``core.plan.build_plan`` attributes them with the
    same charging rule as :func:`segment_times_from_split`: transfers
    only at placement changes, encoding conversions folded into the
    op that performs them, fused nodes priced at their profiled fused
    time)."""
    return tuple(n.kernel_s + n.boundary_s for n in plan.nodes)


def segment_times_from_split(
    segments, kernels, boundaries
) -> tuple:
    """Seconds per segment for a configuration's kernel/boundary split
    — the per-segment generalization of the host/device stage split.

    ``segments`` is any sequence of objects with ``start``/``stop``/
    ``on_device`` (``repro.core.mapper.Segment`` duck-typed, so this
    module stays import-free of the mapper); ``kernels``/``boundaries``
    are the per-layer attributions.  Pricing matches the segment
    executor: a device segment charges boundary only on its edge layers
    (for ``policy="dp"`` attributions interior boundaries are zero
    anyway; for greedy ones the interior roundtrips the executor elides
    are dropped here too), host segments charge every layer's stored
    boundary (zero for CPU placements by construction).

    These predictions are what the adaptive runtime's
    ``DriftDetector`` compares live telemetry against
    (``repro.adapt``), and what ``EfficientConfiguration.stage_times``
    aggregates into the two pipeline stages.
    """
    out = []
    for seg in segments:
        t = 0.0
        for i in range(seg.start, seg.stop):
            t += kernels[i]
            if seg.on_device:
                if i in (seg.start, seg.stop - 1):
                    t += boundaries[i]
            else:
                t += boundaries[i]
        out.append(t)
    return tuple(out)


def contention_inflation(
    co_runner_share: float, gamma: float = 1.0, *, law=None
) -> float:
    """Kernel-time inflation factor for a tenant whose co-runners
    occupy ``co_runner_share`` of a processor's time.

    Processor-sharing model: a co-runner that demands *s* seconds of a
    processor per second of wall clock steals ``s`` of every second,
    stretching this tenant's kernels on that processor by ``1 + s``
    (``gamma`` scales the coupling — <1 models partial overlap, e.g. a
    device whose queues interleave better than a timesliced host).
    Linear in the share, so inflation is monotone: adding co-runner
    load never makes a placement look faster — the property the fleet
    mapper's descent relies on (``repro.fleet.scheduler``).

    ``law`` swaps the assumed linear model for a **calibrated** one —
    any object with ``inflation(share) -> factor`` honoring the
    fitted-law contract (``repro.estimator.interference``: fixed
    point 1 at share 0, >= 1, monotone non-decreasing), typically a
    ``FittedInterference`` recovered from ledger traces.  When given,
    ``gamma`` is ignored.
    """
    if law is not None:
        return float(law.inflation(max(0.0, co_runner_share)))
    if gamma < 0.0:
        raise ValueError("gamma must be non-negative")
    return 1.0 + gamma * max(0.0, co_runner_share)


def inflate_profile(
    table,
    *,
    host_factor: float = 1.0,
    device_factor: float = 1.0,
    registry=None,
):
    """A contention-inflated copy of a ``ProfileTable``: kernel times
    of host-placed configs scale by ``host_factor``, device-placed
    kernels *and* the h2d/d2h boundary rows by ``device_factor`` (the
    transfer link is device-side occupancy — a contended device delays
    its uploads too).  Totals are rebuilt under paper semantics
    (device rows carry the full roundtrip).  Factors of 1.0 share the
    original rows per batch rather than copying.

    This is the per-tenant view ``repro.fleet.scheduler.map_fleet``
    re-runs the DP mapper against: the same table, repriced as if the
    tenant's co-runners were already resident.
    """
    from repro.core.profiler import ProfileTable

    if host_factor <= 0.0 or device_factor <= 0.0:
        raise ValueError("inflation factors must be positive")
    if host_factor == 1.0 and device_factor == 1.0:
        return table

    times: dict = {}
    kernels: dict = {}
    h2d: dict = {}
    d2h: dict = {}
    for b in table.batch_sizes:
        times[b], kernels[b] = [], []
        h2d[b] = [table.h2d(b, i) * device_factor
                  for i in range(len(table.layer_labels))]
        d2h[b] = [table.d2h(b, i) * device_factor
                  for i in range(len(table.layer_labels))]
        for i in range(len(table.layer_labels)):
            krow, trow = {}, {}
            for cfg in table.configs_for(b, i):
                host = _is_host(cfg, registry)
                k = table.kernel_time(b, i, cfg) * (
                    host_factor if host else device_factor
                )
                krow[cfg] = k
                trow[cfg] = k if host else k + h2d[b][i] + d2h[b][i]
            kernels[b].append(krow)
            times[b].append(trow)
    return ProfileTable(
        model_name=table.model_name,
        batch_sizes=table.batch_sizes,
        layer_labels=table.layer_labels,
        times=times,
        kernel_times=kernels,
        h2d_times=h2d,
        d2h_times=d2h,
    )


def pipeline_makespan(
    host_s: float, device_s: float, n_microbatches: int
) -> float:
    """Makespan of a two-stage software pipeline over a micro-batch
    stream (the serving runtime in ``repro.serving.pipeline``).

    Stage H (host segments, ``host_s`` seconds per micro-batch) and
    stage D (device segments plus boundary transfers, ``device_s``)
    overlap across micro-batches: while micro-batch *i* occupies the
    device, micro-batch *i+1* runs its host segments.  The classic
    fill-drain formula::

        makespan = host_s + device_s + (n - 1) * max(host_s, device_s)

    For n == 1 this is the serial latency; the steady-state rate is one
    micro-batch per max(host_s, device_s), which is what
    ``EfficientConfiguration.pipelined_expected_time`` reports per
    example.
    """
    if n_microbatches <= 0:
        return 0.0
    return (
        host_s
        + device_s
        + (n_microbatches - 1) * max(host_s, device_s)
    )
