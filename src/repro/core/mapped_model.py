"""Build executables from an EfficientConfiguration — the JAX analogue
of the paper's generated CUDA/C++ (§III-E), refactored around the
:mod:`repro.core.plan` IR.

There is **one** executor.  Every execution style is a plan shape, not
a separate driver:

    config --build_plan(mode)--> SegmentPlan --build_node_fns--> fns
                                                     |
                                              run_plan(fns)

* ``build_mapped_model(fused=True)`` — the ``"whole"`` plan: one node
  spanning the network, compiled as a single jitted function (layer
  boundaries carry no host roundtrip — the optimization the paper
  names as future work).
* ``build_mapped_model(fused=False)`` — per-layer plan nodes executed
  by the Python driver with an explicit sync per node: mode
  ``"layers"`` crosses the host boundary only at placement changes
  (the elision the DP priced), mode ``"roundtrip"`` round-trips around
  every device layer (paper §IV-A).
* ``build_segment_fns`` — the ``"segments"`` plan: one executable per
  same-placement segment, consumed by the serving pipeline
  (``repro.serving.pipeline.SegmentPipeline``).

A plan node with a ``fused_variant`` resolves to a *segment-scope*
kernel from the variant registry (``repro.kernels.segment_fused``):
the whole node runs as one fused dispatch with activations staying
bit-packed between its layers.  Nodes without one compose their
layers' per-layer implementations under a single jit — bit-exact
either way, since all arithmetic is integer/bool.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import numpy as np

from repro.bnn import layers as L
from repro.bnn.models import BNNModel
from repro.core.mapper import EfficientConfiguration
from repro.core.plan import SegmentPlan, build_plan
from repro.kernels.registry import DEFAULT_REGISTRY, SCOPE_SEGMENT
from repro.tracing import span


def _layer_fn(spec, packed, config: str, registry=None) -> Callable:
    """The layer's computation under `config`, resolved through the
    kernel-variant registry — any registered name (fixed-8 aspect
    config, ``xla_fused``, a Pallas tile variant, ...) is executable.
    `registry` overrides the default resolver (matching a custom
    registry passed to ``autotune_bnn_model``)."""
    reg = registry if registry is not None else DEFAULT_REGISTRY
    if spec.kind == "res":
        gemm = reg.get(config).builder
        return lambda x: L.apply_packed(spec, packed, x, gemm)
    if spec.kind in ("stem", "maxp", "gap", "fcq"):
        return lambda x: L.apply_packed(spec, packed, x)
    if spec.kind == "conv":
        w, k_true = packed["w_words"], packed["k_true"]
        builder = reg.get(config).builder

        def f(x):
            b, h, ww, _ = x.shape
            p = L.extract_patch_words(x).reshape(b, h * ww, -1)
            return builder(p, w, k_true).reshape(b, h, ww, -1)

        return f
    if spec.kind == "fc":
        w, k_true = packed["w_words"], packed["k_true"]
        builder = reg.get(config).builder

        def f(x):
            return builder(x[:, None, :], w, k_true)[:, 0, :]

        return f
    if spec.kind == "mp":
        return L.maxpool_packed
    if spec.kind == "step":
        t, fl = packed["thresh"], packed["flip"]
        return lambda x: L.step_packed(x, t, fl)
    if spec.kind == "flat":
        c = spec.in_shape[-1]
        return lambda x: L.flat_packed(x, c)
    raise ValueError(spec.kind)


def _layer_fns(
    model: BNNModel,
    packed_params: list,
    config: EfficientConfiguration,
    registry=None,
) -> list:
    """Per-layer callables under the mapping — what plan nodes without
    a fused variant compose from."""
    return [
        _layer_fn(spec, packed, cfg, registry)
        for spec, packed, cfg in zip(
            model.specs, packed_params, config.layer_configs
        )
    ]


def node_name(k: int, node) -> str:
    """Plan node `k`'s stable name, ``node<k>_<start>_<stop>_<variant>``
    (the fused variant, else the placement): the name its executable is
    jitted under, so the device trace's ``XLA Modules`` line reads
    ``jit_<name>``, and the ``node`` of its spans."""
    return (f"node{k}_{node.start}_{node.stop}_"
            f"{node.fused_variant or node.placement}")


def node_stream(specs) -> tuple:
    """``(blocks, bytes)``: the residual blocks among `specs` and the
    int32 stream bytes they read and write for one example."""
    blocks = [s for s in specs if s.kind == "res"]
    per_example = sum(
        4 * (int(np.prod(s.in_shape)) + int(np.prod(s.out_shape)))
        for s in blocks
    )
    return len(blocks), per_example


def dispatch_span(name: str, batch: int, stream: tuple):
    """The ``pipeline.dispatch`` span of one node call.  A node with
    residual blocks (`stream`, from :func:`node_stream`) also carries
    their count, ``res_blocks``, and the stream bytes they read and
    write at `batch`, ``stream_bytes``."""
    blocks, per_example = stream
    if not blocks:
        return span("pipeline.dispatch", node=name, batch=batch)
    return span("pipeline.dispatch", node=name, batch=batch,
                res_blocks=blocks, stream_bytes=per_example * batch)


def build_node_fns(
    model: BNNModel,
    packed_params: list,
    config: EfficientConfiguration,
    plan: SegmentPlan,
    registry=None,
) -> list:
    """One jitted callable per plan node, in execution order:
    ``[(PlanNode, fn), ...]``, each jitted under ``node_name``.

    A node carrying a ``fused_variant`` resolves that segment-scope
    variant's builder over the node's layer slice (one fused dispatch,
    activations bit-packed between the node's layers); any other node
    jits the composition of its layers' per-layer implementations.
    """
    reg = registry if registry is not None else DEFAULT_REGISTRY
    fns = _layer_fns(model, packed_params, config, registry)
    out = []
    for k, node in enumerate(plan.nodes):
        if node.fused_variant is not None:
            variant = reg.get(node.fused_variant)
            if variant.scope != SCOPE_SEGMENT:
                raise ValueError(
                    f"plan node [{node.start}:{node.stop}] names "
                    f"{node.fused_variant!r} as fused variant, but its "
                    f"registry scope is {variant.scope!r}"
                )
            fn = variant.builder(
                tuple(model.specs[node.start:node.stop]),
                list(packed_params[node.start:node.stop]),
                node.in_encoding,
            )
        else:
            fn = _compose(fns[node.start:node.stop])
        out.append((node, _jit_named(fn, node_name(k, node))))
    return out


def _compose(layer_fns) -> Callable:
    layer_fns = tuple(layer_fns)

    def fn(x):
        for f in layer_fns:
            x = f(x)
        return x

    return fn


def _jit_named(fn, name: str) -> Callable:
    """`fn` under ``jax.jit`` as `name`.  A function a builder already
    jitted is jitted again from the function it wraps, so the node is
    still one dispatch of the same operations.  Operands a builder
    bound with ``functools.partial`` (its weights) stay arguments of
    the node, so the node's executable does not depend on their
    values."""
    operands = ()
    if isinstance(fn, functools.partial):
        fn, operands = fn.func, fn.args
    if hasattr(fn, "lower") and hasattr(fn, "__wrapped__"):
        fn = fn.__wrapped__

    if operands:
        def node(operands, x):
            return fn(*operands, x)
    else:
        def node(x):
            return fn(x)

    node.__name__ = node.__qualname__ = name
    jitted = jax.jit(node)
    return functools.partial(jitted, operands) if operands else jitted


def run_plan(node_fns, *, device=None, specs=()) -> Callable:
    """The plan interpreter: ``fn(x_words) -> np.ndarray`` walking the
    nodes with the transfer/sync structure the plan encodes — H2D
    (``jax.device_put``) before a ``transfer_in`` node, a blocking
    sync after every node (the per-node cost structure the profiler
    measured), D2H (``np.asarray``) after a ``transfer_out`` node.
    Between co-placed nodes the activation stays where it is.  `specs`,
    the model's layers, tell each node's dispatch span what it counts."""
    dev = device if device is not None else jax.devices()[0]
    names = [node_name(k, node) for k, (node, _) in enumerate(node_fns)]
    streams = [node_stream(specs[node.start:node.stop])
               for node, _ in node_fns]

    def run(x_words):
        x = np.asarray(x_words)          # input starts on the host
        batch = x.shape[0]
        for name, stream, (node, fn) in zip(names, streams, node_fns):
            if node.transfer_in and not isinstance(x, jax.Array):
                with span("pipeline.h2d", batch=batch):
                    x = jax.device_put(x, dev)
            with dispatch_span(name, batch, stream):
                out = fn(x)
            jax.block_until_ready(out)
            x = to_host(out, name, batch) if node.transfer_out else out
        return to_host(x, names[-1], batch)

    return run


def to_host(x, node: str, batch: int) -> np.ndarray:
    """``np.asarray(x)``; for a device result that is the D2H wait, and
    it is spanned as ``pipeline.d2h`` of `node`."""
    if not isinstance(x, jax.Array):
        return np.asarray(x)
    with span("pipeline.d2h", node=node, batch=batch):
        return np.asarray(x)


def build_mapped_model(
    model: BNNModel,
    packed_params: list,
    config: EfficientConfiguration,
    *,
    fused: bool = True,
    elide_transfers: bool | None = None,
    registry=None,
) -> Callable:
    """Returns fn(packed_input_words) -> int32 class scores, executing
    each layer with its mapped implementation.

    ``fused=True`` lowers the ``"whole"`` plan and returns its single
    jitted node directly — one XLA executable, no interior host
    roundtrips.

    ``elide_transfers`` applies to the faithful (``fused=False``)
    driver only: ``True`` (plan mode ``"layers"``) crosses the host
    boundary solely where consecutive layers change placement,
    ``False`` (mode ``"roundtrip"``) round-trips around every non-CPU
    layer (paper §IV-A).  ``None`` follows the mapping policy — DP
    configurations were priced under elision.
    """
    if fused:
        plan = build_plan(config, mode="whole")
        [(node, fn)] = build_node_fns(
            model, packed_params, config, plan, registry
        )
        return fn

    if elide_transfers is None:
        elide_transfers = getattr(config, "policy", "greedy") == "dp"
    plan = build_plan(
        config, mode="layers" if elide_transfers else "roundtrip"
    )
    node_fns = build_node_fns(model, packed_params, config, plan, registry)
    return run_plan(node_fns, specs=model.specs)


def build_segment_fns(
    model: BNNModel,
    packed_params: list,
    config: EfficientConfiguration,
    registry=None,
) -> list:
    """One executable per segment of `config`, in execution order —
    the ``"segments"`` plan's node functions.

    Returns ``[(PlanNode, fn), ...]``; ``PlanNode`` duck-types
    ``mapper.Segment`` so existing consumers (the serving pipeline,
    telemetry observers, the fleet ledger) are unchanged.  Device
    segments selected for fusion (``config.fused_segments``) execute
    as one fused kernel with activations bit-packed end to end;
    everything else composes the per-layer implementations under one
    jit.  All arithmetic is integer/bool, so both forms are bit-exact
    versus per-layer execution.
    """
    plan = build_plan(config, mode="segments")
    return build_node_fns(model, packed_params, config, plan, registry)
