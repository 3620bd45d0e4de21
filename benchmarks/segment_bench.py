"""Fused-segment benchmark: one whole device-resident segment executed
as a single fused dispatch (segment-scope kernel variants,
``repro.kernels.segment_fused``) versus the per-layer launch the
pre-plan driver used — one jitted executable per layer, with a
blocking sync after each.

The workload is ``fashion_mnist`` under the mapping the HEP-BNN search
itself tends to find on this container: the first conv (patch
extraction over the unpacked input image — the one genuinely
compute-heavy layer at bench scale) on the host, everything after it
on the device.  That leaves one device-resident segment spanning
layers ``1..N`` — nine layers whose per-layer execution pays a
dispatch + host sync at every boundary, while the fused variants keep
activations as int32 bitplane words resident on the device and pay one
dispatch for the whole segment.  At batch 1 (the latency-critical
serving case) the per-layer launch tax dominates this segment, which
is exactly the regime segment fusion targets; at larger batches the
GEMM work amortizes the tax and the two paths converge.

For each batch size and each applicable segment-scope variant
(``seg_xla`` always; ``seg_pallas`` off the TPU, when the segment fits
the interpret work cap / VMEM budget), the bench asserts the fused output
bit-exact against the per-layer chain (and against the model's
reference ``forward_packed``), then times best-of-``repeats``.

Rows (``us_per_call`` is us per **example**):

    segment/<model>/b<B>/span<s>:<e>/per_layer    baseline launch
    segment/<model>/b<B>/span<s>:<e>/<variant>    fused, derived
                                                  carries speedup
    segment/<model>/fused_bitexact                functional row
                                                  (us=0 sentinel)

The functional row is the CI coverage gate: its presence proves the
bit-exactness asserts ran; ``derived`` reports the best measured
speedup.  Timing rows are regression-gated like every other suite
(``benchmarks/bench_smoke.py``).
"""

from __future__ import annotations

import time

import jax
import numpy as np

from repro.bnn import build_model
from repro.bnn.models import forward_packed, pack_params, prepare_input_packed
from repro.core.mapped_model import _layer_fns
from repro.core.mapper import price_mapping
from repro.core.parallel_config import CPU, FULL_GPU
from repro.core.plan import build_plan, device_spans
from repro.core.profiler import profile_bnn_model
from repro.kernels.registry import (
    DEFAULT_REGISTRY,
    current_platform,
    segment_shape_of,
)


def _timeit(fn, x, repeats: int) -> float:
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(x)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best


def run(
    scale: float = 0.5,
    batch_sizes=(1, 4),
    repeats: int = 3,
    profile_repeats: int = 1,
    min_speedup: float | None = None,
):
    """``min_speedup`` asserts the best fused-vs-per-layer ratio (the
    acceptance check is >= 1.5x at batch 1 on this container); ``None``
    reports without asserting — timings on a loaded box are advisory."""
    m = build_model("fashion_mnist", scale=scale)
    packed = pack_params(m.specs, m.init(jax.random.PRNGKey(0)))
    table = profile_bnn_model(
        m, packed, batch_sizes=batch_sizes, repeats=profile_repeats
    )
    # first conv on the host, the rest device-resident: one multi-layer
    # device segment (module docstring)
    mapping = (CPU,) + tuple(FULL_GPU for _ in m.specs[1:])
    platform = current_platform()
    device = jax.devices()[0]

    rows = []
    best_speedup = 0.0
    variants_seen: set = set()
    for b in batch_sizes:
        ec = price_mapping(table, b, mapping)
        plan = build_plan(ec, mode="segments")
        (start, stop) = device_spans(ec)[0]
        assert (start, stop) == (1, len(m.specs)), "expected one segment"
        node = next(n for n in plan.nodes if n.on_device)

        x = prepare_input_packed(
            jax.random.uniform(
                jax.random.PRNGKey(1), (b, *m.input_hw, m.in_channels)
            )
        )
        want = np.asarray(forward_packed(m.specs, packed, x))

        # per-layer launch: one jitted executable per layer, blocking
        # sync at every boundary — the pre-plan execution structure
        layer_fns = [jax.jit(f) for f in _layer_fns(m, packed, ec)]
        xd = jax.device_put(
            np.asarray(layer_fns[0](np.asarray(x))), device
        )                                    # host layer 0's output, H2D

        def per_layer(xd, _fns=tuple(layer_fns[start:stop])):
            for f in _fns:
                xd = f(xd)
                jax.block_until_ready(xd)
            return xd

        assert np.array_equal(want, np.asarray(per_layer(xd)))  # warmup
        t_layer = _timeit(per_layer, xd, repeats)
        span = f"span{start}:{stop}"
        rows.append(
            (
                f"segment/{m.name}/b{b}/{span}/per_layer",
                t_layer / b * 1e6,
                f"layers={stop - start}",
            )
        )

        shape = segment_shape_of(m.specs[start:stop], packed[start:stop], b)
        for v in DEFAULT_REGISTRY.applicable_segments(shape, platform):
            fn = v.builder(
                tuple(m.specs[start:stop]),
                list(packed[start:stop]),
                node.in_encoding,
            )
            got = np.asarray(fn(xd))
            assert np.array_equal(want, got), (
                f"fused {v.name} != per-layer output"
            )
            t_fused = _timeit(fn, xd, repeats)
            speedup = t_layer / t_fused
            best_speedup = max(best_speedup, speedup)
            variants_seen.add(v.name)
            rows.append(
                (
                    f"segment/{m.name}/b{b}/{span}/{v.name}",
                    t_fused / b * 1e6,
                    f"speedup={speedup:.2f}x",
                )
            )
    assert variants_seen, "no segment-scope variant was applicable"
    if min_speedup is not None:
        assert best_speedup >= min_speedup, (
            f"best fused speedup {best_speedup:.2f}x < {min_speedup}x"
        )
    rows.append(
        (
            f"segment/{m.name}/fused_bitexact",
            0.0,
            f"variants={','.join(sorted(variants_seen))};"
            f"best_speedup={best_speedup:.2f}x",
        )
    )
    return rows
