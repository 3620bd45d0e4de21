"""Fused whole-segment kernels: one dispatch per device segment, with
activations staying as int32 bitplane words end to end.

The per-layer executors launch one kernel per layer and let every
conv/fc write its unpacked int32 pre-activations back to HBM, only for
the following step layer to read them again, threshold, and repack.
FINN / Larq-CE-style engines get their headline BNN wins by *fusing*
that chain: GEMM -> threshold -> repack happens in on-chip memory and
the segment's interior activations never materialize off-chip.

Two segment-scope builders, registered as ``KernelVariant``\\ s
(``scope="segment"``) so the profiler, DP mapper and serving runtime
price and select them like any other variant:

* ``seg_xla`` — the segment's reference layer chain under a single
  ``jax.jit``: XLA fuses the elementwise tail of each GEMM
  (threshold + shift/or repack) into one executable and launches the
  segment as one dispatch.  Applicable everywhere; the measured
  fallback on hosts without a TPU.
* ``seg_pallas`` — the whole segment as **one** ``pallas_call``: grid
  over the batch (X-parallel, one example per program), every weight /
  threshold array resident in VMEM, and the full layer chain —
  patch-word gather, xnor/popcount GEMM, reshape-max pool, integer
  threshold + bitplane repack, flatten, FC — unrolled inside the
  kernel body.  Interior activations live only in VMEM/registers;
  HBM sees packed words at the segment edges (plus the final int32
  scores).  Runs in interpret mode off the TPU.  Mosaic refuses its
  one-example blocks, so the registry does not offer it on the TPU
  (``kernels/registry.py``).

Both builders compute the exact reference semantics (they reuse the
``repro.bnn.layers`` packed ops on a per-example block), so fused
execution is bit-exact against per-layer execution by construction.

Builder signature (segment scope): ``builder(specs, packed_params,
in_encoding=None) -> fn(x) -> out`` over the segment's layer slice.
``in_encoding`` ("packed" / "unpacked") disambiguates a segment that
*starts* with maxpool layers (mp preserves either encoding); for any
other first layer it is implied by the layer kind.
"""

from __future__ import annotations

import functools
import math
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.bnn import layers as L
from repro.bnn.binarize import PACK_W

PACKED = "packed"
UNPACKED = "unpacked"

# layer kinds whose input encoding is implied by the kind itself
_IN_ENCODING = {
    "conv": PACKED, "fc": PACKED, "flat": PACKED, "step": UNPACKED,
}


def infer_in_encoding(specs: Sequence[L.LayerSpec]) -> str:
    """The encoding a segment's input must arrive in, from its first
    non-mp layer (mp preserves either).  An all-mp segment defaults to
    unpacked — pooling packed words would OR bitplanes, which no valid
    chain produces mid-network without an adjacent non-mp layer."""
    for spec in specs:
        if spec.kind in _IN_ENCODING:
            return _IN_ENCODING[spec.kind]
    return UNPACKED


def encoded_shape(shape: tuple, encoding: str) -> tuple:
    """Per-example array shape for a logical (unpacked) layer shape
    under `encoding`: packed divides the channel axis into 32-bit
    words."""
    if encoding == UNPACKED:
        return tuple(shape)
    return tuple(shape[:-1]) + (math.ceil(shape[-1] / PACK_W),)


def segment_out_encoding(
    specs: Sequence[L.LayerSpec], in_encoding: str
) -> str:
    enc = in_encoding
    for spec in specs:
        if spec.kind in ("conv", "fc"):
            enc = UNPACKED
        elif spec.kind == "step":
            enc = PACKED
        elif spec.kind == "flat":
            enc = PACKED
    return enc


def _run_chain(specs: Sequence[L.LayerSpec], packed_params, x):
    """The segment's reference layer chain on a batched array —
    the single source of semantics for both fused builders."""
    for spec, p in zip(specs, packed_params):
        if spec.kind == "conv":
            x = L.conv_packed(x, p["w_words"], p["k_true"])
        elif spec.kind == "mp":
            x = L.maxpool_packed(x)
        elif spec.kind == "step":
            x = L.step_packed(x, p["thresh"], p["flip"])
        elif spec.kind == "flat":
            x = L.flat_packed(x, spec.in_shape[-1])
        elif spec.kind == "fc":
            x = L.fc_packed(x, p["w_words"], p["k_true"])
        else:
            raise ValueError(spec.kind)
    return x


def segment_weight_bytes(packed_params) -> int:
    """Bytes of parameter data the fused kernel keeps resident."""
    total = 0
    for p in packed_params:
        for v in p.values():
            if hasattr(v, "size"):
                total += int(v.size) * 4
    return total


def segment_vmem_bytes(
    specs: Sequence[L.LayerSpec],
    packed_params,
    in_encoding: str | None = None,
) -> int:
    """Resident-footprint estimate of the fused kernel per example:
    all weights plus the largest unpacked intermediate (double-buffered
    in/out).  Applicability gates on this against the VMEM budget."""
    if in_encoding is None:
        in_encoding = infer_in_encoding(specs)
    peak = 0
    enc = in_encoding
    for spec in specs:
        in_elems = 1
        for d in encoded_shape(spec.in_shape, enc):
            in_elems *= d
        if spec.kind in ("conv", "fc"):
            enc = UNPACKED
        elif spec.kind == "step":
            enc = PACKED
        out_elems = 1
        for d in encoded_shape(spec.out_shape, enc):
            out_elems *= d
        peak = max(peak, (in_elems + out_elems) * 4)
    return segment_weight_bytes(packed_params) + peak


def segment_gemm_work(
    specs: Sequence[L.LayerSpec], packed_params, batch: int
) -> int:
    """Total word-level MAC count of the segment's GEMM layers at
    `batch` — the interpret-mode size proxy (``GemmShape.work``
    summed)."""
    work = 0
    for spec, p in zip(specs, packed_params):
        if spec.kind not in ("conv", "fc"):
            continue
        n, kw = (int(d) for d in p["w_words"].shape)
        pwin = spec.in_shape[0] * spec.in_shape[1] if spec.kind == "conv" else 1
        work += batch * pwin * n * kw
    return work


# ---------------------------------------------------------------------------
# seg_xla: the segment chain as one XLA executable
# ---------------------------------------------------------------------------


def build_xla_segment(
    specs: Sequence[L.LayerSpec],
    packed_params,
    in_encoding: str | None = None,
):
    """One jitted executable for the whole segment — XLA fuses the
    GEMM tails (threshold/repack) so the chain is a single dispatch."""
    specs = tuple(specs)
    packed_params = tuple(packed_params)

    @jax.jit
    def run(x):
        return _run_chain(specs, packed_params, x)

    return run


# ---------------------------------------------------------------------------
# seg_pallas: the whole segment as one pallas_call
# ---------------------------------------------------------------------------


def _segment_kernel(x_ref, *refs, specs, param_slots, k_trues):
    """Pallas kernel body: one example's full segment chain.  The
    block shapes keep a leading batch dim of 1, so the reference layer
    ops apply unchanged — interior activations are kernel-local values
    (VMEM/registers), never written to HBM."""
    x = x_ref[...]                       # (1, *in_shape)
    params = []
    for spec, slot in zip(specs, param_slots):
        if spec.kind in ("conv", "fc"):
            params.append(
                {"w_words": refs[slot][...], "k_true": k_trues[spec.idx]}
            )
        elif spec.kind == "step":
            params.append(
                {
                    "thresh": refs[slot][...],
                    # flip travels as int32 (TPU-friendly); the xor in
                    # step_packed needs the original bool semantics
                    "flip": refs[slot + 1][...].astype(jnp.bool_),
                }
            )
        else:
            params.append({})
    out = _run_chain(specs, params, x)
    refs[-1][...] = out.astype(jnp.int32)


def build_pallas_segment(
    specs: Sequence[L.LayerSpec],
    packed_params,
    in_encoding: str | None = None,
    *,
    interpret: bool | None = None,
):
    """The whole segment as one ``pallas_call``.

    Grid is ``(B,)`` with X parallel — one example per program, the
    paper's X aspect at segment granularity.  Every parameter array is
    a full-block VMEM input (weights stay resident across the chain);
    the input/output blocks carry one example in the segment's edge
    encodings.  Returns ``fn(x) -> out`` with reference semantics.
    """
    specs = tuple(specs)
    if in_encoding is None:
        in_encoding = infer_in_encoding(specs)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    out_encoding = segment_out_encoding(specs, in_encoding)
    in_shape = encoded_shape(specs[0].in_shape, in_encoding)
    out_shape = encoded_shape(specs[-1].out_shape, out_encoding)

    # flatten parameter arrays into pallas inputs; record, per layer,
    # its first slot index in that flat list
    arrays, param_slots, k_trues = [], [], {}
    for spec, p in zip(specs, packed_params):
        param_slots.append(len(arrays))
        if spec.kind in ("conv", "fc"):
            arrays.append(jnp.asarray(p["w_words"], jnp.int32))
            k_trues[spec.idx] = int(p["k_true"])
        elif spec.kind == "step":
            arrays.append(jnp.asarray(p["thresh"], jnp.int32))
            arrays.append(jnp.asarray(p["flip"], jnp.int32))

    kernel = functools.partial(
        _segment_kernel,
        specs=specs,
        param_slots=tuple(param_slots),
        k_trues=k_trues,
    )
    param_specs = [
        pl.BlockSpec(a.shape, lambda *idx, _nd=a.ndim: (0,) * _nd)
        for a in arrays
    ]

    def run(x):
        b = x.shape[0]
        call = pl.pallas_call(
            kernel,
            grid=(b,),
            in_specs=[
                pl.BlockSpec(
                    (1,) + in_shape,
                    lambda i: (i,) + (0,) * len(in_shape),
                ),
                *param_specs,
            ],
            out_specs=pl.BlockSpec(
                (1,) + out_shape,
                lambda i: (i,) + (0,) * len(out_shape),
            ),
            out_shape=jax.ShapeDtypeStruct((b,) + out_shape, jnp.int32),
            interpret=interpret,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",)
            ),
        )
        return call(x, *arrays)

    return jax.jit(run)
