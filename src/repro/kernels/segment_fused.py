"""Fused whole-segment kernels: one dispatch per device segment, with
activations staying as int32 bitplane words end to end.

The per-layer executors launch one kernel per layer and let every
conv/fc write its unpacked int32 pre-activations back to HBM, only for
the following step layer to read them again, threshold, and repack.
FINN / Larq-CE-style engines get their headline BNN wins by *fusing*
that chain: GEMM -> threshold -> repack happens in on-chip memory and
the segment's interior activations never materialize off-chip.

Three segment-scope builders, registered as ``KernelVariant``\\ s
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

* ``seg_mxu`` — the same chain in a ±1 int8 domain: each conv / fc
  is an int8 x int8 -> int32 convolution or matmul (the MXU on a
  TPU) over weights unpacked once at build time, each step writes
  ±1 int8, and packed words appear only at the segment's edges.
  Offered on the TPU only (``kernels/registry.py``).

``seg_xla`` and ``seg_pallas`` compute the exact reference semantics
(they reuse the ``repro.bnn.layers`` packed ops on a per-example
block), so fused execution is bit-exact against per-layer execution by
construction.  ``seg_mxu`` is exact too: a sum of at most 8192
products of ±1 values is an integer that int32 holds, and the
binary-domain pad (−1) and dropped tail lanes match the packed
convention (``repro.bnn.binarize``).

Bi-Real Net's kinds (stem, 3x3 max pool, residual block, global pool,
int8 fc) run in ``seg_xla`` and ``seg_mxu`` through the same layer
functions (``repro.bnn.layers.apply_packed``), with the int32 residual
stream between them; only a block's binary conv differs: xnor-popcount
on -1-padded patch words plus the border correction in ``seg_xla``, an
int8 ±1 convolution with zero padding in ``seg_mxu``.  ``seg_mxu`` also
runs a stem and the max pool directly after it as one Pallas kernel
(:func:`stem_pool_mxu`), whose conv map never leaves the core.
``seg_pallas`` takes the paper's kinds only.

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
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.bnn import layers as L
from repro.bnn.binarize import PACK_W, pack_bits, unpack_pm1
from repro.bnn.layers import KIND_ENCODINGS, PACKED, UNPACKED


def infer_in_encoding(specs: Sequence[L.LayerSpec]) -> str:
    """The encoding a segment's input must arrive in, from its first
    non-mp layer (mp preserves either).  An all-mp segment defaults to
    unpacked — pooling packed words would OR bitplanes, which no valid
    chain produces mid-network without an adjacent non-mp layer."""
    for spec in specs:
        if spec.kind in KIND_ENCODINGS:
            return KIND_ENCODINGS[spec.kind][0]
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
        enc = KIND_ENCODINGS.get(spec.kind, (enc, enc))[1]
    return enc


def _run_chain(specs: Sequence[L.LayerSpec], packed_params, x):
    """The segment's reference layer chain on a batched array —
    the single source of semantics for both fused builders."""
    for spec, p in zip(specs, packed_params):
        x = L.apply_packed(spec, p, x)
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
        enc = KIND_ENCODINGS.get(spec.kind, (enc, enc))[1]
        out_elems = 1
        for d in encoded_shape(spec.out_shape, enc):
            out_elems *= d
        peak = max(peak, (in_elems + out_elems) * 4)
    return segment_weight_bytes(packed_params) + peak


def segment_gemm_work(
    specs: Sequence[L.LayerSpec], packed_params, batch: int
) -> int:
    """Total word-level MAC count of the segment's packed GEMMs (conv,
    fc, a residual block's binary conv) at `batch` — the interpret-mode
    size proxy (``GemmShape.work`` summed)."""
    work = 0
    for spec, p in zip(specs, packed_params):
        if "w_words" not in p:
            continue
        n, kw = (int(d) for d in p["w_words"].shape)
        out = spec.out_shape
        pwin = out[0] * out[1] if len(out) == 3 else 1
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
# seg_mxu: the segment chain with its ±1 products as int8 GEMMs
# ---------------------------------------------------------------------------


def _mxu_params(specs: Sequence[L.LayerSpec], packed_params) -> list:
    """Each conv's ``w_words (Cout, 9*Cw)`` as HWIO ``(3, 3, Cin,
    Cout)`` int8 ±1, each fc's ``(Dout, Kw)`` as ``(Din, Dout)``: the
    patch order of ``extract_patch_words`` (dy-major, dx-minor), tail
    lanes dropped.  A residual block's binary weights likewise, beside
    its other parameters (no border table: the MXU pads 0 itself); a
    stem with the max pool after it, its fused kernel's operands
    (:func:`stem_pool_params`); the int8 layers' parameters as they
    are."""
    out = []
    for i, (spec, p) in enumerate(zip(specs, packed_params)):
        if _stem_fused_at(specs, i):
            out.append(stem_pool_params(spec, p))
        elif spec.kind in ("conv", "res"):
            cin = spec.in_shape[-1]
            w = jnp.asarray(p["w_words"])
            w = unpack_pm1(w.reshape(w.shape[0], 9, -1), cin)
            w = w.reshape(-1, 3, 3, cin).transpose(1, 2, 3, 0)
            rest = {k: v for k, v in p.items()
                    if k not in ("w_words", "k_true", "corr")}
            out.append({"w": w, **rest})
        elif spec.kind == "fc":
            w = unpack_pm1(jnp.asarray(p["w_words"]), spec.in_shape[0])
            out.append({"w": w.T})
        elif spec.kind == "step":
            out.append({"thresh": p["thresh"], "flip": p["flip"]})
        elif spec.kind in ("stem", "fcq"):
            out.append(dict(p))
        else:
            out.append({})
    return out


_PM1 = "pm1"  # int8 ±1 activations, one per lane


def _run_mxu_chain(specs, params, x, form: str):
    """The segment chain on int8 ±1 activations.  `form` is what `x`
    holds: packed words, unpacked int32 sums, or ±1 lanes.  A stem with
    the max pool after it runs as one kernel (:func:`stem_pool_mxu`)."""
    layers = iter(zip(specs, params))
    for spec, p in layers:
        if spec.kind == "stem" and "t" in p:
            next(layers)                 # the max pool, fused
            with jax.named_scope(L.scope_of(spec)):
                x, form = stem_pool_mxu(x, p, spec), UNPACKED
        elif spec.kind == "res":
            with jax.named_scope(L.scope_of(spec)):
                s = jax.lax.conv_general_dilated(
                    jnp.where(x >= 0, jnp.int8(1), jnp.int8(-1)), p["w"],
                    (spec.stride, spec.stride), ((1, 1), (1, 1)),
                    dimension_numbers=("NHWC", "HWIO", "NHWC"),
                    preferred_element_type=jnp.int32,
                )
                x = L.residual(x, s, p, spec)
        elif spec.kind in ("stem", "maxp", "gap", "fcq"):
            x, form = L.apply_packed(spec, p, x), UNPACKED
        elif spec.kind in ("conv", "fc"):
            if form == PACKED:
                x = unpack_pm1(x, spec.in_shape[-1])
            if spec.kind == "conv":
                x = jnp.pad(
                    x, ((0, 0), (1, 1), (1, 1), (0, 0)), constant_values=-1
                )
                x = jax.lax.conv_general_dilated(
                    x, p["w"], (1, 1), "VALID",
                    dimension_numbers=("NHWC", "HWIO", "NHWC"),
                    preferred_element_type=jnp.int32,
                )
            else:
                x = jnp.dot(x, p["w"], preferred_element_type=jnp.int32)
            form = UNPACKED
        elif spec.kind == "mp":
            if form == _PM1:
                # pool what the packed chain pools: the words
                x, form = pack_bits(x), PACKED
            x = L.maxpool_packed(x)
        elif spec.kind == "step":
            bits = (x > p["thresh"]) ^ p["flip"]
            x = jnp.where(bits, jnp.int8(1), jnp.int8(-1))
            form = _PM1
        elif spec.kind == "flat":
            # C % 32 == 0 here, so lanes and words flatten alike
            x = x.reshape(x.shape[0], -1)
        else:
            raise ValueError(spec.kind)
    return pack_bits(x) if form == _PM1 else x


def build_mxu_segment(
    specs: Sequence[L.LayerSpec],
    packed_params,
    in_encoding: str | None = None,
):
    """One jitted executable for the segment whose conv / fc products
    are int8 x int8 -> int32 GEMMs on ±1 values.  Weights are unpacked
    to int8 here, once, and bound with ``functools.partial`` as the
    executable's operands rather than folded in as constants, so its
    compiled form does not depend on their values (one compile-cache
    entry serves every set of weights).  The segment's input and output
    keep the packed chain's encodings, and its int32 results equal
    ``seg_xla``'s bit for bit."""
    specs = tuple(specs)
    if in_encoding is None:
        in_encoding = infer_in_encoding(specs)

    @jax.jit
    def run(params, x):
        return _run_mxu_chain(specs, params, x, in_encoding)

    return functools.partial(run, _mxu_params(specs, packed_params))


# ---------------------------------------------------------------------------
# seg_mxu's stem: the 7x7/2 int8 conv, its requantization and the 3x3/2
# max pool as one pallas_call, so that only the pooled map leaves the core
# ---------------------------------------------------------------------------
#
# The conv.  Its GEMM rows are (band, conv row), a band being 4 conv
# columns, which read 13 padded pixel columns: 8j .. 8j + 12 for band j.
# One int32 word a padded pixel column holds the conv row's 8 pixel rows
# (2oy .. 2oy + 7, the last a zero tap), 4 bits each: a bit set on the
# zero padding, then the channel bits.  A band's 16-word window is
# rolled to lane 0 and copied to 8 lane groups, one a pixel row, and
# each lane then reads its row's bit of a channel: 2 * bit - 1 on the
# image, 0 on the padding.  So a GEMM row is cin blocks of 128 lanes
# (pixel row, window column), and the weights are a banded matrix that gives
# the band's 4 columns of C channels.  The rows of a band come even conv
# rows first, then odd, which is what the pool reads.
#
# The pool.  requant(s) = (m*s + b) >> k does not decrease in s where
# m >= 0 and does not increase where m < 0.  So per channel, with
# sigma = -1 where m < 0 and +1 elsewhere, over any window
#     max(requant(s)) == requant(sigma * max(sigma * s)),
# and requant(sigma * v) = (|m| * v + b) >> k.  sigma is folded into the
# weights; the kernel pools sigma * s (padded taps are the int32 minimum
# and never win; |s| <= 49 * cin * 127, so sigma * s never overflows)
# and requantizes the pooled positions alone.

_STEM_BAND = 4       # conv columns a GEMM row computes
_STEM_GROUP = 4      # bands a GEMM (448 rows at 224x224)
_INT32_MIN = -(2 ** 31)


def _stem_fused_at(specs, i: int) -> bool:
    return (specs[i].kind == "stem" and specs[i].in_shape[-1] <= 3
            and i + 1 < len(specs) and specs[i + 1].kind == "maxp")


def fuses_stem(specs) -> bool:
    """Whether ``seg_mxu`` runs a stem of `specs` and the max pool
    directly after it as one kernel: a stem of at most 3 channels
    directly followed by ``maxp``."""
    return any(_stem_fused_at(specs, i) for i in range(len(specs)))


def _stem_geometry(spec: L.LayerSpec) -> tuple:
    """``(hq, bands, hpad, wpad, cp)``: pooled rows computed (a multiple
    of 8: even and odd conv rows, hq each), bands computed (a multiple of
    the GEMM's group), the padded input's rows and lanes, and lanes of
    one conv column's channels (C to a multiple of 64, so that two
    pooled columns fill whole vregs)."""
    h, w, _ = spec.in_shape
    ho, wo, c = spec.out_shape
    hq = -(-((ho - 1) // 2 + 1) // 8) * 8
    bands = -(-wo // (_STEM_BAND * _STEM_GROUP)) * _STEM_GROUP
    wpad = -(-max(8 * bands + 8, w + 3) // 128) * 128
    return hq, bands, 4 * hq + 6, wpad, -(-c // 64) * 64


def stem_pool_params(spec: L.LayerSpec, p: dict) -> dict:
    """The fused stem's operands from the stem's int8 HWIO ``w`` and its
    fold ``m``, ``b``, ``k``: the banded weights ``t`` (cin * 128,
    4 * cp) with sigma folded in, row (channel, pixel row, window
    column), column (conv column, channel); and ``m`` = |m|, ``b``, ``k``
    for two pooled columns of channels (1, 2 * cp).  Weights take
    [-127, 127], the symmetric int8 range, so that sigma * w is int8
    too."""
    *_, cp = _stem_geometry(spec)
    w = np.asarray(p["w"], np.int32)
    m, b = np.asarray(p["m"], np.int32), np.asarray(p["b"], np.int32)
    if w.min() < -127:
        raise ValueError("the fused stem takes int8 weights in [-127, 127]")
    cin, c = w.shape[2:]
    t = np.zeros((cin, 8, 16, _STEM_BAND, cp), np.int8)
    for col in range(_STEM_BAND):
        # conv column col reads window columns 2 * col .. 2 * col + 6
        t[:, :7, 2 * col:2 * col + 7, col, :c] = (
            w * np.where(m < 0, -1, 1)).transpose(2, 0, 1, 3)

    def two_columns(v):
        return jnp.asarray(np.tile(np.pad(v, (0, cp - c)), 2)[None])

    return {"t": jnp.asarray(t.reshape(cin * 128, _STEM_BAND * cp)),
            "m": two_columns(np.abs(m)), "b": two_columns(b),
            "k": two_columns(np.full(c, int(p["k"]), np.int32))}


def _stem_input(x, spec: L.LayerSpec) -> jax.Array:
    """Packed words (B, H, W, 1) -> (B, wpad / 128, hpad, 128) int32,
    padded by 3 above and to the left with words of the sign bit alone,
    in chunks of 128 columns (the kernel's strided loads read 128-lane
    rows).  A packed word never has its sign bit set."""
    h, w, cin = spec.in_shape
    _, _, hpad, wpad, _ = _stem_geometry(spec)
    words = jnp.pad(x[..., 0], ((0, 0), (3, hpad - 3 - h), (3, wpad - 3 - w)),
                    constant_values=_INT32_MIN)
    return words.reshape(-1, hpad, wpad // 128, 128).transpose(0, 2, 1, 3)


def _stem_pool_kernel(x_ref, t_ref, m_ref, b_ref, k_ref, o_ref, *,
                      cin, hq, bands, cp, ho, wo, hp, wp):
    """One image: the column words, then GEMMs of `_STEM_GROUP` bands;
    each band's pooled rows (conv rows 2py - 1, 2py, 2py + 1: the odd
    row above, the even row, the odd row) and its pooled columns (conv
    columns 4j - 1 .. 4j + 1 and 4j + 1 .. 4j + 3: the band before's
    last, the band's own), requantized and stored as pooled columns 2j
    and 2j + 1 of the (Hp * Wp, C) map."""
    floor = jnp.int32(_INT32_MIN)
    srl = jax.lax.shift_right_logical
    iota = jax.lax.broadcasted_iota
    cols = []                    # a (2 * hq, 128) array a 128 columns
    for ch in range(x_ref.shape[1]):
        col = None
        for dy in range(8):
            r = jnp.concatenate([x_ref[0, ch, pl.ds(dy, hq, stride=4), :],
                                 x_ref[0, ch, pl.ds(dy + 2, hq, stride=4), :]])
            # rotated left by one: a padding bit, then the channel bits
            r = ((r << 1) | srl(r, 31)) & ((2 << cin) - 1)
            col = r if col is None else col | (r << (4 * dy))
        cols.append(col)
    rows = 2 * hq
    lane = iota(jnp.int32, (rows, 128), 1)
    nibble = 4 * (lane // 16)
    row = iota(jnp.int32, (hq, 4 * cp), 0)
    lane4 = iota(jnp.int32, (hq, 4 * cp), 1)
    low_half = iota(jnp.int32, (hq, 2 * cp), 1) < cp
    left = jnp.full((hq, 2 * cp), floor)         # conv column -1
    for g in range(0, bands, _STEM_GROUP):
        blocks = []
        for j in range(g, g + _STEM_GROUP):
            # the band's window, words 8j .. 8j + 15, at lanes 0 .. 15
            ch, off = divmod(8 * j, 128)
            span = (cols[ch] if off <= 112 else
                    jnp.concatenate(cols[ch:ch + 2], axis=1))
            if off:
                span = pltpu.roll(span, span.shape[1] - off, 1)
            wd = span[:, :128]
            wd = jnp.where(lane % 32 < 16, wd, pltpu.roll(wd, 16, 1))
            wd = jnp.where(lane % 64 < 32, wd, pltpu.roll(wd, 32, 1))
            wd = jnp.where(lane < 64, wd, pltpu.roll(wd, 64, 1))
            # 2 * bit - 1 on the image, 0 on the padding
            off_image = (srl(wd, nibble) & 1) - 1
            blocks.append(jnp.concatenate(
                [(srl(wd, nibble + i) & 2) + off_image
                 for i in range(cin)], axis=1))
        s = jnp.dot(jnp.concatenate(blocks).astype(jnp.int8), t_ref[...],
                    preferred_element_type=jnp.int32)
        for jj in range(_STEM_GROUP):
            j = g + jj
            if 2 * j >= wp:
                return
            even = s[jj * rows:jj * rows + hq]
            odd = s[jj * rows + hq:(jj + 1) * rows]
            if ho % 2:                   # conv row ho: the pool's padding
                odd = jnp.where(row == ho // 2, floor, odd)
            above = jnp.where(row == 0, floor, pltpu.roll(odd, 1, 0))
            r = jnp.maximum(jnp.maximum(even, odd), above)
            if 4 * j < wo < 4 * j + 4:   # conv columns past the map
                r = jnp.where(lane4 >= (wo - 4 * j) * cp, floor, r)
            # lanes (conv column, channel): [c0 c1] and [c2 c3]
            c01, c23 = r[:, :2 * cp], r[:, 2 * cp:]
            c10 = pltpu.roll(c01, cp, 1)
            c32 = pltpu.roll(c23, cp, 1)
            v = jnp.where(low_half,
                          jnp.maximum(jnp.maximum(c01, c10), left),
                          jnp.maximum(jnp.maximum(c01, c32), c23))
            left = c32
            q = (m_ref[...] * v + b_ref[...]) >> k_ref[...]
            c = o_ref.shape[-1]
            o_ref[0, pl.ds(2 * j, hp, stride=wp), :] = q[:hp, :c]
            if 2 * j + 1 < wp:
                o_ref[0, pl.ds(2 * j + 1, hp, stride=wp), :] = (
                    pltpu.roll(q, cp, 1)[:hp, :c])


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def stem_pool_mxu(x: jax.Array, p: dict, spec: L.LayerSpec) -> jax.Array:
    """``maxpool3_packed(stem_packed(x))`` bit for bit, as one kernel
    over the batch: packed words (B, H, W, 1) -> int32 (B, Hp, Wp, C).
    `p` is :func:`stem_pool_params`'s."""
    hq, bands, hpad, wpad, cp = _stem_geometry(spec)
    cin = spec.in_shape[-1]
    ho, wo, c = spec.out_shape
    hp, wp = (ho - 1) // 2 + 1, (wo - 1) // 2 + 1
    b = x.shape[0]
    kernel = functools.partial(_stem_pool_kernel, cin=cin, hq=hq,
                               bands=bands, cp=cp, ho=ho, wo=wo, hp=hp,
                               wp=wp)
    fixed = [pl.BlockSpec(p[key].shape, lambda i: (0, 0))
             for key in ("t", "m", "b", "k")]
    out = pl.pallas_call(
        kernel,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, wpad // 128, hpad, 128),
                               lambda i: (i, 0, 0, 0)),
                  *fixed],
        out_specs=pl.BlockSpec((1, hp * wp, c), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hp * wp, c), jnp.int32),
        interpret=_interpret(),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
    )(_stem_input(x, spec), p["t"], p["m"], p["b"], p["k"])
    return out.reshape(b, hp, wp, c)


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
