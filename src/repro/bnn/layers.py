"""BNN layer specs, parameter init, fp-sim (training) and packed-integer
(inference) per-layer implementations.

Two execution domains:

* **fp-sim** (training): values are float32 in {-1,+1} between layers,
  integers-as-floats for pre-activations; weights are latent fp32
  binarized on the forward pass with the straight-through estimator.
* **packed** (inference): binary tensors are bit-packed int32 words
  (see ``repro.bnn.binarize``); pre-activations are int32; step layers
  use batch-norm folded into integer thresholds (``repro.bnn.fold_bn``).

The packed per-layer functions here are the **CPU implementation** in the
paper's sense — the sequential reference. The parallel X/Y/Z variants
live in ``repro.kernels`` and are selected per layer by the HEP mapper.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.bnn.binarize import (
    PACK_W,
    binarize,
    binarize_ste,
    pack_bits,
    popcount,
    unpack_pm1,
)

BN_EPS = 1e-5
BN_MOMENTUM = 0.1

PACKED = "packed"        # int32 bitplane words, 32 activations/word
UNPACKED = "unpacked"    # one int32 value per element


# ---------------------------------------------------------------------------
# Layer specs / notation parsing
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """Static description of one layer in paper notation."""

    idx: int            # 1-based position, as in the paper's tables
    kind: str           # conv mp step flat fc | stem maxp res gap fcq
    notation: str       # e.g. 'C64', 'MP16', 'S', 'FLAT', 'FC1024'
    in_shape: tuple     # per-example logical shape (no batch), unpacked
    out_shape: tuple    # per-example logical shape (no batch), unpacked
    # conv/fc/stem/res/fcq: number of output units; step/mp/maxp/gap:
    # channel count
    units: int = 0
    stride: int = 1     # stem, maxp: 2; res: 1 or 2

    @property
    def reduce_dim(self) -> int:
        """Reduction length K for conv (9*Cin) / fc (Din)."""
        if self.kind == "conv":
            return 9 * self.in_shape[-1]
        if self.kind == "fc":
            return int(np.prod(self.in_shape))
        return 0


# The token grammar, one (pattern, kind) per layer kind: the paper's
# chain (HEP-BNN Tables I, II), then Bi-Real Net's (arXiv:1808.00278)
_TOKENS = (
    (re.compile(r"C(\d+)"), "conv"),
    (re.compile(r"MP(\d+)"), "mp"),
    (re.compile(r"S"), "step"),
    (re.compile(r"FLAT"), "flat"),
    (re.compile(r"FC(\d+)"), "fc"),
    (re.compile(r"STEM(\d+)"), "stem"),
    (re.compile(r"MAXP"), "maxp"),
    (re.compile(r"RBD?(\d+)"), "res"),
    (re.compile(r"GAP"), "gap"),
    (re.compile(r"FCQ(\d+)"), "fcq"),
)

# (in_encoding, out_encoding) of each kind; mp is absent because it
# preserves whatever encoding flows in.  Bi-Real Net's residual stream
# is int32 between its layers: UNPACKED.
KIND_ENCODINGS = {
    "conv": (PACKED, UNPACKED),
    "fc": (PACKED, UNPACKED),
    "step": (UNPACKED, PACKED),
    "flat": (PACKED, PACKED),
    "stem": (PACKED, UNPACKED),
    "maxp": (UNPACKED, UNPACKED),
    "res": (UNPACKED, UNPACKED),
    "gap": (UNPACKED, UNPACKED),
    "fcq": (UNPACKED, UNPACKED),
}


def match_token(token: str) -> tuple:
    """``(kind, n)`` of a notation token, ``n`` its number or None."""
    for pattern, kind in _TOKENS:
        if m := pattern.fullmatch(token):
            return kind, int(m.group(1)) if m.groups() else None
    raise ValueError(f"unknown layer token {token!r}")


def parse_notation(
    notation: Sequence[str],
    input_hw: tuple,
    in_channels: int,
    n_classes: int,
) -> list[LayerSpec]:
    """Build LayerSpecs from paper notation.

    The final FC layer maps its input to ``n_classes`` (the paper's
    trailing '-> 10', its x names its input width); every other FCx maps
    to x units. Convs are 3x3, SAME (pad value -1); maxpool is 2x2/2
    with MPx asserting output x.

    Bi-Real Net's tokens: ``STEM<c>`` a 7x7/2 int8 conv of the ±1 image
    (zero padding 3) to c channels, requantized to int32; ``MAXP`` a
    3x3/2 max pool (padding 1) of int32 values; ``RB<c>`` a residual
    block, ``RBD<c>`` the same at stride 2 with a downsampling shortcut
    (to c from c/2 channels at the published widths); ``GAP`` a global
    sum pool; ``FCQ<n>`` an int8 fc with bias to n scores.
    """
    specs: list[LayerSpec] = []
    shape: tuple = (*input_hw, in_channels)
    fcs = [i for i, t in enumerate(notation) if match_token(t)[0] == "fc"]
    for i, token in enumerate(notation):
        idx = i + 1
        kind, n = match_token(token)
        units, stride = shape[-1], 1
        if kind == "conv":
            out, units = (shape[0], shape[1], n), n
        elif kind == "mp":
            out = (shape[0] // 2, shape[1] // 2, shape[2])
            if out[0] != n:
                raise ValueError(
                    f"{token} at layer {idx}: 2x2 pool of {shape} gives "
                    f"{out[0]}, expected {n}"
                )
        elif kind == "step":
            out = shape
        elif kind == "flat":
            out, units = (int(np.prod(shape)),), 0
        elif kind == "fc":
            shape = (int(np.prod(shape)),)
            out = (n_classes if i == fcs[-1] else n,)
            units = out[0]
        elif kind in ("stem", "maxp"):
            half = ((shape[0] - 1) // 2 + 1, (shape[1] - 1) // 2 + 1)
            units = n if kind == "stem" else shape[2]
            out, stride = (*half, units), 2
        elif kind == "res":
            stride = 2 if token.startswith("RBD") else 1
            if (stride == 1 and shape[2] != n) or (
                    stride == 2 and (shape[0] % 2 or shape[1] % 2)):
                raise ValueError(
                    f"{token} at layer {idx}: an identity shortcut keeps "
                    f"{n} channels, a stride-2 one halves an even map; "
                    f"the input is {shape}"
                )
            out, units = (shape[0] // stride, shape[1] // stride, n), n
        elif kind == "gap":
            out = (shape[2],)
        else:  # fcq
            if len(shape) != 1:
                raise ValueError(f"{token} at layer {idx} takes a vector")
            out, units = (n,), n
        specs.append(LayerSpec(idx, kind, token, shape, out, units, stride))
        shape = out
    return specs


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------


def init_bnn_params(key: jax.Array, specs: Sequence[LayerSpec]) -> list[dict]:
    """One dict per layer. Trainable: conv/fc 'w' (latent fp32), step
    'gamma'/'beta'. State: step 'mean'/'var' (running stats)."""
    params: list[dict] = []
    for spec in specs:
        if spec.kind not in ("conv", "mp", "step", "flat", "fc"):
            raise ValueError(
                f"layer {spec.notation!r} has no fp-sim (training) path; "
                "pack its integer parameters with pack_params"
            )
        if spec.kind == "conv":
            cin = spec.in_shape[-1]
            key, sub = jax.random.split(key)
            scale = 1.0 / np.sqrt(9 * cin)
            params.append(
                {"w": jax.random.uniform(
                    sub, (3, 3, cin, spec.units), jnp.float32, -scale, scale
                )}
            )
        elif spec.kind == "fc":
            din = spec.in_shape[0]
            key, sub = jax.random.split(key)
            scale = 1.0 / np.sqrt(din)
            params.append(
                {"w": jax.random.uniform(
                    sub, (din, spec.units), jnp.float32, -scale, scale
                )}
            )
        elif spec.kind == "step":
            c = spec.units
            params.append(
                {
                    "gamma": jnp.ones((c,), jnp.float32),
                    "beta": jnp.zeros((c,), jnp.float32),
                    "mean": jnp.zeros((c,), jnp.float32),
                    "var": jnp.ones((c,), jnp.float32),
                }
            )
        else:
            params.append({})
    return params


TRAINABLE_KEYS = {"w", "gamma", "beta"}


def split_trainable(params: list[dict]) -> tuple[list[dict], list[dict]]:
    train = [
        {k: v for k, v in p.items() if k in TRAINABLE_KEYS} for p in params
    ]
    state = [
        {k: v for k, v in p.items() if k not in TRAINABLE_KEYS}
        for p in params
    ]
    return train, state


def merge_params(train: list[dict], state: list[dict]) -> list[dict]:
    return [dict(**t, **s) for t, s in zip(train, state)]


# ---------------------------------------------------------------------------
# fp-sim (training) per-layer forwards
# ---------------------------------------------------------------------------


def conv_fp(x: jax.Array, w_latent: jax.Array) -> jax.Array:
    """3x3 SAME binary conv on {-1,+1} inputs; pad value -1 (binary
    domain has no zero). Output is integer-valued float32."""
    wb = binarize_ste(w_latent)
    xp = jnp.pad(
        x, ((0, 0), (1, 1), (1, 1), (0, 0)), constant_values=-1.0
    )
    return jax.lax.conv_general_dilated(
        xp, wb, window_strides=(1, 1), padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def maxpool_fp(x: jax.Array) -> jax.Array:
    b, h, w, c = x.shape
    return x.reshape(b, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))


def step_fp(
    x: jax.Array, p: dict, *, train: bool
) -> tuple[jax.Array, dict]:
    """Batch norm + binary activation (Hard-Tanh STE). Returns output and
    updated running-stat dict."""
    axes = tuple(range(x.ndim - 1))
    if train:
        mean = jnp.mean(x, axis=axes)
        var = jnp.var(x, axis=axes)
        new_state = {
            "mean": (1 - BN_MOMENTUM) * p["mean"] + BN_MOMENTUM * mean,
            "var": (1 - BN_MOMENTUM) * p["var"] + BN_MOMENTUM * var,
        }
    else:
        mean, var = p["mean"], p["var"]
        new_state = {"mean": p["mean"], "var": p["var"]}
    y = (x - mean) * jax.lax.rsqrt(var + BN_EPS) * p["gamma"] + p["beta"]
    return binarize_ste(y), new_state


def fc_fp(x: jax.Array, w_latent: jax.Array) -> jax.Array:
    return x @ binarize_ste(w_latent)


def forward_fp(
    specs: Sequence[LayerSpec],
    params: list[dict],
    x_pm1: jax.Array,
    *,
    train: bool = False,
) -> tuple[jax.Array, list[dict]]:
    """Full fp-sim forward on a {-1,+1} input batch (B,H,W,C). Returns
    (logits, params-with-updated-bn-state)."""
    new_params = []
    x = x_pm1
    for spec, p in zip(specs, params):
        if spec.kind == "conv":
            x = conv_fp(x, p["w"])
            new_params.append(p)
        elif spec.kind == "mp":
            x = maxpool_fp(x)
            new_params.append(p)
        elif spec.kind == "step":
            x, new_state = step_fp(x, p, train=train)
            new_params.append({**p, **new_state})
        elif spec.kind == "flat":
            x = x.reshape(x.shape[0], -1)
            new_params.append(p)
        elif spec.kind == "fc":
            x = fc_fp(x, p["w"])
            new_params.append(p)
    return x, new_params


def binarize_input(x01: jax.Array) -> jax.Array:
    """Map images in [0,1] to {-1,+1} (threshold 0.5)."""
    return binarize(x01 - 0.5)


# ---------------------------------------------------------------------------
# Packed-integer (inference) per-layer forwards — the 'CPU' implementation
# ---------------------------------------------------------------------------


def extract_patch_words(x_words: jax.Array, stride: int = 1) -> jax.Array:
    """(B,H,W,Cw) packed -> (B,Ho,Wo,9*Cw) 3x3 patches at `stride`,
    padding 1, ``Ho = (H - 1) // stride + 1`` (SAME at stride 1).
    Spatial pad words are 0 == all -1 pixels (the binary-domain pad
    value)."""
    b, h, w, cw = x_words.shape
    ho, wo = (h - 1) // stride + 1, (w - 1) // stride + 1
    xp = jnp.pad(x_words, ((0, 0), (1, 1), (1, 1), (0, 0)))
    offs = [
        xp[:, dy : dy + stride * (ho - 1) + 1 : stride,
           dx : dx + stride * (wo - 1) + 1 : stride, :]
        for dy in range(3)
        for dx in range(3)
    ]
    return jnp.concatenate(offs, axis=-1)


def conv_packed(
    x_words: jax.Array, w_words: jax.Array, k_true: int
) -> jax.Array:
    """Packed binary conv. x_words (B,H,W,Cw); w_words (Cout, 9*Cw);
    output int32 (B,H,W,Cout) with exact {-1,+1} conv values."""
    patches = extract_patch_words(x_words)          # (B,H,W,9Cw)
    # xnor each patch against each output channel's weight words, sum
    # popcounts over the word axis
    xn = ~(patches[:, :, :, None, :] ^ w_words[None, None, None, :, :])
    agree = jnp.sum(popcount(xn), axis=-1, dtype=jnp.int32)
    return 2 * agree - k_true


def maxpool_packed(x: jax.Array) -> jax.Array:
    b, h, w, c = x.shape
    return x.reshape(b, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))


def step_packed(
    x_int: jax.Array, thresh: jax.Array, flip: jax.Array
) -> jax.Array:
    """int32 pre-activations -> packed bits via per-channel integer
    threshold: bit = (x > T) ^ flip."""
    bits = (x_int > thresh) ^ flip
    return pack_bits(bits)


def flat_packed(x_words: jax.Array, channels: int) -> jax.Array:
    """(B,h,w,Cw) -> (B, h*w*Cw). Requires channels % 32 == 0 so no tail
    lanes interleave (true for all paper models at the FLAT position)."""
    if channels % PACK_W != 0:
        raise ValueError("flatten of packed words needs C % 32 == 0")
    return x_words.reshape(x_words.shape[0], -1)


def fc_packed(
    x_words: jax.Array, w_words: jax.Array, k_true: int
) -> jax.Array:
    """Packed binary FC. x (B, Kw); w (Dout, Kw); out int32 (B, Dout)."""
    xn = ~(x_words[:, None, :] ^ w_words[None, :, :])
    agree = jnp.sum(popcount(xn), axis=-1, dtype=jnp.int32)
    return 2 * agree - k_true


# ---------------------------------------------------------------------------
# Bi-Real Net (arXiv:1808.00278) layers, packed inference
# ---------------------------------------------------------------------------
#
# Values between these layers are the int32 residual stream.  Every batch
# norm (with a binary conv's per-channel scale) is folded into fixed
# point: a per-channel int32 multiplier m, bias b and one right shift k,
# ``floor((m * s + b) / 2**k)``.  The int8 convs (stem, shortcut) and the
# fc take int8 weights and accumulate in int32.


def xnor_gemm(a: jax.Array, w_words: jax.Array, k_true: int) -> jax.Array:
    """a (B,P,Kw), w (N,Kw) packed -> (B,P,N) int32 exact ±1 dots: the
    packed fc over every window."""
    b, p, kw = a.shape
    return fc_packed(a.reshape(b * p, kw), w_words, k_true).reshape(b, p, -1)


def requant(s: jax.Array, m, b, k) -> jax.Array:
    """``floor((m * s + b) / 2**k)`` per channel (the last axis), in
    int32: an arithmetic right shift is the floor division."""
    return (m * s + b) >> k


def quantize_int8(s: jax.Array, m, b, k) -> jax.Array:
    """:func:`requant` clipped to [-128, 127], as int8."""
    return jnp.clip(requant(s, m, b, k), -128, 127).astype(jnp.int8)


def border_classes(n_in: int, stride: int) -> np.ndarray:
    """For each output row (or column) of a 3x3 window at `stride`,
    padding 1, over `n_in` input rows: which outer taps lie on the
    padding, bit 0 the first (dy = 0), bit 1 the last (dy = 2)."""
    i = stride * np.arange((n_in - 1) // stride + 1)
    return (i - 1 < 0).astype(np.int32) + 2 * (i + 1 > n_in - 1)


_PADDED_TAPS = (set(), {0}, {2}, {0, 2})    # by border class


def border_table(w_pm1: np.ndarray) -> np.ndarray:
    """(4, 4, Cout) int32 from ±1 weights (3, 3, Cin, Cout): for each
    (row class, column class) the sum of the weights at the taps that
    lie on the padding.  The packed path reads those taps as -1 (pad
    words 0); adding the sum gives zero padding's result.  Nine of the
    sixteen classes occur where both sides of the map exceed one
    pixel."""
    tap = np.asarray(w_pm1, np.int64).sum(axis=2)          # (3, 3, Cout)
    out = np.zeros((4, 4, tap.shape[-1]), np.int32)
    for r, rows in enumerate(_PADDED_TAPS):
        for c, cols in enumerate(_PADDED_TAPS):
            on_pad = np.array([[dy in rows or dx in cols for dx in range(3)]
                               for dy in range(3)])
            out[r, c] = tap[on_pad].sum(axis=0)
    return out


def binary_conv_packed(x: jax.Array, p: dict, stride: int,
                       gemm=xnor_gemm) -> jax.Array:
    """A block's binary conv: sign(x) packed, 3x3 at `stride`, zero
    padding (the -1 pad words, corrected per border class), through
    `gemm`.  x int32 (B,H,W,Cin) -> int32 (B,Ho,Wo,Cout)."""
    words = pack_bits(x >= 0)
    b, h, w, _ = words.shape
    patches = extract_patch_words(words, stride)
    _, ho, wo, kw = patches.shape
    dot = gemm(patches.reshape(b, ho * wo, kw), p["w_words"], p["k_true"])
    corr = p["corr"][border_classes(h, stride)][:, border_classes(w, stride)]
    return dot.reshape(b, ho, wo, -1) + corr


def residual(x: jax.Array, s: jax.Array, p: dict, spec: LayerSpec):
    """A block's output from its input stream `x` and its binary conv's
    sums `s`: the requantized sums plus the shortcut.  The shortcut is
    `x` itself, or at stride 2 a 2x2 sum pool, requantized to int8, a
    1x1 int8 conv and its own requantization."""
    out = requant(s, p["m"], p["b"], p["k"])
    if spec.stride == 1:
        return out + x
    with jax.named_scope(f"{scope_of(spec)}.down"):
        b, h, w, c = x.shape
        a = x.reshape(b, h // 2, 2, w // 2, 2, c).sum(axis=(2, 4))
        q = quantize_int8(a, p["qm"], p["qb"], p["qk"])
        t = jnp.dot(q, p["sw"], preferred_element_type=jnp.int32)
        return out + requant(t, p["sm"], p["sb"], p["sk"])


def stem_packed(x_words: jax.Array, p: dict, cin: int) -> jax.Array:
    """7x7/2 int8 conv of the ±1 image, zero padding 3, requantized."""
    x = unpack_pm1(x_words, cin)
    s = jax.lax.conv_general_dilated(
        x, p["w"], (2, 2), ((3, 3), (3, 3)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.int32,
    )
    return requant(s, p["m"], p["b"], p["k"])


def maxpool3_packed(x: jax.Array) -> jax.Array:
    """3x3/2 max pool, padding 1 (padded taps never win), on int32."""
    return jax.lax.reduce_window(
        x, jnp.int32(np.iinfo(np.int32).min), jax.lax.max,
        (1, 3, 3, 1), (1, 2, 2, 1), ((0, 0), (1, 1), (1, 1), (0, 0)),
    )


def fcq_packed(x: jax.Array, p: dict) -> jax.Array:
    """int32 (B, Din) requantized to int8, an int8 fc, plus the int32
    bias: the int32 scores."""
    q = quantize_int8(x, p["qm"], p["qb"], p["qk"])
    return jnp.dot(q, p["w"], preferred_element_type=jnp.int32) + p["bias"]


def scope_of(spec: LayerSpec) -> str | None:
    """The ``jax.named_scope`` a Bi-Real layer runs under, so that the
    device trace names its operations: ``stem`` (with its pool),
    ``res<idx>`` (its shortcut ``res<idx>.down``), ``head`` (the pool
    and the fc)."""
    if spec.kind == "res":
        return f"res{spec.idx}"
    return {"stem": "stem", "maxp": "stem", "gap": "head",
            "fcq": "head"}.get(spec.kind)


def apply_packed(spec: LayerSpec, p: dict, x: jax.Array,
                 gemm=xnor_gemm) -> jax.Array:
    """One layer of packed inference, the CPU implementation.  `gemm`,
    ``(a (B,P,Kw), w (N,Kw), k_true) -> (B,P,N)``, computes a residual
    block's binary GEMM (a kernel variant's builder)."""
    if spec.kind == "conv":
        return conv_packed(x, p["w_words"], p["k_true"])
    if spec.kind == "mp":
        return maxpool_packed(x)
    if spec.kind == "step":
        return step_packed(x, p["thresh"], p["flip"])
    if spec.kind == "flat":
        return flat_packed(x, spec.in_shape[-1])
    if spec.kind == "fc":
        return fc_packed(x, p["w_words"], p["k_true"])
    with jax.named_scope(scope_of(spec)):
        if spec.kind == "stem":
            return stem_packed(x, p, spec.in_shape[-1])
        if spec.kind == "maxp":
            return maxpool3_packed(x)
        if spec.kind == "res":
            return residual(x, binary_conv_packed(x, p, spec.stride, gemm),
                            p, spec)
        if spec.kind == "gap":
            return jnp.sum(x, axis=(1, 2), dtype=jnp.int32)
        if spec.kind == "fcq":
            return fcq_packed(x, p)
    raise ValueError(f"unknown layer kind {spec.kind!r}")
