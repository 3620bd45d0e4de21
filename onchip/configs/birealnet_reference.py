"""Plain reference for Bi-Real-Net-18 (Liu et al., "Bi-Real Net",
ECCV 2018, arXiv:1808.00278; github.com/liuzechun/Bi-Real-net,
``pytorch_implementation/BiReal18_34/birealnet.py``, ``birealnet18``).

It reads a configuration file of this directory (its ``layers``, input
size and class count), makes the weights and integer parameters from a
seed, and computes the class scores in straightforward ``jax.numpy`` in
float32 at ``HIGHEST``.  It imports nothing of the system under test.

Layers, as published, in the configuration's fixed-point arithmetic:

* ``STEM<c>``: conv 7x7, stride 2, zero padding 3, real (int8) weights,
  then batch norm; the published code has no ReLU here.
* ``MAXP``: max pool 3x3, stride 2, padding 1 (padded taps never win).
* ``RB<c>`` / ``RBD<c>``: ``out = BN(binconv3x3(sign(x))) + shortcut(x)``,
  no activation after the add.  ``sign(v)`` is +1 for v >= 0, else -1.
  The binary conv is ±1 x ±1 at stride 1 (``RB``) or 2 (``RBD``) with
  zero padding 1: padded taps add 0.  Its per-channel ``mean|W|`` scale
  is folded with the batch norm.  ``shortcut(x) = x``, or for ``RBD``
  ``BN(conv1x1(avgpool2x2_s2(x)))`` with int8 weights.
* ``GAP``: global average pool, kept as the sum over positions (the
  1/(H*W) is folded into the next requantization).
* ``FCQ<n>``: fc to n classes, int8 weights, int32 bias.

Fixed point.  Every batch norm (with a scale) is ``floor((m*s + b) /
2**k)`` per channel: an integer multiplier m, bias b and one shift k,
drawn from the seed in the ranges below.  The input of an int8 conv or
the fc is requantized the same way and clipped to [-128, 127].  The
residual stream is an integer.  Scores are the fc's integer sums.

**Why float32 is exact here.**  Every product, partial sum and stream
value is an integer below 2**24 in magnitude, which float32 holds
exactly, so any order of summation gives the same integers (at full
width, 224x224, 512 channels; smaller configurations only lower the
bounds):

* stem: |x| = 1, |w| <= 127, 7*7*3 = 147 taps, so |s| <= 18,669;
  |m| <= 255, |b| <= 255 * 1,500 + 2**15 = 415,268:
  |m*s + b| <= 5,175,863; k >= 9 gives |y| <= 10,110 < 2**14.  The
  max pool keeps that bound.
* binary conv: |s| <= 9*512 = 4,608; |m| <= 2,047, |b| <= 2**16:
  |m*s + b| <= 9,498,112; k >= 11 gives |y| <= 4,638 < 2**13.
* shortcut conv: |q| <= 128, |w| <= 127, Cin <= 256:
  |t| <= 4,161,536; |m| <= 3, |b| <= 2**16: |m*t + b| <= 12,550,144;
  k >= 10 gives |y| <= 12,257 < 2**14.
* stream: each stage starts from the stem's pool or a shortcut
  (< 2**14) and adds four blocks (< 2**13 each): |v| < 3 * 2**14 =
  49,152.
* the shortcut's quantizer: |sum of 4| < 196,608; |m| <= 63,
  |b| <= 2**16: |m*a + b| <= 12,451,840.
* the head: |sum over 7*7| < 2,408,448; its quantizer |m| <= 5,
  |b| <= 2**16: |m*g + b| <= 12,107,776; the fc over 512 int8 inputs
  |t| <= 8,323,072, with |bias| <= 2**15: <= 8,355,840.

With ``sum_dtype`` it is the control: the same forward with every sum
(each conv's and the fc's, the pool over positions, the stream after
each add) kept in that lower type (rounded, saturated at its largest
finite value).
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST

# (|m| low, |m| high, |b| max, k low, k high); a batch norm's m has a
# random sign (gamma < 0 inverts a channel), a quantizer's is positive
STEM = (64, 255, 2**15, 9, 10)
MAIN = (512, 2047, 2**16, 11, 12)
SHORT = (1, 3, 2**16, 10, 11)
Q_SHORT = (16, 63, 2**16, 9, 10)
Q_FC = (1, 5, 2**16, 10, 11)
FC_BIAS = 2**15
INT8_W = 127
# the stem's bias also takes |m| times a shift from this range, about
# 1.4 spreads of its sums, so that the pooled stem straddles 0 (as a
# trained net's first blocks see both signs)
STEM_CENTER = (1000, 1500)


def layer_shapes(cfg: dict) -> list:
    """``(kind, in_shape, out_shape)`` per layer, unbatched: ``stem``,
    ``maxpool``, ``block`` (stride 2 where the map halves), ``gap``,
    ``fc_int8``."""
    shape = (*cfg["input_hw"], cfg["in_channels"])
    out = []
    for tok in cfg["layers"]:
        h, w, c = shape if len(shape) == 3 else (1, 1, shape[0])
        half = ((h - 1) // 2 + 1, (w - 1) // 2 + 1)
        if m := re.fullmatch(r"STEM(\d+)", tok):
            kind, nxt = "stem", (*half, int(m.group(1)))
        elif tok == "MAXP":
            kind, nxt = "maxpool", (*half, c)
        elif m := re.fullmatch(r"RB(D?)(\d+)", tok):
            s = 2 if m.group(1) else 1
            kind, nxt = "block", (h // s, w // s, int(m.group(2)))
        elif tok == "GAP":
            kind, nxt = "gap", (c,)
        elif m := re.fullmatch(r"FCQ(\d+)", tok):
            kind, nxt = "fc_int8", (int(m.group(1)),)
        else:
            raise ValueError(f"unknown layer {tok!r}")
        out.append((kind, shape, nxt))
        shape = nxt
    return out


def macs_per_image(cfg: dict) -> dict:
    """MACs of one image: ``binary`` (the blocks' ±1 convs) and ``int8``
    (the stem, the 1x1 shortcuts and the fc)."""
    binary = int8 = 0
    for kind, (*_, cin), out in layer_shapes(cfg):
        if kind == "stem":
            int8 += out[0] * out[1] * 49 * cin * out[2]
        elif kind == "block":
            binary += out[0] * out[1] * 9 * cin * out[2]
            if cin != out[2]:
                int8 += out[0] * out[1] * cin * out[2]
        elif kind == "fc_int8":
            int8 += cin * out[0]
    return {"binary": int(binary), "int8": int(int8)}


def _fold(key, c: int, ranges, signed: bool, center=None) -> dict:
    lo, hi, b_max, k_lo, k_hi = ranges
    km, ks, kb, kk, kc = jax.random.split(key, 5)
    m = jax.random.randint(km, (c,), lo, hi + 1).astype(jnp.float32)
    b = jax.random.randint(kb, (c,), -b_max, b_max + 1).astype(jnp.float32)
    if center is not None:
        b = b - m * jax.random.randint(kc, (c,), center[0], center[1] + 1)
    if signed:
        m = m * jnp.where(jax.random.bernoulli(ks, 0.5, (c,)), 1.0, -1.0)
    k = jax.random.randint(kk, (), k_lo, k_hi + 1).astype(jnp.float32)
    return {"m": m, "b": b, "k": k}


def _int8(key, shape):
    return jax.random.randint(key, shape, -INT8_W, INT8_W + 1).astype(
        jnp.float32)


def _signs(key, shape):
    return jnp.where(jax.random.bernoulli(key, 0.5, shape), 1.0, -1.0)


def _prefixed(fold: dict, pre: str) -> dict:
    return {pre + k: v for k, v in fold.items()}


def init_params(cfg: dict, key: jax.Array) -> list:
    """Weights and fixed-point parameters, integers in float32, from
    `key`.  The caller jits this."""
    params = []
    for kind, in_shape, out_shape in layer_shapes(cfg):
        key, kw, kf, kq, ks, ksf = jax.random.split(key, 6)
        cin, cout = in_shape[-1], out_shape[-1]
        if kind == "stem":
            params.append({"w": _int8(kw, (7, 7, cin, cout)),
                           **_fold(kf, cout, STEM, True, STEM_CENTER)})
        elif kind == "block":
            p = {"w": _signs(kw, (3, 3, cin, cout)),
                 **_fold(kf, cout, MAIN, True)}
            if in_shape[0] != out_shape[0]:
                p.update(_prefixed(_fold(kq, cin, Q_SHORT, False), "q"),
                         sw=_int8(ks, (cin, cout)),
                         **_prefixed(_fold(ksf, cout, SHORT, True), "s"))
            params.append(p)
        elif kind == "fc_int8":
            bias = jax.random.randint(ks, (cout,), -FC_BIAS, FC_BIAS + 1)
            params.append({"w": _int8(kw, (cin, cout)),
                           "bias": bias.astype(jnp.float32),
                           **_prefixed(_fold(kq, cin, Q_FC, False), "q")})
        else:
            params.append({})
    return params


def binarize_input(x01: np.ndarray) -> np.ndarray:
    """Images in [0, 1] to {-1, +1} float32 (``x >= 0.5`` gives +1)."""
    return np.where(x01 >= 0.5, 1.0, -1.0).astype(np.float32)


def keep_in(x, dtype):
    """`x` rounded to `dtype`, saturated at its largest finite value."""
    if dtype is None:
        return x
    big = float(jnp.finfo(dtype).max)
    return jnp.clip(x, -big, big).astype(dtype).astype(jnp.float32)


def _requant(v, p: dict, pre: str = ""):
    return jnp.floor((p[pre + "m"] * v + p[pre + "b"]) / 2.0 ** p[pre + "k"])


def _quantize(v, p: dict, pre: str):
    return jnp.clip(_requant(v, p, pre), -128.0, 127.0)


def _conv(x, w, stride: int, pad: int):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST,
    )


def forward(cfg: dict, params: list, x_pm1, *, sum_dtype=None,
            record: list | None = None):
    """{-1, +1} images (B, H, W, C) to float32 class scores (B, classes).
    Integers, exact, unless `sum_dtype` names a lower type for the sums.
    With `record` (outside ``jit``), the largest magnitude of each
    layer's sums and products is appended to it as ``(name, value)``."""
    def seen(name, v):
        if record is not None:
            record.append((name, float(jnp.max(jnp.abs(v)))))
        return v

    x = jnp.asarray(x_pm1, jnp.float32)
    for i, ((kind, in_shape, out_shape), p) in enumerate(
            zip(layer_shapes(cfg), params)):
        if kind == "stem":
            s = seen("stem.sum", keep_in(_conv(x, p["w"], 2, 3), sum_dtype))
            seen("stem.fold", p["m"] * s + p["b"])
            x = _requant(s, p)
        elif kind == "maxpool":
            x = jax.lax.reduce_window(
                x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                ((0, 0), (1, 1), (1, 1), (0, 0)),
            )
        elif kind == "block":
            stride = in_shape[0] // out_shape[0]
            xs = jnp.where(x >= 0, 1.0, -1.0)
            s = seen(f"{i}.sum", keep_in(_conv(xs, p["w"], stride, 1),
                                         sum_dtype))
            seen(f"{i}.fold", p["m"] * s + p["b"])
            y = _requant(s, p)
            if stride == 1:
                short = x
            else:
                b, h, w, c = x.shape
                a = seen(f"{i}.pool",
                         x.reshape(b, h // 2, 2, w // 2, 2, c).sum((2, 4)))
                seen(f"{i}.qfold", p["qm"] * a + p["qb"])
                t = keep_in(jnp.einsum("bhwc,cd->bhwd", _quantize(a, p, "q"),
                                       p["sw"], precision=HIGHEST), sum_dtype)
                seen(f"{i}.sfold", p["sm"] * seen(f"{i}.ssum", t) + p["sb"])
                short = _requant(t, p, "s")
            x = seen(f"{i}.stream", keep_in(y + short, sum_dtype))
        elif kind == "gap":
            x = seen("gap", keep_in(x.sum(axis=(1, 2)), sum_dtype))
        elif kind == "fc_int8":
            seen("fc.qfold", p["qm"] * x + p["qb"])
            q = _quantize(x, p, "q")
            x = seen("fc.sum", keep_in(
                jnp.dot(q, p["w"], precision=HIGHEST) + p["bias"], sum_dtype))
    return x
