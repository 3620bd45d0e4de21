"""Plain reference for the HEP-BNN paper's binarized CNNs (Tables I, II).

It reads a configuration file of this directory (its ``layers`` in the
paper's notation, input size and class count), makes the weights from a
seed, and computes the class scores in straightforward ``jax.numpy`` on
{-1, +1} values.  It imports nothing of the system under test.

Semantics, as the paper states them:

* ``C<n>``: 3x3 convolution, stride 1, SAME, the pad value -1 (the binary
  domain has no 0), ``n`` output channels, binary weights.
* ``MP<n>``: 2x2 max pool, stride 2, on the integer pre-activations.
* ``S``: batch norm followed by sign; ``y >= 0`` gives +1.
* ``FLAT``: row-major flatten of (H, W, C).
* ``FC<n>``: fully connected, binary weights.  The last ``FC`` maps to the
  class count (its ``n`` names its input width, as in the paper).
* The input image in [0, 1] is binarized at 0.5 (``x >= 0.5`` gives +1).

Every product is of two values in {-1, +1}, so each sum is an integer
whose magnitude is at most the reduction length (4608 here).  The
configurations state exact integer accumulation: in float32 such sums are
exact, so :func:`forward` gives the exact scores.  With ``sum_dtype`` it
is the control: the same forward with each layer's sums kept in that
lower type (rounded, and saturated at its largest finite value).
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def layer_shapes(cfg: dict) -> list:
    """``(kind, in_shape, out_shape)`` per layer, unbatched."""
    shape = (*cfg["input_hw"], cfg["in_channels"])
    notation = cfg["layers"]
    last_fc = max(i for i, t in enumerate(notation) if t.startswith("FC"))
    out = []
    for i, tok in enumerate(notation):
        if m := re.fullmatch(r"C(\d+)", tok):
            kind, nxt = "conv", (shape[0], shape[1], int(m.group(1)))
        elif m := re.fullmatch(r"MP(\d+)", tok):
            kind, nxt = "mp", (shape[0] // 2, shape[1] // 2, shape[2])
            if nxt[0] != int(m.group(1)):
                raise ValueError(f"{tok}: a 2x2 pool of {shape} gives {nxt}")
        elif tok == "S":
            kind, nxt = "step", shape
        elif tok == "FLAT":
            kind, nxt = "flat", (int(np.prod(shape)),)
        elif m := re.fullmatch(r"FC(\d+)", tok):
            units = cfg["n_classes"] if i == last_fc else int(m.group(1))
            kind, nxt = "fc", (units,)
        else:
            raise ValueError(f"unknown layer {tok!r}")
        out.append((kind, shape, nxt))
        shape = nxt
    return out


def init_params(cfg: dict, key: jax.Array) -> list:
    """Weights in {-1, +1} and batch-norm statistics, from `key`.

    Batch norm is drawn so that its sign flips at ``t0 + 0.5`` for an
    integer ``t0`` (``t = mean - beta * sd / gamma``): no integer
    pre-activation lies within 0.5 of a flip, so no reading depends on
    rounding in the fold to thresholds.  Half of the channels have
    ``gamma < 0``, which inverts their sign.  The caller jits this."""
    eps = cfg["bn_eps"]
    params = []
    k_prev = None
    for kind, in_shape, out_shape in layer_shapes(cfg):
        key, sub = jax.random.split(key)
        if kind == "conv":
            cin, cout = in_shape[-1], out_shape[-1]
            k_prev = 9 * cin
            params.append({"w": _signs(sub, (3, 3, cin, cout))})
        elif kind == "fc":
            k_prev = in_shape[0]
            params.append({"w": _signs(sub, (in_shape[0], out_shape[0]))})
        elif kind == "step":
            c = out_shape[-1]
            kg, ks, kv, kb, kt = jax.random.split(sub, 5)
            gamma = jax.random.uniform(kg, (c,), jnp.float32, 0.5, 1.5)
            gamma = gamma * _signs(ks, (c,))
            var = k_prev * jax.random.uniform(kv, (c,), jnp.float32, 0.5, 1.5)
            beta = 0.5 * jax.random.normal(kb, (c,), jnp.float32)
            spread = 0.25 * float(np.sqrt(k_prev))
            t0 = jnp.round(spread * jax.random.normal(kt, (c,))) + 0.5
            mean = t0 + beta * jnp.sqrt(var + eps) / gamma
            params.append(
                {"gamma": gamma, "beta": beta, "mean": mean, "var": var}
            )
        else:
            params.append({})
    return params


def _signs(key, shape):
    return jnp.where(jax.random.bernoulli(key, 0.5, shape), 1.0, -1.0)


def binarize_input(x01: np.ndarray) -> np.ndarray:
    """Images in [0, 1] to {-1, +1} float32."""
    return np.where(x01 >= 0.5, 1.0, -1.0).astype(np.float32)


def keep_in(x, dtype):
    """`x` rounded to `dtype`, saturated at its largest finite value."""
    if dtype is None:
        return x
    big = float(jnp.finfo(dtype).max)
    return jnp.clip(x, -big, big).astype(dtype).astype(jnp.float32)


def forward(cfg: dict, params: list, x_pm1, *, sum_dtype=None):
    """{-1, +1} images (B, H, W, C) to float32 class scores (B, classes).
    Integers, exact, unless `sum_dtype` names a lower type for the sums."""
    eps = cfg["bn_eps"]
    x = jnp.asarray(x_pm1, jnp.float32)
    for (kind, _, _), p in zip(layer_shapes(cfg), params):
        if kind == "conv":
            xp = jnp.pad(
                x, ((0, 0), (1, 1), (1, 1), (0, 0)), constant_values=-1.0
            )
            x = keep_in(jax.lax.conv_general_dilated(
                xp, p["w"], (1, 1), "VALID",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                precision=HIGHEST,
            ), sum_dtype)
        elif kind == "mp":
            b, h, w, c = x.shape
            x = x.reshape(b, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))
        elif kind == "step":
            y = (x - p["mean"]) / jnp.sqrt(p["var"] + eps) * p["gamma"]
            x = jnp.where(y + p["beta"] >= 0, 1.0, -1.0)
        elif kind == "flat":
            x = x.reshape(x.shape[0], -1)
        elif kind == "fc":
            x = keep_in(
                jnp.dot(x, p["w"], precision=HIGHEST), sum_dtype
            )
    return x
