"""Binarization + bit-packing primitives.

Bit convention: bit 1 encodes +1, bit 0 encodes -1. Packing is along the
last axis, 32 values per int32 word, LSB first. Tail lanes (when the axis
length is not a multiple of 32) are padded with ``pad_bit``: activations
use 0, weights use 1, so that `xnor` tail lanes are identically 0 and
``2 * popcount(xnor(a, w)) - K`` equals the exact {-1,+1} dot product over
the K true lanes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

PACK_W = 32  # bits per packed word


def binarize(x: jax.Array) -> jax.Array:
    """Hard sign into {-1, +1}; ties (x == 0) go to +1 (paper's `>` is
    strict on the shifted form, equivalent to >= 0 here)."""
    return jnp.where(x >= 0, 1.0, -1.0).astype(x.dtype)


@jax.custom_vjp
def binarize_ste(x: jax.Array) -> jax.Array:
    """Sign forward, clipped straight-through estimator backward
    (gradient passes where |x| <= 1, i.e. the Hard-Tanh STE of the paper's
    training recipe [Hubara et al. 2016])."""
    return binarize(x)


def _ste_fwd(x):
    return binarize(x), x


def _ste_bwd(x, g):
    return (g * (jnp.abs(x) <= 1.0).astype(g.dtype),)


binarize_ste.defvjp(_ste_fwd, _ste_bwd)


def packed_len(n: int) -> int:
    return (n + PACK_W - 1) // PACK_W


def pack_bits(x: jax.Array, pad_bit: int = 0) -> jax.Array:
    """Pack a {-1,+1} (or {0,1} boolean) array along the last axis into
    int32 words.

    Accepts float/int arrays in {-1,+1} or bool arrays; bit = (x > 0) for
    numeric inputs, x itself for bool.
    """
    if x.dtype == jnp.bool_:
        bits = x
    else:
        bits = x >= 0  # ties -> +1, matching binarize()
    n = bits.shape[-1]
    n_words = packed_len(n)
    pad = n_words * PACK_W - n
    if pad:
        fill = jnp.full(bits.shape[:-1] + (pad,), bool(pad_bit))
        bits = jnp.concatenate([bits, fill], axis=-1)
    bits = bits.reshape(bits.shape[:-1] + (n_words, PACK_W))
    # int32 throughout (Mosaic has no reductions over unsigned integers):
    # the 32 lane values are disjoint bits, so their wrapping sum is the
    # packed word, with lane 31 landing in the sign bit
    shifts = jnp.arange(PACK_W, dtype=jnp.int32)
    return jnp.sum(bits.astype(jnp.int32) << shifts, axis=-1, dtype=jnp.int32)


def unpack_bits(words: jax.Array, n: int) -> jax.Array:
    """Unpack int32 words into a float32 {-1,+1} array of last-axis
    length ``n`` (tail lanes dropped)."""
    w = words.astype(jnp.uint32)
    shifts = jnp.arange(PACK_W, dtype=jnp.uint32)
    bits = (w[..., None] >> shifts) & jnp.uint32(1)
    flat = bits.reshape(bits.shape[:-2] + (bits.shape[-2] * PACK_W,))
    flat = flat[..., :n]
    return jnp.where(flat == 1, 1.0, -1.0).astype(jnp.float32)


def unpack_pm1(words: jax.Array, n: int) -> jax.Array:
    """Packed words ``(..., Kw)`` -> int8 ±1 ``(..., n)``, tail lanes
    dropped."""
    bits = (words[..., None] >> jnp.arange(PACK_W, dtype=jnp.int32)) & 1
    bits = bits.reshape(words.shape[:-1] + (-1,))[..., :n]
    return (2 * bits - 1).astype(jnp.int8)


def popcount(x: jax.Array) -> jax.Array:
    """Population count on int32 words, result int32 (two's-complement
    bits, so the sign bit counts like any other lane)."""
    return jax.lax.population_count(x.astype(jnp.int32))


def xnor_dot_words(a_words: jax.Array, w_words: jax.Array, k_true: int) -> jax.Array:
    """Exact {-1,+1} dot product of two packed vectors (last axis =
    words): ``2 * sum(popcount(~(a ^ w))) - k_true``.

    Relies on the tail-padding convention (a tail bit 0, w tail bit 1)
    making xnor tail lanes 0.
    """
    agree = jnp.sum(
        popcount(~(a_words ^ w_words)), axis=-1, dtype=jnp.int32
    )
    # popcount(xnor) counts only true-lane agreements (tail lanes are 0 by
    # the padding convention), so dot = agree - (k_true - agree).
    return 2 * agree - k_true


def np_pack_bits(x: np.ndarray, pad_bit: int = 0) -> np.ndarray:
    """NumPy twin of pack_bits for host-side weight preparation."""
    bits = (x >= 0) if x.dtype != np.bool_ else x
    n = bits.shape[-1]
    n_words = packed_len(n)
    pad = n_words * PACK_W - n
    if pad:
        fill = np.full(bits.shape[:-1] + (pad,), bool(pad_bit))
        bits = np.concatenate([bits, fill], axis=-1)
    bits = bits.reshape(bits.shape[:-1] + (n_words, PACK_W)).astype(np.uint32)
    shifts = np.arange(PACK_W, dtype=np.uint32)
    words = np.sum(bits << shifts, axis=-1, dtype=np.uint64).astype(np.uint32)
    return words.view(np.int32).reshape(words.shape)
