"""Bit-packed xnor/popcount binary GEMM as a Pallas TPU kernel.

Unified layer compute (see docs/ARCHITECTURE.md §2): activations are packed words
``a (B, P, Kw) int32`` (P = conv windows per image, or 1 for FC), weights
``w (N, Kw) int32`` (N output channels / neurons), output
``o (B, P, N) int32`` with the exact {-1,+1} dot product
``2 * popcount(xnor) - k_true``.

X/Y/Z aspect mapping (paper §II-C -> TPU):
  X (data)   -> grid over B, one image per parallel step
  Y (window) -> grid over P tiles of ``p_blk`` windows
  Z (neuron) -> grid over N tiles of ``n_blk`` channels

All three axes are always grid dimensions (so VMEM blocks stay bounded);
an aspect makes its dimension **parallel** (outermost, Mosaic
``dimension_semantics='parallel'`` — distributed over TensorCores), a
non-aspect dimension is **arbitrary** (innermost, sequential — CUDA's
"images processed one after another in a thread block"). This preserves
the paper's 8-way configuration space with TPU-native semantics: the
aspect choice changes grid order and therefore weight/activation block
reuse distance, i.e. HBM traffic (modeled in core/cost_model.py).

This is a VPU (vector-unit) workload — popcount/xor are not MXU ops; the
MXU idles. BlockSpec lane dims are kept at multiples of 8x128 where the
problem allows; int32 words mean Kw is typically small (<=160 words).
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ASPECTS_ALL = ("X", "Y", "Z")


def _norm_aspects(aspects) -> tuple:
    s = frozenset(aspects)
    bad = s - set(ASPECTS_ALL)
    if bad:
        raise ValueError(f"unknown aspects {bad}")
    return tuple(a for a in ASPECTS_ALL if a in s)  # canonical X,Y,Z order


def _kernel(a_ref, w_ref, o_ref, *, k_true: int):
    # a_ref: (1, p_blk, Kw); w_ref: (n_blk, Kw); o_ref: (1, p_blk, n_blk)
    a = a_ref[0]                      # (p_blk, Kw)
    w = w_ref[...]                    # (n_blk, Kw)
    xn = ~(a[:, None, :] ^ w[None, :, :])         # (p_blk, n_blk, Kw)
    # population_count on int32 counts two's-complement bits — exactly
    # the packed-lane agreement count
    agree = jnp.sum(jax.lax.population_count(xn), axis=-1, dtype=jnp.int32)
    o_ref[0] = (2 * agree - k_true).astype(jnp.int32)


def xnor_gemm_pallas(
    a: jax.Array,
    w: jax.Array,
    k_true: int,
    aspects: Sequence[str] = ("X", "Y", "Z"),
    *,
    p_blk: int = 128,
    n_blk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Pallas xnor GEMM. a (B,P,Kw) int32, w (N,Kw) int32 -> (B,P,N)."""
    B, P, Kw = a.shape
    N, Kw2 = w.shape
    assert Kw == Kw2, (Kw, Kw2)
    aspects = _norm_aspects(aspects)
    p_blk = min(p_blk, P)
    n_blk = min(n_blk, N)

    # grid axes in canonical (B, P, N) order, then re-ordered so aspect
    # (parallel) dims are outermost
    axis_order = [ax for ax in ("X", "Y", "Z") if ax in aspects] + [
        ax for ax in ("X", "Y", "Z") if ax not in aspects
    ]
    sizes = {"X": B, "Y": pl.cdiv(P, p_blk), "Z": pl.cdiv(N, n_blk)}
    grid = tuple(sizes[ax] for ax in axis_order)
    pos = {ax: i for i, ax in enumerate(axis_order)}

    def a_index(*idx):
        return (idx[pos["X"]], idx[pos["Y"]], 0)

    def w_index(*idx):
        return (idx[pos["Z"]], 0)

    def o_index(*idx):
        return (idx[pos["X"]], idx[pos["Y"]], idx[pos["Z"]])

    dim_sem = tuple(
        "parallel" if ax in aspects else "arbitrary" for ax in axis_order
    )

    return pl.pallas_call(
        functools.partial(_kernel, k_true=k_true),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, p_blk, Kw), a_index),
            pl.BlockSpec((n_blk, Kw), w_index),
        ],
        out_specs=pl.BlockSpec((1, p_blk, n_blk), o_index),
        out_shape=jax.ShapeDtypeStruct((B, P, N), jnp.int32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(dimension_semantics=dim_sem),
    )(a, w)
