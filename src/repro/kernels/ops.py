"""Jit'd public entry points for the kernels.

``backend`` selects the execution tier:
  * ``'ref'``     — pure-jnp oracle (the CPU implementation)
  * ``'variant'`` — aspect-structured XLA program (profiled live)
  * ``'pallas'``  — the Pallas TPU kernel, native on a TPU backend and
    in interpret mode elsewhere (``interpret=None`` picks by backend)
"""

from __future__ import annotations

from functools import partial

import jax

from repro.kernels import ref as _ref
from repro.kernels.variants import xnor_gemm_variant
from repro.kernels.xnor_popcount import xnor_gemm_pallas
from repro.kernels.flash_attention import flash_attention_pallas


def _interpret(interpret: bool | None) -> bool:
    return jax.default_backend() != "tpu" if interpret is None else interpret


@partial(
    jax.jit,
    static_argnames=("k_true", "aspects", "backend", "interpret",
                     "p_blk", "n_blk"),
)
def xnor_gemm(
    a: jax.Array,
    w: jax.Array,
    *,
    k_true: int,
    aspects: tuple = ("X", "Y", "Z"),
    backend: str = "ref",
    interpret: bool | None = None,
    p_blk: int = 128,
    n_blk: int = 128,
) -> jax.Array:
    if backend == "ref":
        return _ref.xnor_gemm_ref(a, w, k_true)
    if backend == "variant":
        return xnor_gemm_variant(a, w, k_true, frozenset(aspects))
    if backend == "pallas":
        return xnor_gemm_pallas(
            a, w, k_true, aspects,
            p_blk=p_blk, n_blk=n_blk, interpret=_interpret(interpret),
        )
    raise ValueError(f"unknown backend {backend!r}")


@partial(
    jax.jit,
    static_argnames=("k_true", "aspects", "backend", "interpret",
                     "p_blk", "n_blk"),
)
def binary_conv2d(
    x_words: jax.Array,
    w_words: jax.Array,
    *,
    k_true: int,
    aspects: tuple = ("X", "Y", "Z"),
    backend: str = "ref",
    interpret: bool | None = None,
    p_blk: int = 128,
    n_blk: int = 128,
) -> jax.Array:
    """Packed 3x3 SAME conv = window extraction + xnor GEMM.
    x_words (B,H,W,Cw), w_words (Cout, 9*Cw) -> (B,H,W,Cout) int32."""
    from repro.bnn.layers import extract_patch_words

    b, h, w_, _ = x_words.shape
    patches = extract_patch_words(x_words).reshape(b, h * w_, -1)
    out = xnor_gemm(
        patches, w_words,
        k_true=k_true, aspects=aspects, backend=backend,
        interpret=interpret, p_blk=p_blk, n_blk=n_blk,
    )
    return out.reshape(b, h, w_, -1)


@partial(
    jax.jit,
    static_argnames=("causal", "scale", "backend", "interpret",
                     "q_blk", "k_blk"),
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: float | None = None,
    backend: str = "pallas",
    interpret: bool | None = None,
    q_blk: int = 128,
    k_blk: int = 128,
) -> jax.Array:
    if backend == "ref":
        return _ref.attention_ref(q, k, v, causal=causal, scale=scale).astype(
            q.dtype
        )
    return flash_attention_pallas(
        q, k, v, causal=causal, scale=scale,
        q_blk=q_blk, k_blk=k_blk, interpret=_interpret(interpret),
    )
