"""Blockwise-softmax (flash) attention Pallas kernel — the LM prefill
hot-spot (32k-token prefill would otherwise materialize a 32k x 32k
score tensor per head).

Grid (B, H, nQ, nK) with nK innermost/sequential; running max, sum and
accumulator live in VMEM scratch persisted across the nK steps
(initialized at ik == 0, written to the output block at ik == nK - 1).
GQA folding: kv-head block index = h // (H // Hkv). Causal masking uses
suffix alignment (query i sees keys j <= i + Sk - Sq) and a finite
-1e30 mask so fully-computed blocks underflow to zero weight instead of
producing NaNs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, scale: float, causal: bool, q_blk: int, k_blk: int, sq: int, sk: int,
):
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)            # (q_blk, D)
    k = k_ref[0, 0].astype(jnp.float32)            # (k_blk, D)
    v = v_ref[0, 0].astype(jnp.float32)            # (k_blk, D)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                       # (q_blk, k_blk)

    if causal:
        iq = pl.program_id(2)
        qi = iq * q_blk + jax.lax.broadcasted_iota(
            jnp.int32, (q_blk, k_blk), 0
        )
        kj = ik * k_blk + jax.lax.broadcasted_iota(
            jnp.int32, (q_blk, k_blk), 1
        )
        s = jnp.where(kj <= qi + (sk - sq), s, _NEG)

    m_prev = m_ref[...]                             # (q_blk, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)                          # (q_blk, k_blk)
    alpha = jnp.exp(m_prev - m_new)                 # (q_blk, 1)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: float | None = None,
    q_blk: int = 128,
    k_blk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """q (B,H,Sq,D); k,v (B,Hkv,Sk,D), Hkv | H. Returns (B,H,Sq,D)."""
    B, H, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    assert H % Hkv == 0
    group = H // Hkv
    q_blk = min(q_blk, Sq)
    k_blk = min(k_blk, Sk)
    if Sq % q_blk or Sk % k_blk:
        raise ValueError("Sq/Sk must be multiples of the block sizes")
    scale = float(scale if scale is not None else 1.0 / (D ** 0.5))
    nq, nk = Sq // q_blk, Sk // k_blk

    scratch = [
        pltpu.VMEM((q_blk, 1), jnp.float32),
        pltpu.VMEM((q_blk, 1), jnp.float32),
        pltpu.VMEM((q_blk, D), jnp.float32),
    ]

    return pl.pallas_call(
        functools.partial(
            _flash_kernel,
            scale=scale, causal=causal,
            q_blk=q_blk, k_blk=k_blk, sq=Sq, sk=Sk,
        ),
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, q_blk, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec(
                (1, 1, k_blk, D),
                lambda b, h, iq, ik: (b, h // group, ik, 0),
            ),
            pl.BlockSpec(
                (1, 1, k_blk, D),
                lambda b, h, iq, ik: (b, h // group, ik, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, q_blk, D), lambda b, h, iq, ik: (b, h, iq, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "parallel", "parallel", "arbitrary"
            )
        ),
    )(q, k, v)
