"""xnor GEMM kernel: pallas (interpret) + all 7 aspect variants vs the
pure-jnp oracle, across shape/block sweeps; packing properties."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.bnn.binarize import (
    np_pack_bits, pack_bits, unpack_bits, packed_len
)
from repro.kernels.ops import xnor_gemm, binary_conv2d
from repro.kernels.ref import xnor_gemm_ref
from repro.kernels.variants import xnor_gemm_variant

ALL_ASPECTS = [
    ("X",), ("Y",), ("Z",), ("X", "Y"), ("X", "Z"), ("Y", "Z"),
    ("X", "Y", "Z"),
]


def _packed_operands(key, b, p, k_bits, n):
    """Random ±1 operands in both packed and unpacked form."""
    ka, kw = jax.random.split(key)
    a_pm1 = jnp.where(jax.random.bernoulli(ka, 0.5, (b, p, k_bits)), 1.0, -1.0)
    w_pm1 = jnp.where(jax.random.bernoulli(kw, 0.5, (n, k_bits)), 1.0, -1.0)
    a_words = pack_bits(a_pm1, pad_bit=0)
    w_words = pack_bits(w_pm1, pad_bit=1)
    return a_pm1, w_pm1, a_words, w_words


@pytest.mark.parametrize("b,p,k_bits,n", [
    (1, 1, 32, 1),        # minimal
    (2, 9, 33, 5),        # tail lanes
    (3, 50, 64, 64),
    (4, 17, 100, 10),     # paper-ish FC tail
    (2, 128, 288, 32),    # conv C32 (9*32)
])
def test_xnor_matches_float_dot(b, p, k_bits, n):
    a_pm1, w_pm1, a_words, w_words = _packed_operands(
        jax.random.PRNGKey(b * 1000 + n), b, p, k_bits, n
    )
    want = jnp.einsum("bpk,nk->bpn", a_pm1, w_pm1).astype(jnp.int32)
    got = xnor_gemm_ref(a_words, w_words, k_bits)
    assert np.array_equal(np.asarray(want), np.asarray(got))


@pytest.mark.parametrize("aspects", ALL_ASPECTS)
def test_variants_match_ref(aspects):
    _, _, a_words, w_words = _packed_operands(
        jax.random.PRNGKey(7), 3, 21, 70, 13
    )
    ref = xnor_gemm_ref(a_words, w_words, 70)
    got = xnor_gemm_variant(a_words, w_words, 70, frozenset(aspects))
    assert np.array_equal(np.asarray(ref), np.asarray(got))


@pytest.mark.parametrize("aspects", ALL_ASPECTS)
@pytest.mark.parametrize("p_blk,n_blk", [(8, 8), (16, 32), (128, 128)])
def test_pallas_matches_ref(aspects, p_blk, n_blk):
    _, _, a_words, w_words = _packed_operands(
        jax.random.PRNGKey(11), 2, 24, 96, 48
    )
    ref = xnor_gemm_ref(a_words, w_words, 96)
    got = xnor_gemm(
        a_words, w_words, k_true=96, aspects=aspects,
        backend="pallas", interpret=True, p_blk=p_blk, n_blk=n_blk,
    )
    assert np.array_equal(np.asarray(ref), np.asarray(got))


def test_pallas_ragged_tiles():
    """P, N not multiples of the block sizes."""
    _, _, a_words, w_words = _packed_operands(
        jax.random.PRNGKey(13), 2, 37, 65, 29
    )
    ref = xnor_gemm_ref(a_words, w_words, 65)
    got = xnor_gemm(
        a_words, w_words, k_true=65, aspects=("X", "Z"),
        backend="pallas", interpret=True, p_blk=16, n_blk=16,
    )
    assert np.array_equal(np.asarray(ref), np.asarray(got))


@pytest.mark.parametrize("backend", ["ref", "variant", "pallas"])
def test_binary_conv_matches_fp_conv(backend):
    key = jax.random.PRNGKey(3)
    b, h, w, cin, cout = 2, 8, 8, 33, 17
    kx, kw = jax.random.split(key)
    x_pm1 = jnp.where(jax.random.bernoulli(kx, 0.5, (b, h, w, cin)), 1.0, -1.0)
    wt = jnp.where(
        jax.random.bernoulli(kw, 0.5, (3, 3, cin, cout)), 1.0, -1.0
    )
    xp = jnp.pad(x_pm1, ((0, 0), (1, 1), (1, 1), (0, 0)), constant_values=-1.0)
    want = jax.lax.conv_general_dilated(
        xp, wt, (1, 1), "VALID", dimension_numbers=("NHWC", "HWIO", "NHWC")
    ).astype(jnp.int32)

    x_words = pack_bits(x_pm1, pad_bit=0)
    wt_np = np.transpose(np.asarray(wt), (3, 0, 1, 2)).reshape(cout, 9, cin)
    w_words = jnp.asarray(np_pack_bits(wt_np, pad_bit=1).reshape(cout, -1))
    got = binary_conv2d(
        x_words, w_words, k_true=9 * cin, backend=backend,
        aspects=("Y", "Z"), interpret=True, p_blk=16, n_blk=8,
    )
    assert np.array_equal(np.asarray(want), np.asarray(got))


# ---------------------------------------------------------------------------
# Packing properties (hypothesis)
# ---------------------------------------------------------------------------


@settings(max_examples=50, deadline=None)
@given(
    n=st.integers(1, 130),
    seed=st.integers(0, 2**31 - 1),
)
def test_pack_unpack_roundtrip(n, seed):
    rng = np.random.default_rng(seed)
    x = np.where(rng.random((3, n)) < 0.5, -1.0, 1.0).astype(np.float32)
    words = pack_bits(jnp.asarray(x))
    back = unpack_bits(words, n)
    assert np.array_equal(np.asarray(back), x)
    assert words.shape[-1] == packed_len(n)


@settings(max_examples=30, deadline=None)
@given(
    k_bits=st.integers(1, 97),
    seed=st.integers(0, 2**31 - 1),
)
def test_xnor_dot_exact_vs_float(k_bits, seed):
    """Property: packed dot == float dot for any K (tail correctness)."""
    rng = np.random.default_rng(seed)
    a = np.where(rng.random((1, 1, k_bits)) < 0.5, -1.0, 1.0)
    w = np.where(rng.random((2, k_bits)) < 0.5, -1.0, 1.0)
    want = (a[0] @ w.T).astype(np.int64)
    got = xnor_gemm_ref(
        pack_bits(jnp.asarray(a), 0), pack_bits(jnp.asarray(w), 1), k_bits
    )
    assert np.array_equal(want, np.asarray(got)[0])


def test_np_jnp_pack_agree():
    rng = np.random.default_rng(0)
    x = np.where(rng.random((4, 77)) < 0.5, -1.0, 1.0).astype(np.float32)
    assert np.array_equal(
        np_pack_bits(x, 1), np.asarray(pack_bits(jnp.asarray(x), 1))
    )
    assert np.array_equal(
        np_pack_bits(x, 0), np.asarray(pack_bits(jnp.asarray(x), 0))
    )


@pytest.mark.parametrize("n", [1, 31, 32, 33, 77, 130])
@pytest.mark.parametrize("pad_bit", [0, 1])
def test_pack_bits_int32_matches_uint32_packing(n, pad_bit):
    """pack_bits packs in int32; the words are bit-identical to the
    unsigned packing (sum of uint32 lane shifts, reinterpreted as
    int32), pad lanes and the sign lane included."""
    rng = np.random.default_rng(n * 2 + pad_bit)
    bits = rng.random((5, n)) < 0.5
    n_words = packed_len(n)
    padded = np.concatenate(
        [bits, np.full((5, n_words * 32 - n), bool(pad_bit))], axis=-1
    ).reshape(5, n_words, 32)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    want = jnp.sum(
        jnp.asarray(padded).astype(jnp.uint32) << shifts,
        axis=-1, dtype=jnp.uint32,
    ).astype(jnp.int32)
    got = pack_bits(jnp.asarray(bits), pad_bit)
    assert got.dtype == jnp.int32
    assert np.array_equal(np.asarray(want), np.asarray(got))
