"""Bi-Real-Net-18 on the served path against the benchmark's plain
reference (``onchip/configs/birealnet_reference.py``), at a small size
on the CPU: 32x32 images, widths x 0.25 (word-aligned), the published
depth and 1000 classes.

The reference computes in float32 on integers it keeps below 2**24, so
every comparison here is exact."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.bnn import build_model
from repro.bnn import layers as L
from repro.bnn.models import (
    BIREALNET18_NOTATION,
    forward_packed,
    pack_params,
    prepare_input_packed,
)
from repro.core.parallel_config import FULL_GPU
from repro.core.plan import PlanError, kind_of_label, layer_encodings
from repro.kernels import segment_fused
from repro.kernels.segment_fused import (
    build_mxu_segment,
    build_xla_segment,
    fuses_stem,
    stem_pool_mxu,
    stem_pool_params,
)

from tests.fixtures import bireal_reference, birealnet

HW = (32, 32)
BATCH = 4


@pytest.fixture(scope="module")
def small():
    model, ref, cfg, params, packed = birealnet(scale=0.25, input_hw=HW,
                                                seed=2**31 + 16)
    rng = np.random.default_rng(16)
    x01 = rng.random((6, *HW, 3), dtype=np.float32)
    want = np.asarray(jax.jit(lambda p, x: ref.forward(cfg, p, x))(
        params, ref.binarize_input(x01)))
    return model, packed, np.asarray(prepare_input_packed(x01)), want


def test_the_model_is_the_published_chain():
    m = build_model("birealnet18")
    assert m.specs[0].out_shape == (112, 112, 64)
    assert m.specs[1].out_shape == (56, 56, 64)
    blocks = [s for s in m.specs if s.kind == "res"]
    assert len(blocks) == 16
    assert [s.out_shape for s in blocks if s.stride == 2] == [
        (28, 28, 128), (14, 14, 256), (7, 7, 512)]
    assert m.specs[-1].out_shape == (1000,)
    assert [s.notation for s in m.specs] == list(BIREALNET18_NOTATION)
    # C<n> keeps its meaning: the paper's -1-padded stride-1 conv
    assert L.match_token("C64") == ("conv", 64)
    assert L.match_token("RBD128") == ("res", 128)


def test_plan_chains_the_residual_stream_unpacked():
    kinds = [kind_of_label(f"L{s.idx}:{s.notation}")
             for s in build_model("birealnet18").specs]
    encs = layer_encodings(kinds)
    assert encs[0] == (L.PACKED, L.UNPACKED)
    assert all(e == (L.UNPACKED, L.UNPACKED) for e in encs[1:])
    with pytest.raises(PlanError):
        layer_encodings(["res"])     # a block takes the stream, not words
    with pytest.raises(PlanError):
        kind_of_label("L1:RBX64")


def test_forward_packed_equals_the_reference(small):
    m, packed, xw, want = small
    got = np.asarray(jax.jit(
        lambda x: forward_packed(m.specs, packed, x))(xw))
    assert got.dtype == np.int32
    assert np.array_equal(got, want)


@pytest.mark.parametrize("builder", [build_xla_segment, build_mxu_segment])
def test_a_fused_whole_model_equals_the_reference(small, builder):
    m, packed, xw, want = small
    assert np.array_equal(np.asarray(builder(m.specs, packed)(xw)), want)


@pytest.fixture(scope="module")
def deployment(small):
    m, packed, *_ = small
    dep = api.Deployment.plan((m, packed), batch_sizes=(BATCH,),
                              autotune=True, fuse=True,
                              time_source="analytic", repeats=1)
    dep.serve(max_batch=BATCH)
    return dep


def _served(dep, xw):
    reqs = [dep.submit(x) for x in xw]
    dep.drain()
    return np.stack([np.asarray(r.result) for r in reqs])


def test_served_through_the_api_equals_the_reference(small, deployment):
    m, packed, xw, want = small
    ec = deployment.configuration()
    assert len(ec.layer_configs) == len(m.specs)
    assert np.array_equal(_served(deployment, xw), want)


@pytest.mark.parametrize("variant", ["seg_xla", "seg_mxu"])
def test_served_as_one_fused_device_node(small, deployment, variant):
    """All 20 layers on the device as one node, under each segment
    variant that computes them, served by the same engine."""
    m, packed, xw, want = small
    n = len(m.specs)
    table = api.autotune_model(m, packed, batch_sizes=(BATCH,),
                               time_source="analytic")
    ec = api.price_mapping(table, BATCH, (FULL_GPU,) * n)
    ec = dataclasses.replace(ec, fused_segments=((0, n, variant, 1e-6),))
    deployment.engine.swap_configuration(ec)
    (node,) = deployment.engine.pipeline.plan.nodes
    assert (node.start, node.stop, node.fused_variant) == (0, n, variant)
    assert np.array_equal(_served(deployment, xw), want)


def _one_block(token, in_shape, seed):
    ref = bireal_reference()
    cfg = {"layers": [token], "input_hw": list(in_shape[:2]),
           "in_channels": in_shape[2], "n_classes": 1000}
    params = jax.device_get(jax.jit(lambda k: ref.init_params(cfg, k))(
        jax.random.PRNGKey(seed)))
    (spec,) = L.parse_notation([token], in_shape[:2], in_shape[2], 1000)
    return ref, cfg, params, spec, pack_params([spec], params)[0]


@pytest.mark.parametrize("token, in_shape", [
    ("RBD64", (8, 6, 32)),     # stride 2 with its downsampling shortcut
    ("RB32", (5, 7, 32)),      # stride 1, odd sides
])
def test_a_block_alone_equals_the_reference(token, in_shape):
    ref, cfg, params, spec, p = _one_block(token, in_shape, 2**31 + 7)
    x = jax.random.randint(jax.random.PRNGKey(1), (3, *in_shape), -300, 300,
                           jnp.int32)
    want = np.asarray(ref.forward(cfg, params, np.asarray(x, np.float32)))
    assert np.array_equal(np.asarray(L.apply_packed(spec, p, x)), want)
    mxu = build_mxu_segment((spec,), [p], L.UNPACKED)(x)
    assert np.array_equal(np.asarray(mxu), want)


# maps whose output rows and columns, together, fall in all 16 border
# classes: one-pixel sides (both taps padded), odd sides at stride 2
# (the last row padded), and interiors
BORDER_CASES = [(1, 1, 1), (2, 3, 1), (5, 4, 1), (4, 1, 1), (1, 4, 1),
                (7, 7, 2), (1, 3, 2), (8, 6, 2)]


@pytest.mark.parametrize("h, w, stride", BORDER_CASES)
def test_border_correction_gives_zero_padding(h, w, stride):
    """The packed path pads -1 words and adds the border table; that is
    the zero-padded ±1 conv at every border class the map has."""
    cin, cout = 40, 8
    keys = jax.random.split(jax.random.PRNGKey(h * 10 + w + stride), 2)
    w_pm1 = np.where(jax.random.bernoulli(keys[0], 0.5, (3, 3, cin, cout)),
                     1, -1)
    spec = L.LayerSpec(1, "res", "RB8", (h, w, cin), (h, w, cout), cout)
    (p,) = pack_params([spec], [{"w": w_pm1, "m": np.ones(cout),
                                 "b": np.zeros(cout), "k": 0}])
    x = jax.random.randint(keys[1], (2, h, w, cin), -5, 5, jnp.int32)
    got = L.binary_conv_packed(x, p, stride)
    want = jax.lax.conv_general_dilated(
        jnp.where(x >= 0, 1, -1), jnp.asarray(w_pm1), (stride, stride),
        ((1, 1), (1, 1)), dimension_numbers=("NHWC", "HWIO", "NHWC"))
    assert np.array_equal(np.asarray(got), np.asarray(want))
    rows, cols = L.border_classes(h, stride), L.border_classes(w, stride)
    assert got.shape[1:3] == (len(rows), len(cols))


def test_border_cases_cover_every_class():
    seen = set()
    for h, w, stride in BORDER_CASES:
        rows, cols = L.border_classes(h, stride), L.border_classes(w, stride)
        seen |= {(r, c) for r in rows for c in cols}
    assert seen == {(r, c) for r in range(4) for c in range(4)}


def test_the_paper_models_keep_their_layers():
    for name, n in (("fashion_mnist", 10), ("cifar10", 19)):
        m = build_model(name)
        assert len(m.specs) == n
        assert {s.kind for s in m.specs} <= {"conv", "mp", "step", "flat",
                                             "fc"}
    with pytest.raises(ValueError, match="fp-sim"):
        build_model("birealnet18").init(jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# seg_mxu's stem: the 7x7/2 conv, its requantization and the 3x3/2 max pool
# as one kernel (interpret mode here)
# ---------------------------------------------------------------------------


def _stem(h, w, c, seed):
    """A stem and its pool over an (h, w) RGB map to c channels: the
    layers, and the reference's weights and fold packed for them."""
    ref = bireal_reference()
    cfg = {"layers": [f"STEM{c}", "MAXP"], "input_hw": [h, w],
           "in_channels": 3, "n_classes": 10}
    params = jax.device_get(jax.jit(lambda k: ref.init_params(cfg, k))(
        jax.random.PRNGKey(seed)))
    specs = L.parse_notation(cfg["layers"], (h, w), 3, 10)
    return ref, specs, params


def _stem_check(spec, p, batch, seed):
    x = jax.random.randint(jax.random.PRNGKey(seed),
                           (batch, *spec.in_shape[:2], 1), 0, 8, jnp.int32)
    want = L.maxpool3_packed(L.stem_packed(x, p, 3))
    got = stem_pool_mxu(x, stem_pool_params(spec, p), spec)
    assert got.dtype == jnp.int32 and got.shape == want.shape
    assert np.array_equal(np.asarray(got), np.asarray(want))


# the published map at b2; small and odd maps, whose pooled rows and
# columns take every pad border (odd conv sides pad the pool's last tap)
STEM_MAPS = [(224, 224, 2), (8, 8, 2), (14, 14, 2), (30, 30, 2), (9, 13, 3)]


@pytest.mark.parametrize("h, w, batch", STEM_MAPS,
                         ids=lambda v: str(v))
def test_the_fused_stem_equals_the_stem_and_its_pool(h, w, batch):
    _, specs, params = _stem(h, w, 64, seed=h * 1000 + w)
    p = pack_params(specs, params)[0]
    _stem_check(specs[0], p, batch, seed=h + w)


@pytest.mark.parametrize("k_end", [0, 1])
def test_the_fused_stem_at_the_ends_of_its_folds(k_end):
    """Channels with m > 0, m < 0 and m = 0, |m|, b and k at the ends of
    the reference's stem ranges, weights at +-127."""
    ref, specs, params = _stem(14, 30, 64, seed=31 + k_end)
    lo, hi, b_max, k_lo, k_hi = ref.STEM
    c_lo, c_hi = ref.STEM_CENTER
    params[0]["m"] = np.resize([hi, -hi, 0, lo, -lo], 64).astype(np.float32)
    params[0]["b"] = np.resize(
        [-b_max - hi * c_hi, b_max - lo * c_lo, 0, b_max, -b_max],
        64).astype(np.float32)
    params[0]["k"] = np.float32((k_lo, k_hi)[k_end])
    w = np.array(params[0]["w"])
    w[0, :, 0], w[6, :, 2], w[3, 3] = 127, -127, -127
    params[0]["w"] = w
    p = pack_params(specs, params)[0]
    _stem_check(specs[0], p, 2, seed=5 + k_end)


def test_the_fused_stem_takes_symmetric_int8_weights():
    _, specs, params = _stem(8, 8, 64, seed=3)
    params[0]["w"] = np.array(params[0]["w"])
    params[0]["w"][2, 2, 1, 5] = -128
    with pytest.raises(ValueError, match="-127, 127"):
        stem_pool_params(specs[0], pack_params(specs, params)[0])


@pytest.mark.parametrize("start, stop, fused", [
    (0, 2, True),      # the stem and its pool
    (0, 3, True),      # and the first block
    (0, 1, False),     # a segment that ends at the stem
    (1, 3, False),     # one that starts at the pool
])
def test_seg_mxu_fuses_a_stem_only_with_the_pool_after_it(
        small, monkeypatch, start, stop, fused):
    m, packed, xw, _ = small
    calls = []

    def counted(*args):
        calls.append(args[2].idx)
        return stem_pool_mxu(*args)

    monkeypatch.setattr(segment_fused, "stem_pool_mxu", counted)
    specs, pp = m.specs[start:stop], packed[start:stop]
    assert fuses_stem(specs) is fused
    x = forward_packed(m.specs[:start], packed[:start], xw)
    got = build_mxu_segment(specs, pp)(x)
    assert np.array_equal(np.asarray(got),
                          np.asarray(forward_packed(specs, pp, x)))
    assert calls == ([1] if fused else [])
