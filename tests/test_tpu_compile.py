"""Compile the kernels of the served path for a described TPU v5e.

Interpret-mode tests cannot see what Mosaic refuses (block shapes off
the (8, 128) tiling, unsigned reductions, in-kernel reshapes).  These
tests compile for a v5e chip that is described, not attached, at the
paper models' published widths:

* every Pallas layer variant applicable on ``"tpu"`` compiles natively,
  and every one reported not applicable is one Mosaic refuses;
* ``seg_pallas`` is not applicable on ``"tpu"``; ``seg_xla`` and
  ``seg_mxu``, which serve fused device segments there, compile, the
  latter with its ±1 products as int8 convolutions and no popcount;
* ``seg_mxu``'s fused stem compiles natively, and a ``seg_mxu`` node
  holds it exactly where it has a stem and its pool.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and under several
test workers only the worker given this file may do so.  The persistent
compilation cache is off around the compiles (a compile for a described
chip cannot be read back without one).
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.bnn import build_model
from repro.bnn.models import pack_params
from repro.core.profiler import gemm_shape_of
from repro.kernels import segment_fused
from repro.kernels.registry import DEFAULT_REGISTRY, segment_shape_of
from repro.kernels.segment_fused import (
    build_mxu_segment,
    build_pallas_segment,
    build_xla_segment,
    encoded_shape,
    fuses_stem,
    infer_in_encoding,
    stem_pool_mxu,
    stem_pool_params,
)
from repro.kernels.xnor_popcount import xnor_gemm_pallas

from tests.fixtures import birealnet

BATCH = 16
PALLAS_LAYER_VARIANTS = tuple(
    v.name for v in DEFAULT_REGISTRY
    if v.scope == "layer" and v.p_blk is not None
)
# (model, notation, occurrence): fashion FC2048, both cifar C256 and
# both cifar C512 layers at published widths
LAYERS = (
    ("fashion_mnist", "FC2048", 0),
    ("cifar10", "C256", 0),
    ("cifar10", "C256", 1),
    ("cifar10", "C512", 0),
    ("cifar10", "C512", 1),
)
# Bi-Real-Net-18's block GEMMs, one of each (windows, neurons) shape
BIREAL_LAYERS = tuple(
    ("birealnet18", notation, 0)
    for notation in ("RB64", "RBD128", "RBD256", "RB512")
)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        if "TPU_LOG_DIR" not in os.environ:
            mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            return topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def models():
    out = {}
    for name in ("fashion_mnist", "cifar10"):
        m = build_model(name)
        out[name] = (m, pack_params(m.specs, m.init(jax.random.PRNGKey(0))))
    m, *_, packed = birealnet()
    out["birealnet18"] = (m, packed)
    return out


def _layer_shape(models, name, notation, occurrence):
    m, packed = models[name]
    hits = [
        (s, p) for s, p in zip(m.specs, packed) if s.notation == notation
    ]
    spec, p = hits[occurrence]
    return gemm_shape_of(spec, p, BATCH)


@pytest.mark.parametrize("variant", PALLAS_LAYER_VARIANTS)
@pytest.mark.parametrize("layer", LAYERS + BIREAL_LAYERS,
                         ids=lambda t: f"{t[0]}-{t[1]}.{t[2]}")
def test_pallas_layer_variant_compiles_iff_applicable(
    one_chip, no_compile_cache, models, layer, variant
):
    shape = _layer_shape(models, *layer)
    v = DEFAULT_REGISTRY.get(variant)
    a = jax.ShapeDtypeStruct(
        (shape.b, shape.p, shape.kw), jnp.int32, sharding=one_chip
    )
    w = jax.ShapeDtypeStruct((shape.n, shape.kw), jnp.int32, sharding=one_chip)
    fn = jax.jit(
        lambda a, w: xnor_gemm_pallas(
            a, w, 32 * shape.kw,
            p_blk=v.p_blk, n_blk=v.n_blk, interpret=False,
        )
    )
    if v.applies_to(shape, "tpu"):
        compiled = fn.lower(a, w).compile()
        assert "tpu_custom_call" in compiled.as_text()
    else:
        with pytest.raises(Exception, match="divisible by 8 and 128"):
            fn.lower(a, w).compile()


def test_p64n64_not_applicable_on_tpu_past_64_neurons(models):
    v = DEFAULT_REGISTRY.get("pallas_p64n64")
    for layer in LAYERS:
        shape = _layer_shape(models, *layer)
        assert shape.n > 64
        assert not v.applies_to(shape, "tpu"), layer


@pytest.mark.parametrize("name", ("fashion_mnist", "cifar10"))
def test_device_segment_compiles_and_seg_pallas_stays_off(
    one_chip, no_compile_cache, models, name
):
    """The segment bench's span (first conv on the host, the rest on
    the device): ``seg_pallas`` is not offered on the TPU, because
    Mosaic cannot compile its fused body; ``seg_xla`` and ``seg_mxu``
    compile."""
    m, packed = models[name]
    specs = tuple(m.specs[1:])
    pp = list(packed[1:])
    names = {
        v.name
        for v in DEFAULT_REGISTRY.applicable_segments(
            segment_shape_of(specs, pp, BATCH), "tpu"
        )
    }
    assert names == {"seg_xla", "seg_mxu"}
    enc = infer_in_encoding(specs)
    x = jax.ShapeDtypeStruct(
        (BATCH,) + encoded_shape(specs[0].in_shape, enc),
        jnp.int32, sharding=one_chip,
    )
    build_xla_segment(specs, pp, enc).lower(x).compile()
    _assert_int8_gemms(_compile_mxu(specs, pp, enc, x), specs)
    # what keeps seg_pallas off: Mosaic refuses the fused body
    with pytest.raises(Exception):
        build_pallas_segment(specs, pp, enc, interpret=False).lower(x).compile()


def _compile_mxu(specs, pp, enc, x):
    """``build_mxu_segment``'s executable compiled for `x`'s device,
    its bound weights described there too."""
    fn = build_mxu_segment(specs, pp, enc)
    weights = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=x.sharding),
        fn.args,
    )
    return fn.func.lower(*weights, x).compile()


def _assert_int8_gemms(compiled, specs):
    """Every conv / fc of the span is a convolution over int8 ±1
    weights; nothing counts bits."""
    text = compiled.as_text()
    n_gemms = sum(s.kind in ("conv", "fc") for s in specs)
    assert len(re.findall(r" convolution\(", text)) == n_gemms
    assert "popcnt" not in text
    for s in specs:
        if s.kind == "conv":
            assert f"s8[3,3,{s.in_shape[-1]},{s.units}]" in text
        elif s.kind == "fc":
            assert f"s8[{s.in_shape[0]},{s.units}]" in text


@pytest.mark.parametrize("name", ("fashion_mnist", "cifar10"))
def test_whole_model_mxu_segment_compiles_at_b256(
    one_chip, no_compile_cache, models, name
):
    """The node the saturate cells serve, 0:n at B = 256, from packed
    images: ``seg_mxu`` lowers with int8 GEMMs, including the first
    conv's 1- or 3-channel input."""
    m, packed = models[name]
    specs = tuple(m.specs)
    x = jax.ShapeDtypeStruct(
        (256,) + encoded_shape(specs[0].in_shape, "packed"),
        jnp.int32, sharding=one_chip,
    )
    _assert_int8_gemms(_compile_mxu(specs, list(packed), "packed", x), specs)


def test_bireal_blocks_compile_on_the_mxu(one_chip, no_compile_cache, models):
    """Bi-Real-Net-18's last stage-1 block and the stride-2 block after
    it at b64, as ``seg_mxu`` runs them: a zero-padded int8 conv each
    (the second strided), the downsampling shortcut's int8 1x1 conv,
    no popcount; ``seg_xla`` compiles too."""
    m, packed = models["birealnet18"]
    specs, pp = tuple(m.specs[5:7]), list(packed[5:7])
    assert [s.notation for s in specs] == ["RB64", "RBD128"]
    x = jax.ShapeDtypeStruct((64, *specs[0].in_shape), jnp.int32,
                             sharding=one_chip)
    text = _compile_mxu(specs, pp, "unpacked", x).as_text()
    assert len(re.findall(r" convolution\(", text)) == 3
    assert "s8[3,3,64,64]" in text and "s8[3,3,64,128]" in text
    assert "popcnt" not in text
    build_xla_segment(specs, pp, "unpacked").lower(x).compile()


@pytest.fixture
def native_stem(monkeypatch):
    """The fused stem as Mosaic compiles it: the host here is a CPU, so
    the kernel would otherwise pick interpret mode."""
    monkeypatch.setattr(segment_fused, "_interpret", lambda: False)


def _described(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def test_fused_stem_compiles_natively_at_b64(
    one_chip, no_compile_cache, models, native_stem
):
    """Bi-Real-Net-18's stem and max pool at b64 as one Mosaic kernel:
    the (64, 112, 112, 64) conv map is no buffer of the program."""
    m, packed = models["birealnet18"]
    spec = m.specs[0]
    fp = _described(stem_pool_params(spec, packed[0]), one_chip)
    x = jax.ShapeDtypeStruct((64, 224, 224, 1), jnp.int32, sharding=one_chip)
    text = jax.jit(lambda fp, x: stem_pool_mxu(x, fp, spec)).lower(
        fp, x).compile().as_text()
    assert "tpu_custom_call" in text
    assert "s32[64,112,112,64]" not in text


@pytest.mark.parametrize("name, batch", [
    ("fashion_mnist", 256), ("cifar10", 256), ("birealnet18", 64)])
def test_mxu_node_holds_a_stem_kernel_iff_it_has_a_stem(
    one_chip, no_compile_cache, models, native_stem, name, batch
):
    """The node each saturate cell serves, 0:n from packed images: the
    paper models' has no stem and no Mosaic kernel; Bi-Real-Net-18's
    runs its stem as one, and holds no (64, 112, 112, 64) map."""
    m, packed = models[name]
    specs = tuple(m.specs)
    x = jax.ShapeDtypeStruct(
        (batch,) + encoded_shape(specs[0].in_shape, "packed"),
        jnp.int32, sharding=one_chip,
    )
    text = _compile_mxu(specs, list(packed), "packed", x).as_text()
    assert fuses_stem(specs) is (name == "birealnet18")
    assert ("tpu_custom_call" in text) is fuses_stem(specs)
    assert "s32[64,112,112,64]" not in text
