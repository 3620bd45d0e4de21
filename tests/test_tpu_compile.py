"""Compile the kernels of the served path for a described TPU v5e.

Interpret-mode tests cannot see what Mosaic refuses (block shapes off
the (8, 128) tiling, unsigned reductions, in-kernel reshapes).  These
tests compile for a v5e chip that is described, not attached, at the
paper models' published widths:

* every Pallas layer variant applicable on ``"tpu"`` compiles natively,
  and every one reported not applicable is one Mosaic refuses;
* ``seg_pallas`` is not applicable on ``"tpu"``, and ``seg_xla``, which
  serves fused device segments there, compiles.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and under several
test workers only the worker given this file may do so.  The persistent
compilation cache is off around the compiles (a compile for a described
chip cannot be read back without one).
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.bnn import build_model
from repro.bnn.models import pack_params
from repro.core.profiler import gemm_shape_of
from repro.kernels.registry import DEFAULT_REGISTRY, segment_shape_of
from repro.kernels.segment_fused import (
    build_pallas_segment,
    build_xla_segment,
    encoded_shape,
    infer_in_encoding,
)
from repro.kernels.xnor_popcount import xnor_gemm_pallas

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
    return out


def _layer_shape(models, name, notation, occurrence):
    m, packed = models[name]
    hits = [
        (s, p) for s, p in zip(m.specs, packed) if s.notation == notation
    ]
    spec, p = hits[occurrence]
    return gemm_shape_of(spec, p, BATCH)


@pytest.mark.parametrize("variant", PALLAS_LAYER_VARIANTS)
@pytest.mark.parametrize("layer", LAYERS, ids=lambda t: f"{t[0]}-{t[1]}.{t[2]}")
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
    Mosaic cannot compile its fused body, and ``seg_xla`` compiles."""
    m, packed = models[name]
    specs = tuple(m.specs[1:])
    pp = list(packed[1:])
    names = {
        v.name
        for v in DEFAULT_REGISTRY.applicable_segments(
            segment_shape_of(specs, pp, BATCH), "tpu"
        )
    }
    assert names == {"seg_xla"}
    enc = infer_in_encoding(specs)
    x = jax.ShapeDtypeStruct(
        (BATCH,) + encoded_shape(specs[0].in_shape, enc),
        jnp.int32, sharding=one_chip,
    )
    build_xla_segment(specs, pp, enc).lower(x).compile()
    # what keeps seg_pallas off: Mosaic refuses the fused body
    with pytest.raises(Exception):
        build_pallas_segment(specs, pp, enc, interpret=False).lower(x).compile()
