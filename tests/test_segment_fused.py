"""Fused segment kernels (`repro.kernels.segment_fused`) + the
segment-scope registry surface: bit-exactness on both BNN
architectures, applicability caps, segment-row profiling, and
fused-vs-per-layer selection (analytic and measured)."""

from __future__ import annotations

import functools
import json

import jax
import numpy as np
import pytest

from repro.bnn import build_model
from repro.bnn import layers as L
from repro.bnn.models import forward_packed, pack_params, prepare_input_packed
from repro.core.mapped_model import build_node_fns, build_segment_fns
from repro.core.mapper import (
    EfficientConfiguration,
    configuration_from_mapping,
)
from repro.core.parallel_config import CPU, FULL_GPU
from repro.core.plan import (
    PACKED,
    UNPACKED,
    build_plan,
    device_spans,
    fuse_configuration,
    fuse_mapping,
    select_fused_segments,
)
from repro.core.profiler import (
    ProfileTable,
    profile_bnn_model,
    profile_segment_variants,
)
from repro.kernels.registry import (
    DEFAULT_REGISTRY,
    PALLAS_INTERPRET_MAX_WORK,
    SCOPE_LAYER,
    SCOPE_SEGMENT,
    SEGMENT_VMEM_BUDGET,
    SegmentShape,
    current_platform,
    segment_shape_of,
)
from repro.kernels.segment_fused import (
    build_mxu_segment,
    build_pallas_segment,
    build_xla_segment,
    encoded_shape,
    infer_in_encoding,
    segment_out_encoding,
)


def _setup(name, scale=0.25, batch=2):
    m = build_model(name, scale=scale)
    packed = pack_params(m.specs, m.init(jax.random.PRNGKey(0)))
    x = prepare_input_packed(
        jax.random.uniform(
            jax.random.PRNGKey(1), (batch, *m.input_hw, m.in_channels)
        )
    )
    return m, packed, x


# ---------------------------------------------------------------------------
# Encoding helpers
# ---------------------------------------------------------------------------


def test_encoded_shape():
    assert encoded_shape((4, 8, 8, 64), PACKED) == (4, 8, 8, 2)
    assert encoded_shape((4, 8, 8, 40), PACKED) == (4, 8, 8, 2)
    assert encoded_shape((4, 8, 8, 64), UNPACKED) == (4, 8, 8, 64)


def test_infer_and_out_encoding_follow_the_chain():
    m, _, _ = _setup("fashion_mnist")
    specs = m.specs
    # whole network: packed input, fc scores out (unpacked ints)
    assert infer_in_encoding(specs) == PACKED
    assert segment_out_encoding(specs, PACKED) == UNPACKED
    # a tail starting at a step layer consumes unpacked
    step_i = next(i for i, s in enumerate(specs) if s.kind == "step")
    assert infer_in_encoding(specs[step_i:]) == UNPACKED


# ---------------------------------------------------------------------------
# Bit-exactness on both architectures
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ["fashion_mnist", "cifar10"])
def test_fused_segment_bitexact_whole_network(name):
    """Acceptance: both fused builders reproduce the reference packed
    forward exactly, on both BNN architectures."""
    m, packed, x = _setup(name)
    want = np.asarray(forward_packed(m.specs, packed, x))
    xla = build_xla_segment(tuple(m.specs), list(packed))
    assert np.array_equal(want, np.asarray(xla(x)))
    pallas = build_pallas_segment(
        tuple(m.specs), list(packed), interpret=True
    )
    assert np.array_equal(want, np.asarray(pallas(x)))


@pytest.mark.parametrize("name", ["fashion_mnist", "cifar10"])
def test_fused_segment_bitexact_tail_span(name):
    """Spans that start mid-network (unpacked input encoding) are
    bit-exact too — the encoding is inferred from the first layer."""
    m, packed, x = _setup(name)
    step_i = next(i for i, s in enumerate(m.specs) if s.kind == "step")
    head = build_xla_segment(tuple(m.specs[:step_i]), list(packed[:step_i]))
    mid = head(x)                      # unpacked pre-activations
    want = np.asarray(forward_packed(m.specs, packed, x))
    for builder in (
        build_xla_segment,
        lambda s, p: build_pallas_segment(s, p, interpret=True),
    ):
        tail = builder(tuple(m.specs[step_i:]), list(packed[step_i:]))
        assert np.array_equal(want, np.asarray(tail(mid)))


def test_registry_applicable_segments_bitexact():
    """Every variant the registry deems applicable for the segment
    shape executes bit-exactly (the autotuner's contract)."""
    m, packed, x = _setup("fashion_mnist")
    shape = segment_shape_of(m.specs, packed, int(x.shape[0]))
    variants = DEFAULT_REGISTRY.applicable_segments(
        shape, current_platform()
    )
    assert {v.name for v in variants} >= {"seg_xla"}
    want = np.asarray(forward_packed(m.specs, packed, x))
    for v in variants:
        fn = v.builder(tuple(m.specs), list(packed), PACKED)
        assert np.array_equal(want, np.asarray(fn(x))), v.name


# ---------------------------------------------------------------------------
# seg_mxu: int8 GEMMs, bit-exact against seg_xla at published widths
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _published(name):
    """A published-width model whose step layers flip and threshold
    away from 0, so every ±1 of the int8 domain is exercised."""
    m = build_model(name)
    key = jax.random.PRNGKey(0)
    params = m.init(key)
    for spec, p in zip(m.specs, params):
        if spec.kind == "step":
            key, k1, k2, k3 = jax.random.split(key, 4)
            p["gamma"] = jax.random.normal(k1, p["gamma"].shape)
            p["beta"] = jax.random.normal(k2, p["beta"].shape)
            p["mean"] = jax.random.normal(k3, p["mean"].shape) * 5
    return m, pack_params(m.specs, params)


@functools.lru_cache(maxsize=None)
def _layer_inputs(name, batch):
    """Each layer's input, then the scores: ``forward_packed`` one
    layer at a time, so ``xs[stop]`` is the reference at any cut."""
    m, packed = _published(name)
    x = prepare_input_packed(
        jax.random.uniform(
            jax.random.PRNGKey(batch), (batch, *m.input_hw, m.in_channels)
        )
    )
    xs = [x]
    for i in range(len(m.specs)):
        xs.append(forward_packed(m.specs[i:i + 1], packed[i:i + 1], xs[-1]))
    return xs


def _mxu_span(specs, which):
    """The spans the MXU chain must match on: the whole model, a head
    that ends packed after S/FLAT (border and Cin-tail lanes of the
    first conv), a span that starts packed at a later conv, and spans
    that start unpacked at MP or S."""
    kinds = [s.kind for s in specs]
    n = len(specs)
    flat = kinds.index("flat")
    if which == "whole":
        return 0, n
    if which == "head_to_flat":
        return 0, flat + 1
    if which == "from_conv":
        return kinds.index("conv", 1), n
    if which == "from_mp":
        return kinds.index("mp"), n
    return kinds.index("step"), n


@pytest.mark.parametrize("batch", [1, 5])
@pytest.mark.parametrize("name", ["fashion_mnist", "cifar10"])
@pytest.mark.parametrize(
    "which", ["whole", "head_to_flat", "from_conv", "from_mp", "from_step"]
)
def test_mxu_segment_bitexact_published_widths(name, batch, which):
    m, packed = _published(name)
    start, stop = _mxu_span(m.specs, which)
    specs, pp = tuple(m.specs[start:stop]), list(packed[start:stop])
    xs = _layer_inputs(name, batch)
    enc = infer_in_encoding(specs)
    got = np.asarray(build_mxu_segment(specs, pp, enc)(xs[start]))
    want = np.asarray(xs[stop])
    assert got.dtype == want.dtype == np.int32
    assert np.array_equal(got, want)
    assert np.array_equal(
        got, np.asarray(build_xla_segment(specs, pp, enc)(xs[start]))
    )


def test_mxu_segment_pads_with_minus_one():
    """An all-(+1) image through one conv: a ±1 sum over the 3x3 window
    counts the border pixels as −1, so corner, edge and interior
    outputs differ, exactly as the packed conv reads them."""
    m, packed = _published("cifar10")
    spec, p = m.specs[0], packed[0]
    x = prepare_input_packed(np.ones((1, *m.input_hw, m.in_channels)))
    got = np.asarray(build_mxu_segment((spec,), [p])(x))
    want = np.asarray(L.conv_packed(x, p["w_words"], p["k_true"]))
    assert np.array_equal(got, want)
    assert not np.array_equal(got[0, 0, 0], got[0, 1, 1])


@pytest.mark.parametrize("lead", ["mp", "step_mp"])
def test_mxu_segment_pools_words_like_the_packed_chain(lead):
    """MP on packed words (a span that starts there, or MP after S)
    pools the words, as the packed chain does, where pooling ±1 lanes
    would OR them: seg_mxu still equals seg_xla."""
    m, packed = _published("cifar10")
    conv_i = [i for i, s in enumerate(m.specs) if s.kind == "conv"][1]
    conv, step = m.specs[conv_i], m.specs[conv_i - 1]
    h, w, c = conv.in_shape
    big = (2 * h, 2 * w, c)
    mp = L.LayerSpec(0, "mp", f"MP{h}", big, conv.in_shape, c)
    key = jax.random.PRNGKey(3)
    if lead == "mp":
        span, pp, enc = (mp, conv), [{}, packed[conv_i]], PACKED
        x = jax.random.randint(
            key, (2, 2 * h, 2 * w, c // 32), -(2**31), 2**31 - 1, "int32"
        )
    else:
        s_big = L.LayerSpec(0, "step", "S", big, big, c)
        span = (s_big, mp, conv)
        pp = [packed[conv_i - 1], {}, packed[conv_i]]
        enc = UNPACKED
        assert step.kind == "step" and step.units == c
        x = jax.random.randint(key, (2, *big), -40, 40, "int32")
    got = build_mxu_segment(span, pp, enc)(x)
    want = build_xla_segment(span, pp, enc)(x)
    assert np.array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# Registry scope rules
# ---------------------------------------------------------------------------


def test_scopes_partition_the_registry():
    seg_names = set(DEFAULT_REGISTRY.segment_names())
    assert {"seg_xla", "seg_pallas"} <= seg_names
    for name in seg_names:
        assert DEFAULT_REGISTRY.get(name).scope == SCOPE_SEGMENT
    # layer-scope applicability never returns segment variants: the
    # per-layer autotuner can't accidentally pick one
    from repro.kernels.registry import GemmShape

    layer_vs = DEFAULT_REGISTRY.applicable(
        GemmShape(b=2, p=16, n=64, kw=4), "tpu"
    )
    assert not ({v.name for v in layer_vs} & seg_names)
    for v in layer_vs:
        assert v.scope == SCOPE_LAYER


def test_seg_pallas_applicability_caps():
    def seg_names(shape, platform):
        return {
            v.name
            for v in DEFAULT_REGISTRY.applicable_segments(shape, platform)
        }

    small = SegmentShape(b=1, n_layers=3, work=1 << 10, vmem_bytes=1 << 20)
    assert "seg_pallas" in seg_names(small, "cpu")
    over_work = SegmentShape(
        b=1, n_layers=3,
        work=PALLAS_INTERPRET_MAX_WORK + 1, vmem_bytes=1 << 20,
    )
    # the interpret-mode work cap binds off-TPU
    assert "seg_pallas" not in seg_names(over_work, "cpu")
    over_vmem = SegmentShape(
        b=1, n_layers=3, work=1 << 10,
        vmem_bytes=SEGMENT_VMEM_BUDGET + 1,
    )
    assert "seg_pallas" not in seg_names(over_vmem, "cpu")
    # Mosaic cannot compile the fused body: never offered on the TPU
    for shape in (small, over_work, over_vmem):
        assert "seg_pallas" not in seg_names(shape, "tpu")
        assert "seg_xla" in seg_names(shape, "tpu")
    # seg_xla has no cap
    for shape in (small, over_work, over_vmem):
        assert "seg_xla" in {
            v.name
            for v in DEFAULT_REGISTRY.applicable_segments(shape, "cpu")
        }


def test_seg_mxu_offered_only_on_the_tpu():
    """The int8 GEMMs are timed only where the MXU runs them: CPU plans
    never see seg_mxu."""
    for shape in (
        SegmentShape(b=1, n_layers=3, work=1 << 10, vmem_bytes=1 << 20),
        SegmentShape(b=256, n_layers=19, work=1 << 40, vmem_bytes=1 << 30),
    ):
        on = {v.name for v in DEFAULT_REGISTRY.applicable_segments(shape, "tpu")}
        off = {v.name for v in DEFAULT_REGISTRY.applicable_segments(shape, "cpu")}
        assert "seg_mxu" in on
        assert "seg_mxu" not in off
    v = DEFAULT_REGISTRY.get("seg_mxu")
    assert (v.scope, v.placement) == (SCOPE_SEGMENT, "device")
    names = DEFAULT_REGISTRY.segment_names()
    assert names.index("seg_xla") < names.index("seg_mxu")


def test_analytic_fusion_keeps_seg_xla_on_ties():
    """Analytic pricing (platform "tpu") prices seg_mxu as seg_xla; the
    tie keeps seg_xla, so analytic plans are what they were."""
    m, packed, x = _setup("fashion_mnist")
    table, ec = _mixed_ec(m, packed)
    fused = fuse_mapping(m, packed, table, ec, time_source="analytic")
    assert fused.fused_segments
    for start, stop, name, _ in fused.fused_segments:
        assert name == "seg_xla"
        assert table.segment_time(2, start, stop, "seg_mxu") == (
            table.segment_time(2, start, stop, "seg_xla")
        )


# ---------------------------------------------------------------------------
# Segment-row profiling + selection
# ---------------------------------------------------------------------------


def _mixed_ec(m, packed, batch=2, time_source="analytic"):
    table = profile_bnn_model(
        m, packed, batch_sizes=(batch,), time_source=time_source
    )
    mapping = tuple(
        FULL_GPU if s.kind in ("conv", "fc") else CPU for s in m.specs
    )
    # put the elementwise layers between GEMMs on the device too so a
    # multi-layer device segment exists
    mapping = (mapping[0],) + tuple(
        FULL_GPU for _ in mapping[1:-1]
    ) + (mapping[-1],)
    return table, configuration_from_mapping(table, batch, mapping)


def test_profile_segment_variants_stores_rows_and_roundtrips():
    m, packed, x = _setup("fashion_mnist")
    table, ec = _mixed_ec(m, packed)
    spans = device_spans(ec)
    assert spans
    profile_segment_variants(
        m, packed, table, spans=spans, batch_sizes=(2,),
        time_source="analytic",
    )
    for start, stop in spans:
        names = table.segment_variants_for(2, start, stop)
        assert "seg_xla" in names
        for name in names:
            assert table.segment_time(2, start, stop, name) > 0.0
    again = ProfileTable.from_json(table.to_json())
    assert again.segment_times == table.segment_times
    with pytest.raises(KeyError):
        table.segment_time(2, 0, 1, "seg_xla")


def test_stored_segment_rows_are_not_timed_again(monkeypatch):
    """A warm start (the table already holds a span's rows) measures
    nothing: no layer inputs are captured and no variant is built.
    A variant without a row is still timed."""
    import repro.core.profiler as profiler

    m, packed, x = _setup("fashion_mnist")
    table, ec = _mixed_ec(m, packed)
    spans = device_spans(ec)
    for start, stop in spans:
        shape = segment_shape_of(m.specs[start:stop], packed[start:stop], 2)
        table.add_segment_row(2, start, stop, {
            v.name: 7.0
            for v in DEFAULT_REGISTRY.applicable_segments(shape, "cpu")
        })

    def refuse(*args, **kwargs):
        raise AssertionError("a stored row was timed again")

    monkeypatch.setattr(profiler, "_capture_layer_inputs", refuse)
    monkeypatch.setattr(profiler, "_timeit", refuse)
    profile_segment_variants(
        m, packed, table, spans=spans, batch_sizes=(2,),
        time_source="measured", platform="cpu",
    )
    profile_segment_variants(
        m, packed, table, spans=spans, batch_sizes=(2,),
        time_source="analytic",
    )
    for start, stop in spans:
        assert table.segment_time(2, start, stop, "seg_xla") == 7.0
        assert table.segment_time(2, start, stop, "seg_mxu") > 0.0


def test_unprofiled_batch_rejected():
    m, packed, x = _setup("fashion_mnist")
    table, ec = _mixed_ec(m, packed)
    with pytest.raises(ValueError, match="not profiled"):
        profile_segment_variants(
            m, packed, table, spans=device_spans(ec),
            batch_sizes=(64,), time_source="analytic",
        )


def test_analytic_selection_prefers_fused_when_cheaper():
    """Acceptance: the analytic model prices a fused multi-layer device
    segment below its per-layer kernel sum (one dispatch instead of N),
    so selection records a fused variant and the fused plan is cheaper."""
    m, packed, x = _setup("fashion_mnist")
    table, ec = _mixed_ec(m, packed)
    fused = fuse_configuration(
        m, packed, table, ec, time_source="analytic"
    )
    multi = [
        (s, e) for (s, e) in device_spans(ec) if e - s > 1
    ]
    assert multi
    chosen = {(s, e): name for s, e, name, _ in fused.fused_segments}
    for span in multi:
        assert span in chosen
    base = build_plan(ec, mode="segments")
    plan = build_plan(fused, mode="segments")
    assert (
        plan.expected_time_per_example
        < base.expected_time_per_example
    )
    # per-layer attribution is untouched by fusion
    assert fused.per_layer_kernel_times == ec.per_layer_kernel_times
    assert fused.expected_time_per_example == ec.expected_time_per_example


def test_selection_ignores_variants_missing_from_registry():
    m, packed, x = _setup("fashion_mnist")
    table, ec = _mixed_ec(m, packed)
    spans = device_spans(ec)
    profile_segment_variants(
        m, packed, table, spans=spans, batch_sizes=(2,),
        time_source="analytic",
    )
    # poison the table with a variant no registry knows
    (start, stop) = spans[0]
    table.add_segment_row(2, start, stop, {"seg_ghost": 1e-12})
    fused = select_fused_segments(ec, table)
    assert all(
        name != "seg_ghost" for _, _, name, _ in fused.fused_segments
    )


def test_fused_execution_end_to_end_measured():
    """Measured path: profile segment variants, select, build the
    segments plan — fused nodes resolve through the registry and the
    full chain stays bit-exact."""
    m, packed, x = _setup("fashion_mnist")
    table, ec = _mixed_ec(m, packed, time_source="measured")
    fused = fuse_configuration(
        m, packed, table, ec, time_source="measured", repeats=1
    )
    want = np.asarray(forward_packed(m.specs, packed, x))
    out = x
    for node, fn in build_segment_fns(m, packed, fused):
        out = fn(out)
    assert np.array_equal(want, np.asarray(out))


def test_ec_json_roundtrip_with_fused_segments():
    m, packed, x = _setup("fashion_mnist")
    table, ec = _mixed_ec(m, packed)
    fused = fuse_configuration(
        m, packed, table, ec, time_source="analytic"
    )
    assert fused.fused_segments
    back = EfficientConfiguration.from_json(fused.to_json())
    assert back == fused
    # the key is emitted only when selection chose something, so
    # unfused configurations keep their exact legacy JSON shape
    d = json.loads(ec.to_json())
    assert "fused_segments" not in d
    assert EfficientConfiguration.from_json(
        ec.to_json()
    ).fused_segments == ()


def test_layer_scope_variant_rejected_as_fused():
    import dataclasses

    m, packed, x = _setup("fashion_mnist")
    table, ec = _mixed_ec(m, packed)
    (start, stop) = device_spans(ec)[0]
    bad = dataclasses.replace(
        ec, fused_segments=((start, stop, "xla_fused", 1e-6),)
    )
    plan = build_plan(bad, mode="segments")
    with pytest.raises(ValueError, match="scope"):
        build_node_fns(m, packed, bad, plan)


def test_mxu_node_keeps_its_weights_as_operands():
    """A plan node served by seg_mxu is jitted under its node name with
    the int8 weights as arguments, not constants: two sets of weights
    lower to one program (one compile-cache entry), and each node
    answers bit for bit."""
    import dataclasses

    from repro.core.mapped_model import node_name

    m = build_model("fashion_mnist", scale=0.25)
    x = _setup("fashion_mnist")[2]
    texts = []
    for seed in (0, 1):
        packed = pack_params(m.specs, m.init(jax.random.PRNGKey(seed)))
        table, ec = _mixed_ec(m, packed)
        (start, stop) = device_spans(ec)[0]
        fused = dataclasses.replace(
            ec, fused_segments=((start, stop, "seg_mxu", 1e-6),)
        )
        y = x
        for k, (node, fn) in enumerate(build_node_fns(
            m, packed, fused, build_plan(fused, mode="segments")
        )):
            if node.fused_variant == "seg_mxu":
                text = fn.func.lower(*fn.args, y).as_text()
                assert f"jit_{node_name(k, node)}" in text
                texts.append(text)
            y = fn(y)
        want = forward_packed(m.specs, packed, x)
        assert np.array_equal(np.asarray(y), np.asarray(want))
    assert len(texts) == 2 and texts[0] == texts[1]
