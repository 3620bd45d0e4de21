"""Spans on the profiler's clock (``repro.tracing``): recorded only while
a profiler session runs, written at the serving engine's boundaries
with their counts, and plan nodes jitted under stable names."""

import gc
import re
from collections import Counter

import jax
import numpy as np
import pytest

import repro.api as api
import repro.tracing as tracing
from repro.bnn.models import (
    build_model, forward_packed, pack_params, prepare_input_packed,
)
from repro.core.mapped_model import build_node_fns, node_name
from repro.core.parallel_config import CPU, FULL_GPU
from repro.core.plan import build_plan
from repro.core.profiler import profile_bnn_model
from repro.kernels.registry import DEFAULT_REGISTRY
from repro.serving.pipeline import SegmentPipeline, canonical_mixed_mapping

from tests.fixtures import FakeClock


@pytest.fixture(scope="module")
def small():
    m = build_model("fashion_mnist", scale=0.25)
    packed = pack_params(m.specs, m.init(jax.random.PRNGKey(0)))
    rng = np.random.default_rng(5)
    x01 = rng.integers(0, 2, size=(6, 28, 28, 1)).astype(np.float32)
    xw = np.asarray(prepare_input_packed(x01))
    return m, packed, xw, np.asarray(forward_packed(m.specs, packed, xw))


def _mixed(m, packed, batch=2, mapping=None):
    table = profile_bnn_model(m, packed, batch_sizes=(batch,),
                              time_source="analytic")
    return table, api.price_mapping(
        table, batch, mapping or canonical_mixed_mapping(m))


class _Counting(jax.profiler.TraceAnnotation):
    made = 0

    def __init__(self, *a, **k):
        type(self).made += 1
        super().__init__(*a, **k)


def test_span_builds_no_annotation_without_a_session(monkeypatch):
    monkeypatch.setattr(tracing, "TraceAnnotation", _Counting)
    _Counting.made = 0
    with tracing.span("pipeline.dispatch", node="n", batch=4) as sp:
        sp.set_metadata(more=1)
    gc.collect()
    assert _Counting.made == 0
    assert tracing.span("engine.step") is tracing.span("batcher.form")


def test_span_is_an_annotation_inside_a_session(monkeypatch, tmp_path):
    monkeypatch.setattr(tracing, "TraceAnnotation", _Counting)
    _Counting.made = 0
    with jax.profiler.trace(str(tmp_path)):
        with tracing.span("engine.step", step=0):
            pass
    assert _Counting.made == 1


def _program_events(path):
    """Every event of the program's spans on the host:
    ``(name, start, end, stats)``."""
    from jax.profiler import ProfileData

    (pb,) = path.rglob("*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(str(pb)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)) for e in line.events]
    return out


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_one_step_writes_nested_spans_with_their_counts(small, tmp_path):
    m, packed, xw, ref = small
    table, ec = _mixed(m, packed, batch=4)
    dep = api.Deployment.plan((m, packed), batch_sizes=(4,),
                              time_source="analytic", repeats=1)
    dep.serve(max_batch=4)
    # the engine serves the mixed mapping: host and device nodes
    dep.engine.swap_configuration(ec)
    names = dep.engine.pipeline.node_names
    assert len(names) > 2
    warm = [dep.submit(x) for x in xw[:4]]
    dep.drain()
    reqs = [dep.submit(x) for x in xw]
    with jax.profiler.trace(str(tmp_path)):
        assert dep.step(force=True) == 6
        gc.collect()
    for r, want in zip(warm + reqs, np.concatenate([ref[:4], ref])):
        np.testing.assert_array_equal(np.asarray(r.result), want)

    events = _program_events(tmp_path)
    mine = [e for e in events if e[0] in (
        "engine.step", "batcher.form", "pipeline.h2d", "pipeline.dispatch",
        "pipeline.d2h", "engine.complete")]
    (step,) = [e for e in mine if e[0] == "engine.step"]
    assert step[3] == {"step": 1, "batches": 2, "images": 6}
    assert all(_inside(e, step) for e in mine)
    count = Counter(e[0] for e in mine)
    # two micro-batches (4 and 2 padded to 4) through every node
    assert count["batcher.form"] == 2 and count["engine.complete"] == 2
    assert count["pipeline.dispatch"] == 2 * len(names)
    forms = sorted((e[3]["n_real"], e[3]["padded"]) for e in mine
                   if e[0] == "batcher.form")
    assert forms == [(2, 4), (4, 4)]
    assert sorted(e[3]["n_real"] for e in mine
                  if e[0] == "engine.complete") == [2, 4]
    dispatched = Counter(e[3]["node"] for e in mine
                         if e[0] == "pipeline.dispatch")
    assert dispatched == {n: 2 for n in names}
    assert all(e[3]["batch"] == 4 for e in mine if e[0].startswith("pipe"))
    # each micro-batch's output is downloaded from the last node
    assert Counter(e[3]["node"] for e in mine if e[0] == "pipeline.d2h")[
        names[-1]] == 2
    assert count["pipeline.h2d"] >= 2
    # the collector's pause inside the session is a span of its own
    gcs = [e for e in events if e[0] == "python.gc"]
    assert gcs and gcs[-1][3] == {"generation": 2}


def test_serial_and_faithful_drivers_take_the_same_spans(small, tmp_path):
    m, packed, xw, ref = small
    table, ec = _mixed(m, packed, batch=2)
    pipe = SegmentPipeline(m, packed, ec)
    from repro.core.mapped_model import run_plan

    run = run_plan(build_node_fns(m, packed, ec,
                                  build_plan(ec, mode="layers")))
    np.testing.assert_array_equal(pipe.run_serial(xw[:2]), ref[:2])
    np.testing.assert_array_equal(run(xw[:2]), ref[:2])
    with jax.profiler.trace(str(tmp_path)):
        pipe.run_serial(xw[:2])
        run(xw[:2])
    count = Counter(e[0] for e in _program_events(tmp_path))
    n_layers_nodes = len(build_plan(ec, mode="layers").nodes)
    assert count["pipeline.dispatch"] == len(pipe.node_names) + n_layers_nodes
    assert count["pipeline.d2h"] >= 2 and count["pipeline.h2d"] >= 2


def test_plan_nodes_are_jitted_under_their_stable_names(small):
    m, packed, xw, _ = small
    table, ec = _mixed(m, packed, batch=2)
    plan = build_plan(ec, mode="segments")
    fns = build_node_fns(m, packed, ec, plan)
    x = xw[:2]
    for k, (node, fn) in enumerate(fns):
        name = node_name(k, node)
        assert name == f"node{k}_{node.start}_{node.stop}_{node.placement}"
        assert f"jit_{name}" in fn.lower(x).as_text()
        x = fn(x)


def _op_names(compiled_text):
    return sorted(re.findall(r"^\s*(?:ROOT )?%?([\w.\-]+) = ",
                             compiled_text, re.M))


def test_a_fused_node_keeps_one_dispatch_and_its_operations(small):
    """A fused variant's node is the builder's function jitted again
    under the node's name: one module, the same operations."""
    m, packed, xw, ref = small
    mapping = (CPU,) + (FULL_GPU,) * (len(m.specs) - 2) + (CPU,)
    table, ec = _mixed(m, packed, batch=2, mapping=mapping)
    fused = api.fuse_mapping(m, packed, table, ec, time_source="analytic")
    fns = build_node_fns(m, packed, fused,
                         build_plan(fused, mode="segments"))
    x = xw[:2]
    for k, (node, fn) in enumerate(fns):
        if node.fused_variant is not None:
            name = node_name(k, node)
            assert name.endswith(node.fused_variant)
            own = DEFAULT_REGISTRY.get(node.fused_variant).builder(
                tuple(m.specs[node.start:node.stop]),
                list(packed[node.start:node.stop]), node.in_encoding)
            xd = jax.device_put(np.asarray(x))
            ours = fn.lower(xd).compile().as_text()
            assert ours.startswith(f"HloModule jit_{name},")
            assert _op_names(ours) == _op_names(
                own.lower(xd).compile().as_text())
        x = fn(x)
    assert any(n.fused_variant for n, _ in fns)
    np.testing.assert_array_equal(np.asarray(x), ref[:2])


def test_an_empty_step_still_runs_nothing(small, tmp_path):
    m, packed, xw, _ = small
    from repro.serving import ServingEngine

    table, ec = _mixed(m, packed, batch=2)
    eng = ServingEngine(m, packed, ec, clock=FakeClock())
    with jax.profiler.trace(str(tmp_path)):
        assert eng.step(force=True) == 0
    names = Counter(e[0] for e in _program_events(tmp_path))
    assert names["engine.step"] == 1 and names["pipeline.dispatch"] == 0


@pytest.fixture(scope="module")
def bireal():
    from tests.fixtures import birealnet

    m, ref, cfg, params, packed = birealnet(scale=0.25, input_hw=(32, 32))
    rng = np.random.default_rng(7)
    xw = np.asarray(prepare_input_packed(
        rng.random((2, 32, 32, 3), dtype=np.float32)))
    return m, packed, xw


BIREAL_SCOPES = ["stem", "res3", "res7", "res7.down", "res18", "head"]


def test_bireal_layers_run_under_their_named_scopes_in_every_path(bireal):
    """Every path that runs a Bi-Real layer names its operations by the
    layer's scope: the packed forward, both fused variants, and a plan's
    per-layer nodes."""
    from repro.kernels.segment_fused import (
        build_mxu_segment, build_xla_segment,
    )

    m, packed, xw = bireal
    mxu = build_mxu_segment(m.specs, packed)
    texts = {
        "forward": jax.jit(
            lambda x: forward_packed(m.specs, packed, x)).lower(xw),
        "seg_xla": build_xla_segment(m.specs, packed).lower(xw),
        "seg_mxu": mxu.func.lower(*mxu.args, xw),
    }
    table, ec = _mixed(m, packed, batch=2, mapping=(FULL_GPU,) * 20)
    x = xw
    layer_texts = []
    for node, fn in build_node_fns(m, packed, ec,
                                   build_plan(ec, mode="layers")):
        layer_texts.append(fn.lower(x).as_text(debug_info=True))
        x = fn(x)
    texts = {k: v.as_text(debug_info=True) for k, v in texts.items()}
    texts["layers"] = "\n".join(layer_texts)
    for path, text in texts.items():
        for scope in BIREAL_SCOPES:
            assert f"/{scope}/" in text, (path, scope)


def test_a_dispatch_counts_its_residual_blocks_and_stream_bytes(
        bireal, tmp_path):
    m, packed, xw = bireal
    mapping = (CPU,) * 4 + (FULL_GPU,) * 16
    table, ec = _mixed(m, packed, batch=2, mapping=mapping)
    pipe = SegmentPipeline(m, packed, ec)
    with jax.profiler.trace(str(tmp_path)):
        pipe.run_serial(xw)
    spans = [e[3] for e in _program_events(tmp_path)
             if e[0] == "pipeline.dispatch"]
    assert len(spans) == len(pipe.node_names) == 2
    host, device = spans
    # layers 2, 3 (blocks 1, 2) on the host; 14 blocks on the device
    assert host["res_blocks"] == 2 and device["res_blocks"] == 14

    def stream(specs):
        return sum(4 * (np.prod(s.in_shape) + np.prod(s.out_shape))
                   for s in specs if s.kind == "res")

    assert host["stream_bytes"] == 2 * stream(m.specs[:4])
    assert device["stream_bytes"] == 2 * stream(m.specs[4:])
    assert all(s["batch"] == 2 for s in spans)
