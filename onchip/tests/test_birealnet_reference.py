"""The `bireal18.saturate` cell's parts: Bi-Real-Net-18's plain reference
(its operation counts, its integer bound, its controls), the cell's
readers, and one small run of the cell on the CPU through the harness,
correct against the reference, with the control not."""

import copy
import functools
import time
from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest

import _bench
import loadgen

CELL = "bireal18.saturate"


@pytest.fixture(scope="module")
def full():
    cell = _bench.harness.load_cell(CELL)
    return cell.config, _bench.harness.reference(cell.config)


def small_cell(batch=4, outstanding=8, pool=16):
    """The cell at 32x32 images and a quarter of the widths (what
    ``build_model``'s scale 0.25 gives), served at b4."""
    cell = copy.deepcopy(_bench.harness.load_cell(CELL))
    from repro.bnn import build_model

    model = build_model(cell.config["model"], scale=0.25, input_hw=(32, 32))
    cell.config.update(scale=0.25, input_hw=[32, 32],
                       layers=[s.notation for s in model.specs])
    cell.traffic.update(batch=batch, outstanding=outstanding, pool=pool)
    cell.traffic.pop("profile_store", None)   # tests keep no store
    return cell


def test_macs_per_image_are_the_papers(full):
    cfg, ref = full
    assert ref.macs_per_image(cfg) == {"binary": 1_676_279_808,
                                       "int8": 137_793_536}


def test_integer_bound_holds_at_full_width(full):
    """Every sum, product and stream value of two images stays below
    2**24, as the reference's docstring proves for any seed."""
    cfg, ref = full
    params = _bench.harness.make_weights(cfg, 2**31 + 160)
    x01 = loadgen.make_images(160, 2, cfg["input_hw"], cfg["in_channels"])
    seen = []
    scores = ref.forward(cfg, params, ref.binarize_input(x01), record=seen)
    assert scores.shape == (2, 1000)
    names = {name.split(".")[-1] for name, _ in seen}
    assert {"sum", "fold", "pool", "qfold", "ssum", "sfold", "stream",
            "gap"} <= names
    assert max(v for _, v in seen) < 2**24
    assert np.array_equal(scores, np.rint(scores))


@pytest.mark.parametrize("dtype", [jnp.float8_e4m3fn, jnp.bfloat16])
def test_lower_precision_sums_mismatch(dtype):
    cfg = small_cell().config
    params = _bench.harness.make_weights(cfg, 2**31 + 161)
    x01 = loadgen.make_images(161, 8, cfg["input_hw"], cfg["in_channels"])
    exact = _bench.harness.reference_scores(cfg, params, x01)
    low = _bench.harness.reference_scores(cfg, params, x01, sum_dtype=dtype)
    assert (np.abs(exact - low).max(axis=1) > 0).all()


def test_the_cell_reports_its_metrics():
    cell = _bench.harness.load_cell(CELL)
    assert cell.chips == 1
    assert dict(cell.metrics) == {"images_per_s": "images/s", "setup_s": "s"}
    assert set(dict(cell.per_layer)) == {
        "step_us_per_image", "device_us_per_image", "idle_share.throughput",
        "mfu_int8_all", "device_mfu_int8_all"}


def _fake_run(cell, completed=1000, window_s=2.0, device_op_s=0.05):
    return SimpleNamespace(
        cell=cell, device_kind="TPU v5 lite", completed=completed,
        window_s=window_s, completed_by={cell.name.split(".")[0]: completed},
        trace=SimpleNamespace(device_op_s=device_op_s),
    )


def test_shares_of_the_int8_peak_count_every_mac():
    cell = _bench.harness.load_cell(CELL)
    cell.tenants = {"bireal18": cell.config}
    run = _fake_run(cell)
    ops_per_image = 2 * (1_676_279_808 + 137_793_536)
    host = _bench.harness.reader("mfu_int8_all")(run)
    device = _bench.harness.reader("device_mfu_int8_all")(run)
    assert host == pytest.approx(ops_per_image * 1000 / 2.0 / 393e12 * 100)
    assert device == pytest.approx(ops_per_image * 1000 / 0.05 / 393e12 * 100)
    run.trace = None
    assert _bench.harness.reader("device_mfu_int8_all")(run) is None


def test_the_shares_read_nothing_where_the_reference_counts_no_macs():
    """A paper model's reference has no ``macs_per_image``: the readers
    return None rather than raise."""
    cell = _bench.harness.load_cell("cifar10.saturate")
    cell.tenants = {"cifar10": cell.config}
    run = _fake_run(cell)
    assert _bench.harness.reader("mfu_int8_all")(run) is None
    assert _bench.harness.reader("device_mfu_int8_all")(run) is None


@pytest.fixture
def small_model(monkeypatch):
    """`build_model` as the harness calls it, at the cell's small input."""
    import repro.bnn

    monkeypatch.setattr(repro.bnn, "build_model", functools.partial(
        repro.bnn.build_model, input_hw=(32, 32)))


def test_a_small_run_is_correct_and_the_control_is_not(small_model,
                                                       monkeypatch):
    """Also: `trace_scopes.py` finds the scopes in the compiled text of
    the nodes the run serves."""
    import control
    import trace_scopes

    monkeypatch.setattr(_bench.harness, "plan_signature",
                        _bench.harness.plan_signature)
    texts = []
    trace_scopes.capture_node_texts(texts)
    cell = small_cell()
    result, checks = _bench.harness.run(
        cell, seed=2**31 + 162, seconds=0.3, trace=False,
        t_start=time.perf_counter(), plan_kwargs=_bench.CPU_PLAN,
        log=lambda line: None,
    )
    assert result["correct"] is True
    assert all(c["value"] == 0 for c in checks.values())
    assert set(result["metrics"]) == {"images_per_s", "setup_s"}
    scopes = set(trace_scopes.scope_map("\n".join(texts)).values())
    assert texts and {"stem", "res3", "res7.down", "head"} <= scopes
    out = control.control(cell, 2**31 + 163)
    assert out["correct"] is False
    assert out["checks"]["mismatched_responses"]["value"] > 0



def test_trace_scopes_charges_device_time_to_named_scopes():
    """`trace_scopes.py` maps a node's compiled operations to the scopes
    the program names, and sums the window's device time by them."""
    import trace_reduce
    import trace_scopes
    from repro.bnn import build_model
    from repro.bnn.models import pack_params
    from repro.kernels.segment_fused import build_xla_segment

    cfg = small_cell().config
    model = build_model("birealnet18", scale=0.25, input_hw=(32, 32))
    packed = pack_params(model.specs,
                         _bench.harness.make_weights(cfg, 2**31 + 165))
    x = np.zeros((2, 32, 32, 1), np.int32)
    text = build_xla_segment(model.specs, packed).lower(x).compile().as_text()
    names = trace_scopes.scope_map(text)
    assert {"stem", "res3", "res7.down", "head"} <= set(names.values())
    op = next(n for n, s in names.items() if s == "res7.down")
    events = trace_reduce.TraceEvents(
        device={"/device:TPU:0": [(op, 100, 300), ("fusion.999", 300, 350),
                                  (op, 900, 1200)]},
        host=[("window", 200, 1000)])
    got = trace_scopes.by_scope(events, names)
    assert got == pytest.approx({"res7.down": 200e-9, "unmapped": 50e-9})
