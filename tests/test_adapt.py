"""Adaptive runtime: telemetry sampling/statistics, drift-detector
hysteresis, profile folding, remap atomicity (bit-exactness across hot
swaps, swaps never landing mid-wave), the idle force-flush regression,
and the registry-wired BNN mapping hillclimb."""

import dataclasses

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

import jax

from repro.adapt import (
    DriftDetector,
    RemapController,
    SegmentTelemetry,
    fold_observed,
)
from repro.bnn import build_model
from repro.bnn.models import (
    forward_packed, pack_params, prepare_input_packed,
)
from repro.core.mapper import (
    configuration_from_mapping,
    map_efficient_configuration,
)
from repro.core.parallel_config import CONFIGS, CPU
from repro.core.profiler import ProfileTable
from repro.launch.hillclimb import bnn_mapping_hillclimb
from repro.serving import ServingEngine, canonical_mixed_mapping

from fixtures import FakeClock, flat_table, observe_segments


@pytest.fixture(scope="module")
def small():
    m = build_model("fashion_mnist", scale=0.25)
    packed = pack_params(m.specs, m.init(jax.random.PRNGKey(0)))
    table = flat_table(m)
    ec = configuration_from_mapping(table, 4, canonical_mixed_mapping(m))
    return m, packed, table, ec


def _inputs(m, n, batch=4, seed0=0):
    return [
        np.asarray(prepare_input_packed(
            jax.random.uniform(
                jax.random.PRNGKey(seed0 + i), (batch, 28, 28, 1)
            )
        ))
        for i in range(n)
    ]


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------


class _Seg:
    placement = "host"


def test_telemetry_sampling_cadence_and_warmup():
    tel = SegmentTelemetry(sample_every=2, warmup=1)
    # step 1 is warmup, then every 2nd step is sampled
    got = [tel.sample() is not None for _ in range(6)]
    assert got == [False, True, False, True, False, True]
    tel.reset()
    assert tel.sample() is None          # warmup again after reset


def test_telemetry_disabled_is_never_sampled():
    assert SegmentTelemetry(enabled=False).sample() is None
    assert SegmentTelemetry(sample_every=0).sample() is None


def test_telemetry_stats_per_example_normalization():
    tel = SegmentTelemetry(alpha=0.5, warmup=0)
    tel.on_segment(0, _Seg(), 8.0, 4)     # 2 s/example
    tel.flush()                           # step boundary
    tel.on_segment(0, _Seg(), 4.0, 4)     # 1 s/example
    s = tel.observed(0)
    assert s.count == 2
    assert s.ewma == pytest.approx(1.5)   # 2 -> 0.5*2 + 0.5*1
    assert s.recent_median(2) == pytest.approx(1.5)
    assert s.quantile(0.0) == 1.0 and s.quantile(1.0) == 2.0
    snap = tel.snapshot()
    assert snap[0]["count"] == 2 and snap[0]["placement"] == "host"
    tel.reset()
    assert tel.observed(0) is None


def test_telemetry_recent_median_ignores_single_outlier():
    tel = SegmentTelemetry(warmup=0)
    for v in (1.0, 1.0, 100.0):
        tel.on_segment(0, _Seg(), v, 1)
        tel.flush()
    assert tel.observed(0).recent_median(3) == 1.0


def test_telemetry_recent_floor_survives_outlier_runs():
    """The floor holds the true cost through any run of fewer than k
    slow steps — and tracks a genuine regime change once every recent
    step sits at the new level."""
    tel = SegmentTelemetry(warmup=0)
    for v in (1.0, 50.0, 80.0):          # 2-of-3 slow: still 1.0
        tel.on_segment(0, _Seg(), v, 1)
        tel.flush()
    assert tel.observed(0).recent_floor(3) == 1.0
    for v in (40.0, 50.0, 60.0):         # sustained: floor moves
        tel.on_segment(0, _Seg(), v, 1)
        tel.flush()
    assert tel.observed(0).recent_floor(3) == 40.0


def test_telemetry_aggregates_one_sample_per_step_and_segment():
    """One engine step may drain many micro-batches; they must fold
    into a single window sample (the step's best) so a single stalled
    wave-train can never fill the hysteresis window."""
    tel = SegmentTelemetry(warmup=0)
    for v in (9.0, 3.0, 7.0):            # three micro-batches, one step
        tel.on_segment(0, _Seg(), v, 1)
    s = tel.observed(0)                  # read flushes the step
    assert s.count == 1 and s.window[0] == 3.0


def test_telemetry_validates():
    with pytest.raises(ValueError):
        SegmentTelemetry(alpha=0.0)
    with pytest.raises(ValueError):
        SegmentTelemetry(window=0)
    with pytest.raises(ValueError):
        SegmentTelemetry(sample_every=-1)
    with pytest.raises(ValueError):
        SegmentTelemetry(warmup=-1)


# ---------------------------------------------------------------------------
# drift detection
# ---------------------------------------------------------------------------


# _observe: the shared telemetry feeder (predicted * factor per
# segment, n steps) now lives in tests/fixtures.py
_observe = observe_segments


def test_no_drift_when_observed_matches_predicted(small):
    _, _, _, ec = small
    tel = SegmentTelemetry(warmup=0)
    _observe(tel, ec, {})
    assert DriftDetector(min_samples=3).check(ec, tel) == ()


def test_slow_batches_never_trigger_until_sustained(small):
    """The hysteresis contract: any run of fewer than min_samples slow
    batches — however extreme — cannot clear the recent-floor gate."""
    _, _, _, ec = small
    tel = SegmentTelemetry(warmup=0)
    _observe(tel, ec, {}, n=6)
    pred = ec.segment_expected_times()
    det = DriftDetector(min_samples=3)
    for _ in range(2):                    # two consecutive slow batches
        for idx, seg in enumerate(ec.segments()):
            tel.on_segment(idx, seg, pred[idx] * 1000 * 4, 4)
        assert det.check(ec, tel) == ()
    # the third consecutive slow batch makes it sustained
    for idx, seg in enumerate(ec.segments()):
        tel.on_segment(idx, seg, pred[idx] * 1000 * 4, 4)
    assert det.check(ec, tel) != ()


def test_sustained_drift_is_reported_with_evidence(small):
    _, _, _, ec = small
    tel = SegmentTelemetry(warmup=0)
    _observe(tel, ec, {0: 5.0, 1: 5.0})
    det = DriftDetector(rel_threshold=0.5, min_samples=3)
    reports = det.check(ec, tel)
    assert {r.segment_index for r in reports} == {0, 1}
    for r in reports:
        assert r.ratio == pytest.approx(5.0, rel=1e-6)
        assert r.samples == 8
        assert r.placement == ec.segments()[r.segment_index].placement


def test_drift_needs_min_samples(small):
    _, _, _, ec = small
    tel = SegmentTelemetry(warmup=0)
    _observe(tel, ec, {0: 5.0}, n=2)
    assert DriftDetector(min_samples=3).check(ec, tel) == ()
    _observe(tel, ec, {0: 5.0}, n=1)
    assert DriftDetector(min_samples=3).check(ec, tel) != ()


def test_drift_direction_and_threshold(small):
    _, _, _, ec = small
    tel = SegmentTelemetry(warmup=0)
    _observe(tel, ec, {0: 0.1})           # much faster than predicted
    assert DriftDetector(min_samples=3).check(ec, tel) == ()
    both = DriftDetector(min_samples=3, direction="both").check(ec, tel)
    assert [r.segment_index for r in both] == [0]
    # within threshold: quiet in both directions
    tel2 = SegmentTelemetry(warmup=0)
    _observe(tel2, ec, {0: 1.3})
    assert DriftDetector(
        min_samples=3, rel_threshold=0.5, direction="both"
    ).check(ec, tel2) == ()


def test_drift_min_share_keys_on_observed_too(small):
    """A segment priced as negligible but observed as expensive is the
    contention case — the share gate must not filter it."""
    _, _, _, ec = small
    tel = SegmentTelemetry(warmup=0)
    _observe(tel, ec, {0: 1000.0})
    det = DriftDetector(min_samples=3, min_share=0.5)
    assert [r.segment_index for r in det.check(ec, tel)] == [0]


def test_drift_gates_on_retained_window_not_lifetime_count(small):
    """A telemetry window shorter than min_samples can never prove a
    sustained deviation — the lifetime count must not stand in for
    samples actually retained."""
    _, _, _, ec = small
    tel = SegmentTelemetry(warmup=0, window=2)
    _observe(tel, ec, {0: 50.0}, n=20)    # count=20, retained=2
    assert DriftDetector(min_samples=3).check(ec, tel) == ()


def test_drift_detector_validates():
    with pytest.raises(ValueError):
        DriftDetector(rel_threshold=0.0)
    with pytest.raises(ValueError):
        DriftDetector(min_samples=0)
    with pytest.raises(ValueError):
        DriftDetector(direction="sideways")


# ---------------------------------------------------------------------------
# profile folding
# ---------------------------------------------------------------------------


def test_fold_observed_changes_only_drifted_layers_same_placement(small):
    _, _, table, ec = small
    tel = SegmentTelemetry(warmup=0)
    _observe(tel, ec, {0: 3.0})
    reports = DriftDetector(min_samples=3).check(ec, tel)
    assert len(reports) == 1
    seg = ec.segments()[0]
    corrected = fold_observed(table, ec, reports)
    drifted_host = not seg.on_device
    for b in table.batch_sizes:
        for i in range(len(table.layer_labels)):
            for c in table.configs_for(b, i):
                old = table.kernel_time(b, i, c)
                new = corrected.kernel_time(b, i, c)
                in_seg = seg.start <= i < seg.stop
                same_place = (c == CPU) == drifted_host
                if in_seg and same_place:
                    assert new == pytest.approx(old * reports[0].ratio)
                else:
                    assert new == old
                # totals rebuilt as kernel + unchanged boundary
                assert corrected.times[b][i][c] == pytest.approx(
                    new + corrected.boundary_time(b, i, c)
                )
    assert corrected.h2d_times == table.h2d_times
    assert corrected.d2h_times == table.d2h_times


def test_fold_observed_noop_without_reports(small):
    _, _, table, ec = small
    assert fold_observed(table, ec, ()) is table


# ---------------------------------------------------------------------------
# engine hot swap: atomicity + the idle force-flush regression
# ---------------------------------------------------------------------------


def test_force_flush_on_idle_engine_is_noop(small):
    """Regression: step(force=True) with an empty queue must be a
    no-op — no zero batch is padded and run, nothing errors, and
    telemetry records nothing."""
    m, packed, table, ec = small
    tel = SegmentTelemetry(warmup=0)
    engine = ServingEngine(
        m, packed, ec, allowed_batch_sizes=table.batch_sizes,
        clock=FakeClock(), telemetry=tel,
    )
    for _ in range(3):
        assert engine.step(force=True) == 0
    assert engine.served == 0 and engine.steps == 0
    assert tel.stats() == {}
    # and a pending swap still applies at the idle boundary
    ec2 = configuration_from_mapping(table, 4, (CPU,) * len(m.specs))
    engine._pending_swap = ec2
    assert engine.step(force=True) == 0
    assert engine.config is ec2 and engine.swaps == 1


def test_swap_between_steps_applies_immediately(small):
    m, packed, table, ec = small
    engine = ServingEngine(
        m, packed, ec, allowed_batch_sizes=table.batch_sizes,
        clock=FakeClock(),
    )
    old_pipe = engine.pipeline
    ec2 = configuration_from_mapping(table, 4, ("XYZ",) * len(m.specs))
    assert engine.swap_configuration(ec2) is True
    assert engine.config is ec2 and engine.pipeline is not old_pipe
    assert engine.swaps == 1


def test_swap_must_preserve_serving_batch_size(small):
    """The batcher was sized for the serving batch — a configuration
    priced at another batch is an engine rebuild, not a swap."""
    m, packed, _, ec = small
    table2 = flat_table(m, batch=2)
    engine = ServingEngine(m, packed, ec, clock=FakeClock())
    other = configuration_from_mapping(
        table2, 2, canonical_mixed_mapping(m)
    )
    with pytest.raises(ValueError, match="batch size"):
        engine.swap_configuration(other)
    assert engine.config is ec and engine.swaps == 0


def test_reprice_only_swap_reuses_compiled_pipeline(small):
    """A swap that changes expectations but not the mapping (the
    controller's calibration case) must not re-jit the segments."""
    m, packed, table, ec = small
    engine = ServingEngine(
        m, packed, ec, allowed_batch_sizes=table.batch_sizes,
        clock=FakeClock(),
    )
    old_pipe = engine.pipeline
    repriced = dataclasses.replace(
        ec, expected_time_per_example=ec.expected_time_per_example * 2
    )
    assert engine.swap_configuration(repriced) is True
    assert engine.config is repriced
    assert engine.pipeline is old_pipe and engine.swaps == 1


def test_swap_requested_mid_step_is_deferred_to_batch_boundary(small):
    """A swap from inside a completion callback — i.e. mid-pipeline —
    must not land until the in-flight wave-train retires."""
    m, packed, table, ec = small
    engine = ServingEngine(
        m, packed, ec, allowed_batch_sizes=table.batch_sizes,
        clock=FakeClock(),
    )
    ec2 = configuration_from_mapping(table, 4, ("XYZ",) * len(m.specs))
    xs = _inputs(m, 3)
    for xw in xs:
        for j in range(4):
            engine.submit(xw[j])
    seen = []

    # hook the pipeline to request the swap while micro-batches are in
    # flight, recording what config was live at each completion
    real_run = engine.pipeline.run_pipelined

    def run_with_midstream_swap(inputs, *, on_complete=None, observer=None):
        def complete(i, out):
            if i == 0:
                assert engine.swap_configuration(ec2) is False  # deferred
            seen.append(engine.config)
            on_complete(i, out)

        return real_run(inputs, on_complete=complete, observer=observer)

    engine.pipeline.run_pipelined = run_with_midstream_swap
    assert engine.step(force=True) == 12
    # every completion in that step saw the OLD configuration...
    assert all(c is ec for c in seen) and len(seen) == 3
    # ...and the swap landed exactly at the batch boundary
    assert engine.config is ec2 and engine.swaps == 1


@settings(max_examples=5, deadline=None)
@given(swap_at=st.integers(0, 2), seed=st.integers(0, 2**31 - 1))
def test_outputs_bit_exact_before_during_after_swap(swap_at, seed):
    """Property: for any swap point within a served stream, every
    response equals the serial packed reference — remapping never
    perturbs results."""
    m = build_model("fashion_mnist", scale=0.25)
    packed = pack_params(m.specs, m.init(jax.random.PRNGKey(0)))
    table = flat_table(m)
    ec = configuration_from_mapping(table, 4, canonical_mixed_mapping(m))
    ec2 = map_efficient_configuration(table, policy="dp")
    engine = ServingEngine(
        m, packed, ec, allowed_batch_sizes=table.batch_sizes,
        clock=FakeClock(),
    )
    rng = np.random.default_rng(seed)
    xs = _inputs(m, 4, seed0=int(rng.integers(0, 1000)))
    for step_i, xw in enumerate(xs):
        if step_i == swap_at:
            engine.swap_configuration(ec2)
        reqs = [engine.submit(xw[j]) for j in range(4)]
        assert engine.step(force=True) == 4
        ref = np.asarray(forward_packed(m.specs, packed, xw))
        for j, r in enumerate(reqs):
            assert np.array_equal(r.wait(timeout=5.0), ref[j])
    assert engine.swaps == 1


# ---------------------------------------------------------------------------
# controller: fold -> remap -> swap -> journal
# ---------------------------------------------------------------------------


def test_controller_remaps_on_drift_and_journals(small):
    m, packed, table, ec = small
    tel = SegmentTelemetry(warmup=0)
    engine = ServingEngine(
        m, packed, ec, allowed_batch_sizes=table.batch_sizes,
        clock=FakeClock(), telemetry=tel,
    )
    ctl = RemapController(
        engine, table,
        detector=DriftDetector(rel_threshold=0.5, min_samples=3),
        clock=FakeClock(),
    )
    assert ctl.maybe_remap() is None      # no samples -> no remap
    # host segments observed 50x slower than predicted (contention)
    host_idx = [
        i for i, s in enumerate(ec.segments()) if not s.on_device
    ]
    _observe(tel, ec, {i: 50.0 for i in host_idx})
    rec = ctl.maybe_remap()
    assert rec is not None and ctl.journal == [rec]
    assert engine.swaps == 1 and engine.config is not ec
    assert rec.applied_immediately and rec.changed
    assert {r.segment_index for r in rec.reports} == set(host_idx)
    # the remap routed every *drifted* layer off the contended host
    # (undrifted layers may legally migrate anywhere the DP likes)
    segs = ec.segments()
    for i_seg in host_idx:
        for li in range(segs[i_seg].start, segs[i_seg].stop):
            assert engine.config.layer_configs[li] != CPU
    # DP on the corrected table can only improve on the old mapping
    assert rec.new_expected_s <= rec.old_expected_s
    # remap stays at the serving batch; telemetry starts fresh
    assert engine.config.proper_batch_size == ec.proper_batch_size
    assert tel.stats() == {} and ctl.table is not table
    # journal is exportable
    d = rec.to_dict()
    assert d["changed"] and d["reports"][0]["segment_index"] in host_idx


def test_controller_respects_max_remaps(small):
    m, packed, table, ec = small
    tel = SegmentTelemetry(warmup=0)
    engine = ServingEngine(
        m, packed, ec, allowed_batch_sizes=table.batch_sizes,
        clock=FakeClock(), telemetry=tel,
    )
    ctl = RemapController(
        engine, table, max_remaps=1,
        detector=DriftDetector(rel_threshold=0.5, min_samples=3),
        clock=FakeClock(),
    )
    _observe(tel, ec, {i: 50.0 for i in range(len(ec.segments()))})
    assert ctl.maybe_remap() is not None
    _observe(tel, engine.config,
             {i: 50.0 for i in range(len(engine.config.segments()))})
    assert ctl.maybe_remap() is None      # budget exhausted
    assert engine.swaps == 1


def test_controller_requires_telemetry(small):
    m, packed, table, ec = small
    engine = ServingEngine(
        m, packed, ec, allowed_batch_sizes=table.batch_sizes,
        clock=FakeClock(),
    )
    with pytest.raises(ValueError, match="telemetry"):
        RemapController(engine, table)


def test_controller_serves_bit_exact_across_live_remap(small):
    """End to end through the controller: drift injected between
    steps, outputs stay bit-exact with the reference throughout."""
    m, packed, table, ec = small
    # the engine feeds no wall times (sample_every=0): the injected
    # drift alone decides the remap, where a real sample in the floor
    # window would make the outcome depend on the machine's speed
    tel = SegmentTelemetry(warmup=0, sample_every=0)
    engine = ServingEngine(
        m, packed, ec, allowed_batch_sizes=table.batch_sizes,
        clock=FakeClock(), telemetry=tel,
    )
    ctl = RemapController(
        engine, table,
        detector=DriftDetector(rel_threshold=0.5, min_samples=3),
        clock=FakeClock(),
    )
    xs = _inputs(m, 3, seed0=7)
    for step_i, xw in enumerate(xs):
        if step_i == 1:                   # drift appears mid-stream
            _observe(tel, engine.config, {0: 50.0})
        reqs = [engine.submit(xw[j]) for j in range(4)]
        assert ctl.step(force=True) == 4
        ref = np.asarray(forward_packed(m.specs, packed, xw))
        for j, r in enumerate(reqs):
            assert np.array_equal(r.wait(timeout=5.0), ref[j])
    assert engine.swaps >= 1


def test_tenant_id_namespaces_journal_and_snapshot(small):
    """Two engines' controllers in one process must produce
    attributable records: the telemetry's tenant id rides in its
    snapshot and (via the controller default) in every SwapRecord."""
    m, packed, table, ec = small
    host_idx = [
        i for i, s in enumerate(ec.segments()) if not s.on_device
    ]
    records = []
    for name in ("tenant-a", "tenant-b"):
        tel = SegmentTelemetry(warmup=0, tenant=name)
        engine = ServingEngine(
            m, packed, ec, allowed_batch_sizes=table.batch_sizes,
            clock=FakeClock(), telemetry=tel,
        )
        ctl = RemapController(
            engine, table,
            detector=DriftDetector(rel_threshold=0.5, min_samples=3),
            clock=FakeClock(),
        )
        assert ctl.tenant == name         # defaulted from telemetry
        _observe(tel, ec, {i: 50.0 for i in host_idx})
        assert tel.snapshot()["tenant"] == name
        records.append(ctl.maybe_remap())
    assert [r.tenant for r in records] == ["tenant-a", "tenant-b"]
    assert records[0].to_dict()["tenant"] == "tenant-a"
    # explicit tenant= beats the telemetry default
    tel = SegmentTelemetry(warmup=0, tenant="from-tel")
    engine = ServingEngine(
        m, packed, ec, allowed_batch_sizes=table.batch_sizes,
        clock=FakeClock(), telemetry=tel,
    )
    assert RemapController(
        engine, table, tenant="explicit", clock=FakeClock()
    ).tenant == "explicit"
    # legacy single-tenant loops: unnamed telemetry keeps the old
    # snapshot schema (segment indices only)
    assert "tenant" not in SegmentTelemetry().snapshot()


# ---------------------------------------------------------------------------
# registry-wired hillclimb
# ---------------------------------------------------------------------------


def _variable_space_table():
    """Synthetic table with variable-size per-layer candidate sets
    drawn from the open registry: xla_fused is clearly cheapest on
    layer 1 — a fixed-8 searcher could never find it."""
    rows = [
        {"CPU": 5e-4, "X": 4e-4, "XYZ": 3e-4},
        {"CPU": 5e-4, "XYZ": 4e-4, "xla_fused": 1e-4},
        {"CPU": 2e-4, "X": 4e-4, "XYZ": 4e-4, "pallas_p64n64": 3e-4},
    ]
    kernels = [dict(r) for r in rows]
    return ProfileTable(
        "synthetic", (1,), ("L1:C64", "L2:C64", "L3:FC128"),
        times={1: rows}, kernel_times={1: kernels},
        h2d_times={1: [1e-5] * 3}, d2h_times={1: [1e-5] * 3},
    )


def test_hillclimb_searches_registry_candidate_sets():
    table = _variable_space_table()
    ec, trajectory = bnn_mapping_hillclimb(table)
    ec_dp = map_efficient_configuration(table, policy="dp")
    # sandwich: dp (exact) <= hillclimb <= greedy seed
    assert ec_dp.expected_time_per_example <= (
        ec.expected_time_per_example + 1e-15
    )
    assert ec.expected_time_per_example <= trajectory[0] + 1e-15
    assert trajectory == sorted(trajectory, reverse=True)
    # the climb moved beyond the fixed 8 where the registry wins
    assert ec.layer_configs[1] == "xla_fused"


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_hillclimb_never_worse_than_seed_and_dp_is_lower_bound(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(2, 6))
    batches = (1, 2)
    times, kernels, h2d, d2h = {}, {}, {}, {}
    for b in batches:
        times[b], kernels[b], h2d[b], d2h[b] = [], [], [], []
        for _ in range(n):
            krow = {c: float(rng.uniform(1e-6, 1e-3)) for c in CONFIGS}
            up, down = rng.uniform(1e-6, 5e-4, 2)
            kernels[b].append(krow)
            times[b].append({
                c: krow[c] if c == CPU else krow[c] + up + down
                for c in CONFIGS
            })
            h2d[b].append(float(up))
            d2h[b].append(float(down))
    table = ProfileTable(
        "synthetic", batches, tuple(f"L{i+1}:C8" for i in range(n)),
        times, kernel_times=kernels, h2d_times=h2d, d2h_times=d2h,
    )
    ec, trajectory = bnn_mapping_hillclimb(table)
    ec_dp = map_efficient_configuration(table, policy="dp")
    assert ec.expected_time_per_example <= trajectory[0] + 1e-15
    assert ec_dp.expected_time_per_example <= (
        ec.expected_time_per_example + 1e-12
    )
