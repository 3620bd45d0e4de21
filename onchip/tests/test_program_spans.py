"""The reduction of the program's own spans: the split of each step,
device idle put down to the innermost span, the collector's idle, and
device time per module."""

import pytest

import _bench
import program_spans as P
import trace_reduce as T

# a traced run of fmnist.stream on a TPU v5e with the program's spans
SPANS_FIXTURE = _bench.BENCH / "tests" / "trace_fmnist_stream_spans.xplane.pb"


def _events():
    # device busy [10, 30] and [50, 60] in the window [0, 100]; the
    # benchmark's step spans [5, 40] and [55, 100]
    ev = T.TraceEvents(
        device={"/device:TPU:0": [("a", 10, 20), ("b", 15, 30), ("a", 50, 60),
                                  ("a", 120, 130)]},
        host=[("window", 0, 100), ("step", 5, 40), ("submit", 40, 55),
              ("step", 55, 100)],
    )
    program = [
        ("engine.step", 6, 39, {"step": 0, "batches": 1, "images": 1}),
        ("batcher.form", 7, 9, {"n_real": 1, "padded": 1}),
        ("pipeline.dispatch", 9, 14, {"node": "node0_0_10_host", "batch": 1}),
        ("pipeline.d2h", 14, 35, {"node": "node0_0_10_host", "batch": 1}),
        ("python.gc", 20, 25, {"generation": 0}),
        ("engine.complete", 35, 38, {"n_real": 1}),
        ("python.gc", 42, 50, {"generation": 1}),      # inside submit
        ("engine.step", 56, 98, {"step": 1, "batches": 1, "images": 2}),
        ("pipeline.dispatch", 57, 70, {"node": "node0_0_10_host", "batch": 2}),
        ("pipeline.d2h", 70, 90, {"node": "node0_0_10_host", "batch": 2}),
        ("python.gc", 92, 95, {"generation": 0}),
    ]
    modules = {"/device:TPU:0": [("jit_node0_0_10_host", 10, 30),
                                 ("jit_node0_0_10_host", 50, 60),
                                 ("jit_other", 120, 130)]}
    return P.ProgramEvents(events=ev, program=program, modules=modules)


def test_step_split_by_hand():
    s = P.reduce(_events())
    split = {k: v.tolist() for k, v in s.step_split.items()}
    assert split["pipeline.dispatch"] == pytest.approx([5e-9, 13e-9])
    assert split["pipeline.d2h"] == pytest.approx([21e-9, 20e-9])
    assert split["batcher.form"] == pytest.approx([2e-9, 0])
    assert split["engine.complete"] == pytest.approx([3e-9, 0])
    assert split["python.gc"] == pytest.approx([5e-9, 3e-9])
    assert split["engine.step"] == pytest.approx([33e-9, 42e-9])
    assert split["pipeline.h2d"] == [0, 0]
    assert s.step_s == pytest.approx(80e-9) and s.images == 3


def test_idle_goes_to_the_innermost_span_and_sums_to_the_step_gap():
    pe = _events()
    s = P.reduce(pe)
    idle = {k: v * 1e9 for k, v in s.idle_by_program_span.items()}
    assert idle == pytest.approx({
        "step:unspanned": 4, "engine.step": 7, "batcher.form": 2,
        "pipeline.dispatch": 11, "pipeline.d2h": 25, "python.gc": 3,
        "engine.complete": 3,
    })
    gaps = dict(T.reduce(pe.events).idle_gaps)
    assert sum(s.idle_by_program_span.values()) == pytest.approx(gaps["step"])
    own = {k: v * 1e9 for k, v in s.self_by_program_span.items()}
    assert own == pytest.approx({
        "step:unspanned": 5, "engine.step": 8, "batcher.form": 2,
        "pipeline.dispatch": 18, "pipeline.d2h": 36, "python.gc": 8,
        "engine.complete": 3,
    })
    assert s.covered == pytest.approx(1 - 13 / 80)


def test_collector_idle_and_device_modules():
    s = P.reduce(_events())
    # [20, 25] is busy; [42, 50] and [92, 95] are idle
    assert s.gc_idle_s == pytest.approx(11e-9)
    assert s.device_modules == [["jit_node0_0_10_host", pytest.approx(30e-9)]]


def test_readings_by_hand():
    s = P.reduce(_events())
    r = P.readings(s, images=3, window_s=100e-9)
    assert r == pytest.approx({
        "gc_idle_share": 11.0,
        "dispatch_ms.latency": 9e-9 * 1e3,
        "d2h_ms.latency": 20.5e-9 * 1e3,
        "batch_form_us_per_image": 2e-9 / 3 * 1e6,
        "complete_us_per_image": 3e-9 / 3 * 1e6,
    })
    # no image completed: no share per image
    assert set(P.readings(s, images=0, window_s=100e-9)) == {
        "gc_idle_share", "dispatch_ms.latency", "d2h_ms.latency"}


def test_reduce_needs_a_window_and_a_device():
    pe = _events()
    pe.events.device = {}
    assert P.reduce(pe) is None


def test_one_pass_reads_what_trace_reduce_reads():
    """On the older chip fixture, recorded before the program had spans."""
    pe = P.load(_bench.FIXTURE)
    assert pe.events == T.load(_bench.FIXTURE)
    assert pe.program == []
    s = P.reduce(pe)
    assert s.idle_by_program_span == {
        "step:unspanned": pytest.approx(dict(T.reduce(pe.events).idle_gaps)[
            "step"])}
    assert s.device_modules[0][0] == "jit_fn"


def _asarray_events(path):
    from jax.profiler import ProfileData

    return [(e.start_ns, e.start_ns + e.duration_ns)
            for plane in ProfileData.from_file(str(path)).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name == "np.asarray(jax.Array)"]


def test_recorded_chip_trace_with_program_spans():
    """A few requests of fmnist.stream traced on a TPU v5e: the spans
    below engine.step cover the steps, every download waits inside a
    pipeline.d2h span, and the modules carry the plan nodes' names."""
    pe = P.load(SPANS_FIXTURE)
    s = P.reduce(pe)
    assert s.covered >= 0.9
    gaps = dict(T.reduce(pe.events).idle_gaps)
    assert sum(s.idle_by_program_span.values()) == pytest.approx(
        gaps["step"], rel=1e-6)
    (window,) = [e for e in pe.events.host if e[0] == "window"]
    d2h = [(a, b) for n, a, b, _ in pe.program if n == "pipeline.d2h"]
    waits = [(a, b) for a, b in _asarray_events(SPANS_FIXTURE)
             if window[1] <= a and b <= window[2]]
    assert waits and all(
        any(a0 <= a and b <= b0 for a0, b0 in d2h) for a, b in waits)
    assert s.device_modules
    assert all(name.startswith("jit_node") for name, _ in s.device_modules)
    steps = [args for n, *_, args in pe.program if n == "engine.step"]
    assert steps and all(args["images"] == 1 for args in steps)


def test_report_is_json_ready():
    import json

    r = P.report(P.reduce(_events()), 100e-9)
    assert json.loads(json.dumps(r)) == r
    assert r["steps"] == 2 and r["images"] == 3
    assert r["step_split_median_ms"]["pipeline.dispatch"] == pytest.approx(
        9e-6)
    assert r["readings"]["gc_idle_share"] == pytest.approx(11.0)


def test_trace_cell_keeps_the_trace_with_the_programs_spans(tmp_path,
                                                            monkeypatch):
    """The traced run of trace_cell.py off the chip: the harness's result
    is as run.py's, and the kept trace holds the engine's spans (the CPU
    has no TPU plane, so there is nothing to reduce)."""
    import time

    import trace_cell

    keep = tmp_path / "kept.xplane.pb"
    tap = trace_cell._Tap(str(keep))
    monkeypatch.setattr(_bench.harness, "trace_reduce", tap)
    result, _ = _bench.harness.run(
        _bench.small_cell("fmnist.stream", batch=1, outstanding=1),
        seed=2**31 + 14, seconds=0.3, trace=True,
        t_start=time.perf_counter(), plan_kwargs=_bench.CPU_PLAN,
        log=lambda line: None,
    )
    assert result["correct"] is True and tap.summary is None
    pe = P.load(keep)
    steps = [args for n, *_, args in pe.program if n == "engine.step"]
    assert steps and all(args["images"] == 1 for args in steps)
    names = {n for n, *_ in pe.program}
    assert {"batcher.form", "pipeline.dispatch", "pipeline.d2h",
            "engine.complete"} <= names
