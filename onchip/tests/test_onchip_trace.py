"""The reduction from a profiler trace to busy time, idle share, device
time per operation and idle gaps by host span."""

import pytest

import _bench
import trace_reduce as T


def _events():
    # device: [10, 30] and [50, 60] busy; host: window [0, 100]
    return T.TraceEvents(
        device={"/device:TPU:0": [("a", 10, 20), ("b", 15, 30), ("a", 50, 60),
                                  ("a", 120, 130)]},
        host=[("window", 0, 100), ("step", 5, 40), ("submit", 40, 55),
              ("step", 55, 100)],
    )


def test_merge_and_busy_before():
    m = T.merge([(5, 7), (0, 2), (1, 3), (6, 9), (10, 11)])
    assert m.tolist() == [[0, 3], [5, 9], [10, 11]]
    assert T.busy_before(m, [0, 1, 4, 6, 12]).tolist() == [0, 1, 3, 4, 8]


def test_reduce_by_hand():
    s = T.reduce(_events())
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx(30e-9)           # 20 + 10, in window
    assert s.device_op_s == pytest.approx(35e-9)      # 10 + 15 + 10
    assert s.device_ops == [["a", pytest.approx(20e-9)],
                            ["b", pytest.approx(15e-9)]]
    gaps = dict(s.idle_gaps)
    assert gaps["step"] == pytest.approx((35 - 20 + 45 - 5) * 1e-9)
    assert gaps["submit"] == pytest.approx((15 - 5) * 1e-9)
    assert gaps["other"] == pytest.approx(5e-9)
    assert sum(gaps.values()) == pytest.approx(s.window_s - s.busy_s)


def test_reduce_needs_a_window_and_a_device():
    ev = _events()
    assert T.reduce(T.TraceEvents(device={}, host=ev.host)) is None
    assert T.reduce(T.TraceEvents(device=ev.device, host=ev.host[1:])) is None


def _naive_busy(intervals):
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e > end:
            total += e - max(s, end)
            end = e
    return total


def test_recorded_chip_trace():
    """A few requests of fmnist.stream traced on a TPU v5e, against a
    plain loop over the same events."""
    events = T.load(_bench.FIXTURE)
    assert list(events.device) == ["/device:TPU:0"]
    (window,) = [e for e in events.host if e[0] == "window"]
    _, w0, w1 = window
    ops = [(max(s, w0), min(e, w1)) for _, s, e in events.device["/device:TPU:0"]
           if e > w0 and s < w1]
    s = T.reduce(events)
    assert s.window_s == pytest.approx((w1 - w0) * 1e-9)
    assert s.busy_s == pytest.approx(_naive_busy(ops) * 1e-9)
    assert s.device_op_s == pytest.approx(sum(e - b for b, e in ops) * 1e-9)
    assert 0 < s.busy_s < s.window_s
    steps = [e for e in events.host if e[0] == "step"]
    assert steps and all(w0 <= b and e <= w1 for _, b, e in steps)
    gaps = dict(s.idle_gaps)
    assert set(gaps) <= set(T.SPAN_NAMES) | {"other"}
    assert sum(gaps.values()) == pytest.approx(s.window_s - s.busy_s, rel=1e-6)
