"""The correctness check fails what it must: the control (the reference
in a lower precision) and faults planted in the timed path."""

import time

import numpy as np
import pytest

import _bench
from repro.serving import pipeline


def _run(cell, seed):
    result, checks = _bench.harness.run(
        cell, seed=seed, seconds=0.3, trace=False,
        t_start=time.perf_counter(), plan_kwargs=_bench.CPU_PLAN,
        log=lambda line: None,
    )
    return result, checks


def _plant(monkeypatch, fault):
    """Break ``SegmentPipeline.run_pipelined`` where it hands each
    micro-batch's output to the engine."""
    real = pipeline.SegmentPipeline.run_pipelined

    def broken(self, inputs, *, on_complete=None, observer=None):
        def complete(i, out):
            on_complete(i, fault(np.array(out)))
        return real(self, inputs, on_complete=complete, observer=observer)

    monkeypatch.setattr(pipeline.SegmentPipeline, "run_pipelined", broken)


def _alter_one_answer(out):
    out[0, 0] += 1
    return out


def _leave_out_half_the_batch(out):
    half = len(out) // 2
    out[half:] = out[:len(out) - half]   # only the first half computed
    return out


def test_sound_run_is_correct():
    result, checks = _run(_bench.small_cell(), 2**31 + 11)
    assert result["correct"] is True
    assert all(c["value"] == 0 for c in checks.values())


@pytest.mark.parametrize("fault", [_alter_one_answer, _leave_out_half_the_batch])
def test_a_fault_in_the_timed_path_is_not_correct(monkeypatch, fault):
    _plant(monkeypatch, fault)
    result, checks = _run(_bench.small_cell(), 2**31 + 12)
    assert result["correct"] is False
    assert checks["mismatched_responses"]["value"] > 0


@pytest.mark.parametrize("seed", [2**31 + 21, 2**31 + 22, 2**31 + 23])
def test_control_is_not_correct(seed):
    import control

    out = control.control(_bench.small_cell(), seed)
    assert out["correct"] is False
    assert out["checks"]["mismatched_responses"]["value"] > 0
