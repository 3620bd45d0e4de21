"""A run off the chip, and the shape of a run's result line."""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

import _bench

ARGS = ["--workload", "fmnist.stream", "--seed", str(2**31 + 99),
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "onchip/run.py", *ARGS], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


def _no_result(stdout: str) -> bool:
    return all('"correct"' not in line for line in stdout.splitlines())


def test_off_the_chip_exits_nonzero_and_prints_no_result():
    p = _run(_bench.ROOT)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "needs 1 TPU chip" in p.stderr


def test_benchmark_files_alone_print_no_result(tmp_path):
    shutil.copy(_bench.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(_bench.BENCH, tmp_path / "onchip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert _no_result(p.stdout)


@pytest.fixture(scope="module")
def traced_result():
    """One traced run of the small cell on the CPU.  The CPU has no TPU
    plane, so the trace's reduction is taken from the chip fixture, and
    the CPU gets the v5e's peaks."""
    import ops
    import trace_reduce

    summary = trace_reduce.reduce(trace_reduce.load(_bench.FIXTURE))
    mp = pytest.MonkeyPatch()
    mp.setattr(trace_reduce, "reduce", lambda events: summary)
    mp.setattr(ops, "peaks", lambda kind: json.loads(
        ops.PEAKS_FILE.read_text())["TPU v5 lite"])
    try:
        result, _ = _bench.harness.run(
            _bench.small_cell(), seed=2**31 + 5, seconds=0.3, trace=True,
            t_start=time.perf_counter(), plan_kwargs=_bench.CPU_PLAN,
            log=lambda line: None,
        )
    finally:
        mp.undo()
    return result


def test_result_line_keys(traced_result):
    assert list(traced_result) == ["correct", "attempted", "failed", "metrics",
                                   "device", "breakdown", "plan", "checks"]
    assert traced_result["correct"] is True
    assert set(traced_result["device"]) == {
        "platform", "kind", "count", "memory_peak_bytes", "busy_s", "window_s"
    }
    assert set(traced_result["breakdown"]) == {"device_ops", "idle_gaps"}
    assert set(traced_result["metrics"]) == {
        "step_us_per_image", "device_us_per_image", "mfu_int8",
        "idle_share.throughput",
    }
    for c in traced_result["checks"].values():
        assert set(c) == {"value", "limit"}
    json.dumps(traced_result)


def test_untraced_result_line_keys():
    result, _ = _bench.harness.run(
        _bench.small_cell("fmnist.stream", batch=1, outstanding=1),
        seed=2**31 + 6, seconds=0.3, trace=False,
        t_start=time.perf_counter(), plan_kwargs=_bench.CPU_PLAN,
        log=lambda line: None,
    )
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "plan", "checks"]
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"latency_p50_ms", "latency_p95_ms",
                                      "setup_s"}
    ((tenant, plan),) = result["plan"].items()
    assert tenant == "fashion_mnist" and len(plan) == 12


def test_a_second_tenant_is_served_and_checked_from_data_alone():
    """A mix that names a second configuration serves both through one
    deployment and checks every answer of each against its reference."""
    cell = _bench.small_cell(batch=4, outstanding=8)
    other = dict(cell.config, layers=list(cell.config["layers"]))
    cell.tenants = {"fashion_mnist": cell.config, "fmnist_b": other}
    lines = []
    result, checks = _bench.harness.run(
        cell, seed=2**31 + 8, seconds=0.3, trace=False,
        t_start=time.perf_counter(), plan_kwargs=_bench.CPU_PLAN,
        log=lines.append,
    )
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["plan"]) == {"fashion_mnist", "fmnist_b"}
    assert sum(line.startswith("phase_s ") for line in lines) == 2
    assert result["attempted"] >= 16


def test_a_mix_with_a_profile_store_profiles_once_per_checkout(
        tmp_path, monkeypatch):
    """The first run keeps its profile; the next loads it, profiles
    nothing and serves the same plan."""
    from repro import api

    monkeypatch.setattr(_bench.harness, "STORE", tmp_path / "store")
    calls = []
    real = api._profile_fn

    def counting(**kw):
        profile = real(**kw)
        return lambda *a, **k: calls.append(1) or profile(*a, **k)

    monkeypatch.setattr(api, "_profile_fn", counting)
    cell = _bench.small_cell()
    cell.traffic["profile_store"] = True
    plans = []
    for seed in (2**31 + 31, 2**31 + 32):
        result, _ = _bench.harness.run(
            cell, seed=seed, seconds=0.2, trace=False,
            t_start=time.perf_counter(), plan_kwargs=_bench.CPU_PLAN,
            log=lambda line: None,
        )
        assert result["correct"] is True
        plans.append(result["plan"])
    assert len(calls) == 1 and plans[0] == plans[1]
    assert any((tmp_path / "store").rglob("*"))
