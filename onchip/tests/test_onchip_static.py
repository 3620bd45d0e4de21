"""The benchmark's static parts: BENCHMARK.json against its contract, the
op counts, the peak table and the traffic generator."""

import json
import re

import numpy as np
import pytest

import _bench
import loadgen
import ops

BENCHMARK = json.loads((_bench.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}$")


def test_benchmark_keys():
    assert set(BENCHMARK) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    assert BENCHMARK["command"] == ["python3", "onchip/run.py"]
    assert BENCHMARK["paths"] == ["onchip"]
    assert 1 <= BENCHMARK["run_seconds"] <= 51


def test_names_and_units_use_allowed_characters():
    names = [c["name"] for c in BENCHMARK["configs"]]
    names += [w["name"] for w in BENCHMARK["workloads"]]
    names += [w["traffic"] for w in BENCHMARK["workloads"]]
    names += [m["name"] for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]]
    for name in names:
        assert NAME.match(name), name
    for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for w in BENCHMARK["workloads"]:
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]


def test_every_named_file_exists():
    by_name = {c["name"]: c for c in BENCHMARK["configs"]}
    for w in BENCHMARK["workloads"]:
        cfg = json.loads((_bench.ROOT / by_name[w["config"]]["file"]).read_text())
        assert (_bench.BENCH / "configs" / f"{cfg['reference']}.py").exists()
        traffic = json.loads(
            (_bench.BENCH / "traffic" / f"{w['traffic']}.json").read_text()
        )
        window = _bench.harness.loop(traffic)
        assert callable(window.warm) and callable(window.drive)
    for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]:
        assert callable(_bench.harness.reader(m["name"]))


def test_a_reader_is_found_by_the_metrics_name_or_its_stem():
    metrics = _bench.BENCH / "metrics"
    assert _bench.harness.reader_path("step_ms.latency") == \
        metrics / "step_ms.latency.py"
    assert _bench.harness.reader_path("idle_share.latency") == \
        _bench.harness.reader_path("idle_share.throughput") == \
        metrics / "idle_share.py"


def test_a_metric_without_workloads_follows_the_metric_it_moves(tmp_path):
    """Without `workloads`, a per-layer metric is reported in exactly the
    cells that report the end-to-end metric it moves."""
    bench = json.loads(json.dumps(BENCHMARK))
    bench["per_layer"].append({
        "name": "idle_share.any", "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "device", "moves": "images_per_s",
    })
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    for w in bench["workloads"]:
        cell = _bench.harness.load_cell(w["name"], tmp_path / "BENCHMARK.json")
        reports = "images_per_s" in dict(cell.metrics)
        assert ("idle_share.any" in dict(cell.per_layer)) == reports


def test_per_layer_metrics_move_an_end_to_end_metric_of_their_cells():
    e2e = {m["name"]: m for m in BENCHMARK["end_to_end"]}
    cells = {w["name"] for w in BENCHMARK["workloads"]}
    for m in BENCHMARK["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m["workloads"]:
            assert cell in cells
            assert cell in moved.get("workloads", cells)


@pytest.mark.parametrize("config, macs", [
    ("fashion_mnist", 14_119_936),
    ("cifar10", 463_153_152),
])
def test_binary_macs_per_image(config, macs):
    cfg = json.loads((_bench.BENCH / "configs" / f"{config}.json").read_text())
    ref = _bench.harness.reference(cfg)
    assert ops.binary_macs_per_image(ref.layer_shapes(cfg)) == macs


def test_peaks_are_keyed_by_device_kind():
    v5e = ops.peaks("TPU v5 lite")
    assert v5e["int8_ops_per_s"] == 393e12 and v5e["source"]
    with pytest.raises(KeyError):
        ops.peaks("cpu")


def test_traffic_is_fixed_by_the_seed():
    seed = 2**31 + 12345
    a = loadgen.make_images(seed, 16, (28, 28), 1)
    b = loadgen.make_images(seed, 16, (28, 28), 1)
    c = loadgen.make_images(seed + 1, 16, (28, 28), 1)
    assert np.array_equal(a, b) and not np.array_equal(a, c)

    def take(s):
        order = loadgen.request_order(s, 64)
        return [next(order) for _ in range(200)]

    assert take(seed) == take(seed)
    assert take(seed) != take(seed + 1)
    assert sorted(take(seed)[:64]) == list(range(64))   # a permutation


def test_requests_of_one_tenant_follow_the_request_order():
    seed = 2**31 + 77
    pool = np.arange(64)
    stream = loadgen.requests(seed, {"a": pool})
    order = loadgen.request_order(seed, 64)
    for _ in range(200):
        tenant, i, x = next(stream)
        assert tenant == "a" and i == next(order) and x == pool[i]


def test_requests_of_several_tenants_are_fixed_by_the_seed():
    seed = 2**31 + 78
    pools = {"a": np.arange(64), "b": np.arange(32) + 100}

    def take(s):
        stream = loadgen.requests(s, pools)
        return [next(stream)[:2] for _ in range(3000)]

    assert take(seed) == take(seed) != take(seed + 1)
    assert 0.45 < sum(t == "b" for t, _ in take(seed)) / 3000 < 0.55
    first_b = [i for t, i in take(seed) if t == "b"][:32]
    assert sorted(first_b) == list(range(32))          # a permutation


def test_pack_images_matches_the_request_format():
    from repro.bnn.models import prepare_input_packed

    x = loadgen.make_images(7, 4, (8, 8), 3)
    assert np.array_equal(
        loadgen.pack_images(x), np.asarray(prepare_input_packed(x))
    )


def test_reference_matches_the_programs_packed_forward():
    """The plain reference and the program agree where both are exact,
    and the control's lower precision does not."""
    from repro.bnn import build_model
    from repro.bnn.models import forward_packed, pack_params

    cell = _bench.small_cell()
    cfg = cell.config
    params = _bench.harness.make_weights(cfg, 2**31 + 3)
    x01 = loadgen.make_images(5, 32, cfg["input_hw"], cfg["in_channels"])
    model = build_model(cfg["model"], scale=cfg["scale"])
    want = np.asarray(forward_packed(
        model.specs, pack_params(model.specs, params), loadgen.pack_images(x01)
    ))
    assert np.array_equal(_bench.harness.reference_scores(cfg, params, x01), want)
    import jax.numpy as jnp

    low = _bench.harness.reference_scores(
        cfg, params, x01, sum_dtype=jnp.float8_e4m3fn
    )
    assert not np.array_equal(low, want)
