"""Chip smoke test: the mapped-BNN serving path, natively on one TPU.

    python chip_smoke.py

Run from the repository root on a machine with a TPU.  It needs no
arguments, no network and no installed copy of the package (it puts
``src/`` on the import path itself), and it runs in one process.

Phases, each checked bit for bit against the packed reference:

* **kernels** -- every layer-scope GEMM shape of ``fashion_mnist`` and
  ``cifar10`` at their published widths, at b1 and b16, under every
  registered layer-scope variant applicable on this platform, against
  ``xnor_gemm_ref``; then every applicable segment-scope variant on the
  device span ``benchmarks/segment_bench.py`` uses (first conv on the
  host, the rest fused), against ``forward_packed``.
* **main path** -- ``fashion_mnist`` at published widths through
  ``api.Deployment.plan(autotune=True, fuse=True)`` with measured
  profiling, then ``serve()``: 256 seeded single-image requests,
  stepped while they arrive, then drained, each response against
  ``forward_packed``.

It prints the device, per-phase wall times, the chosen per-layer
variants and fused segments, the device each plan node's output lands
on, peak device memory and the request count.  Latencies it prints are
smoke numbers, not benchmark results.  The last line of standard output
is one JSON object, ``{"ok": true, "device": {...}}``.  Any mismatch,
exception or a platform other than ``tpu`` exits non-zero without it.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro import api  # noqa: E402
from repro.bnn import build_model  # noqa: E402
from repro.bnn import layers as L  # noqa: E402
from repro.bnn.models import (  # noqa: E402
    forward_packed,
    pack_params,
    prepare_input_packed,
)
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core.profiler import gemm_shape_of  # noqa: E402
from repro.data import make_image_dataset  # noqa: E402
from repro.kernels.ref import xnor_gemm_ref  # noqa: E402
from repro.kernels.registry import (  # noqa: E402
    DEFAULT_REGISTRY,
    current_platform,
    segment_shape_of,
)
from repro.kernels.segment_fused import infer_in_encoding  # noqa: E402

MODELS = ("fashion_mnist", "cifar10")
SEED = 0


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def _packed_model(name: str, scale: float):
    model = build_model(name, scale=scale)
    return model, pack_params(model.specs, model.init(jax.random.PRNGKey(SEED)))


def _images(model, n: int, seed: int) -> np.ndarray:
    ds = make_image_dataset(seed, n, model.input_hw, model.in_channels)
    return np.asarray(prepare_input_packed(ds.x))


def _gemm_operand(spec, x_words):
    """The packed (B, P, Kw) operand a conv/fc layer hands its GEMM."""
    if spec.kind == "conv":
        b, h, w, _ = x_words.shape
        return L.extract_patch_words(x_words).reshape(b, h * w, -1)
    return x_words[:, None, :]


def kernel_phase(*, scale: float = 1.0, batch_sizes=(1, 16)) -> int:
    """Run every applicable layer- and segment-scope variant once per
    shape and compare bit for bit.  Returns the number of checks."""
    platform = current_platform()
    checks = 0
    seen = set()
    for name in MODELS:
        model, packed = _packed_model(name, scale)
        for b in batch_sizes:
            x = _images(model, b, seed=b)
            for i, (spec, p) in enumerate(zip(model.specs, packed)):
                shape = gemm_shape_of(spec, p, b)
                if shape is None or shape in seen:
                    continue
                seen.add(shape)
                a = _gemm_operand(
                    spec, forward_packed(model.specs[:i], packed[:i], x)
                )
                w, k_true = p["w_words"], p["k_true"]
                want = np.asarray(xnor_gemm_ref(a, w, k_true))
                t0 = time.perf_counter()
                names = []
                for v in DEFAULT_REGISTRY.applicable(shape, platform):
                    fn = jax.jit(lambda a, w, _b=v.builder: _b(a, w, k_true))
                    got = np.asarray(fn(a, w))
                    _check(
                        np.array_equal(got, want),
                        f"{v.name} != xnor_gemm_ref at {name} "
                        f"{spec.notation} {shape}",
                    )
                    names.append(v.name)
                    checks += 1
                print(
                    f"  kernels {name} L{spec.idx}:{spec.notation} "
                    f"b={b} p={shape.p} n={shape.n} kw={shape.kw}: "
                    f"{len(names)} variants bit-exact "
                    f"({time.perf_counter() - t0:.2f}s): {' '.join(names)}",
                    flush=True,
                )
            # the segment bench's device span: first conv on the host,
            # everything after it fused into one dispatch
            start, stop = 1, len(model.specs)
            specs = tuple(model.specs[start:stop])
            seg_in = forward_packed(model.specs[:start], packed[:start], x)
            want = np.asarray(forward_packed(model.specs, packed, x))
            seg_shape = segment_shape_of(specs, packed[start:stop], b)
            names = []
            for v in DEFAULT_REGISTRY.applicable_segments(seg_shape, platform):
                fn = v.builder(
                    specs, list(packed[start:stop]), infer_in_encoding(specs)
                )
                got = np.asarray(fn(seg_in))
                _check(
                    np.array_equal(got, want),
                    f"{v.name} != forward_packed on {name} "
                    f"span {start}:{stop} b={b}",
                )
                names.append(v.name)
                checks += 1
            _check(bool(names), f"no segment variant applies to {name}")
            print(
                f"  segments {name} span {start}:{stop} b={b}: "
                f"{' '.join(names)} bit-exact",
                flush=True,
            )
    return checks


def _node_devices(engine, x_words: np.ndarray) -> list:
    """Walk the served plan's nodes once, as the serial driver does,
    and report the device(s) each node's output is committed to."""
    pipe = engine.pipeline
    rows = []
    x = x_words
    for node, fn in pipe.segment_fns:
        if node.on_device:
            x = jax.device_put(np.asarray(x), pipe.device)
        else:
            x = np.asarray(x)
        out = fn(x)
        jax.block_until_ready(out)
        rows.append((node, sorted(str(d) for d in out.devices())))
        x = out
    return rows


def main_path_phase(
    *, scale: float = 1.0, batch_sizes=(1, 16), n_requests: int = 256
) -> dict:
    """Plan ``fashion_mnist`` through ``Deployment.plan``, serve
    `n_requests` seeded requests and check every response."""
    model, packed = _packed_model("fashion_mnist", scale)
    dep = api.Deployment.plan(
        (model, packed),
        batch_sizes=batch_sizes,
        autotune=True,
        fuse=True,
        time_source="measured",
    )
    (tp,) = dep.tenants.values()
    ec = dep.configuration()
    for phase, secs in tp.phase_s.items():
        print(f"  phase {phase}: {secs:.2f}s", flush=True)
    print(f"  proper batch {ec.proper_batch_size}")
    for label, cfg in zip(ec.layer_labels, ec.layer_configs):
        print(f"  layer {label}: {cfg}")
    for s, e, variant, t in ec.fused_segments:
        print(f"  fused segment {s}:{e}: {variant} (priced {t:.3e} s/ex)")
    if not ec.fused_segments:
        print("  fused segments: none selected")

    t0 = time.perf_counter()
    dep.serve()
    xw = _images(model, n_requests, seed=7)
    reqs, served = [], 0
    for i in range(n_requests):
        reqs.append(dep.submit(xw[i]))
        served += dep.step()
    served += dep.drain()
    serve_s = time.perf_counter() - t0
    _check(served == n_requests, f"served {served} of {n_requests}")
    ref = np.asarray(forward_packed(model.specs, packed, xw))
    for i, r in enumerate(reqs):
        _check(
            np.array_equal(r.wait(timeout=60.0), ref[i]),
            f"response {i} != forward_packed",
        )
    lat_us = np.asarray([r.latency_s * 1e6 for r in reqs])
    print(f"  phase serve: {serve_s:.2f}s (compiles included)")
    print(
        f"  requests {served}, all bit-exact vs forward_packed; "
        f"smoke latency, not a benchmark result: "
        f"p50 {np.percentile(lat_us, 50):.0f}us "
        f"p99 {np.percentile(lat_us, 99):.0f}us"
    )
    for node, devices in _node_devices(dep.engine, xw[: ec.proper_batch_size]):
        print(
            f"  node {node.start}:{node.stop} {node.placement} "
            f"{node.fused_variant or '+'.join(node.configs)} "
            f"-> output on {','.join(devices)}"
        )
    return {"served": served, "phase_s": dict(tp.phase_s, serve=serve_s)}


def _device_info() -> dict:
    d = jax.devices()[0]
    return {
        "platform": d.platform,
        "kind": d.device_kind,
        "count": len(jax.devices()),
    }


def main() -> int:
    device = _device_info()
    print(
        f"jax {jax.__version__}: platform={device['platform']} "
        f"kind={device['kind']} count={device['count']}",
        flush=True,
    )
    if device["platform"] != "tpu":
        print("chip_smoke: no TPU found; this script runs only on the chip",
              file=sys.stderr)
        return 1
    print(f"compile cache: {enable_compile_cache()}")

    t0 = time.perf_counter()
    print("kernel phase (published widths, native kernels):", flush=True)
    checks = kernel_phase()
    print(f"kernel phase: {checks} checks, {time.perf_counter() - t0:.1f}s")

    t0 = time.perf_counter()
    print("main-path phase (fashion_mnist, scale 1.0):", flush=True)
    main_path_phase()
    print(f"main-path phase: {time.perf_counter() - t0:.1f}s")

    stats = jax.devices()[0].memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'not reported')}")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
