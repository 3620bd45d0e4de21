"""Batched-request serving through the HEP-mapped BNN, via the
``repro.api`` facade — the blessed profile → map → serve path.

``Deployment.plan`` profiles the model and maps it with the
transfer-aware DP; ``serve()`` stands up the segment-pipelined
engine: single-example requests are coalesced by the dynamic
micro-batcher (max-batch = the mapper's proper batch size, partial
batches padded to a profiled batch size) and executed as a two-stage
host/device segment pipeline.  Reports p50/p99 request latency and
verifies every response bit-exact against the reference model.

    PYTHONPATH=src python examples/serve_mapped.py
    PYTHONPATH=src python examples/serve_mapped.py \
        --requests 256 --scale 0.25 --policy greedy --max-wait-ms 5
"""

import argparse
from pathlib import Path

import jax
import numpy as np

from repro import api
from repro.bnn import build_model
from repro.bnn.models import (
    forward_packed, pack_params, prepare_input_packed,
)
from repro.compile_cache import enable_compile_cache
from repro.data import make_image_dataset


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.5)
    ap.add_argument("--requests", type=int, default=512)
    ap.add_argument("--policy", default="dp", choices=("greedy", "dp"))
    ap.add_argument("--max-wait-ms", type=float, default=2.0)
    args = ap.parse_args()

    model = build_model("fashion_mnist", scale=args.scale)
    packed = pack_params(model.specs, model.init(jax.random.PRNGKey(0)))

    dep = api.Deployment.plan(
        (model, packed),
        batch_sizes=(1, 4, 16), policy=args.policy, repeats=2,
    )
    ec = dep.configuration()
    # own filename: results/efficient_config_fmnist.json is the
    # committed legacy-schema fixture tests round-trip — never clobber
    artifact = Path("results") / "serve_mapped_config.json"
    artifact.parent.mkdir(exist_ok=True)
    artifact.write_text(ec.to_json())
    print(f"wrote mapping artifact -> {artifact}")
    segs = ec.segments()
    print(
        f"schedule: {len(segs)} segments "
        + " ".join(f"[{s.placement[0].upper()}x{len(s)}]" for s in segs)
        + f", proper batch {ec.proper_batch_size}"
    )

    dep.serve(max_wait_s=args.max_wait_ms * 1e-3)

    n = args.requests
    ds = make_image_dataset(7, n, model.input_hw, model.in_channels)
    xw_all = np.asarray(prepare_input_packed(ds.x))
    # trickle requests in, stepping as we go: full micro-batches drain
    # immediately, stragglers age out under --max-wait-ms, and a final
    # forced drain flushes the partial tail
    reqs = []
    served = 0
    for i in range(n):
        reqs.append(dep.submit(xw_all[i]))
        served += dep.step()
    served += dep.drain()
    assert served == n

    ref = np.asarray(forward_packed(model.specs, packed, xw_all))
    correct = 0
    lat_us = []
    for i, r in enumerate(reqs):
        scores = r.wait(timeout=1.0)
        assert np.array_equal(scores, ref[i]), f"response {i} mismatch"
        lat_us.append(r.latency_s * 1e6)
        correct += int(np.argmax(scores) == ds.y[i])
    lat_us = np.asarray(lat_us)
    stats = dep.stats()
    print(
        f"served {stats['served']} requests @ max_batch "
        f"{ec.proper_batch_size}: "
        f"p50 {np.percentile(lat_us, 50):.0f}us  "
        f"p99 {np.percentile(lat_us, 99):.0f}us  "
        f"(untrained acc {correct / n:.3f})"
    )
    print("all responses verified exact vs reference")


if __name__ == "__main__":
    main()
