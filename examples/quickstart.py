"""Quickstart: train a small BNN with the STE recipe, quantize to
bit-packed inference form, let HEP-BNN map each layer to its fastest
implementation, run the mapped model, and serve it through the
segment-pipelined engine (the README's train -> profile -> map ->
serve walkthrough).

    PYTHONPATH=src python examples/quickstart.py
    PYTHONPATH=src python examples/quickstart.py --smoke   # CI-sized
"""

import argparse

import jax
import numpy as np

from repro import api
from repro.bnn import build_model
from repro.bnn.models import (
    forward_packed, pack_params, prepare_input_packed,
)
from repro.bnn.train import eval_step, init_train_state, train_step
from repro.compile_cache import enable_compile_cache
from repro.core import build_mapped_model
from repro.core.mapper import best_uniform
from repro.data import ShardedBatcher, make_image_dataset
from repro.serving import ServingEngine


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="shrink model/steps/profiling for CI")
    args = ap.parse_args()
    scale = 0.25 if args.smoke else 0.5
    steps = 10 if args.smoke else 60
    batch_sizes = (1, 4) if args.smoke else (1, 4, 16)
    repeats = 1 if args.smoke else 2

    # 1. train (synthetic Fashion-MNIST stand-in — offline container)
    model = build_model("fashion_mnist", scale=scale)
    ds = make_image_dataset(0, 2048, model.input_hw, model.in_channels)
    state, opt = init_train_state(model, jax.random.PRNGKey(0), lr=2e-3)
    batcher = ShardedBatcher(n=2048, global_batch=64, seed=0)
    for step in range(steps):
        x, y = batcher.batch((ds.x, ds.y), step)
        state, metrics = train_step(model, opt, state, x, y)
    xe, ye = batcher.batch((ds.x, ds.y), 9_999)
    print(f"eval acc after {steps} steps: "
          f"{eval_step(model, state.params, xe, ye):.3f}")

    # 2. quantize -> packed xnor/popcount inference model
    packed = pack_params(model.specs, state.params)

    # 3. HEP-BNN: profile every layer under all 8 implementations,
    #    then map with both policies — the paper's greedy Algorithm 1
    #    and the transfer-aware DP that prices the fused executor
    table = api.profile_model(
        model, packed, batch_sizes=batch_sizes, repeats=repeats
    )
    ec_greedy = api.map_model(table, policy="greedy")
    ec = api.map_model(table, policy="dp")
    print(f"proper batch size: {ec.proper_batch_size}")
    for label, c, k, b in zip(
        ec.layer_labels, ec.layer_configs,
        ec.per_layer_kernel_times, ec.per_layer_boundary_times,
    ):
        print(f"  {label:12s} -> {c:4s} kernel {k*1e6:7.1f}us "
              f"boundary {b*1e6:7.1f}us")
    _, t_xyz = best_uniform(table, "XYZ")
    print(
        f"HEP-dp {ec.expected_time_per_example*1e6:.0f} us/img vs "
        f"HEP-greedy {ec_greedy.expected_time_per_example*1e6:.0f} us/img vs "
        f"full-XYZ {t_xyz*1e6:.0f} us/img "
        f"(dp is {t_xyz/ec.expected_time_per_example:.2f}x vs XYZ, "
        f"{ec_greedy.expected_time_per_example/ec.expected_time_per_example:.2f}x vs greedy)"
    )

    # 4. build + run the mapped model; verify exactness
    mapped = build_mapped_model(model, packed, ec)
    x, _ = batcher.batch((ds.x, ds.y), 123)
    x = x[: ec.proper_batch_size]
    xw = prepare_input_packed(x)
    out = mapped(xw)
    ref = forward_packed(model.specs, packed, xw)
    assert np.array_equal(np.asarray(out), np.asarray(ref))
    print("mapped model output == reference (exact)")

    # 5. serve it: the segment-pipelined engine coalesces single
    #    requests into micro-batches of the proper batch size
    engine = ServingEngine(
        model, packed, ec, allowed_batch_sizes=table.batch_sizes
    )
    n_req = 8
    xw_all = np.asarray(prepare_input_packed(x[:1].repeat(n_req, 0)))
    reqs = [engine.submit(xw_all[i]) for i in range(n_req)]
    engine.step(force=True)
    ref1 = np.asarray(ref)[0]
    assert all(np.array_equal(r.wait(1.0), ref1) for r in reqs)
    segs = " ".join(
        f"[{s.placement[0].upper()}x{len(s)}]" for s in ec.segments()
    )
    print(f"served {n_req} requests through segment schedule {segs} "
          "— responses exact")


if __name__ == "__main__":
    main()
