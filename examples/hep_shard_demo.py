import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

"""HEP-Shard demo: the paper's greedy mapping algorithm selecting a
sharding scheme for an LM cell from compiled dry-run costs, on a local
8-device debug mesh (2 data x 4 model).

    PYTHONPATH=src python examples/hep_shard_demo.py --arch olmo_1b
"""

import argparse
import dataclasses

import jax

from repro import configs as C
from repro.compile_cache import enable_compile_cache
from repro.core.cost_model import HOST_LATENCY, HOST_LINK_BW
from repro.core.hep_shard import ShardTrial, search
from repro.launch import hlo_analysis as H
from repro.launch.dryrun import HBM_BW, ICI_BW, PEAK_BF16, build_lowered


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo_1b")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--layers", type=int, default=2)
    args = ap.parse_args()

    cfg = dataclasses.replace(C.get(args.arch), n_layers=args.layers)
    mesh = jax.make_mesh((2, 4), ("data", "model"))
    sh = C.SHAPES[args.shape]
    # real per-step host staging: the token batch up (ids + targets,
    # int32) and the metrics scalars down — parameters and optimizer
    # state stay device-resident across steps and are NOT charged.
    # The global batch reaches the devices whole under every scheme, so
    # this term is scheme-invariant: reporting-only, it never moves the
    # search's argmin (scheme-dependent staging would mis-price
    # resident state, the bias the layer-level DP exists to avoid)
    step_in_bytes = 2 * sh.batch * sh.seq * 4

    def evaluate(scheme):
        compiled = build_lowered(cfg, args.shape, mesh, scheme).compile()
        txt = compiled.as_text()
        mem = compiled.memory_analysis()
        peak = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                + mem.output_size_in_bytes - mem.alias_size_in_bytes)
        return ShardTrial(
            scheme=scheme,
            compute_s=H.dot_flops(txt) / PEAK_BF16,
            memory_s=H.hbm_bytes(txt) / HBM_BW,
            collective_s=H.collective_bytes(txt, 8).total_bytes / ICI_BW,
            peak_bytes=peak,
            # host staging split the same way the layer profiler splits
            # kernel vs boundary
            h2d_s=HOST_LATENCY + step_in_bytes / HOST_LINK_BW,
            d2h_s=HOST_LATENCY,
        )

    knobs = {  # reduced lattice for the demo
        "tp": (True, False),
        "fsdp": ("zero1", "zero3"),
        "batch_over_model": (False, True),
    }
    best, history = search(evaluate, knobs=knobs, max_rounds=2)
    print(f"\nevaluated {len(history)} trials; best scheme:")
    print(f"  {best.scheme}")
    print(
        f"  compute {best.compute_s*1e3:.2f}ms  "
        f"memory {best.memory_s*1e3:.2f}ms  "
        f"collective {best.collective_s*1e3:.2f}ms  "
        f"transfer {best.transfer_s*1e3:.2f}ms  "
        f"peak {best.peak_bytes/2**30:.2f}GiB"
    )


if __name__ == "__main__":
    main()
