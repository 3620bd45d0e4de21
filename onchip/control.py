"""The control of the benchmark's correctness check.

    python3 onchip/control.py --workload <name> --seeds 1,2,3

For each seed it makes the cell's weights and image pool as a run does,
puts the plain reference in the system's place computed in a lower
precision (each layer's sums kept in float8 e4m3, where the configuration
states exact integer accumulation), answers every image of the pool with
it, and judges those answers as a run judges the system's.  Each seed's
line must read ``correct: false``: that is the upper reading the limits
are set below.  The benchmark's own runs do not run this.
"""

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import jax.numpy as jnp  # noqa: E402

import harness  # noqa: E402
import loadgen  # noqa: E402

SUM_DTYPE = jnp.float8_e4m3fn


def control(cell, seed: int) -> dict:
    cfg = cell.config
    params = harness.make_weights(cfg, seed)
    x01 = loadgen.make_images(
        seed % 2**64, int(cell.traffic["pool"]), cfg["input_hw"],
        cfg["in_channels"],
    )
    want = harness.reference_scores(cfg, params, x01)
    got = harness.reference_scores(cfg, params, x01, sum_dtype=SUM_DTYPE)
    numbers = harness.compare(want, list(range(len(x01))), list(got))
    correct, checks = harness.judge(numbers)
    return {"seed": seed, "correct": correct,
            "checked": numbers["checked"], "checks": checks}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    args = p.parse_args(argv)
    cell = harness.load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(control(cell, seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
