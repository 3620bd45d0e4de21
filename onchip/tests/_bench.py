"""Shared by the benchmark's tests: puts the benchmark and the program on
the import path, and builds a small cell that runs on the CPU."""

import copy
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402

# fashion_mnist at a quarter of its widths (what build_model's scale 0.25
# gives), served at b4 with two micro-batches in flight
SMALL_LAYERS = ["C32", "MP14", "S", "C32", "MP7", "S", "FLAT", "FC512", "S",
                "FC512"]
# a plan that needs no timing on the CPU: the analytic cost model
CPU_PLAN = {"time_source": "analytic", "autotune": False, "fuse": False}


def small_cell(name="fmnist.saturate", batch=4, outstanding=8, pool=64):
    cell = copy.deepcopy(harness.load_cell(name))
    cell.config.update(scale=0.25, layers=SMALL_LAYERS)
    cell.traffic.update(batch=batch, outstanding=outstanding, pool=pool)
    cell.traffic.pop("profile_store", None)   # tests keep no store
    return cell

# a traced run of fmnist.stream on a TPU v5e, a few requests long
FIXTURE = BENCH / "tests" / "trace_fmnist_stream.xplane.pb"
