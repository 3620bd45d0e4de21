"""The on-chip benchmark: one run of one cell.

    python3 onchip/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with a TPU.  The cell (a
configuration under a traffic mix) is found by name in ``BENCHMARK.json``.
The run plans and serves the configuration through ``repro.api``, warms
the served shape, drives the traffic mix's loop for ``--seconds``, then
checks every answer against the plain reference.  ``--trace 1``
records a profiler trace of the window and reports the per-layer metrics
instead of the end-to-end ones.

Standard output ends with one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``,
``plan``, the hash of each tenant's served plan, and last ``checks``,
each compared number with its limit.  The same numbers are the last
lines of standard error.  Without a TPU, or with fewer chips than the
cell asks for, it exits non-zero and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent / "src"))
sys.path.insert(0, str(HERE))

import harness  # noqa: E402


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def log(line: str) -> None:
    print(line, flush=True)


def main(argv=None) -> int:
    args = parse(argv)
    cell = harness.load_cell(args.workload)

    import jax

    devices = jax.devices()
    d = devices[0]
    log(f"jax {jax.__version__}: platform={d.platform} kind={d.device_kind} "
        f"count={len(devices)}")
    if d.platform != "tpu" or len(devices) < cell.chips:
        print(f"run.py: {args.workload} needs {cell.chips} TPU chip(s); "
              f"found {len(devices)} {d.platform} device(s)", file=sys.stderr)
        return 1
    from repro.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    result, checks = harness.run(
        cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        t_start=T_START, log=log,
    )
    print(f"correct {str(result['correct']).lower()}", file=sys.stderr)
    for name, c in checks.items():
        print(f"{name} {c['value']} limit {c['limit']}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
