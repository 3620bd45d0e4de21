"""One traced run of a cell, with the program's own spans reduced.

    python3 onchip/trace_cell.py --workload <name> --seed <n> --seconds <s> [--keep <file>]

Runs the cell as ``run.py --trace 1`` does, on a TPU, and prints the
same result object with two additions: the end-to-end metrics read from
the traced window beside the per-layer ones, and ``program``, the
reduction of the serving engine's spans (``program_spans.report``),
made from the trace before the harness discards it.  ``--keep`` copies
the trace to <file> first.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent / "src"))
sys.path.insert(0, str(HERE))

import harness  # noqa: E402
import program_spans  # noqa: E402
import trace_reduce  # noqa: E402


class _Tap:
    """``trace_reduce`` as the harness calls it, reducing the program's
    spans from the same file on the way."""

    reduce = staticmethod(trace_reduce.reduce)

    def __init__(self, keep):
        self.keep, self.summary = keep, None

    def load(self, pb):
        if self.keep:
            Path(self.keep).parent.mkdir(parents=True, exist_ok=True)
            shutil.copyfile(pb, self.keep)
        pe = program_spans.load(pb)
        self.summary = program_spans.reduce(pe)
        return pe.events


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--keep", default=None)
    args = p.parse_args(argv)
    cell = harness.load_cell(args.workload)
    cell.per_layer = cell.per_layer + cell.metrics

    import jax

    d = jax.devices()[0]
    if d.platform != "tpu" or len(jax.devices()) < cell.chips:
        print(f"trace_cell.py: {args.workload} needs {cell.chips} TPU "
              f"chip(s); found {d.platform}", file=sys.stderr)
        return 1
    from repro.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    tap = _Tap(args.keep)
    harness.trace_reduce = tap
    result, _ = harness.run(cell, seed=args.seed, seconds=args.seconds,
                            trace=True, t_start=T_START,
                            log=lambda line: print(line, flush=True))
    if tap.summary is not None:
        result["program"] = program_spans.report(
            tap.summary, result["device"]["window_s"])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
