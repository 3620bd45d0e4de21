"""Benchmark harness — one module per paper table/figure, plus the
beyond-paper serving path.  Suite-by-suite details: EXPERIMENTS.md.

Prints ``name,us_per_call,derived`` CSV. Mapping to the paper:
  profile_layers     -> Fig. 4 (per-layer x per-implementation matrix)
  efficient_configs  -> Tables IV/V (mappings) + Table VI (min times),
                        DP vs greedy vs uniform baselines side by side
  batch_sweep        -> Fig. 5 (+ Fig. 1 CPU-vs-parallel gap)
  kernel_bench       -> §II-C compute substrate micro-bench, plus the
                        autotuned (open registry space) vs fixed-8
                        end-to-end DP expected-time comparison
  roofline           -> EXPERIMENTS.md §Roofline (reads results/dryrun)
  segment_bench      -> beyond-paper: fused device-segment dispatch
                        (plan IR + segment-scope kernel variants) vs
                        per-layer launch, bit-exact + speedup
  serve_bench        -> beyond-paper: segment-pipelined vs serial
                        serving (EfficientConfiguration.segments() ->
                        repro.serving), throughput + p50/p99
  adapt_bench        -> beyond-paper: drift-triggered remapping under
                        injected contention (repro.adapt) — frozen vs
                        adaptive latency, recovery ratio
  fleet_bench        -> beyond-paper: two-model co-serving
                        (repro.fleet) — joint contention-aware mapping
                        vs both-solo-all-GPU, measured co-run makespan
  cluster_bench      -> beyond-paper: multi-host cluster tier
                        (repro.cluster) — aggregate throughput vs host
                        count, noisy-tenant isolation, journaled
                        elastic scale-up
  cachesvc_bench     -> beyond-paper: shared cache service
                        (repro.cachesvc) — warm-start hit rate through
                        a shared backend, background explore loop
                        recovering a planted-stale mapping
  elastic_bench      -> beyond-paper: elastic nested-width subnets
                        (repro.elastic) — SLO-driven width degradation
                        vs fixed-width shedding under surge, bit-exact
                        per level, journaled degrade/restore
  estimator_bench    -> beyond-paper: learned latency estimator
                        (repro.estimator) — predictor-seeded DP on an
                        unprofiled model (zero profiling passes) vs
                        fully-profiled DP, plus planted-gamma
                        interference-law recovery

The CI regression gate over the tiny-size variants of kernel_bench,
serve_bench, adapt_bench, fleet_bench and cluster_bench lives in
``benchmarks/bench_smoke.py``.

A suite that raises prints a ``SUITE-ERROR`` row and the others still
run; the harness then exits non-zero.  Compiled programs persist in
the compile cache (``repro.compile_cache``).
"""

from __future__ import annotations

import sys
import time
import traceback


def main() -> None:
    from benchmarks import (
        adapt_bench, batch_sweep, cachesvc_bench, cluster_bench,
        efficient_configs, elastic_bench, estimator_bench, fleet_bench,
        kernel_bench, profile_layers, roofline, segment_bench,
        serve_bench,
    )

    from benchmarks.bench_smoke import SMOKE_KWARGS
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    quick = "--quick" in sys.argv
    suites = [
        # --quick reuses the bench-smoke gate's tiny settings so CI and
        # local quick runs measure the same workload
        ("kernel_bench", kernel_bench.run,
         SMOKE_KWARGS["kernel_bench"] if quick else {}),
        ("roofline", roofline.run, {}),
        ("efficient_configs", efficient_configs.run,
         {"scale": 0.25, "batch_sizes": (1, 4), "repeats": 1}
         if quick else {}),
        ("batch_sweep", batch_sweep.run,
         {"scale": 0.25, "batch_sizes": (1, 4), "repeats": 1}
         if quick else {}),
        ("profile_layers", profile_layers.run,
         {"scale": 0.25, "batch_sizes": (1,), "repeats": 1}
         if quick else {}),
        ("segment_bench", segment_bench.run,
         SMOKE_KWARGS["segment_bench"] if quick else {}),
        ("serve_bench", serve_bench.run,
         SMOKE_KWARGS["serve_bench"] if quick else {}),
        ("adapt_bench", adapt_bench.run,
         SMOKE_KWARGS["adapt_bench"] if quick else {}),
        ("fleet_bench", fleet_bench.run,
         SMOKE_KWARGS["fleet_bench"] if quick else {}),
        ("cluster_bench", cluster_bench.run,
         SMOKE_KWARGS["cluster_bench"] if quick else {}),
        ("cachesvc_bench", cachesvc_bench.run,
         SMOKE_KWARGS["cachesvc_bench"] if quick else {}),
        ("elastic_bench", elastic_bench.run,
         SMOKE_KWARGS["elastic_bench"] if quick else {}),
        # not in bench_smoke: the gates inside the suite are the gate
        ("estimator_bench", estimator_bench.run,
         {"train_scales": (0.25, 0.375), "target_scale": 0.5}
         if quick else {}),
    ]
    print("name,us_per_call,derived")
    failed = []
    for name, fn, kwargs in suites:
        t0 = time.time()
        try:
            rows = fn(**kwargs)
        except Exception as e:  # a failing suite must not hide others
            traceback.print_exc()
            print(f"{name}/SUITE-ERROR,0,{e!r}")
            failed.append(name)
            continue
        for rname, us, derived in rows:
            print(f"{rname},{us:.2f},{derived}")
        print(f"# suite {name} took {time.time()-t0:.1f}s",
              file=sys.stderr)
    if failed:
        sys.exit(f"suites failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
