"""Co-serve two BNN models on one platform through the ``repro.api``
facade: contention-aware joint mapping, SLO router, device-time
ledger — one ``Deployment`` object end to end.

The full fleet loop (docs/ARCHITECTURE.md §10, via §13's facade):

1. ``Deployment.plan`` profiles both models over the near-tied
   CPU/XYZ placement pair and jointly maps them under the
   contention-inflation model (never worse than both-solo-all-GPU),
   persisting the joint mappings in a **fleet-scoped**
   ``ProfileStore`` key (a mapping optimized against these co-runners
   must not warm-start a solo deployment, or another fleet);
2. ``serve(adapt=True)`` stands up the ``FleetRouter``: per-tenant
   priorities and deadlines, admission control shedding requests that
   would miss their SLO, a shared ``DeviceTimeLedger`` metering who
   occupied what, and one tenant-named ``RemapController`` per engine
   (namespaced journals) sharing the fleet store.

Every served response is verified bit-exact against its model's packed
reference.

    PYTHONPATH=src python examples/serve_fleet.py
    PYTHONPATH=src python examples/serve_fleet.py --smoke
"""

import argparse
import tempfile

import jax
import numpy as np

from repro import api
from repro.bnn import build_model
from repro.bnn.models import (
    forward_packed, pack_params, prepare_input_packed,
)
from repro.compile_cache import enable_compile_cache
from repro.core.parallel_config import CPU, FULL_GPU
from repro.store import ProfileStore, fleet_scope

SPACE = (CPU, FULL_GPU)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.5)
    ap.add_argument("--requests", type=int, default=128,
                    help="per tenant")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes for the CI docs job")
    args = ap.parse_args()
    if args.smoke:
        args.scale, args.requests = 0.25, 32

    names = ("narrow", "wide")
    models = {}
    for name, s in zip(names, (args.scale, args.scale * 1.5)):
        m = build_model("fashion_mnist", scale=s)
        packed = pack_params(m.specs, m.init(jax.random.PRNGKey(0)))
        models[name] = (m, packed)

    # fleet-scoped persistence: these mappings key under this exact
    # co-tenancy — a solo warm start can never pick them up
    store = ProfileStore(
        tempfile.mkdtemp(prefix="fleet_store_"),
        scope=fleet_scope(names),
    )
    dep = api.Deployment.plan(
        models, store=store, configs=SPACE,
        batch_sizes=(args.batch,), gamma=2.0, repeats=1,
        priorities={"narrow": 1},
    )
    plan = dep.fleet_plan
    print(
        f"joint plan: makespan {plan.joint_makespan_s * 1e6:.0f}us "
        f"vs all-GPU {plan.baseline_makespan_s * 1e6:.0f}us "
        f"({plan.vs_all_gpu:.2f}x, {plan.rounds} descent rounds)"
    )
    for t in plan.tenants:
        segs = t.config.segments()
        print(
            f"  {t.name}: "
            + " ".join(f"[{s.placement[0].upper()}x{len(s)}]"
                       for s in segs)
            + f" infl(host={t.host_inflation:.2f}, "
            f"dev={t.device_inflation:.2f})"
        )
    print(f"persisted joint mappings under scope {store.scope}")

    # the narrow tenant is latency-critical: higher priority (set at
    # plan time), a deadline tight enough that backlog bursts get shed
    narrow = dep.tenants["narrow"].config
    narrow_step_s = (
        narrow.expected_time_per_example * narrow.proper_batch_size
    )
    dep.tenants["narrow"].deadline_s = 4.0 * narrow_step_s

    dep.serve(adapt=True, telemetry_sample_every=2)

    n = args.requests
    xs, refs, reqs = {}, {}, {name: [] for name in names}
    for name in names:
        m, packed = models[name]
        x01 = jax.random.uniform(
            jax.random.PRNGKey(7), (n, *m.input_hw, m.in_channels)
        )
        xs[name] = np.asarray(prepare_input_packed(x01))
        refs[name] = np.asarray(forward_packed(m.specs, packed, xs[name]))

    # interleaved trickle: the narrow tenant bursts 2 requests per
    # round, the wide one 1; the router steps as traffic arrives
    i = {name: 0 for name in names}
    while any(i[name] < n for name in names):
        for name, per_round in (("narrow", 2), ("wide", 1)):
            for _ in range(per_round):
                if i[name] < n:
                    reqs[name].append(
                        (i[name], dep.submit(xs[name][i[name]],
                                             tenant=name))
                    )
                    i[name] += 1
        dep.step()
    dep.drain()

    stats = dep.stats()
    for name in names:
        lat_us, shed = [], 0
        for j, r in reqs[name]:
            if r is None:
                shed += 1
                continue
            scores = r.wait(timeout=5.0)
            assert np.array_equal(scores, refs[name][j]), (
                f"{name} response {j} mismatch"
            )
            lat_us.append(r.latency_s * 1e6)
        s = stats["tenants"][name]
        u = stats["ledger"][name]
        print(
            f"{name}: served {s['served']} shed {shed} "
            f"p50 {np.percentile(lat_us, 50):.0f}us "
            f"p99 {np.percentile(lat_us, 99):.0f}us  "
            f"ledger host {u['host_s'] * 1e3:.1f}ms / "
            f"device {u['device_s'] * 1e3:.1f}ms"
        )
        assert s["rejected"] == shed
    print("all served responses verified exact vs per-model references")


if __name__ == "__main__":
    main()
