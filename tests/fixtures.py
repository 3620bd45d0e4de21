"""Shared deterministic test fixtures.

Pure-python helpers the estimator, fleet and adapt tests share instead
of hand-rolling: seeded synthetic ProfileTables, planted-gamma ledger
traces, fake clocks, telemetry feeders and an exactly log-linear
ground-truth cost law for held-out predictor recovery.  Everything
here is deterministic given its seed/arguments — no wall clock, no
real profiling.
"""

import importlib.util
import math
import random
from pathlib import Path
from types import SimpleNamespace

from repro.bnn.layers import LayerSpec
from repro.core.mapper import DEVICE, HOST
from repro.core.parallel_config import CONFIGS, CPU
from repro.core.profiler import ProfileTable
from repro.estimator.features import (
    boundary_features,
    feature_vector,
    group_key,
    layer_geometry,
    variant_meta,
)
from repro.fleet.ledger import DeviceTimeLedger


class FakeClock:
    """Injectable monotonic clock: starts at 0, advances only when the
    test says so — batcher max-waits and router deadlines become
    deterministic on loaded CI runners."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, seconds):
        self.t += seconds


# ---------------------------------------------------------------------------
# synthetic ProfileTables
# ---------------------------------------------------------------------------


def random_split_table(rng, n_layers=5, batches=(1, 4), name="synthetic"):
    """Random kernel/boundary-split table over the fixed-8 space
    (``rng`` is a ``numpy.random.Generator``)."""
    kernel, times, h2d, d2h = {}, {}, {}, {}
    for b in batches:
        kernel[b], times[b], h2d[b], d2h[b] = [], [], [], []
        for _ in range(n_layers):
            krow = {c: float(rng.uniform(1e-6, 1e-3)) for c in CONFIGS}
            up = float(rng.uniform(1e-6, 5e-4))
            down = float(rng.uniform(1e-6, 5e-4))
            times[b].append({
                c: krow[c] if c == CPU else krow[c] + up + down
                for c in CONFIGS
            })
            kernel[b].append(krow)
            h2d[b].append(up)
            d2h[b].append(down)
    return ProfileTable(
        name, tuple(batches),
        tuple(f"L{i+1}:C64" for i in range(n_layers)), times,
        kernel_times=kernel, h2d_times=h2d, d2h_times=d2h,
    )


def tied_table(name, n_layers=4, batch=4, cpu=1.0, gpu=0.9, bnd=0.005):
    """CPU and device near-tied per layer — the regime where joint
    mapping has a genuine placement choice."""
    times = {batch: [
        {c: cpu if c == CPU else gpu + 2 * bnd for c in CONFIGS}
        for _ in range(n_layers)
    ]}
    kernels = {batch: [
        {c: cpu if c == CPU else gpu for c in CONFIGS}
        for _ in range(n_layers)
    ]}
    return ProfileTable(
        name, (batch,),
        tuple(f"L{i+1}:C64" for i in range(n_layers)), times,
        kernel_times=kernels,
        h2d_times={batch: [bnd] * n_layers},
        d2h_times={batch: [bnd] * n_layers},
    )


def flat_table(model, batch=4, t=1e-4, up=1e-5, down=1e-5):
    """Uniform-cost table for a real model's specs: every config costs
    the same, so mappings are placement-driven and deterministic."""
    n = len(model.specs)
    return ProfileTable(
        model.name, (batch,),
        tuple(f"L{s.idx}:{s.notation}" for s in model.specs),
        times={batch: [
            {c: t if c == CPU else t + up + down for c in CONFIGS}
            for _ in range(n)
        ]},
        kernel_times={batch: [{c: t for c in CONFIGS} for _ in range(n)]},
        h2d_times={batch: [up] * n},
        d2h_times={batch: [down] * n},
    )


# ---------------------------------------------------------------------------
# telemetry feeding
# ---------------------------------------------------------------------------


def observe_segments(tel, ec, factors, batch=4, n=8):
    """Feed `n` steps' worth of observations into a SegmentTelemetry:
    each segment observed at its predicted time times
    ``factors.get(index, 1.0)``."""
    pred = ec.segment_expected_times()
    for _ in range(n):
        for idx, seg in enumerate(ec.segments()):
            f = factors.get(idx, 1.0)
            tel.on_segment(idx, seg, pred[idx] * f * batch, batch)
        tel.flush()                       # step boundary


# ---------------------------------------------------------------------------
# planted-gamma ledger traces
# ---------------------------------------------------------------------------

DEFAULT_OCCUPANCIES = {
    "t0": (0.6, 0.9),
    "t1": (0.25, 0.55),
    "t2": (0.9, 0.15),
}


def planted_gamma_ledger(
    gamma,
    occupancies=DEFAULT_OCCUPANCIES,
    *,
    steps=6,
    noise=0.0,
    seed=0,
):
    """A :class:`DeviceTimeLedger` whose step rows embody a **known**
    linear interference law, plus the solo step expectations that
    decode it.

    Each tenant's per-step measured (host_s, device_s) occupancy is
    its `occupancies` entry, jittered by a per-(tenant, step)
    multiplicative factor in ``[1-noise, 1+noise]`` applied *jointly*
    to both processors — so every tenant's normalized shares (and
    therefore every co-runner share) stay exact under noise.  The
    returned ``expected`` maps tenant -> solo (host_s, device_s) such
    that ``measured / expected == 1 + gamma * co_runner_share``
    exactly at ``noise=0``:
    ``InterferenceFit.from_ledger(ledger, expected).fit()`` must
    recover `gamma`.
    """
    rng = random.Random(seed)
    ledger = DeviceTimeLedger(window=steps + 2)
    shares = {
        t: (h / (h + d), d / (h + d))
        for t, (h, d) in occupancies.items()
    }
    co = {
        t: (
            sum(s[0] for u, s in shares.items() if u != t),
            sum(s[1] for u, s in shares.items() if u != t),
        )
        for t in occupancies
    }
    expected = {
        t: (
            h / (1.0 + gamma * co[t][0]),
            d / (1.0 + gamma * co[t][1]),
        )
        for t, (h, d) in occupancies.items()
    }
    for _ in range(steps):
        for t, (h, d) in occupancies.items():
            jit = 1.0 + (rng.uniform(-noise, noise) if noise else 0.0)
            ledger.record(t, HOST, h * jit)
            ledger.record(t, DEVICE, d * jit)
            ledger.close_step(t)
    return ledger, expected


# ---------------------------------------------------------------------------
# exactly log-linear ground-truth cost law (predictor recovery)
# ---------------------------------------------------------------------------

# one weight vector per estimator regression group / boundary
# direction — the truth lies exactly in the predictor's hypothesis
# class, so held-out error measures the fit, not model mismatch
TRUTH_WEIGHTS = {
    "gemm/host/host": (
        -13.0, -0.25, 0.55, 0.65, 0.45, 0.0, 0.0, 0.0, 0.0, 0.0
    ),
    "gemm/device/tiled": (
        -14.0, -0.35, 0.5, 0.6, 0.4, 0.0, 0.0, -0.05, -0.1, -0.15
    ),
    "ew/host/host": (-16.0, -0.2, 0.8),
    "ew/device/tiled": (-16.5, -0.25, 0.75),
    "h2d": (-14.0, -0.1, 0.6),
    "d2h": (-14.5, -0.1, 0.6),
}


def truth_kernel_s(geometry, meta, weights=TRUTH_WEIGHTS):
    x = feature_vector(geometry, meta)
    w = weights[group_key(geometry, meta)]
    return math.exp(sum(a * b for a, b in zip(x, w)))


def truth_boundary_s(geometry, direction, weights=TRUTH_WEIGHTS):
    x = boundary_features(geometry, direction)
    return math.exp(sum(a * b for a, b in zip(x, weights[direction])))


def synthetic_model(name, conv_units=(32, 64), fc_units=(128, 10), hw=12):
    """A spec-only model (no jax, no parameters): conv layers at
    `hw` x `hw` spatial size, then fc layers — enough structure to
    exercise both estimator geometry classes."""
    specs = []
    idx = 1
    cin = 32
    for u in conv_units:
        specs.append(LayerSpec(
            idx, "conv", f"C{u}", (hw, hw, cin), (hw, hw, u), units=u
        ))
        idx += 1
        specs.append(LayerSpec(
            idx, "step", "S", (hw, hw, u), (hw, hw, u), units=u
        ))
        idx += 1
        cin = u
    feat = hw * hw * cin
    specs.append(LayerSpec(
        idx, "flat", "FLAT", (hw, hw, cin), (feat,)
    ))
    idx += 1
    din = feat
    for u in fc_units:
        specs.append(LayerSpec(
            idx, "fc", f"FC{u}", (din,), (u,), units=u
        ))
        idx += 1
        din = u
    return SimpleNamespace(name=name, specs=tuple(specs))


def loglinear_table(model, batches=(1, 4), weights=TRUTH_WEIGHTS):
    """A ProfileTable for `model` priced exactly by the log-linear
    ground truth — what a profiler on a perfectly power-law platform
    would measure."""
    labels = tuple(f"L{s.idx}:{s.notation}" for s in model.specs)
    times, kernels, h2d, d2h = {}, {}, {}, {}
    for b in batches:
        times[b], kernels[b], h2d[b], d2h[b] = [], [], [], []
        for spec in model.specs:
            geom = layer_geometry(spec, b)
            up = truth_boundary_s(geom, "h2d", weights)
            down = truth_boundary_s(geom, "d2h", weights)
            krow, trow = {}, {}
            for cfg in CONFIGS:
                meta = variant_meta(cfg)
                k = truth_kernel_s(geom, meta, weights)
                krow[cfg] = k
                trow[cfg] = k if cfg == CPU else k + up + down
            kernels[b].append(krow)
            times[b].append(trow)
            h2d[b].append(up)
            d2h[b].append(down)
    return ProfileTable(
        model.name, tuple(batches), labels, times,
        kernel_times=kernels, h2d_times=h2d, d2h_times=d2h,
        provenance="analytic",
    )


BIREAL_REFERENCE = (Path(__file__).resolve().parents[1] / "onchip" / "configs"
                    / "birealnet_reference.py")


def bireal_reference():
    """The benchmark's plain Bi-Real-Net reference module, which imports
    nothing of the program."""
    spec = importlib.util.spec_from_file_location("birealnet_reference",
                                                  BIREAL_REFERENCE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def birealnet(scale=1.0, input_hw=None, seed=0):
    """``(model, ref, cfg, params, packed)``: Bi-Real-Net-18 built by the
    program, the plain reference, its configuration dict, the weights
    and integer parameters the reference draws from `seed`, and the
    program's packing of them."""
    import jax

    from repro.bnn import build_model
    from repro.bnn.models import pack_params

    model = build_model("birealnet18", scale=scale, input_hw=input_hw)
    ref = bireal_reference()
    cfg = {"layers": [s.notation for s in model.specs],
           "input_hw": list(model.input_hw),
           "in_channels": model.in_channels, "n_classes": model.n_classes}
    params = jax.device_get(
        jax.jit(lambda k: ref.init_params(cfg, k))(jax.random.PRNGKey(seed)))
    return model, ref, cfg, params, pack_params(model.specs, params)
