"""Per-layer latency profiling (paper §III-A, Fig. 4) and the
registry-driven autotune pass.

Two entry points, one ``ProfileTable`` output:

* :func:`profile_bnn_model` — the paper's sweep: for every batch size
  and every layer, time a **fixed** candidate list (default: ``CPU`` +
  the 7 aspect configs).
* :func:`autotune_bnn_model` — the open-space sweep: per-layer
  candidates come from the kernel-variant registry
  (:mod:`repro.kernels.registry`) filtered by each GEMM layer's shape
  and the host platform, so rows are **variable-size** (and always a
  superset of the fixed-8 space — the paper's configs carry no
  applicability predicate).  In measured mode, extended variants get a
  cheap one-repeat warm-up timing first and are pruned (skipped for
  the full ``repeats`` sweep) when dominated by ``prune_factor`` x the
  best warm-up so far; the fixed-8 names are never pruned.

**Kernel/boundary time model.**  Each profiled entry is split into two
independently-stored components:

* ``kernel``  — the layer's compute alone, wherever it is placed;
* ``boundary`` — the host<->device transfer cost of the layer's operand
  (H2D) and result (D2H), measured/modeled **separately** per
  direction and stored per layer in ``h2d_times`` / ``d2h_times``.

The paper-faithful total (``times``) charges device-placed layers
``kernel + h2d + d2h`` — §IV-A: "data transfer between CPU and GPU
takes place before and after every layer's execution".  The split
exists because the fused executor (``mapped_model.build_mapped_model``
with ``fused=True``) elides the interior transfers between co-placed
device layers; the transfer-aware DP mapper (``mapper`` with
``policy='dp'``) prices exactly that execution: kernel time per layer,
boundary cost only where placement changes host<->device.

Times are stored **seconds per example** so totals are comparable
across batch sizes (the paper profiles the full test set per batch
size; per-example normalization is equivalent).

``time_source='measured'`` times real XLA executables on the host
platform; ``'analytic'`` uses the TPU v5e cost model
(``repro.core.cost_model``) — the dry-run-style path for hardware we
cannot run.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import time
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.bnn import layers as L
from repro.bnn.models import BNNModel, prepare_input_packed
from repro.core import cost_model as cm
from repro.core.parallel_config import CONFIGS, is_host_config
from repro.kernels.registry import DEFAULT_REGISTRY, GemmShape


@dataclasses.dataclass
class ProfileTable:
    model_name: str
    batch_sizes: tuple
    layer_labels: tuple          # e.g. ('L1:C64', 'L2:MP14', ...)
    # times[batch][layer_idx][config] -> seconds per example, paper
    # semantics: kernel + full per-layer boundary for device configs.
    # Rows are dicts keyed by variant name, so per-layer config spaces
    # may differ in size (autotuned tables) — consumers must iterate
    # row keys (``configs_for``), never assume the fixed 8.
    times: dict
    # kernel_times[batch][layer_idx][config] -> kernel-only s/example
    kernel_times: dict | None = None
    # h2d_times/d2h_times[batch][layer_idx] -> boundary s/example for
    # the layer's operand upload / result download (config-independent)
    h2d_times: dict | None = None
    d2h_times: dict | None = None
    # segment_times[batch]["start:stop"][variant] -> kernel s/example
    # for a whole device segment executed as one fused dispatch
    # (segment-scope variants, ``repro.kernels.segment_fused``) —
    # the candidate rows ``core.plan.select_fused_segments`` compares
    # against the span's per-layer kernel sum
    segment_times: dict | None = None
    # where the rows came from: "measured" / "analytic" (the profiler
    # stamps its time_source) or "predicted" (synthesized by
    # repro.estimator.LatencyPredictor with zero profiling passes).
    # None on legacy tables; additive, so the schema stays at 1.
    provenance: str | None = None

    @staticmethod
    def span_key(start: int, stop: int) -> str:
        return f"{start}:{stop}"

    def segment_variants_for(
        self, batch: int, start: int, stop: int
    ) -> tuple:
        """Segment-scope variant names profiled for the span at
        `batch` (``()`` when the span was never segment-profiled)."""
        if self.segment_times is None:
            return ()
        row = self.segment_times.get(batch, {}).get(
            self.span_key(start, stop)
        )
        return tuple(row) if row else ()

    def segment_time(
        self, batch: int, start: int, stop: int, variant: str
    ) -> float:
        return self.segment_times[batch][self.span_key(start, stop)][
            variant
        ]

    def add_segment_row(
        self, batch: int, start: int, stop: int, row: dict
    ) -> None:
        """Record (merge) a span's segment-variant timings at `batch`."""
        if self.segment_times is None:
            self.segment_times = {}
        self.segment_times.setdefault(batch, {}).setdefault(
            self.span_key(start, stop), {}
        ).update(row)

    def configs_for(self, batch: int, layer: int) -> tuple:
        """The candidate config names profiled for (batch, layer) —
        the layer's searchable space, variable-size by design."""
        return tuple(self.times[batch][layer])

    def best_config(self, batch: int, layer: int) -> tuple:
        row = self.times[batch][layer]
        cfg = min(row, key=row.get)
        return cfg, row[cfg]

    # -- split accessors (legacy tables without the split degrade to
    #    kernel == total, boundary == 0, under which the DP mapper
    #    reproduces the greedy mapping exactly) ----------------------
    def kernel_time(self, batch: int, layer: int, config: str) -> float:
        if self.kernel_times is not None:
            return self.kernel_times[batch][layer][config]
        return self.times[batch][layer][config]

    def h2d(self, batch: int, layer: int) -> float:
        if self.h2d_times is None:
            return 0.0
        return self.h2d_times[batch][layer]

    def d2h(self, batch: int, layer: int) -> float:
        if self.d2h_times is None:
            return 0.0
        return self.d2h_times[batch][layer]

    def boundary_time(self, batch: int, layer: int, config: str) -> float:
        """Full per-layer roundtrip charged under paper semantics."""
        if is_host_config(config):
            return 0.0
        return self.h2d(batch, layer) + self.d2h(batch, layer)

    # -- JSON round-trip (mirrors the EfficientConfiguration
    #    conventions: versioned schema, legacy-tolerant loader) -------
    SCHEMA_VERSION = 1

    def to_json(self) -> str:
        """Serialize the table, kernel/boundary split included when
        present.  Batch keys are stringified (JSON object keys);
        :meth:`from_json` restores them to ints."""

        def by_batch(d):
            return (
                None if d is None else {str(b): d[b] for b in sorted(d)}
            )

        return json.dumps(
            {
                "schema": self.SCHEMA_VERSION,
                "kind": "profile_table",
                "model": self.model_name,
                "batch_sizes": list(self.batch_sizes),
                "layer_labels": list(self.layer_labels),
                "times": by_batch(self.times),
                "kernel_times": by_batch(self.kernel_times),
                "h2d_times": by_batch(self.h2d_times),
                "d2h_times": by_batch(self.d2h_times),
                "segment_times": by_batch(self.segment_times),
                "provenance": self.provenance,
            },
            indent=2,
        )

    @staticmethod
    def from_json(s: str) -> "ProfileTable":
        """Inverse of :meth:`to_json`.  Legacy-tolerant: a document
        without the ``schema``/``kind`` envelope (or without the
        kernel/boundary split fields) still loads — missing split
        components degrade exactly like a pre-split in-memory table
        (kernel == total, boundary == 0).  A document from a *newer*
        schema than this code understands is refused rather than
        silently misread."""
        d = json.loads(s)
        schema = d.get("schema", 1)
        if schema > ProfileTable.SCHEMA_VERSION:
            raise ValueError(
                f"profile_table schema {schema} is newer than supported "
                f"({ProfileTable.SCHEMA_VERSION}); upgrade the loader"
            )
        kind = d.get("kind", "profile_table")
        if kind != "profile_table":
            raise ValueError(f"expected a profile_table document, got {kind!r}")

        def by_batch(key):
            raw = d.get(key)
            return (
                None if raw is None else {int(b): raw[b] for b in raw}
            )

        return ProfileTable(
            model_name=d["model"],
            batch_sizes=tuple(int(b) for b in d["batch_sizes"]),
            layer_labels=tuple(d["layer_labels"]),
            times=by_batch("times"),
            kernel_times=by_batch("kernel_times"),
            h2d_times=by_batch("h2d_times"),
            d2h_times=by_batch("d2h_times"),
            segment_times=by_batch("segment_times"),
            provenance=d.get("provenance"),
        )


def _timeit(fn: Callable[[], object], repeats: int) -> float:
    fn()  # warmup / compile
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn()
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best


def _measure_h2d(x_in: jax.Array, repeats: int) -> float:
    """Host->device upload cost of a layer's operand."""
    x_np = np.asarray(x_in)

    def upload():
        dev = jnp.asarray(x_np)
        jax.block_until_ready(dev)
        return dev

    return _timeit(upload, repeats)


def _measure_d2h(x_out: jax.Array, repeats: int) -> float:
    """Device->host download cost of a layer's result."""
    return _timeit(lambda: np.asarray(x_out), repeats)


def prune_survivors(
    warmups: dict, *, never_prune=CONFIGS, prune_factor: float = 3.0
) -> tuple:
    """Autotune pruning decision: given one-repeat warm-up timings
    (name -> seconds), keep every name in `never_prune` plus any
    variant within ``prune_factor`` x the fastest warm-up.  Dominated
    extended variants are skipped for the full-repeats sweep (and
    dropped from the profile row)."""
    if not warmups:
        return ()
    best = min(warmups.values())
    keep = set(never_prune)
    return tuple(
        name
        for name, t in warmups.items()
        if name in keep or t <= prune_factor * best
    )


def gemm_shape_of(spec: L.LayerSpec, packed: dict, batch: int):
    """The packed GEMM dispatch shape of a conv/fc layer, or of a
    residual block's binary conv (one window per output position), at
    `batch` (None for layers without one) — what variant applicability
    predicates see."""
    if spec.kind not in ("conv", "fc", "res"):
        return None
    w_words = packed["w_words"]
    n, kw = int(w_words.shape[0]), int(w_words.shape[1])
    if spec.kind == "fc":
        return GemmShape(b=batch, p=1, n=n, kw=kw)
    h, w, _ = spec.out_shape
    return GemmShape(b=batch, p=h * w, n=n, kw=kw)


def _layer_impls(
    spec: L.LayerSpec, packed: dict, candidates: Sequence[str], registry
):
    """Return {config: jitted fn} for one layer, all computing the packed
    reference semantics.  GEMM layers resolve each candidate name to its
    registered builder; elementwise layers share one computation (the
    candidates differ only by the boundary cost the profiler adds — the
    paper's finding that these layers never win on GPU emerges from
    measurement, not fiat)."""
    if spec.kind in ("conv", "fc"):
        w, k_true = packed["w_words"], packed["k_true"]

        def gemm_for(cfg):
            builder = registry.get(cfg).builder
            if spec.kind == "conv":

                @jax.jit
                def f(x):
                    from repro.bnn.layers import extract_patch_words

                    b, h, ww, _ = x.shape
                    p = extract_patch_words(x).reshape(b, h * ww, -1)
                    return builder(p, w, k_true).reshape(b, h, ww, -1)

            else:

                @jax.jit
                def f(x):
                    return builder(x[:, None, :], w, k_true)[:, 0, :]

            return f

        return {cfg: gemm_for(cfg) for cfg in candidates}

    if spec.kind == "res":
        # the block around its binary GEMM, under each variant.  Its
        # arrays are operands, not constants, so blocks of one shape are
        # one program: the persistent cache compiles each variant once
        # per shape, not once per block
        k_true = packed["k_true"]
        arrays = {k: v for k, v in packed.items() if k != "k_true"}

        def block_for(cfg):
            gemm = registry.get(cfg).builder

            @jax.jit
            def f(arrays, x):
                return L.apply_packed(spec, {**arrays, "k_true": k_true}, x,
                                      gemm)

            return functools.partial(f, arrays)

        return {cfg: block_for(cfg) for cfg in candidates}
    if spec.kind in ("stem", "maxp", "gap", "fcq"):
        f = jax.jit(lambda x: L.apply_packed(spec, packed, x))
    elif spec.kind == "mp":
        f = jax.jit(L.maxpool_packed)
    elif spec.kind == "step":
        t, fl = packed["thresh"], packed["flip"]
        f = jax.jit(lambda x: L.step_packed(x, t, fl))
    elif spec.kind == "flat":
        c = spec.in_shape[-1]
        f = jax.jit(lambda x: L.flat_packed(x, c))
    else:  # pragma: no cover
        raise ValueError(spec.kind)
    return {cfg: f for cfg in candidates}


def _capture_layer_inputs(
    model: BNNModel, packed_params: list, x_words: jax.Array
) -> list:
    """Run the packed reference forward, returning each layer's input."""
    xs = []
    x = x_words
    for spec, p in zip(model.specs, packed_params):
        xs.append(x)
        x = L.apply_packed(spec, p, x)
    return xs


def _analytic_rows(spec, candidates, batch, registry):
    """(row, krow, h2d, d2h) for one layer from the TPU cost model."""
    row, krow = {}, {}
    h2d = d2h = 0.0
    for cfg in candidates:
        kern, th2d, td2h = cm.layer_time_split_tpu(
            spec, cfg, batch, registry=registry
        )
        krow[cfg] = kern / batch
        row[cfg] = (kern + th2d + td2h) / batch
        if not is_host_config(cfg, registry):
            h2d, d2h = th2d / batch, td2h / batch
    return row, krow, h2d, d2h


def _measured_rows(
    spec, packed, candidates, batch, x_in, repeats, prune_factor, registry
):
    """(row, krow, h2d, d2h) for one layer by timing real executables.

    With ``prune_factor`` set, every candidate gets a one-repeat warm-up
    timing first; extended variants dominated by ``prune_factor`` x the
    best warm-up are dropped before the full-repeats sweep.
    """
    impls = _layer_impls(spec, packed, candidates, registry)
    x_out = impls[candidates[0]](x_in)
    h2d = _measure_h2d(x_in, repeats) / batch
    d2h = _measure_d2h(x_out, repeats) / batch
    warmups = {
        cfg: _timeit(lambda f=impls[cfg]: f(x_in), 1) for cfg in candidates
    }
    if prune_factor is not None:
        survivors = prune_survivors(
            warmups, never_prune=CONFIGS, prune_factor=prune_factor
        )
    else:
        survivors = tuple(candidates)
    row, krow = {}, {}
    for cfg in survivors:
        t = warmups[cfg]
        if repeats > 1:
            t = min(t, _timeit(lambda f=impls[cfg]: f(x_in), repeats - 1))
        t /= batch
        krow[cfg] = t
        row[cfg] = t if is_host_config(cfg, registry) else t + h2d + d2h
    return row, krow, h2d, d2h


def _profile(
    model: BNNModel,
    packed_params: list,
    candidates_fn: Callable,
    *,
    batch_sizes: Sequence[int],
    repeats: int,
    seed: int,
    time_source: str,
    prune_factor: float | None,
    registry=None,
) -> ProfileTable:
    """Shared sweep: ``candidates_fn(spec, packed, batch) -> names``
    decides each layer's searchable space."""
    labels = tuple(f"L{s.idx}:{s.notation}" for s in model.specs)
    times: dict = {}
    kernel_times: dict = {}
    h2d_times: dict = {}
    d2h_times: dict = {}
    key = jax.random.PRNGKey(seed)

    for batch in batch_sizes:
        x01 = jax.random.uniform(
            key, (batch, *model.input_hw, model.in_channels)
        )
        x_words = prepare_input_packed(x01)
        layer_inputs = _capture_layer_inputs(model, packed_params, x_words)
        per_layer: list = []
        per_layer_kernel: list = []
        per_layer_h2d: list = []
        per_layer_d2h: list = []
        for spec, packed, x_in in zip(
            model.specs, packed_params, layer_inputs
        ):
            candidates = tuple(candidates_fn(spec, packed, batch))
            if time_source == "analytic":
                row, krow, h2d, d2h = _analytic_rows(
                    spec, candidates, batch, registry
                )
            else:
                row, krow, h2d, d2h = _measured_rows(
                    spec, packed, candidates, batch, x_in, repeats,
                    prune_factor,
                    registry if registry is not None else DEFAULT_REGISTRY,
                )
            per_layer.append(row)
            per_layer_kernel.append(krow)
            per_layer_h2d.append(h2d)
            per_layer_d2h.append(d2h)
        times[batch] = per_layer
        kernel_times[batch] = per_layer_kernel
        h2d_times[batch] = per_layer_h2d
        d2h_times[batch] = per_layer_d2h

    return ProfileTable(
        model.name,
        tuple(batch_sizes),
        labels,
        times,
        kernel_times=kernel_times,
        h2d_times=h2d_times,
        d2h_times=d2h_times,
        provenance=time_source,
    )


def profile_bnn_model(
    model: BNNModel,
    packed_params: list,
    *,
    batch_sizes: Sequence[int] = (1, 2, 4, 8, 16, 32, 64, 128),
    configs: Sequence[str] = CONFIGS,
    repeats: int = 3,
    seed: int = 0,
    time_source: str = "measured",
) -> ProfileTable:
    """The paper's fixed-space sweep: every layer is timed under the
    same candidate list (default CPU + 7 aspect configs)."""
    configs = tuple(configs)
    return _profile(
        model,
        packed_params,
        lambda spec, packed, batch: configs,
        batch_sizes=batch_sizes,
        repeats=repeats,
        seed=seed,
        time_source=time_source,
        prune_factor=None,
    )


def autotune_bnn_model(
    model: BNNModel,
    packed_params: list,
    *,
    registry=None,
    batch_sizes: Sequence[int] = (1, 2, 4, 8, 16, 32, 64, 128),
    repeats: int = 3,
    seed: int = 0,
    time_source: str = "measured",
    prune_factor: float = 3.0,
    platform: str | None = None,
) -> ProfileTable:
    """Registry-driven autotune sweep with variable per-layer spaces.

    GEMM layers are timed under the fixed-8 configs **plus** every
    registered variant whose applicability predicate accepts the
    layer's dispatch shape on `platform`; elementwise layers keep the
    fixed 8 (their candidates share one computation — only placement
    matters).  Measured mode prunes dominated extended variants after
    a one-repeat warm-up (:func:`prune_survivors`); the fixed 8 are
    always fully timed, so any mapping feasible in the paper's space
    remains feasible in the autotuned table.

    ``platform=None`` resolves to the live JAX backend in measured
    mode; in analytic mode it defaults to ``"tpu"`` — the analytic
    sweep executes nothing, it prices the TPU target, so variants
    gated off non-TPU hosts (Pallas tiles) must still be priced.
    """
    reg = registry if registry is not None else DEFAULT_REGISTRY
    if platform is None and time_source == "analytic":
        platform = "tpu"

    def candidates(spec, packed, batch):
        shape = gemm_shape_of(spec, packed, batch)
        if shape is None:
            return CONFIGS
        extra = tuple(
            v.name
            for v in reg.applicable(shape, platform)
            if v.name not in CONFIGS
        )
        return CONFIGS + extra

    return _profile(
        model,
        packed_params,
        candidates,
        batch_sizes=batch_sizes,
        repeats=repeats,
        seed=seed,
        time_source=time_source,
        prune_factor=prune_factor if time_source == "measured" else None,
        registry=reg,
    )


def profile_segment_variants(
    model: BNNModel,
    packed_params: list,
    table: ProfileTable,
    *,
    spans: Sequence[tuple],
    batch_sizes: Sequence[int] | None = None,
    registry=None,
    time_source: str = "measured",
    repeats: int = 3,
    seed: int = 0,
    platform: str | None = None,
) -> ProfileTable:
    """Profile fused whole-segment execution over `spans` and record
    the rows on ``table.segment_times`` (the table is updated in place
    and returned).

    For each ``(start, stop)`` span and each batch size, every
    *segment-scope* registry variant whose applicability predicate
    accepts the span's :class:`~repro.kernels.registry.SegmentShape`
    is timed (measured mode: the real fused executable on this
    backend, same ``_timeit`` discipline as the per-layer sweep) or
    priced (analytic mode: the TPU cost model —
    ``cost_model.fused_segment_kernel_time_tpu`` for single-pass
    fused variants, ``cost_model.xla_segment_kernel_time_tpu``
    otherwise).  Times are kernel-only seconds per example: the
    segment's boundary transfers are unchanged by fusion (same edge
    operands) and stay priced by the per-layer h2d/d2h rows.

    A variant the table already has a row for at that span and batch
    (a stored profile's) keeps its row and is not timed again, so a
    warm start compiles no segment executable.

    Spans must be device-resident layer runs of the profiled model —
    typically ``core.plan.device_spans(config)``.
    """
    reg = registry if registry is not None else DEFAULT_REGISTRY
    if platform is None and time_source == "analytic":
        platform = "tpu"
    if batch_sizes is None:
        batch_sizes = table.batch_sizes
    from repro.kernels.registry import segment_shape_of

    key = jax.random.PRNGKey(seed)
    for batch in batch_sizes:
        if batch not in table.batch_sizes:
            raise ValueError(
                f"batch {batch} not profiled (have {table.batch_sizes})"
            )
        layer_inputs = None
        for start, stop in spans:
            specs = tuple(model.specs[start:stop])
            pp = list(packed_params[start:stop])
            shape = segment_shape_of(specs, pp, batch)
            timed = set(table.segment_variants_for(batch, start, stop))
            row = {}
            for v in reg.applicable_segments(shape, platform):
                if v.name in timed:
                    continue
                if time_source == "analytic":
                    if v.analytic == "fused":
                        t = cm.fused_segment_kernel_time_tpu(specs, batch)
                    else:
                        t = cm.xla_segment_kernel_time_tpu(
                            specs, batch, registry=reg
                        )
                else:
                    if layer_inputs is None:
                        x01 = jax.random.uniform(
                            key, (batch, *model.input_hw, model.in_channels)
                        )
                        layer_inputs = _capture_layer_inputs(
                            model, packed_params, prepare_input_packed(x01)
                        )
                    fn = v.builder(specs, pp)
                    x_in = layer_inputs[start]
                    t = _timeit(lambda: fn(x_in), repeats)
                row[v.name] = t / batch
            if row:
                table.add_segment_row(batch, start, stop, row)
    return table
