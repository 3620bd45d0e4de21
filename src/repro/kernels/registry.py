"""Open kernel-variant registry — the searchable per-layer GEMM space.

The paper fixes 8 implementations per layer (CPU + 7 aspect configs).
Larq-CE-style engines show that the real cost surface is wider: tiling,
packing and fusion choices matter per layer shape and platform.  This
module turns the fixed tuple into an **extensible registry**: every
implementation of the packed xnor GEMM declares

* a unique ``name`` (what ``ProfileTable`` rows, mappings and JSON
  carry — the registry is the single resolver from name to code);
* a ``placement`` (``"host"`` or ``"device"`` — what the mapper's
  boundary-cost model keys on);
* a ``builder`` ``(a, w, k_true) -> out`` over packed operands
  ``a (B,P,Kw) int32``, ``w (N,Kw) int32``;
* an ``applicable(shape, platform)`` predicate gating which layer
  shapes / platforms the variant may be timed on;
* analytic metadata (``aspects``, ``p_blk``/``n_blk``, ``analytic``
  kind) so ``core.cost_model`` can price it on hardware we cannot run.

``DEFAULT_REGISTRY`` ships the paper's 8 configs (always applicable —
the fixed-8 space stays a subset of every autotune sweep), a fused
device-side reference (``xla_fused``: the plain XLA program with no
aspect structure, often the fastest device option on a host backend),
and the Pallas ``xnor_popcount`` kernel at several tile sizes
(``pallas_p{P}n{N}``; the 32-bit packing width is fixed by the operand
layout, tile sizes are the free parameters).  Register more with
:func:`register` / :meth:`VariantRegistry.register`.

``core.profiler.autotune_bnn_model`` sweeps the registry per layer;
``core.mapped_model`` resolves chosen names back to builders, so a
mapping is executable iff every config name is registered (or one of
the legacy fixed-8 names).

Custom ``VariantRegistry`` instances (the ``registry=`` kwarg on the
profiler/executor entry points) scope *candidate sweeps and builder
resolution*; the placement/validation authority consulted by the
mapper, serving and ``EfficientConfiguration`` round-trips is the
process-wide :data:`DEFAULT_REGISTRY` — register a variant globally
(:func:`register`) before mapping or serving it.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable

import jax

from repro.kernels.ref import xnor_gemm_ref
from repro.kernels.segment_fused import (
    build_mxu_segment,
    build_pallas_segment,
    build_xla_segment,
    infer_in_encoding,
    segment_gemm_work,
    segment_vmem_bytes,
)
from repro.kernels.variants import xnor_gemm_variant
from repro.kernels.xnor_popcount import xnor_gemm_pallas

HOST = "host"
DEVICE = "device"
ASPECT_NAMES = ("X", "Y", "Z", "XY", "XZ", "YZ", "XYZ")

# variant scopes: a "layer" variant implements one packed xnor-GEMM
# dispatch (builder (a, w, k_true) -> out); a "segment" variant
# implements a whole same-placement layer run as one fused executable
# (builder (specs, packed_params, in_encoding=None) -> fn(x)).  The
# two scopes are separate candidate spaces: the per-layer autotuner
# sweeps layer-scope variants, the segment fuser
# (``core.plan.select_fused_segments``) sweeps segment-scope ones.
SCOPE_LAYER = "layer"
SCOPE_SEGMENT = "segment"
SCOPES = (SCOPE_LAYER, SCOPE_SEGMENT)

# The paper's 8 names are resolvable without the registry (they predate
# it, and `core.parallel_config` short-circuits on them so placement
# and pricing work without importing jax).  Their placement/aspect
# semantics are therefore frozen: re-registering one with a different
# builder is allowed (implementation hot-swap), but changing its
# placement or aspects would silently disagree with that short-circuit.
_FIXED8_META = {
    "CPU": (HOST, ()),
    **{name: (DEVICE, tuple(name)) for name in ASPECT_NAMES},
}

# non-TPU backends run Pallas in interpret mode (a Python-level grid
# walk) — cap the problem size a pallas variant is *applicable* to
# there, so live profiling sweeps stay fast; the autotuner's warm-up
# pruning catches anything the cap lets through
PALLAS_INTERPRET_MAX_WORK = 1 << 21


@dataclasses.dataclass(frozen=True)
class GemmShape:
    """Shape of one packed xnor-GEMM dispatch — what applicability
    predicates see.  ``b`` batch, ``p`` windows per image (1 for FC),
    ``n`` output neurons, ``kw`` packed reduction words."""

    b: int
    p: int
    n: int
    kw: int

    @property
    def work(self) -> int:
        """Word-level MAC count — the size proxy predicates gate on."""
        return self.b * self.p * self.n * self.kw


@dataclasses.dataclass(frozen=True)
class SegmentShape:
    """Shape of one fused-segment dispatch — what segment-scope
    applicability predicates see.  ``b`` batch, ``n_layers`` layers in
    the span, ``work`` total word-level GEMM MACs, ``vmem_bytes``
    resident footprint (weights + peak intermediate), ``kinds`` the
    layer kinds in the span."""

    b: int
    n_layers: int
    work: int
    vmem_bytes: int
    kinds: frozenset = frozenset()


def segment_shape_of(specs, packed_params, batch: int) -> SegmentShape:
    """The :class:`SegmentShape` of a layer slice at `batch`."""
    specs = tuple(specs)
    return SegmentShape(
        b=batch,
        n_layers=len(specs),
        work=segment_gemm_work(specs, packed_params, batch),
        vmem_bytes=segment_vmem_bytes(
            specs, packed_params, infer_in_encoding(specs)
        ),
        kinds=frozenset(s.kind for s in specs),
    )


def current_platform() -> str:
    """The JAX backend the live profiler times on (``cpu``/``tpu``/…)."""
    return jax.default_backend()


@dataclasses.dataclass(frozen=True)
class KernelVariant:
    """One registered implementation of the packed xnor GEMM."""

    name: str
    # layer scope: (a, w, k_true) -> (B, P, N) int32
    # segment scope: (specs, packed_params, in_encoding=None) -> fn(x)
    builder: Callable
    placement: str = DEVICE      # HOST or DEVICE (mapper boundary model)
    scope: str = SCOPE_LAYER     # SCOPE_LAYER or SCOPE_SEGMENT
    # analytic-pricing metadata (core.cost_model): grid order comes from
    # `aspects`, block sizes from p_blk/n_blk (None -> model defaults),
    # `analytic` picks the traffic model: "tiled" (loop-nest reuse),
    # "fused" (single pass over operands), "host" (CPU-side)
    aspects: tuple = ("X", "Y", "Z")
    p_blk: int | None = None
    n_blk: int | None = None
    analytic: str = "tiled"
    applicable: Callable | None = None   # (GemmShape, platform) -> bool
    description: str = ""

    def applies_to(self, shape: GemmShape, platform: str | None = None) -> bool:
        if self.applicable is None:
            return True
        return bool(
            self.applicable(
                shape, platform if platform is not None else current_platform()
            )
        )


class VariantRegistry:
    """Name -> KernelVariant store with applicability filtering."""

    def __init__(self):
        self._variants: dict = {}

    def register(
        self, variant: KernelVariant, *, replace: bool = False
    ) -> KernelVariant:
        if not variant.name:
            raise ValueError("variant needs a non-empty name")
        if variant.placement not in (HOST, DEVICE):
            raise ValueError(
                f"variant {variant.name!r}: placement must be "
                f"{HOST!r} or {DEVICE!r}, got {variant.placement!r}"
            )
        if variant.scope not in SCOPES:
            raise ValueError(
                f"variant {variant.name!r}: scope must be one of "
                f"{SCOPES}, got {variant.scope!r}"
            )
        if variant.name in self._variants and not replace:
            raise ValueError(
                f"variant {variant.name!r} already registered "
                "(pass replace=True to override)"
            )
        frozen = _FIXED8_META.get(variant.name)
        if frozen is not None and (
            variant.placement, tuple(variant.aspects)
        ) != frozen:
            raise ValueError(
                f"variant {variant.name!r} is a fixed-8 name with "
                f"frozen placement/aspects {frozen}; register the new "
                "semantics under a different name"
            )
        self._variants[variant.name] = variant
        return variant

    def get(self, name: str) -> KernelVariant:
        try:
            return self._variants[name]
        except KeyError:
            raise ValueError(
                f"unknown kernel variant {name!r}; registered: "
                f"{sorted(self._variants)}"
            ) from None

    def remove(self, name: str) -> KernelVariant:
        """Unregister and return `name` (ValueError if absent)."""
        return self._variants.pop(self.get(name).name)

    def __contains__(self, name: str) -> bool:
        return name in self._variants

    def __iter__(self):
        return iter(self._variants.values())

    def __len__(self) -> int:
        return len(self._variants)

    def names(self) -> tuple:
        return tuple(self._variants)

    def applicable(
        self, shape: GemmShape, platform: str | None = None
    ) -> tuple:
        """Layer-scope variants timeable for `shape` on `platform`,
        registration order (the autotuner's candidate list).  Segment
        variants are a different dispatch granularity and never appear
        here — they are swept by :meth:`applicable_segments`."""
        platform = platform if platform is not None else current_platform()
        return tuple(
            v for v in self._variants.values()
            if v.scope == SCOPE_LAYER and v.applies_to(shape, platform)
        )

    def applicable_segments(
        self, shape: SegmentShape, platform: str | None = None
    ) -> tuple:
        """Segment-scope variants timeable for a fused span of `shape`
        on `platform` (``core.profiler.profile_segment_variants``'s
        candidate list)."""
        platform = platform if platform is not None else current_platform()
        return tuple(
            v for v in self._variants.values()
            if v.scope == SCOPE_SEGMENT and v.applies_to(shape, platform)
        )

    def segment_names(self) -> tuple:
        """Names of the registered segment-scope variants."""
        return tuple(
            v.name for v in self._variants.values()
            if v.scope == SCOPE_SEGMENT
        )

    def placement_of(self, name: str) -> str:
        return self.get(name).placement


def _pallas_builder(p_blk: int, n_blk: int) -> Callable:
    def build(a, w, k_true):
        return xnor_gemm_pallas(
            a, w, k_true, ("X", "Y", "Z"),
            p_blk=p_blk, n_blk=n_blk,
            interpret=current_platform() != "tpu",
        )

    return build


def _pallas_applicable(p_blk: int, n_blk: int) -> Callable:
    def applicable(shape: GemmShape, platform: str) -> bool:
        if platform != "tpu":
            # interpret mode elsewhere, only for small problems
            return shape.work <= PALLAS_INTERPRET_MAX_WORK
        # Mosaic's block rule for the (1, p, n) output block: the lane
        # block is a multiple of 128 or the whole N, the sublane block a
        # multiple of 8 or the whole P (the kernel clips tiles to P, N)
        p, n = min(p_blk, shape.p), min(n_blk, shape.n)
        return (n % 128 == 0 or n == shape.n) and (
            p % 8 == 0 or p == shape.p
        )

    return applicable


# the fused kernel keeps every weight + the widest intermediate
# resident; leave headroom under the ~128 MiB v5e VMEM for Mosaic's
# own buffers
SEGMENT_VMEM_BUDGET = 96 * 1024 * 1024


def _seg_pallas_builder(specs, packed_params, in_encoding=None):
    return build_pallas_segment(
        specs, packed_params, in_encoding,
        interpret=current_platform() != "tpu",
    )


# the kinds whose parameters the fused kernel body takes as refs
_SEG_PALLAS_KINDS = frozenset({"conv", "mp", "step", "flat", "fc"})


def _seg_pallas_applicable(shape: SegmentShape, platform: str) -> bool:
    if not shape.kinds <= _SEG_PALLAS_KINDS:
        return False
    if platform == "tpu":
        # Mosaic refuses the fused body's one-example blocks (a (1, N)
        # output block is neither 8-row aligned nor the whole batch);
        # the TPU keeps seg_xla until a Mosaic-shaped fused kernel
        # exists (ROADMAP A6)
        return False
    if shape.vmem_bytes > SEGMENT_VMEM_BUDGET:
        return False
    return shape.work <= PALLAS_INTERPRET_MAX_WORK


def _seg_xla_applicable(shape: SegmentShape, platform: str) -> bool:
    return True


def _seg_mxu_applicable(shape: SegmentShape, platform: str) -> bool:
    # the int8 GEMMs are worth timing only where the MXU runs them
    return platform == "tpu"


def _register_defaults(reg: VariantRegistry) -> VariantRegistry:
    reg.register(
        KernelVariant(
            name="CPU",
            builder=xnor_gemm_ref,
            placement=HOST,
            aspects=(),
            analytic="host",
            description="paper's sequential CPU implementation "
            "(host-placed reference, no boundary cost)",
        )
    )
    for name in ASPECT_NAMES:
        reg.register(
            KernelVariant(
                name=name,
                builder=partial(
                    xnor_gemm_variant, aspects=frozenset(name)
                ),
                placement=DEVICE,
                aspects=tuple(name),
                analytic="tiled",
                description=f"aspect-structured XLA variant ({name} "
                "parallel, rest sequential)",
            )
        )
    reg.register(
        KernelVariant(
            name="xla_fused",
            builder=xnor_gemm_ref,
            placement=DEVICE,
            aspects=("X", "Y", "Z"),
            analytic="fused",
            description="device-placed fused XLA reference — no aspect "
            "structure, single pass over the operands",
        )
    )
    for p_blk, n_blk in ((64, 64), (128, 128), (128, 256)):
        reg.register(
            KernelVariant(
                name=f"pallas_p{p_blk}n{n_blk}",
                builder=_pallas_builder(p_blk, n_blk),
                placement=DEVICE,
                aspects=("X", "Y", "Z"),
                p_blk=p_blk,
                n_blk=n_blk,
                analytic="tiled",
                applicable=_pallas_applicable(p_blk, n_blk),
                description=f"Pallas xnor_popcount kernel, "
                f"{p_blk}x{n_blk} window/neuron tiles",
            )
        )
    reg.register(
        KernelVariant(
            name="seg_xla",
            builder=build_xla_segment,
            placement=DEVICE,
            scope=SCOPE_SEGMENT,
            aspects=("X", "Y", "Z"),
            # segment-scope analytic dispatch: "fused" prices the
            # single-pass mega-kernel, anything else the XLA-composed
            # chain (core.cost_model.xla_segment_kernel_time_tpu)
            analytic="tiled",
            applicable=_seg_xla_applicable,
            description="whole segment as one XLA executable — the "
            "layer chain jitted together, threshold/repack fused into "
            "the GEMM tails",
        )
    )
    reg.register(
        KernelVariant(
            name="seg_mxu",
            builder=build_mxu_segment,
            placement=DEVICE,
            scope=SCOPE_SEGMENT,
            aspects=("X", "Y", "Z"),
            # priced like seg_xla, registered after it: analytic ties
            # keep seg_xla, and only a measured profile picks this one
            analytic="tiled",
            applicable=_seg_mxu_applicable,
            description="whole segment as one XLA executable with the "
            "±1 products as int8 convolutions/matmuls (the MXU), "
            "weights unpacked to int8 once",
        )
    )
    reg.register(
        KernelVariant(
            name="seg_pallas",
            builder=_seg_pallas_builder,
            placement=DEVICE,
            scope=SCOPE_SEGMENT,
            aspects=("X",),
            analytic="fused",
            applicable=_seg_pallas_applicable,
            description="whole segment as one pallas_call: weights "
            "VMEM-resident, activations bit-packed end to end, "
            "interior results never touch HBM",
        )
    )
    return reg


#: The process-wide default registry (the paper's 8 + open extensions).
DEFAULT_REGISTRY = _register_defaults(VariantRegistry())
REGISTRY = DEFAULT_REGISTRY


def register(variant: KernelVariant, *, replace: bool = False) -> KernelVariant:
    """Register `variant` in the default registry."""
    return DEFAULT_REGISTRY.register(variant, replace=replace)


def get_variant(name: str) -> KernelVariant:
    return DEFAULT_REGISTRY.get(name)
