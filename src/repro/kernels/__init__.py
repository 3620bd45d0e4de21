"""Pallas TPU kernels for the paper's compute hot-spots.

* ``xnor_popcount`` — the BNN binary GEMM (conv-as-GEMM and FC), grid
  parameterized by the paper's X/Y/Z parallelism aspects (see
  docs/ARCHITECTURE.md §2): aspect axes become *parallel* grid
  dimensions, non-aspect axes
  *arbitrary* (sequential) ones — the TPU-native analogue of CUDA
  thread-block decomposition vs in-block serialization.
* ``flash_attention`` — blockwise-softmax attention for LM prefill.

Each kernel ships with ``ref.py`` (pure-jnp oracle) and ``ops.py``
(jit'd entry points). ``variants.py`` holds the pure-XLA aspect-
structured implementations that the live profiler times on whatever
platform it runs on.  Pallas kernels run natively on a TPU and in
interpret mode elsewhere; ``chip_smoke.py`` checks them on the chip.

``registry.py`` is the open kernel-variant registry: every GEMM
implementation — the fixed 8, the fused device reference, Pallas tile
variants, and anything registered later — declares its name,
placement, applicability predicate, and builder there; the profiler's
autotune sweep and the mapped-model executors resolve variants through
it (see docs/ARCHITECTURE.md §8).
"""

from repro.kernels.ops import (
    xnor_gemm,
    binary_conv2d,
    flash_attention,
)
from repro.kernels.registry import (
    DEFAULT_REGISTRY,
    GemmShape,
    KernelVariant,
    VariantRegistry,
    get_variant,
    register,
)
