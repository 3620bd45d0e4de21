"""The benchmark's one traffic generator: the requests, their order and
the host spans around the calls into the system.

A traffic mix is a JSON file in ``traffic/``.  The parameters every mix
has are read here and by the harness:

* ``loop``: what drives the window, ``loops/<loop>.py``, which reads
  its own parameters from the same file (``loops/closed.py``: a closed
  loop);
* ``batch``: the micro-batch the served plan is profiled and served at;
* ``pool``: distinct images per configuration, made from the seed, that
  requests draw from in a seeded order.  Every seed gives the same
  sizes and the same load, in another order;
* ``profile_store`` (optional): plan from the profile store in the
  checkout, as a deployment that serves one plan for long does: the
  first run of a checkout profiles and keeps the profile, later runs
  load it and serve the plan it gives;
* ``tenants`` (optional): names of further configurations in
  ``BENCHMARK.json`` served beside the cell's own, on the same chip,
  each sent an equal share of the requests.

Images are class prototypes with noise, thresholdable at 0.5, as the
system's own synthetic data set makes them.
"""

from __future__ import annotations

import time
from contextlib import nullcontext

import numpy as np

PACK_W = 32


def make_images(seed: int, n: int, hw, channels: int, n_classes=10,
                noise=0.35) -> np.ndarray:
    """(n, H, W, C) float32 images in [0, 1]."""
    rng = np.random.default_rng(seed)
    h, w = hw
    protos = rng.random((n_classes, h, w, channels)).astype(np.float32)
    y = rng.integers(0, n_classes, size=n).astype(np.int32)
    eps = rng.normal(0.0, noise, size=(n, h, w, channels)).astype(np.float32)
    return np.clip(protos[y] + eps, 0.0, 1.0)


def pack_images(x01: np.ndarray) -> np.ndarray:
    """The request format: bit 1 for a pixel >= 0.5, 32 channels to an
    int32 word along the last axis, least significant bit first, unused
    bits 0.  (N, H, W, C) to (N, H, W, ceil(C / 32))."""
    bits = x01 >= 0.5
    c = bits.shape[-1]
    words = -(-c // PACK_W)
    pad = words * PACK_W - c
    if pad:
        bits = np.concatenate(
            [bits, np.zeros(bits.shape[:-1] + (pad,), bool)], axis=-1
        )
    bits = bits.reshape(bits.shape[:-1] + (words, PACK_W)).astype(np.uint64)
    packed = (bits << np.arange(PACK_W, dtype=np.uint64)).sum(axis=-1)
    return packed.astype(np.uint32).view(np.int32)


def request_order(seed: int, pool: int):
    """Pool indices in a seeded order: one permutation of the pool after
    another, without end."""
    rng = np.random.default_rng([seed, 1])
    while True:
        yield from rng.permutation(pool).tolist()


class Spans:
    """The benchmark's host spans: kept in memory as ``(name, t0, t1)``
    and, when `annotate`, written into the profiler's trace."""

    def __init__(self, annotate: bool):
        self.annotate = annotate
        self.spans: list = []
        if annotate:
            from jax.profiler import TraceAnnotation

            self._annotation = TraceAnnotation

    def __call__(self, name: str):
        return _Span(self, name) if self.annotate else nullcontext()


class _Span:
    __slots__ = ("owner", "name", "t0", "ann")

    def __init__(self, owner, name):
        self.owner, self.name = owner, name

    def __enter__(self):
        self.ann = self.owner._annotation(self.name)
        self.ann.__enter__()
        self.t0 = time.perf_counter()

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self.ann.__exit__(*exc)
        self.owner.spans.append((self.name, self.t0, t1))


def requests(seed: int, pools: dict):
    """An endless seeded stream of ``(tenant, pool index, request)``.

    `pools` maps each tenant to its packed images.  With one tenant the
    indices follow ``request_order``; with several, each request's tenant
    is drawn from the seed and its index is that tenant's next."""
    names = list(pools)
    orders = {
        name: request_order(seed if k == 0 else seed + k, len(pools[name]))
        for k, name in enumerate(names)
    }
    if len(names) == 1:
        (name,) = names
        pool, order = pools[name], orders[name]
        for i in order:
            yield name, i, pool[i]
    rng = np.random.default_rng([seed, 2])
    while True:
        for k in rng.integers(len(names), size=4096).tolist():
            name = names[k]
            i = next(orders[name])
            yield name, i, pools[name][i]
