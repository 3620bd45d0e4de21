"""Paper BNN models (Tables I & II) + packed-inference parameter
preparation.

`build_model` returns a :class:`BNNModel` whose `specs` drive both the
fp-sim training forward and the per-layer packed inference used by the
HEP mapper.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.bnn import layers as L
from repro.bnn.binarize import np_pack_bits, pack_bits
from repro.bnn.fold_bn import fold_bn

# Table II — FashionMNIST BNN (10 layers)
FASHION_MNIST_NOTATION = (
    "C64", "MP14", "S", "C64", "MP7", "S", "FLAT", "FC2048", "S", "FC2048",
)
# Table I — CIFAR-10 BNN (19 layers)
CIFAR10_NOTATION = (
    "C64", "S", "C64", "MP16", "S", "C256", "S", "C256", "MP8", "S",
    "C512", "S", "C512", "MP4", "S", "FLAT", "FC1024", "S", "FC1024",
)
# Bi-Real-Net-18 (arXiv:1808.00278; birealnet18 = BiRealNet(BasicBlock,
# [4, 4, 4, 4])): stem, 16 residual blocks of one binary conv each,
# stages of 64/128/256/512 channels, the head to 1000 classes
BIREALNET18_NOTATION = (
    "STEM64", "MAXP", *("RB64",) * 4,
    *(tok for c in (128, 256, 512) for tok in (f"RBD{c}", *(f"RB{c}",) * 3)),
    "GAP", "FCQ1000",
)


@dataclasses.dataclass(frozen=True)
class BNNModel:
    name: str
    specs: tuple
    input_hw: tuple
    in_channels: int
    n_classes: int

    def init(self, key: jax.Array) -> list[dict]:
        return L.init_bnn_params(key, self.specs)

    def apply_fp(self, params, x01, *, train=False):
        """[0,1] images -> logits (fp-sim path)."""
        x = L.binarize_input(x01)
        return L.forward_fp(self.specs, params, x, train=train)


_REGISTRY = {
    "fashion_mnist": (FASHION_MNIST_NOTATION, (28, 28), 1, 10),
    "cifar10": (CIFAR10_NOTATION, (32, 32), 3, 10),
    "birealnet18": (BIREALNET18_NOTATION, (224, 224), 3, 1000),
}


def build_model(
    name: str, *, scale: float = 1.0, input_hw: tuple | None = None
) -> BNNModel:
    """Build a registered model. ``scale`` < 1 shrinks channel/unit
    counts (for smoke tests) while preserving the layer structure;
    ``input_hw`` replaces the registered input size (Bi-Real-Net's
    layers take any size whose stages halve evenly)."""
    notation, hw, cin, ncls = _REGISTRY[name]
    if scale != 1.0:
        def shrink(tok: str) -> str:
            if m := re.fullmatch(r"(C|FC|STEM|RBD?)(\d+)", tok):
                n = max(32, int(int(m.group(2)) * scale))
                n = (n // 32) * 32  # keep word-aligned
                return f"{m.group(1)}{n}"
            return tok
        notation = tuple(shrink(t) for t in notation)
    hw = tuple(input_hw) if input_hw is not None else hw
    specs = tuple(L.parse_notation(notation, hw, cin, ncls))
    return BNNModel(name, specs, hw, cin, ncls)


# ---------------------------------------------------------------------------
# Packed-inference parameter preparation
# ---------------------------------------------------------------------------


def _conv_words(w: np.ndarray) -> np.ndarray:
    """(3,3,Cin,Cout) -> words (Cout, 9*ceil(Cin/32)), tail bit 1, in
    the patch order of extract_patch_words (dy-major, dx-minor)."""
    cin, cout = w.shape[2], w.shape[3]
    wt = np.transpose(w, (3, 0, 1, 2)).reshape(cout, 9, cin)
    return np_pack_bits(np.sign(wt) + 0.5, pad_bit=1).reshape(cout, -1)


def _ints(v, dtype=np.int32) -> jax.Array:
    return jnp.asarray(np.rint(np.asarray(v)).astype(dtype))


def _fold(p: dict, pre: str = "") -> dict:
    """A fixed-point fold's m, b (int32 per channel) and k."""
    return {pre + key: _ints(p[pre + key]) for key in ("m", "b", "k")}


def pack_params(specs: Sequence[L.LayerSpec], params: list[dict]) -> list[dict]:
    """Quantize trained fp params into packed inference params.

    conv:  w (3,3,Cin,Cout) -> words (Cout, 9*ceil(Cin/32)), tail bit 1
    fc:    w (Din,Dout)     -> words (Dout, ceil(Din/32)),   tail bit 1
    step:  gamma/beta/mean/var -> (thresh int32, flip bool) per channel

    Bi-Real layers take integer-valued arrays: fixed-point folds
    ``m``/``b``/``k`` (``q*`` quantizing an int8 input, ``s*`` a
    shortcut's), int8 weights ``w`` (stem HWIO, fc (Din, Dout)) and
    ``sw`` (a shortcut's (Cin, Cout)), the fc's int32 ``bias``, and a
    block's ±1 ``w`` (3,3,Cin,Cout), packed like a conv's with its
    border table (:func:`repro.bnn.layers.border_table`).
    """
    packed: list[dict] = []
    for spec, p in zip(specs, params):
        if spec.kind == "conv":
            w = np.asarray(p["w"])              # (3,3,Cin,Cout)
            packed.append(
                {"w_words": jnp.asarray(_conv_words(w)),
                 "k_true": 9 * w.shape[2]}
            )
        elif spec.kind == "fc":
            w = np.asarray(p["w"])              # (Din, Dout)
            words = np_pack_bits(np.sign(w.T) + 0.5, pad_bit=1)
            packed.append(
                {"w_words": jnp.asarray(words), "k_true": w.shape[0]}
            )
        elif spec.kind == "step":
            t, f = fold_bn(p["gamma"], p["beta"], p["mean"], p["var"])
            packed.append({"thresh": jnp.asarray(t), "flip": jnp.asarray(f)})
        elif spec.kind == "stem":
            packed.append({"w": _ints(p["w"], np.int8), **_fold(p)})
        elif spec.kind == "res":
            w = np.asarray(p["w"])              # (3,3,Cin,Cout) ±1
            q = {"w_words": jnp.asarray(_conv_words(w)),
                 "k_true": 9 * w.shape[2],
                 "corr": jnp.asarray(L.border_table(np.where(w >= 0, 1, -1))),
                 **_fold(p)}
            if spec.stride != 1:
                q.update(_fold(p, "q"), sw=_ints(p["sw"], np.int8),
                         **_fold(p, "s"))
            packed.append(q)
        elif spec.kind == "fcq":
            packed.append({"w": _ints(p["w"], np.int8),
                           "bias": _ints(p["bias"]), **_fold(p, "q")})
        else:
            packed.append({})
    return packed


def prepare_input_packed(x01: jax.Array) -> jax.Array:
    """[0,1] images (B,H,W,C) -> packed words (B,H,W,ceil(C/32)),
    matching the fp path's binarize_input (threshold 0.5, ties -> +1)."""
    return pack_bits(x01 - 0.5 >= 0)


def forward_packed(
    specs: Sequence[L.LayerSpec], packed: list[dict], x_words: jax.Array
) -> jax.Array:
    """Reference packed inference (the 'CPU' implementation end to end).
    Returns int32 class scores."""
    x = x_words
    for spec, p in zip(specs, packed):
        x = L.apply_packed(spec, p, x)
    return x
