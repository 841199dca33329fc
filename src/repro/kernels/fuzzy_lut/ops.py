"""Jit'd public wrapper for the fused fuzzy-LUT matmul kernel.

Handles layout prep (one-hot features, the kernel's transposed layout,
group padding) and exposes a `PegasusLinear`-level entry point used by the
serving stack (`repro.core.amm.pegasus_linear_apply(..., path="kernel")`).
"""

from __future__ import annotations

import weakref

import jax
import jax.numpy as jnp
import numpy as np

from .kernel import bank_layout, fuzzy_lut_pallas

__all__ = [
    "bank_operands", "fuzzy_lut_matmul", "fuzzy_lut_matmul_q8",
    "padded_layout", "prepare_feat_onehot", "quantized_lut_cached",
    "LAYOUT_STATS", "QUANT_STATS",
]

# int8-LUT memo: production deployments quantize offline exactly once; the
# convenience wrapper below used to re-quantize the whole bank on EVERY call.
# Keyed on the layer instance id; a weakref finalizer evicts the entry when
# the layer dies so ids can be reused safely.
QUANT_STATS = {"quantize_calls": 0, "cache_hits": 0}
_Q8_MEMO: dict[int, tuple] = {}

# Static-operand layout memo: the kernel layout of (feat_oh, thresholds,
# lut, bias) depends only on the layer, so it is built once per (layer id,
# q8?), weakref-evicted with the layer like the q8 memo; only the batch is
# transposed and padded per call (inside the kernel's launcher).
LAYOUT_STATS = {"layout_builds": 0, "cache_hits": 0}
_LAYOUT_MEMO: dict[tuple, tuple] = {}


def quantized_lut_cached(layer) -> tuple[jax.Array, jax.Array]:
    """(int8 LUT, per-group f32 scales) for a PegasusLinear, memoized."""
    from .quantized import quantize_lut_int8

    key = id(layer)
    entry = _Q8_MEMO.get(key)
    if entry is not None and entry[0]() is layer:
        QUANT_STATS["cache_hits"] += 1
        return entry[1], entry[2]
    lut_q8, scales = quantize_lut_int8(layer.lut.astype(jnp.float32))
    QUANT_STATS["quantize_calls"] += 1
    ref = weakref.ref(layer, lambda _ref, key=key: _Q8_MEMO.pop(key, None))
    _Q8_MEMO[key] = (ref, lut_q8, scales)
    return lut_q8, scales


def prepare_feat_onehot(features: jax.Array, group_size: int) -> jax.Array:
    """Offline: one-hot the per-node split features. [K, I] → [K, I, v]."""
    return jax.nn.one_hot(features, group_size, dtype=jnp.float32)


def bank_operands(layer, lut, scales=None) -> tuple:
    """:func:`repro.kernels.fuzzy_lut.kernel.bank_layout` of a
    PegasusLinear's trees and bias with ``lut`` — its f32 table, or an int8
    table with ``scales``."""
    bias = (jnp.zeros(layer.out_features) if layer.bias is None
            else layer.bias)
    return bank_layout(
        prepare_feat_onehot(layer.trees.features, layer.group_size),
        layer.trees.thresholds, lut, bias, scales)


def padded_layout(layer, *, quant: bool) -> tuple:
    """The single-bank kernel's operands for one PegasusLinear, memoized:
    :func:`repro.kernels.fuzzy_lut.kernel.bank_layout` of the layer —
    ``(sel, thr, lut_t, bias)``, or ``(sel, thr, lut_q8_t, scales, bias)``
    when ``quant``. Built exactly once per (layer, quant)."""
    key = (id(layer), quant)
    entry = _LAYOUT_MEMO.get(key)
    if entry is not None and entry[0]() is layer:
        LAYOUT_STATS["cache_hits"] += 1
        if quant:
            # the cached layout embeds the cached quantization — keep the
            # q8 memo's observable hit contract for callers that count it
            QUANT_STATS["cache_hits"] += 1
        return entry[1]
    layout = (bank_operands(layer, *quantized_lut_cached(layer)) if quant
              else bank_operands(layer, layer.lut))
    LAYOUT_STATS["layout_builds"] += 1
    ref = weakref.ref(layer, lambda _ref, key=key: _LAYOUT_MEMO.pop(key, None))
    _LAYOUT_MEMO[key] = (ref, layout)
    return layout


def _depth(layer) -> int:
    return int(np.log2(layer.num_centroids) + 0.5)


def fuzzy_lut_matmul(layer, x: jax.Array, *,
                     interpret: bool | None = None) -> jax.Array:
    """Apply a PegasusLinear via the Pallas kernel. x: [..., D] → [..., N].
    ``layer`` is duck-typed to avoid an import cycle."""
    n = layer.out_features
    y = fuzzy_lut_pallas(
        x.reshape(-1, x.shape[-1]), *padded_layout(layer, quant=False),
        depth=_depth(layer), n_out=n, interpret=interpret)
    return y.reshape(*x.shape[:-1], n)


def fuzzy_lut_matmul_q8(layer, x: jax.Array, *,
                        interpret: bool | None = None) -> jax.Array:
    """int8-LUT kernel path: quantize the bank once, run the q8 kernel.

    Production deployments quantize offline and keep only the int8 LUT in
    HBM (half the bytes — the decode-roofline lever, EXPERIMENTS §Perf D4);
    the quantization is memoized per layer (``quantized_lut_cached``) so
    repeated calls pay it exactly once.
    """
    from .quantized import fuzzy_lut_q8_pallas

    n = layer.out_features
    y = fuzzy_lut_q8_pallas(
        x.reshape(-1, x.shape[-1]), *padded_layout(layer, quant=True),
        depth=_depth(layer), n_out=n, interpret=interpret)
    return y.reshape(*x.shape[:-1], n)
