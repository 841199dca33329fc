"""int8-LUT variant of the fused fuzzy-LUT kernel (beyond the paper).

LUT rows are stored int8 with one f32 scale per partition group; the scale
is folded into the one-hot BEFORE the MXU matmul (exact — the matmul sums
over (group, centroid) and the scale is constant within a group):

    y = Σ_k s_k · LUT8[k, idx_k]  ==  (onehot ⊙ s)[T, K·C] @ LUT8[K·C, N]

Wire effect at decode: LUT bytes halve vs bf16; with v=16, C=16 the total
weight-byte cost is 0.5·(C/v)=0.5× the dense bf16 weights — the decode
memory-roofline lever.

The stacked variant stores the scales already broadcast per centroid
(``[L, 1, K·C]``, :func:`repro.kernels.fuzzy_lut.kernel.stack_layout`) and
folds them into each layer's table in VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .kernel import (
    _check_divisible,
    _lut_contrib,
    _stack_layers,
    _tpu_compiler_params,
    _tree_leaf,
    default_interpret,
    resolve_strategy,
    stack_call,
)

__all__ = ["quantize_lut_int8", "fuzzy_lut_q8_pallas", "fuzzy_lut_q8_ref",
           "fuzzy_lut_stack_q8_pallas"]


def quantize_lut_int8(lut: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-group symmetric int8 quantization. [K,C,N] → (int8 [K,C,N], f32 [K])."""
    amax = jnp.max(jnp.abs(lut.astype(jnp.float32)), axis=(1, 2))
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(lut.astype(jnp.float32) / scale[:, None, None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def fuzzy_lut_q8_ref(x, features, thresholds, lut_q8, scales):
    """Oracle: hard descent + dequantized gather-sum."""
    from .ref import tree_descent_ref

    idx = tree_descent_ref(x, features, thresholds)            # [T, K]
    gathered = jnp.take_along_axis(
        lut_q8[None].astype(jnp.float32), idx[:, :, None, None], axis=2
    )[:, :, 0, :]                                              # [T, K, N]
    return (gathered * scales[None, :, None]).sum(axis=1)


def _q8_kernel(x_ref, feat_oh_ref, thr_ref, lut_ref, scale_ref, out_ref, *,
               depth, strategy: str = "mxu"):
    x = x_ref[...].astype(jnp.float32)
    feat_oh = feat_oh_ref[...].astype(jnp.float32)
    thr = thr_ref[...].astype(jnp.float32)

    leaf = _tree_leaf(x, feat_oh, thr, depth=depth, strategy=strategy)
    contrib = _lut_contrib(
        leaf, lut_ref[...].astype(jnp.float32), strategy=strategy,
        scale=scale_ref[...].astype(jnp.float32))

    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = contrib

    @pl.when(pl.program_id(2) != 0)
    def _accum():
        out_ref[...] += contrib


@functools.partial(
    jax.jit, static_argnames=("depth", "block_t", "block_n", "block_k",
                              "interpret", "strategy"))
def fuzzy_lut_q8_pallas(
    x, feat_oh, thresholds, lut_q8, scales, *,
    depth: int, block_t: int = 256, block_n: int = 256, block_k: int = 128,
    interpret: bool | None = None, strategy: str = "auto",
):
    if interpret is None:
        interpret = default_interpret()
    strategy = resolve_strategy(strategy, interpret)
    t, k, v = x.shape
    _, c, n = lut_q8.shape
    bt, bn, bk = min(block_t, t), min(block_n, n), min(block_k, k)
    _check_divisible("fuzzy_lut_q8_pallas", T=(t, bt), N=(n, bn), K=(k, bk))
    n_internal = c - 1
    grid = (t // bt, n // bn, k // bk)
    return pl.pallas_call(
        functools.partial(_q8_kernel, depth=depth, strategy=strategy),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, bk, v), lambda i, j, kk: (i, kk, 0)),
            pl.BlockSpec((bk, n_internal, v), lambda i, j, kk: (kk, 0, 0)),
            pl.BlockSpec((bk, n_internal), lambda i, j, kk: (kk, 0)),
            pl.BlockSpec((bk, c, bn), lambda i, j, kk: (kk, 0, j)),
            pl.BlockSpec((bk,), lambda i, j, kk: (kk,)),
        ],
        out_specs=pl.BlockSpec((bt, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((t, n), jnp.float32),
        compiler_params=_tpu_compiler_params(("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, feat_oh, thresholds, lut_q8, scales)


def _stack_q8_kernel(x_ref, sel_ref, thr_ref, lut_ref, scale_ref, bias_ref,
                     out_ref, *, depth, strategy):
    """One batch tile through ALL L fused banks; int8 LUT stack dequantized
    in-VMEM via the pre-broadcast per-(layer, group) scale rows."""
    out_ref[...] = _stack_layers(
        x_ref[...], sel_ref, thr_ref, lut_ref, bias_ref, scale_ref,
        depth=depth, strategy=strategy)


@functools.partial(
    jax.jit, static_argnames=("depth", "ks", "n_out", "block_t", "interpret",
                              "strategy"))
def fuzzy_lut_stack_q8_pallas(
    x,            # [T, ks[0]·v]
    sel,          # [L, K·C, W] one-hot feature select (stack_layout)
    thr,          # [L, K·C, 1]
    lut_q8,       # [L, W, K·C] int8, transposed
    scales,       # [L, 1, K·C] f32 dequant factors, broadcast per centroid
    bias,         # [L, W, 1]
    *,
    depth: int,
    ks: tuple[int, ...],
    n_out: int,
    block_t: int | None = None,
    interpret: bool | None = None,
    strategy: str = "auto",
):
    """int8 stacked-layer kernel: the fused counterpart of
    :func:`fuzzy_lut_q8_pallas` — LUT bytes stay quartered in HBM AND the
    dequantized rows never leave VMEM between banks. Contract mirrors
    :func:`repro.kernels.fuzzy_lut.kernel.fuzzy_lut_stack_pallas`."""
    return stack_call(_stack_q8_kernel, x, (sel, thr, lut_q8, scales, bias),
                      depth=depth, ks=ks, n_out=n_out, block_t=block_t,
                      interpret=interpret, strategy=strategy,
                      where="fuzzy_lut_stack_q8_pallas")
