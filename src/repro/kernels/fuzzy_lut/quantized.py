"""int8-LUT variant of the fused fuzzy-LUT kernel (beyond the paper).

LUT rows are stored int8 with one f32 scale per partition group; the scale
is folded into the table in VMEM, before the MXU matmul (exact — the matmul
sums over (group, centroid) and the scale is constant within a group):

    y^T = Σ_k s_k · LUT8[k, idx_k] == (LUT8^T ⊙ s)[N, K·C] @ onehot[K·C, T]

Wire effect at decode: LUT bytes halve vs bf16; with v=16, C=16 the total
weight-byte cost is 0.5·(C/v)=0.5× the dense bf16 weights — the decode
memory-roofline lever.

Both kernels here take the scales already broadcast per centroid (``[1,
K·C]`` per bank, :func:`repro.kernels.fuzzy_lut.kernel.stack_layout`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import _bank_step, _stack_layers, bank_call, stack_call

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


@functools.partial(
    jax.jit, static_argnames=("depth", "n_out", "interpret", "strategy"))
def fuzzy_lut_q8_pallas(
    x,            # [T, K·v]
    sel,          # [K·C, W] one-hot feature select (bank_layout)
    thr,          # [K·C, 1]
    lut_q8,       # [W, K·C] int8, transposed
    scales,       # [1, K·C] f32 dequant factors, broadcast per centroid
    bias,         # [W, 1]
    *,
    depth: int,
    n_out: int,
    interpret: bool | None = None,
    strategy: str = "auto",
):
    """int8 single-bank kernel: LUT bytes quartered in HBM, dequantized
    in-VMEM. Contract mirrors
    :func:`repro.kernels.fuzzy_lut.kernel.fuzzy_lut_pallas`."""
    return bank_call(_bank_step, x, (sel, thr, lut_q8, scales, bias),
                     depth=depth, n_out=n_out, interpret=interpret,
                     strategy=strategy, where="fuzzy_lut_q8_pallas")


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
