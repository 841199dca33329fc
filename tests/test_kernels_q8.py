"""int8-LUT kernel validation: quantization properties + kernel vs oracle."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.fuzzy_tree import fit_tree, stack_trees
from repro.kernels.fuzzy_lut.kernel import bank_layout
from repro.kernels.fuzzy_lut.ops import prepare_feat_onehot
from repro.kernels.fuzzy_lut.quantized import (
    fuzzy_lut_q8_pallas, fuzzy_lut_q8_ref, quantize_lut_int8,
)
from repro.kernels.fuzzy_lut.ref import fuzzy_lut_matmul_ref


def _problem(rng, t, k, v, depth, n):
    data = rng.normal(size=(max(4 * 2**depth, 64), k * v)).astype(np.float32)
    trees = stack_trees(
        [fit_tree(data[:, g * v : (g + 1) * v], depth) for g in range(k)])
    lut = jnp.asarray(rng.normal(size=(k, 2**depth, n)).astype(np.float32))
    x = jnp.asarray(rng.normal(size=(t, k, v)).astype(np.float32))
    return x, trees, lut


def _single_q8(x, feat_oh, thr, q, s, bias=None, **kw):
    """The int8 single-bank kernel on ``[T, K, v]`` activations, its
    operands in the plan-build layout (``bank_layout``)."""
    k, c, n = q.shape
    bias = jnp.zeros(n) if bias is None else bias
    return fuzzy_lut_q8_pallas(
        x.reshape(x.shape[0], -1), *bank_layout(feat_oh, thr, q, bias, s),
        depth=c.bit_length() - 1, n_out=n, **kw)


def test_quantize_lut_int8_roundtrip():
    rng = np.random.default_rng(0)
    lut = jnp.asarray(rng.normal(size=(4, 16, 8)).astype(np.float32))
    q, s = quantize_lut_int8(lut)
    assert q.dtype == jnp.int8
    deq = q.astype(jnp.float32) * s[:, None, None]
    rel = float(jnp.linalg.norm(deq - lut) / jnp.linalg.norm(lut))
    assert rel < 0.01  # int8 symmetric: ~0.4% rms for gaussian


@pytest.mark.parametrize("t,k,v,depth,n", [
    (16, 4, 4, 3, 8),
    (64, 8, 2, 4, 16),
])
def test_q8_kernel_matches_ref(t, k, v, depth, n):
    rng = np.random.default_rng(t + k)
    x, trees, lut = _problem(rng, t, k, v, depth, n)
    q, s = quantize_lut_int8(lut)
    feat_oh = prepare_feat_onehot(trees.features, v)
    got = _single_q8(x, feat_oh, trees.thresholds, q, s)
    want = fuzzy_lut_q8_ref(x, trees.features, trees.thresholds, q, s)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("strategy", ["lookup", "mxu"])
def test_q8_stack_kernel_matches_chained_q8(strategy):
    """Stacked q8 kernel (dequant folded into the in-VMEM table) ≡ chaining
    the single-bank q8 kernel with the same int8 tables and scales."""
    from repro.kernels.fuzzy_lut.kernel import stack_layout
    from repro.kernels.fuzzy_lut.quantized import fuzzy_lut_stack_q8_pallas

    rng = np.random.default_rng(11)
    ks, v, depth, n_out, t = (4, 4, 4), 2, 3, 4, 16
    c = 2 ** depth
    i = c - 1
    l, kmax, nmax = len(ks), max(ks), 8
    feat_oh = np.zeros((l, kmax, i, v), np.float32)
    thr = np.full((l, kmax, i), np.inf, np.float32)
    lut_q8 = np.zeros((l, kmax, c, nmax), np.int8)
    scales = np.zeros((l, kmax), np.float32)
    bias = np.zeros((l, nmax), np.float32)
    for layer in range(l):
        k = ks[layer]
        feats = rng.integers(0, v, size=(k, i))
        feat_oh[layer, :k] = np.eye(v, dtype=np.float32)[feats]
        thr[layer, :k] = rng.normal(size=(k, i)).astype(np.float32)
        n = n_out if layer == l - 1 else ks[layer + 1] * v
        fp = rng.normal(size=(k, c, n)).astype(np.float32) * 0.3
        q, s = quantize_lut_int8(jnp.asarray(fp))
        lut_q8[layer, :k, :, :n] = np.asarray(q)
        scales[layer, :k] = np.asarray(s)
        bias[layer, :n] = rng.normal(size=n).astype(np.float32) * 0.1
    x = jnp.asarray(rng.normal(size=(t, ks[0], v)).astype(np.float32))
    args = tuple(map(jnp.asarray, (feat_oh, thr, lut_q8, scales, bias)))

    sel, thr_col, lut_t, bias_col, scale_row = stack_layout(
        args[0], args[1], args[2], args[4], args[3])
    got = fuzzy_lut_stack_q8_pallas(
        x.reshape(t, -1), sel, thr_col, lut_t, scale_row, bias_col,
        depth=depth, ks=ks, n_out=n_out, strategy=strategy)
    h = x
    for layer, k in enumerate(ks):
        n = n_out if layer == l - 1 else ks[layer + 1] * v
        y = _single_q8(
            h[:, :k], args[0][layer, :k], args[1][layer, :k],
            args[2][layer, :k, :, :n], args[3][layer, :k], args[4][layer, :n],
            strategy=strategy)
        if layer + 1 < l:
            h = y.reshape(t, ks[layer + 1], v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(y),
                               rtol=1e-4, atol=1e-5)


def test_q8_close_to_fp32_path():
    """End-to-end: int8 LUT result within quantization error of fp32 LUT."""
    rng = np.random.default_rng(5)
    x, trees, lut = _problem(rng, 32, 8, 4, 4, 16)
    q, s = quantize_lut_int8(lut)
    feat_oh = prepare_feat_onehot(trees.features, 4)
    got = _single_q8(x, feat_oh, trees.thresholds, q, s)
    want = fuzzy_lut_matmul_ref(x, trees.features, trees.thresholds, lut)
    rel = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert rel < 0.01, rel
