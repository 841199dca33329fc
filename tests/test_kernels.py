"""Per-kernel validation: shape/dtype sweeps + hypothesis property tests,
all against the pure-jnp oracle (interpret=True executes the kernel body on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import init_pegasus_linear
from repro.core.amm import apply_gather
from repro.core.fuzzy_tree import fit_tree, stack_trees
from repro.kernels.fuzzy_lut.kernel import fuzzy_lut_pallas, stack_layout
from repro.kernels.fuzzy_lut.ops import fuzzy_lut_matmul, prepare_feat_onehot
from repro.kernels.fuzzy_lut.ref import fuzzy_lut_matmul_ref, tree_descent_ref

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    HAVE_HYPOTHESIS = False


def _random_problem(rng, t, k, v, depth, n, lut_dtype=jnp.float32):
    data = rng.normal(size=(max(4 * 2**depth, 64), k * v)).astype(np.float32)
    trees = stack_trees(
        [fit_tree(data[:, g * v : (g + 1) * v], depth) for g in range(k)]
    )
    lut = jnp.asarray(
        rng.normal(size=(k, 2**depth, n)).astype(np.float32), dtype=lut_dtype
    )
    x = jnp.asarray(rng.normal(size=(t, k, v)).astype(np.float32))
    return x, trees, lut


SHAPE_SWEEP = [
    # t, k, v, depth, n, (bt, bn, bk)
    (8, 2, 2, 1, 4, (8, 4, 2)),
    (16, 4, 4, 2, 8, (8, 8, 2)),
    (32, 8, 4, 3, 16, (16, 16, 4)),
    (64, 16, 8, 4, 32, (32, 32, 8)),
    pytest.param(128, 32, 4, 4, 64, (64, 64, 16), marks=pytest.mark.slow),
    pytest.param(256, 64, 2, 5, 128, (128, 128, 32), marks=pytest.mark.slow),
]


@pytest.mark.parametrize("t,k,v,depth,n,blocks", SHAPE_SWEEP)
def test_kernel_matches_oracle_shape_sweep(t, k, v, depth, n, blocks):
    rng = np.random.default_rng(t * 1000 + k)
    x, trees, lut = _random_problem(rng, t, k, v, depth, n)
    feat_oh = prepare_feat_onehot(trees.features, v)
    bt, bn, bk = blocks
    got = fuzzy_lut_pallas(
        x, feat_oh, trees.thresholds, lut,
        depth=depth, block_t=bt, block_n=bn, block_k=bk,
    )
    want = fuzzy_lut_matmul_ref(x, trees.features, trees.thresholds, lut)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("lut_dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_dtype_sweep(lut_dtype):
    rng = np.random.default_rng(11)
    x, trees, lut = _random_problem(rng, 32, 8, 4, 4, 16, lut_dtype=lut_dtype)
    feat_oh = prepare_feat_onehot(trees.features, 4)
    got = fuzzy_lut_pallas(
        x, feat_oh, trees.thresholds, lut, depth=4,
        block_t=16, block_n=16, block_k=4,
    )
    want = fuzzy_lut_matmul_ref(x, trees.features, trees.thresholds, lut)
    tol = 1e-5 if lut_dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=tol, atol=tol)


def test_kernel_accumulation_over_k_blocks():
    """K-innermost accumulation must equal single-block result."""
    rng = np.random.default_rng(13)
    x, trees, lut = _random_problem(rng, 16, 8, 4, 3, 8)
    feat_oh = prepare_feat_onehot(trees.features, 4)
    one = fuzzy_lut_pallas(x, feat_oh, trees.thresholds, lut, depth=3,
                           block_t=16, block_n=8, block_k=8)
    many = fuzzy_lut_pallas(x, feat_oh, trees.thresholds, lut, depth=3,
                            block_t=16, block_n=8, block_k=2)
    np.testing.assert_allclose(np.asarray(one), np.asarray(many), rtol=1e-5, atol=1e-5)


def test_ops_wrapper_pads_ragged_shapes():
    """T/K/N not divisible by blocks → wrapper pads; result unchanged."""
    rng = np.random.default_rng(15)
    d, n, s = 24, 10, 1024  # K=6 groups of 4 — not a multiple of block_k
    w = rng.normal(size=(d, n)).astype(np.float32)
    calib = rng.normal(size=(s, d)).astype(np.float32)
    layer = init_pegasus_linear(w, None, calib, group_size=4, depth=3, lut_bits=None)
    x = jnp.asarray(calib[:37])  # ragged T
    got = fuzzy_lut_matmul(layer, x, block_t=16, block_n=8, block_k=4)
    want = apply_gather(layer, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


def test_ops_wrapper_batch_dims():
    rng = np.random.default_rng(16)
    d, n, s = 16, 8, 512
    w = rng.normal(size=(d, n)).astype(np.float32)
    b = rng.normal(size=(n,)).astype(np.float32)
    calib = rng.normal(size=(s, d)).astype(np.float32)
    layer = init_pegasus_linear(w, b, calib, group_size=4, depth=3, lut_bits=None)
    x = jnp.asarray(rng.normal(size=(3, 5, d)).astype(np.float32))
    got = fuzzy_lut_matmul(layer, x, block_t=8, block_n=8, block_k=4)
    want = apply_gather(layer, x)
    assert got.shape == (3, 5, n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


def _stacked_problem(rng, l, t, kmax, v, depth, nmax, ks, n_out):
    """Random stacked operands: padded groups carry +inf thr / zero LUT."""
    c = 2 ** depth
    i = c - 1
    feat_oh = np.zeros((l, kmax, i, v), np.float32)
    thr = np.full((l, kmax, i), np.inf, np.float32)
    lut = np.zeros((l, kmax, c, nmax), np.float32)
    bias = np.zeros((l, nmax), np.float32)
    for layer in range(l):
        k = ks[layer]
        feats = rng.integers(0, v, size=(k, i))
        feat_oh[layer, :k] = np.eye(v, dtype=np.float32)[feats]
        thr[layer, :k] = rng.normal(size=(k, i)).astype(np.float32)
        n = n_out if layer == l - 1 else ks[layer + 1] * v
        lut[layer, :k, :, :n] = rng.normal(size=(k, c, n)).astype(np.float32) * 0.3
        bias[layer, :n] = rng.normal(size=n).astype(np.float32) * 0.1
    x = rng.normal(size=(t, ks[0], v)).astype(np.float32)
    return map(jnp.asarray, (x, feat_oh, thr, lut, bias))


def _stack_args(x, feat_oh, thr, lut, bias):
    """Flat batch + the stacked kernel's plan-build layout."""
    return (x.reshape(x.shape[0], -1),
            *stack_layout(feat_oh, thr, lut, bias))


@pytest.mark.parametrize("strategy", ["lookup", "mxu"])
def test_stack_kernel_matches_chained_single_bank(strategy):
    """The stacked-layer kernel ≡ chaining the single-bank kernel per layer
    (re-partition + bias applied between layers), on both strategies."""
    from repro.kernels.fuzzy_lut.kernel import fuzzy_lut_stack_pallas

    rng = np.random.default_rng(7)
    ks, v, depth, n_out, t = (6, 4, 4, 4), 2, 3, 3, 16
    x, feat_oh, thr, lut, bias = _stacked_problem(
        rng, len(ks), t, max(ks), v, depth, 8, ks, n_out)
    got = fuzzy_lut_stack_pallas(
        *_stack_args(x, feat_oh, thr, lut, bias), depth=depth, ks=ks,
        n_out=n_out, strategy=strategy)

    h = x
    for layer, k in enumerate(ks):
        n = n_out if layer == len(ks) - 1 else ks[layer + 1] * v
        y = fuzzy_lut_pallas(
            h[:, :k], feat_oh[layer, :k], thr[layer, :k],
            lut[layer, :k, :, :n], depth=depth, block_t=t, block_n=n,
            block_k=k, strategy=strategy)
        y = y + bias[layer, :n]
        if layer + 1 < len(ks):
            h = y.reshape(t, ks[layer + 1], v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(y),
                               rtol=1e-5, atol=1e-5)


def test_stack_kernel_tiles_batch():
    """T larger than block_t: grid-tiled result equals the one-tile result."""
    from repro.kernels.fuzzy_lut.kernel import fuzzy_lut_stack_pallas

    rng = np.random.default_rng(9)
    ks, v, depth, n_out = (4, 4), 2, 3, 8
    args = _stack_args(*_stacked_problem(
        rng, len(ks), 64, max(ks), v, depth, 8, ks, n_out))
    one = fuzzy_lut_stack_pallas(*args, depth=depth, ks=ks, n_out=n_out,
                                 block_t=64)
    many = fuzzy_lut_stack_pallas(*args, depth=depth, ks=ks, n_out=n_out,
                                  block_t=16)
    np.testing.assert_allclose(np.asarray(one), np.asarray(many),
                               rtol=1e-5, atol=1e-5)


def test_block_divisibility_raises_value_error():
    """Satellite bugfix: mis-padded operands raise ValueError naming the
    offending dims (an assert would vanish under ``python -O`` and the
    engine fallback could never catch it)."""
    from repro.kernels.fuzzy_lut.kernel import fuzzy_lut_stack_pallas

    rng = np.random.default_rng(21)
    x, trees, lut = _random_problem(rng, 12, 4, 4, 2, 8)   # T=12 vs block 8
    feat_oh = prepare_feat_onehot(trees.features, 4)
    with pytest.raises(ValueError, match=r"T=12 % block 8"):
        fuzzy_lut_pallas(x, feat_oh, trees.thresholds, lut, depth=2,
                         block_t=8, block_n=8, block_k=4)
    with pytest.raises(ValueError, match=r"N=8 % block 3"):
        fuzzy_lut_pallas(x, feat_oh, trees.thresholds, lut, depth=2,
                         block_t=12, block_n=3, block_k=4)

    ks, v, depth, n_out = (4, 4), 4, 2, 8
    args = _stack_args(*_stacked_problem(
        rng, 2, 12, 4, v, depth, 16, ks, n_out))
    with pytest.raises(ValueError, match=r"T=12 % block 8"):
        fuzzy_lut_stack_pallas(*args, depth=depth, ks=ks,
                               n_out=n_out, block_t=8)
    with pytest.raises(ValueError, match="ks has 3 entries"):
        fuzzy_lut_stack_pallas(*args, depth=depth,
                               ks=(4, 4, 4), n_out=n_out, block_t=12)


def test_resolve_strategy_refuses_compiled_lookup():
    """``lookup`` gathers exist only under the interpreter: a compiled
    request is refused up front, not deep inside Mosaic."""
    from repro.kernels.fuzzy_lut.kernel import resolve_strategy

    with pytest.raises(ValueError, match="lookup"):
        resolve_strategy("lookup", interpret=False)
    assert resolve_strategy("lookup", interpret=True) == "lookup"
    assert resolve_strategy("auto", interpret=False) == "mxu"


if HAVE_HYPOTHESIS:

    @settings(max_examples=20, deadline=None)
    @given(
        t=st.integers(2, 24),
        k=st.sampled_from([2, 4, 8]),
        v=st.sampled_from([2, 4]),
        depth=st.integers(1, 4),
        n=st.sampled_from([4, 8, 16]),
        seed=st.integers(0, 2**16),
    )
    def test_property_kernel_equals_oracle(t, k, v, depth, n, seed):
        rng = np.random.default_rng(seed)
        x, trees, lut = _random_problem(rng, t, k, v, depth, n)
        feat_oh = prepare_feat_onehot(trees.features, v)
        got = fuzzy_lut_pallas(
            x, feat_oh, trees.thresholds, lut, depth=depth,
            block_t=t, block_n=n, block_k=k,
        )
        want = fuzzy_lut_matmul_ref(x, trees.features, trees.thresholds, lut)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)

    @settings(max_examples=20, deadline=None)
    @given(
        seed=st.integers(0, 2**16),
        depth=st.integers(1, 5),
    )
    def test_property_descent_reaches_valid_leaf(seed, depth):
        """Invariant: every input reaches exactly one leaf in [0, 2^d)."""
        rng = np.random.default_rng(seed)
        data = rng.normal(size=(128, 3)).astype(np.float32)
        tree = fit_tree(data, depth)
        stacked = stack_trees([tree])
        idx = tree_descent_ref(
            jnp.asarray(data[:, None, :]), stacked.features, stacked.thresholds
        )
        assert int(idx.min()) >= 0 and int(idx.max()) < 2**depth
