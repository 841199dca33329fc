"""Per-kernel validation: shape/dtype sweeps + hypothesis property tests,
all against the pure-jnp oracle (interpret=True executes the kernel body on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import init_pegasus_linear
from repro.core.amm import apply_gather
from repro.core.fuzzy_tree import fit_tree, stack_trees
from repro.kernels.fuzzy_lut.kernel import (
    bank_layout, descent_windows, fuzzy_lut_pallas, fuzzy_lut_stack_pallas,
    stack_layout,
)
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


def _single(x, trees, lut, bias=None, **kw):
    """The single-bank kernel on ``[T, K, v]`` activations, its operands in
    the plan-build layout (``bank_layout``)."""
    k, c, n = lut.shape
    v = x.shape[-1]
    bias = jnp.zeros(n) if bias is None else bias
    ops = bank_layout(prepare_feat_onehot(trees.features, v), trees.thresholds,
                      lut, bias)
    return fuzzy_lut_pallas(x.reshape(x.shape[0], -1), *ops,
                            depth=c.bit_length() - 1, n_out=n, **kw)


SHAPE_SWEEP = [
    # t, k, v, depth, n
    (8, 2, 2, 1, 4),
    (16, 4, 4, 2, 8),
    (32, 8, 4, 3, 16),
    (64, 16, 8, 4, 32),
    pytest.param(128, 32, 4, 4, 64, marks=pytest.mark.slow),
    pytest.param(256, 64, 2, 5, 128, marks=pytest.mark.slow),
]


@pytest.mark.parametrize("t,k,v,depth,n", SHAPE_SWEEP)
def test_kernel_matches_oracle_shape_sweep(t, k, v, depth, n):
    rng = np.random.default_rng(t * 1000 + k)
    x, trees, lut = _random_problem(rng, t, k, v, depth, n)
    got = _single(x, trees, lut)
    want = fuzzy_lut_matmul_ref(x, trees.features, trees.thresholds, lut)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("lut_dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_dtype_sweep(lut_dtype):
    rng = np.random.default_rng(11)
    x, trees, lut = _random_problem(rng, 32, 8, 4, 4, 16, lut_dtype=lut_dtype)
    got = _single(x, trees, lut)
    want = fuzzy_lut_matmul_ref(x, trees.features, trees.thresholds, lut)
    tol = 1e-5 if lut_dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=tol, atol=tol)


def test_kernel_accumulation_over_k_blocks():
    """Group-innermost accumulation must equal the single-block result: at
    RNN-B's recurrent width (K=24 groups of 1, depth 8) the single-bank
    kernel runs 3 group tiles of 8 (``bank_tiles``) and 2 batch tiles; the
    stacked kernel with L=1 runs the whole bank in one step. Both
    strategies."""
    rng = np.random.default_rng(13)
    k, depth, n, t = 24, 8, 24, 256
    c = 2 ** depth
    feats = jnp.zeros((k, c - 1), jnp.int32)
    thr = jnp.asarray(rng.normal(size=(k, c - 1)), jnp.float32)
    lut = jnp.asarray(rng.normal(size=(k, c, n)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=n), jnp.float32)
    x = jnp.asarray(rng.normal(size=(t, k)), jnp.float32)
    feat_oh = prepare_feat_onehot(feats, 1)
    for strategy in ("lookup", "mxu"):
        many = fuzzy_lut_pallas(x, *bank_layout(feat_oh, thr, lut, bias),
                                depth=depth, n_out=n, strategy=strategy)
        one = fuzzy_lut_stack_pallas(
            x, *stack_layout(feat_oh[None], thr[None], lut[None], bias[None]),
            depth=depth, ks=(k,), n_out=n, block_t=t, strategy=strategy)
        np.testing.assert_allclose(np.asarray(one), np.asarray(many),
                                   rtol=1e-5, atol=1e-5, err_msg=strategy)


# RNN-B's banks (v=1, depth 8): input (K=2, N=24), recurrent (K=24, N=24)
# and classifier (K=24, N=3)
RNN_B_BANKS = [(2, 24), (24, 24), (24, 3)]


@pytest.mark.parametrize("strategy", ["lookup", "mxu"])
@pytest.mark.parametrize("k,n", RNN_B_BANKS)
def test_single_bank_matches_gather_at_rnn_b_widths(k, n, strategy):
    """The plan's single-bank path at RNN-B's widths ≡ the ``gather``
    backend, on both kernel backends, for 600 flows: not a multiple of the
    batch tile (512 at K=2, 128 at K=24), and K=24 spans 3 group tiles."""
    from repro.core.amm import PegasusLinear
    from repro.core.fuzzy_tree import FuzzyTree
    from repro.engine.plan import CompiledBank
    from repro.kernels.fuzzy_lut.quantized import (
        fuzzy_lut_q8_ref, quantize_lut_int8,
    )

    rng = np.random.default_rng(100 * k + n)
    c, t = 256, 600
    feats = jnp.zeros((k, c - 1), jnp.int32)
    thr = jnp.asarray(rng.normal(size=(k, c - 1)), jnp.float32)
    lut = jnp.asarray(rng.normal(size=(k, c, n)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=n), jnp.float32)
    trees = FuzzyTree(feats, thr, jnp.zeros((k, c, 1), jnp.float32))
    bank = CompiledBank(PegasusLinear(trees, lut, bias, group_size=1),
                        interpret=True, strategy=strategy)
    assert bank.tiles == ((512, 2) if k == 2 else (128, 8))
    x = jnp.asarray(rng.normal(size=(t, k)), jnp.float32)
    np.testing.assert_allclose(np.asarray(bank.apply(x, "kernel")),
                               np.asarray(bank.apply(x, "gather")),
                               rtol=1e-5, atol=1e-5)
    q8, scales = quantize_lut_int8(lut)
    want = fuzzy_lut_q8_ref(x[:, :, None], feats, thr, q8, scales) + bias
    np.testing.assert_allclose(np.asarray(bank.apply(x, "kernel_q8")),
                               np.asarray(want), rtol=1e-5, atol=1e-5)


def test_ops_wrapper_pads_ragged_shapes():
    """Ragged T, K and N → the layout and the launcher pad; result
    unchanged."""
    rng = np.random.default_rng(15)
    d, n, s = 24, 10, 1024  # K=6 groups of 4, N=10, T=37: all ragged
    w = rng.normal(size=(d, n)).astype(np.float32)
    calib = rng.normal(size=(s, d)).astype(np.float32)
    layer = init_pegasus_linear(w, None, calib, group_size=4, depth=3, lut_bits=None)
    x = jnp.asarray(calib[:37])  # ragged T
    got = fuzzy_lut_matmul(layer, x)
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
    got = fuzzy_lut_matmul(layer, x)
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
    rng = np.random.default_rng(7)
    ks, v, depth, n_out, t = (6, 4, 4, 4), 2, 3, 3, 16
    x, feat_oh, thr, lut, bias = _stacked_problem(
        rng, len(ks), t, max(ks), v, depth, 8, ks, n_out)
    got = fuzzy_lut_stack_pallas(
        *_stack_args(x, feat_oh, thr, lut, bias), depth=depth, ks=ks,
        n_out=n_out, strategy=strategy)

    h = x.reshape(t, -1)
    for layer, k in enumerate(ks):
        n = n_out if layer == len(ks) - 1 else ks[layer + 1] * v
        y = fuzzy_lut_pallas(
            h[:, :k * v], *bank_layout(feat_oh[layer, :k], thr[layer, :k],
                                       lut[layer, :k, :, :n], bias[layer, :n]),
            depth=depth, n_out=n, strategy=strategy)
        h = y
    np.testing.assert_allclose(np.asarray(got), np.asarray(y),
                               rtol=1e-5, atol=1e-5)


def test_stack_kernel_tiles_batch():
    """T larger than block_t: grid-tiled result equals the one-tile result."""
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
    rng = np.random.default_rng(21)
    # single bank: groups not padded to the group tile (K=24, C=256 splits
    # into tiles of 8 groups; 23 is not a multiple), or x wider than W
    k, depth, n = 24, 8, 24
    c = 2 ** depth
    sel, thr, lut, bias = bank_layout(
        prepare_feat_onehot(jnp.zeros((k, c - 1), jnp.int32), 1),
        jnp.zeros((k, c - 1)), jnp.zeros((k, c, n)), jnp.zeros(n))
    x = jnp.zeros((8, k))
    cut = (k - 1) * c
    with pytest.raises(ValueError, match=r"K=23 % block 8"):
        fuzzy_lut_pallas(x, sel[:cut], thr[:cut], lut[:, :cut], bias,
                         depth=depth, n_out=n)
    with pytest.raises(ValueError, match=r"x carries 32 features"):
        fuzzy_lut_pallas(jnp.zeros((8, 32)), sel, thr, lut, bias,
                         depth=depth, n_out=n)

    ks, v, depth, n_out = (4, 4), 4, 2, 8
    args = _stack_args(*_stacked_problem(
        rng, 2, 12, 4, v, depth, 16, ks, n_out))
    with pytest.raises(ValueError, match=r"T=12 % block 8"):
        fuzzy_lut_stack_pallas(*args, depth=depth, ks=ks,
                               n_out=n_out, block_t=8)
    with pytest.raises(ValueError, match="ks has 3 entries"):
        fuzzy_lut_stack_pallas(*args, depth=depth,
                               ks=(4, 4, 4), n_out=n_out, block_t=12)


# the row window of each level of a depth-8 descent, as (lo, hi); a tree of
# depth d >= 3 takes the first d, and one of depth <= 3 (C <= 8) reads the
# whole C at every level
DESCENT_WINDOWS = [(0, 8), (0, 8), (0, 8), (0, 16), (8, 32), (24, 64),
                   (56, 128), (120, 256)]
# rows a descent touches, of C * depth: 312 of 2048 at depth 8 (RNN-B,
# CNN-L), 104 of 384 at depth 6 (MLP-B)
DESCENT_ROWS = {1: 2, 2: 8, 3: 24, 4: 40, 5: 64, 6: 104, 7: 176, 8: 312}


@pytest.mark.parametrize("depth", range(1, 9))
def test_descent_windows(depth):
    """Each level's window is whole 8-row tiles clamped to C and covers the
    level's nodes 2^l - 1 ... 2^(l+1) - 2."""
    c = 2 ** depth
    windows = descent_windows(depth)
    assert windows == (tuple(DESCENT_WINDOWS[:depth]) if depth >= 3
                       else ((0, c),) * depth)
    for level, (lo, hi) in enumerate(windows):
        assert lo % 8 == 0 and (hi % 8 == 0 or hi == c) and hi <= c
        assert lo <= 2 ** level - 1 and 2 ** (level + 1) - 2 < hi
    assert sum(hi - lo for lo, hi in windows) == DESCENT_ROWS[depth]


def _bst_problem(rng, depth, k):
    """Groups of one feature whose depth-``depth`` trees split ``[0, C)``
    in order at the half-integers, so flows at 0, 1, ..., C and at every
    threshold reach every node from both sides and every leaf; each group
    sees the values in its own order. Returns ``x [2C, k, 1]``, the split
    features and the thresholds."""
    c = 2 ** depth
    nodes = np.arange(c - 1)
    level = np.array([int(n + 1).bit_length() - 1 for n in nodes])
    span = c >> level                                  # leaves under a node
    thr = (nodes - (2 ** level - 1)) * span + span // 2 - 0.5
    values = np.concatenate([np.arange(c + 1), thr]).astype(np.float32)
    x = np.stack([rng.permutation(values) for _ in range(k)], axis=1)
    return (jnp.asarray(x[:, :, None]), jnp.zeros((k, c - 1), jnp.int32),
            jnp.asarray(np.tile(thr, (k, 1)), jnp.float32))


@pytest.mark.parametrize("kernel", ["single", "stack"])
@pytest.mark.parametrize("depth", range(1, 9))
def test_mxu_descent_matches_oracle_at_every_depth(depth, kernel):
    """The ``mxu`` body, windowed descent, ≡ the oracle at depths 1-8 on
    flows placed on every threshold and between them, so a window that
    misses a node flips a bit and picks another LUT row. ``stack``: two
    fused banks against the chained single-bank oracle; the first bank's
    table holds multiples of 1/2, so the second bank's inputs land exactly
    on its thresholds too."""
    rng = np.random.default_rng(depth)
    k, n_out, c = 4, 3, 2 ** depth
    x, feats, thr = _bst_problem(rng, depth, k)
    t = x.shape[0]
    lut = jnp.asarray(rng.normal(size=(k, c, n_out)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=n_out), jnp.float32)
    if kernel == "single":
        got = fuzzy_lut_pallas(
            x.reshape(t, k), *bank_layout(prepare_feat_onehot(feats, 1), thr,
                                          lut, bias),
            depth=depth, n_out=n_out, interpret=True, strategy="mxu")
        want = fuzzy_lut_matmul_ref(x, feats, thr, lut, bias)
    else:
        # bank 1: k groups -> k values in [0, C], each a multiple of 1/2
        lut1 = jnp.asarray(rng.integers(0, 2 * c // k + 1, size=(k, c, k)) / 2,
                           jnp.float32)
        h = fuzzy_lut_matmul_ref(x, feats, thr, lut1)
        assert float(jnp.mean(jnp.any(h[:, :, None] == thr[None], -1))) > 0
        want = fuzzy_lut_matmul_ref(h[:, :, None], feats, thr, lut, bias)
        lut2 = jnp.pad(lut, ((0, 0), (0, 0), (0, k - n_out)))
        bias2 = jnp.pad(bias, (0, k - n_out))
        got = fuzzy_lut_stack_pallas(
            x.reshape(t, k), *stack_layout(
                jnp.stack([prepare_feat_onehot(feats, 1)] * 2),
                jnp.stack([thr, thr]), jnp.stack([lut1, lut2]),
                jnp.stack([jnp.zeros(k), bias2])),
            depth=depth, ks=(k, k), n_out=n_out, interpret=True,
            strategy="mxu")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


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
        got = _single(x, trees, lut)
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
