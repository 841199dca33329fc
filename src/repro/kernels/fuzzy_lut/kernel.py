"""Pallas TPU kernel: fused fuzzy-index + LUT accumulate (Pegasus Map+SumReduce).

TPU-native realization of the paper's table lookup:

  * The clustering-tree descent is *gather-free*: feature selection becomes a
    tiny one-hot matmul (the one-hot is precomputed offline), node selection
    a one-hot reduction — every step is VPU compare/select or MXU matmul, so
    the whole "fuzzy match" is branchless and systolic-friendly.
  * The Map (leaf→row lookup) + SumReduce (Σ over groups) pair is ONE MXU
    matmul: ``onehot(leaf): [Tt, Kt·C] @ LUT-block: [Kt·C, Nt]`` — the same
    primitive-fusion insight as the paper's, re-expressed for a systolic
    array instead of a MAT stage.

Every matmul that carries a value (an activation or a LUT entry) runs at
``Precision.HIGHEST``: a one-hot select must return the activation exactly,
or a tree decision next to its threshold flips.

Single-bank tiling (BlockSpec, all VMEM):
  grid = (T/Tt, N/Nt, K/Kt);   K innermost → output block accumulates.
    x        [T, K, v]   → block (Tt, Kt, v)      index (i, k, 0)
    feat_oh  [K, I, v]   → block (Kt, I, v)       index (k, 0, 0)   I = 2^d - 1
    thr      [K, I]      → block (Kt, I)          index (k, 0)
    lut      [K, C, N]   → block (Kt, C, Nt)      index (k, 0, j)
    out      [T, N]      → block (Tt, Nt)         index (i, j)

Stacked-layer variant (:func:`fuzzy_lut_stack_pallas` — Cross-bank Primitive
Fusion): a compatible run of L banks executes as ONE kernel invocation. The
grid tiles ONLY the batch; every per-layer operand rides whole (stacked along
a leading L axis), so the inter-bank activation never leaves VMEM. The stack
works in a *transposed, lane-dense* layout that :func:`stack_layout` builds
once at plan build: flows on lanes, features and tree nodes on sublanes. In
that layout the between-bank re-partition is free — the next bank's
block-diagonal one-hot select reads its groups straight out of the previous
bank's output rows — and the per-group tree walk splits only the sublane
axis (``[K·C, Tt] → [K, C, Tt]``), which Mosaic lays out tile for tile:

  grid = (T/Tt,)
    x^T      [W, T]          → block (W, Tt)           index (0, i)
    sel      [L, K·C, W]     → whole   one-hot: node (k, n) reads feature k·v+f
    thr      [L, K·C, 1]     → whole   +inf on the padding node n = C-1
    lut^T    [L, W, K·C]     → whole   f32, or int8 with scales [L, 1, K·C]
    bias     [L, W, 1]       → whole
    out^T    [W, T]          → block (W, Tt)           index (0, i)

``W`` (the activation width, a multiple of 8) covers the stack's widest
input and output. Padded groups hold +inf thresholds and zero LUT rows, so
they descend to leaf 0 and contribute nothing. The batch tile is derived
from the bank shape (:func:`stack_block_t`) so the ``[K·C, Tt]`` descent
intermediates stay ≈1 MiB each.

Both entry points raise ``ValueError`` (never ``assert``, which dies
silently under ``python -O``) when a dimension is not block-divisible, so
the engine can catch mis-padded operands and fall back to the per-bank path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["default_interpret", "fuzzy_lut_kernel", "fuzzy_lut_pallas",
           "fuzzy_lut_stack_pallas", "resolve_strategy", "stack_block_t",
           "stack_layout"]

# Byte budget of ONE [K·C, Tt] f32 descent intermediate of the stacked
# kernel (values, bits, one-hots); the batch tile follows from it.
STACK_TILE_BYTES = 1 << 20

_HIGHEST = jax.lax.Precision.HIGHEST


def default_interpret() -> bool:
    """Pallas interpret mode unless a real TPU backend is attached.

    This is THE static gate for the non-interpret path: callers pass
    ``interpret=None`` and get the Mosaic-compiled kernel on TPU, the
    traceable interpreter everywhere else (CPU CI, tests). The flag is a
    static jit arg throughout, so both modes live in separate compile-cache
    entries and can coexist in one process.
    """
    return jax.default_backend() != "tpu"


def _tpu_compiler_params(dimension_semantics: tuple[str, ...]):
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics)


def _tree_leaf(x, feat_oh, thr, *, depth: int, strategy: str):
    """Shared descent: [Tt, Kt, v] activations → [Tt, Kt] leaf indices.

    Both strategies compute the SAME bits (identical fp compare); they differ
    in how much of the tree they touch:
      ``mxu``    — score EVERY internal node up front (one-hot einsum for the
                   feature select, one-hot reduction per level): branchless
                   and gather-free — what the systolic/VPU path wants.
      ``lookup`` — walk only the ``depth`` visited nodes, one flat-index
                   gather per level (features recovered from the one-hot via
                   argmax, [Kt, I] — tiny): no [Tt, Kt, I] intermediates at
                   all, which is what the interpreter/CPU wants (the dense
                   form materializes I/d ≈ 10× more values than the walk
                   reads).
    """
    tt, kt = x.shape[0], x.shape[1]
    n_internal = thr.shape[-1]
    node = jnp.zeros((tt, kt), dtype=jnp.int32)
    if strategy == "lookup":
        # sparse walk: gather (feature, threshold) of the CURRENT node only
        feat_flat = jnp.argmax(feat_oh, axis=-1).astype(jnp.int32).reshape(-1)
        thr_flat = thr.reshape(-1)
        base = (jnp.arange(kt, dtype=jnp.int32) * n_internal)[None]  # [1, Kt]
        for _ in range(depth):
            idx = node + base                                 # [Tt, Kt]
            f_sel = jnp.take(feat_flat, idx)
            t_sel = jnp.take(thr_flat, idx)
            val = jnp.take_along_axis(x, f_sel[:, :, None], axis=2)[..., 0]
            node = 2 * node + 1 + (val > t_sel).astype(jnp.int32)
        return node - n_internal                  # [Tt, Kt] in [0, C)

    # dense scoring: vals[t,k,n] = x[t,k,feat[k,n]] as an einsum against the
    # precomputed one-hot (gather-free), then one-hot-select per level.
    vals = jax.lax.dot_general(
        x,
        feat_oh,
        # contract v; batch over k
        dimension_numbers=(((2,), (2,)), ((1,), (0,))),
        precision=_HIGHEST,
        preferred_element_type=jnp.float32,
    )                                             # [Kt, Tt, I]
    vals = vals.transpose(1, 0, 2)                # [Tt, Kt, I]
    bits = (vals > thr[None]).astype(jnp.int32)   # decision at every node
    iota_nodes = jax.lax.broadcasted_iota(jnp.int32, (tt, kt, n_internal), 2)
    for _ in range(depth):
        node_oh = (iota_nodes == node[:, :, None]).astype(jnp.int32)
        bit = jnp.sum(bits * node_oh, axis=-1)    # [Tt, Kt]
        node = 2 * node + 1 + bit
    return node - n_internal                      # [Tt, Kt] in [0, C)


def _lut_contrib(leaf, lut, *, strategy: str, scale=None):
    """Map + SumReduce over one tile: [Tt, Kt] leaves × [Kt, C, Nt] LUT →
    [Tt, Nt] contributions. ``scale`` ([Kt] per-group dequant factors, q8
    path) folds in exactly — it is constant within a group, and both
    realizations sum over (group, centroid).

      ``mxu``    — onehot(leaf) [Tt, Kt·C] @ lut [Kt·C, Nt]: one systolic
                   matmul, gather-free.
      ``lookup`` — flat-index gather-sum (rows picked from the [Kt·C, Nt]
                   table view): O(T·K·N) instead of the matmul's O(T·K·C·N);
                   the interpreter/CPU-fast form.
    """
    tt, kt = leaf.shape
    c = lut.shape[1]
    if strategy == "lookup":
        base = (jnp.arange(kt, dtype=jnp.int32) * c)[None]    # [1, Kt]
        rows = jnp.take(lut.reshape(kt * c, -1), leaf + base,
                        axis=0)                   # [Tt, Kt, Nt]
        if scale is not None:
            rows = rows * scale[None, :, None]
        return rows.sum(axis=1)                   # [Tt, Nt]
    iota_c = jax.lax.broadcasted_iota(jnp.int32, (tt, kt, c), 2)
    onehot = (iota_c == leaf[:, :, None]).astype(jnp.float32)
    if scale is not None:
        onehot = onehot * scale[None, :, None]
    return jax.lax.dot_general(
        onehot.reshape(tt, kt * c),
        lut.reshape(kt * c, -1),
        dimension_numbers=(((1,), (0,)), ((), ())),
        precision=_HIGHEST,
        preferred_element_type=jnp.float32,
    )                                             # [Tt, Nt]


def fuzzy_lut_kernel(
    x_ref, feat_oh_ref, thr_ref, lut_ref, out_ref, *, depth: int,
    strategy: str = "mxu",
):
    """One (Tt, Nt, Kt) tile: descend trees, accumulate LUT rows into out."""
    x = x_ref[...].astype(jnp.float32)            # [Tt, Kt, v]
    feat_oh = feat_oh_ref[...].astype(jnp.float32)  # [Kt, I, v]
    thr = thr_ref[...].astype(jnp.float32)        # [Kt, I]

    leaf = _tree_leaf(x, feat_oh, thr, depth=depth, strategy=strategy)
    lut = lut_ref[...].astype(jnp.float32)        # [Kt, C, Nt]
    contrib = _lut_contrib(leaf, lut, strategy=strategy)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = contrib

    @pl.when(pl.program_id(2) != 0)
    def _accum():
        out_ref[...] += contrib


def _check_divisible(where: str, **dims: tuple[int, int]) -> None:
    """Raise ``ValueError`` naming every dim not divisible by its block.

    A raised error (not ``assert``) so (a) ``python -O`` can't silently skip
    the check and (b) the engine's fused-path fallback can catch a mis-padded
    operand stack and dispatch per-bank instead of dying.
    """
    bad = [f"{name}={size} % block {blk} != 0"
           for name, (size, blk) in dims.items() if size % blk != 0]
    if bad:
        raise ValueError(
            f"{where}: {'; '.join(bad)} — operands must be pre-padded to "
            "block multiples (CompiledBank / ops.py layout prep does this)")


def resolve_strategy(strategy: str, interpret: bool) -> str:
    """``auto`` → ``lookup`` under the interpreter (CPU executes the one-hot
    matmul's C× redundant work serially), ``mxu`` on compiled TPU (systolic
    arrays eat dense matmuls; gathers don't vectorize). Both strategies are
    semantics-identical and parity-tested against each other. ``lookup``
    exists only in interpret mode: Mosaic cannot compile its gathers, so a
    compiled ``lookup`` request is refused here rather than deep in the
    compiler."""
    if strategy == "auto":
        return "lookup" if interpret else "mxu"
    if strategy not in ("mxu", "lookup"):
        raise ValueError(f"unknown strategy {strategy!r}; expected auto|mxu|lookup")
    if strategy == "lookup" and not interpret:
        raise ValueError(
            "strategy 'lookup' runs only in Pallas interpret mode (Mosaic "
            "cannot compile its gathers); use 'mxu' or 'auto' with "
            "interpret=False")
    return strategy


@functools.partial(
    jax.jit,
    static_argnames=("depth", "block_t", "block_n", "block_k", "interpret",
                     "strategy"),
)
def fuzzy_lut_pallas(
    x: jax.Array,          # [T, K, v]
    feat_oh: jax.Array,    # [K, I, v] one-hot of split features (offline)
    thresholds: jax.Array, # [K, I]
    lut: jax.Array,        # [K, C, N]
    *,
    depth: int,
    block_t: int = 256,
    block_n: int = 256,
    block_k: int = 128,
    interpret: bool | None = None,
    strategy: str = "auto",
) -> jax.Array:
    """Pallas-tiled fused Pegasus matmul. Returns [T, N] f32 (no bias).

    Fully traceable inside an outer ``jax.jit`` (the engine jits whole-plan
    forwards through here); ``interpret`` and ``strategy`` are static args,
    ``None``/``"auto"`` resolve via :func:`default_interpret` /
    :func:`resolve_strategy`.
    """
    if interpret is None:
        interpret = default_interpret()
    strategy = resolve_strategy(strategy, interpret)
    t, k, v = x.shape
    _, c, n = lut.shape
    bt, bn, bk = min(block_t, t), min(block_n, n), min(block_k, k)
    _check_divisible("fuzzy_lut_pallas", T=(t, bt), N=(n, bn), K=(k, bk))
    n_internal = c - 1

    grid = (t // bt, n // bn, k // bk)
    return pl.pallas_call(
        functools.partial(fuzzy_lut_kernel, depth=depth, strategy=strategy),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, bk, v), lambda i, j, kk: (i, kk, 0)),
            pl.BlockSpec((bk, n_internal, v), lambda i, j, kk: (kk, 0, 0)),
            pl.BlockSpec((bk, n_internal), lambda i, j, kk: (kk, 0)),
            pl.BlockSpec((bk, c, bn), lambda i, j, kk: (kk, 0, j)),
        ],
        out_specs=pl.BlockSpec((bt, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((t, n), jnp.float32),
        compiler_params=_tpu_compiler_params(("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, feat_oh, thresholds, lut)


# ---------------------------------------------------------------------------
# Stacked-layer variant: L compatible banks in ONE kernel invocation
# ---------------------------------------------------------------------------


def stack_layout(feat_oh, thr, lut, bias, scales=None) -> tuple:
    """Plan-build layout prep: group-major stacked operands → the stacked
    kernel's transposed, lane-dense layout (module docstring).

    Takes ``feat_oh [L, Kmax, I, v]``, ``thr [L, Kmax, I]``,
    ``lut [L, Kmax, C, Nmax]`` (f32 or int8), ``bias [L, Nmax]`` and, for
    the int8 stack, ``scales [L, Kmax]``. Returns ``(sel, thr, lut_t,
    bias)`` — plus the scales pre-broadcast to ``[L, 1, Kmax·C]`` when
    given — with ``W = round8(max(Kmax·v, Nmax))`` activation rows.
    """
    nl, kmax, n_int, v = feat_oh.shape
    c, nmax = lut.shape[2], lut.shape[3]
    kc = kmax * c
    w = -(-max(kmax * v, nmax) // 8) * 8
    # node axis padded to C: the extra node n = C-1 is never visited
    fo = jnp.pad(feat_oh.astype(jnp.float32),
                 ((0, 0), (0, 0), (0, c - n_int), (0, 0)))
    # block-diagonal select: row k·C+n picks column k·v+f
    sel = jnp.einsum("lknf,kj->lknjf", fo, jnp.eye(kmax, dtype=jnp.float32))
    sel = jnp.pad(sel.reshape(nl, kc, kmax * v),
                  ((0, 0), (0, 0), (0, w - kmax * v)))
    thr_col = jnp.pad(thr.astype(jnp.float32),
                      ((0, 0), (0, 0), (0, c - n_int)),
                      constant_values=jnp.inf).reshape(nl, kc, 1)
    lut_t = jnp.pad(lut.reshape(nl, kc, nmax).transpose(0, 2, 1),
                    ((0, 0), (0, w - nmax), (0, 0)))
    bias_col = jnp.pad(bias.astype(jnp.float32),
                       ((0, 0), (0, w - nmax)))[:, :, None]
    out = (sel, thr_col, lut_t, bias_col)
    if scales is not None:
        out += (jnp.repeat(scales.astype(jnp.float32), c, axis=1)[:, None, :],)
    return out


def stack_block_t(kc: int) -> int:
    """Batch tile of the stacked kernel for ``K·C`` tree nodes per layer:
    the largest power of two in [128, 1024] whose ``[K·C, Tt]`` f32
    descent intermediates fit :data:`STACK_TILE_BYTES`."""
    bt = 1024
    while bt > 128 and kc * bt * 4 > STACK_TILE_BYTES:
        bt //= 2
    return bt


def _stack_layer_mxu(h, sel, thr, tab, *, depth: int):
    """One bank in the transposed layout, gather-free: ``h [W, Tt]`` →
    ``[W, Tt]`` contributions (bias not yet added)."""
    kc, tt = sel.shape[0], h.shape[1]
    c = 1 << depth
    k = kc // c
    vals = jax.lax.dot_general(                    # [K·C, Tt]: x at each node
        sel, h, (((1,), (0,)), ((), ())), precision=_HIGHEST,
        preferred_element_type=jnp.float32)
    bits = (vals > thr).astype(jnp.int32).reshape(k, c, tt)
    iota = jax.lax.broadcasted_iota(jnp.int32, (k, c, tt), 1)
    node = jnp.zeros((k, 1, tt), jnp.int32)
    for _ in range(depth):
        bit = jnp.sum(jnp.where(iota == node, bits, 0), axis=1, keepdims=True)
        node = 2 * node + 1 + bit
    onehot = (iota == node - (c - 1)).astype(jnp.float32).reshape(kc, tt)
    return jax.lax.dot_general(                    # [W, Tt]: Σ_k lut[k, leaf]
        tab, onehot, (((1,), (0,)), ((), ())), precision=_HIGHEST,
        preferred_element_type=jnp.float32)


def _stack_layer_lookup(h, sel, thr, tab, *, depth: int):
    """Interpreter form of :func:`_stack_layer_mxu`: walk only the visited
    nodes with gathers, then gather-sum the leaf columns."""
    kc, tt = sel.shape[0], h.shape[1]
    c = 1 << depth
    k = kc // c
    feat = jnp.argmax(sel, axis=1).astype(jnp.int32)          # [K·C]
    thr = thr[:, 0]
    base = (jnp.arange(k, dtype=jnp.int32) * c)[:, None]      # [K, 1]
    node = jnp.zeros((k, tt), jnp.int32)
    for _ in range(depth):
        idx = node + base
        val = jnp.take_along_axis(h, jnp.take(feat, idx), axis=0)
        node = 2 * node + 1 + (val > jnp.take(thr, idx)).astype(jnp.int32)
    rows = jnp.take(tab, node - (c - 1) + base, axis=1)        # [W, K, Tt]
    # group by group, so no row's sum depends on the tile width
    return sum(rows[:, g] for g in range(k))                   # [W, Tt]


def _stack_layers(h, sel_ref, thr_ref, lut_ref, bias_ref, scale_ref, *,
                  depth: int, strategy: str):
    """Run the whole bank stack over one batch tile, all in VMEM: each
    bank's output rows are the next bank's input rows. ``scale_ref`` (q8
    path) dequantizes each layer's int8 table in-VMEM — the per-group
    factor is constant within a group, so folding it into the table is
    exact; ``None`` on the fp path."""
    layer = _stack_layer_mxu if strategy == "mxu" else _stack_layer_lookup
    for l in range(sel_ref.shape[0]):
        tab = lut_ref[l].astype(jnp.float32)               # [W, K·C]
        if scale_ref is not None:
            tab = tab * scale_ref[l]                       # [1, K·C] rows
        h = layer(h, sel_ref[l], thr_ref[l], tab, depth=depth) + bias_ref[l]
    return h


def fuzzy_lut_stack_kernel(x_ref, sel_ref, thr_ref, lut_ref, bias_ref,
                           out_ref, *, depth: int, strategy: str):
    """One batch tile through ALL L fused banks (fp32 LUT stack)."""
    out_ref[...] = _stack_layers(
        x_ref[...], sel_ref, thr_ref, lut_ref, bias_ref, None,
        depth=depth, strategy=strategy)


def _whole_block(ndim: int):
    """Index map of an operand that rides whole in every grid step."""
    return lambda i: (0,) * ndim


def stack_call(kernel, x, operands, *, depth: int, ks: tuple[int, ...],
               n_out: int, block_t: int | None, interpret: bool | None,
               strategy: str, where: str) -> jax.Array:
    """Shared launcher of the fp32 and int8 stacked kernels: validates the
    stack against ``ks``, transposes the ``[T, ks[0]·v]`` batch into the
    ``[W, T]`` layout, tiles the batch, and returns ``[T, n_out]``."""
    if interpret is None:
        interpret = default_interpret()
    strategy = resolve_strategy(strategy, interpret)
    sel = operands[0]
    nlayers, kc, w = sel.shape
    if len(ks) != nlayers:
        raise ValueError(f"ks has {len(ks)} entries for {nlayers} stacked layers")
    t, d0 = x.shape
    if d0 > w or kc % (1 << depth) or max(ks) > kc >> depth:
        raise ValueError(
            f"{where}: x carries {d0} features and ks={ks}, but the stack "
            f"holds {kc} tree nodes over {w} activation rows per layer")
    bt = min(stack_block_t(kc) if block_t is None else block_t, t)
    _check_divisible(where, T=(t, bt))

    whole = [pl.BlockSpec(op.shape, _whole_block(op.ndim)) for op in operands]
    xt = jnp.pad(x.astype(jnp.float32).T, ((0, w - d0), (0, 0)))
    out = pl.pallas_call(
        functools.partial(kernel, depth=depth, strategy=strategy),
        grid=(t // bt,),
        in_specs=[pl.BlockSpec((w, bt), lambda i: (0, i))] + whole,
        out_specs=pl.BlockSpec((w, bt), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((w, t), jnp.float32),
        compiler_params=_tpu_compiler_params(("parallel",)),
        interpret=interpret,
    )(xt, *operands)
    return out[:n_out].T


@functools.partial(
    jax.jit,
    static_argnames=("depth", "ks", "n_out", "block_t", "interpret",
                     "strategy"),
)
def fuzzy_lut_stack_pallas(
    x: jax.Array,          # [T, ks[0]·v]
    sel: jax.Array,        # [L, K·C, W] one-hot feature select (stack_layout)
    thr: jax.Array,        # [L, K·C, 1]
    lut: jax.Array,        # [L, W, K·C] f32, transposed
    bias: jax.Array,       # [L, W, 1] (zeros where a bank has no bias)
    *,
    depth: int,
    ks: tuple[int, ...],   # true group count per layer (≤ K)
    n_out: int,            # true out_features of the LAST layer (≤ W)
    block_t: int | None = None,
    interpret: bool | None = None,
    strategy: str = "auto",
) -> jax.Array:
    """Cross-bank Primitive Fusion: L banks, ONE ``pallas_call``.

    Returns ``[T, n_out]`` f32 — bias already applied (it must be: every
    non-final layer's bias feeds the next layer's tree descent in-VMEM).
    Operands come from :func:`stack_layout` at plan build; only the batch
    axis is tiled (``block_t`` defaults to :func:`stack_block_t`), so T is
    the only dim with a divisibility constraint (``ValueError`` otherwise —
    catchable, see module docstring).
    """
    return stack_call(fuzzy_lut_stack_kernel, x, (sel, thr, lut, bias),
                      depth=depth, ks=ks, n_out=n_out, block_t=block_t,
                      interpret=interpret, strategy=strategy,
                      where="fuzzy_lut_stack_pallas")
