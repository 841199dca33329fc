"""Pallas TPU kernel: fused fuzzy-index + LUT accumulate (Pegasus Map+SumReduce).

TPU-native realization of the paper's table lookup:

  * The clustering-tree descent is *gather-free*: feature selection becomes a
    one-hot matmul (the one-hot is precomputed offline), node selection a
    one-hot reduction over only the current level's nodes (a static window
    of whole 8-row tiles, :func:`descent_windows`) — every step is VPU
    compare/select or MXU matmul, so the whole "fuzzy match" is branchless
    and systolic-friendly.
  * The Map (leaf→row lookup) + SumReduce (Σ over groups) pair is ONE MXU
    matmul: ``LUT^T: [W, K·C] @ onehot(leaf): [K·C, Tt]`` — the same
    primitive-fusion insight as the paper's, re-expressed for a systolic
    array instead of a MAT stage.

Every matmul that carries a value (an activation or a LUT entry) runs at
``Precision.HIGHEST``: a one-hot select must return the activation exactly,
or a tree decision next to its threshold flips.

Both kernels work in one *transposed, lane-dense* layout that
:func:`stack_layout` builds once at plan build: flows on lanes, features and
tree nodes on sublanes. The per-group tree walk then splits only the sublane
axis (``[K·C, Tt] → [K, C, Tt]``), which Mosaic lays out tile for tile, and
nothing is relaid out inside a kernel. Per bank:

    x^T      [W, T]        flows on lanes
    sel      [K·C, W]      one-hot: node (k, n) reads feature k·v+f
    thr      [K·C, 1]      +inf on the padding node n = C-1
    lut^T    [W, K·C]      f32, or int8 with scales [1, K·C]
    bias     [W, 1]
    out^T    [W, T]

``W`` (the activation width, a multiple of 8) covers the bank's input and
output. Padded groups hold +inf thresholds and zero LUT rows, so they
descend to leaf 0 and contribute nothing.

Single-bank tiling (:func:`fuzzy_lut_pallas`, operands from
:func:`bank_layout`):
  grid = (T/Tt, K/Kt);   groups innermost ("arbitrary") → out accumulates
    x^T      [W, T]      → block (W, Tt)        index (0, i)
    sel      [K·C, W]    → block (Kt·C, W)      index (g, 0)
    thr      [K·C, 1]    → block (Kt·C, 1)      index (g, 0)
    lut^T    [W, K·C]    → block (W, Kt·C)      index (0, g)
    bias     [W, 1]      → whole, added after the last group tile
    out^T    [W, T]      → block (W, Tt)        index (0, i)

``(Tt, Kt)`` follow from the bank's shape (:func:`bank_tiles`): the stacked
kernel's batch tile, and as many groups per step as keep each ``[Kt·C, Tt]``
f32 descent intermediate within :data:`STACK_TILE_BYTES`. The launcher pads
the batch to a tile multiple itself (a batch under the tile to whole
128-lane tiles).

Stacked-layer variant (:func:`fuzzy_lut_stack_pallas` — Cross-bank Primitive
Fusion): a compatible run of L banks executes as ONE kernel invocation. The
grid tiles ONLY the batch; every per-layer operand rides whole (stacked along
a leading L axis), so the inter-bank activation never leaves VMEM, and the
between-bank re-partition is free — the next bank's block-diagonal one-hot
select reads its groups straight out of the previous bank's output rows:

  grid = (T/Tt,)
    x^T      [W, T]          → block (W, Tt)           index (0, i)
    sel      [L, K·C, W]     → whole
    thr      [L, K·C, 1]     → whole
    lut^T    [L, W, K·C]     → whole   f32, or int8 with scales [L, 1, K·C]
    bias     [L, W, 1]       → whole
    out^T    [W, T]          → block (W, Tt)           index (0, i)

There ``W`` covers the stack's widest input and output, and the batch tile
(:func:`stack_block_t`) keeps the ``[K·C, Tt]`` intermediates ≈1 MiB each.

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

__all__ = ["bank_layout", "bank_tiles", "default_interpret",
           "descent_windows", "fuzzy_lut_kernel", "fuzzy_lut_pallas",
           "fuzzy_lut_stack_pallas", "resolve_strategy", "stack_block_t",
           "stack_layout"]

# Byte budget of ONE [K·C, Tt] f32 descent intermediate (values, bits,
# one-hots) of either kernel; the batch and group tiles follow from it.
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


# ---------------------------------------------------------------------------
# Layout and tiles, built once at plan build
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


def bank_tiles(k: int, c: int) -> tuple[int, int]:
    """``(Tt, Kt)`` of the single-bank kernel for ``K`` groups of ``C`` tree
    nodes: the stacked kernel's batch tile (:func:`stack_block_t`), and the
    whole bank per step unless ``[K·C, Tt]`` outgrows
    :data:`STACK_TILE_BYTES` even at the 128-row floor — then the groups
    split evenly over the fewest steps that fit it, each step spanning a
    whole number of 128-lane tiles of nodes, as the ``[W, Kt·C]`` LUT block
    needs."""
    bt = stack_block_t(k * c)
    fit = max(1, STACK_TILE_BYTES // (4 * c * bt))
    if fit >= k:
        return bt, k
    steps = -(-k // fit)
    lanes = max(1, 128 // c)
    return bt, -(-k // (steps * lanes)) * lanes


@jax.jit
def bank_layout(feat_oh, thr, lut, bias, scales=None) -> tuple:
    """Plan-build layout of ONE bank for the single-bank kernels:
    :func:`stack_layout` with L = 1, the groups padded (+inf thresholds,
    zero LUT rows and scales) to a multiple of the group tile
    (:func:`bank_tiles`). One jitted computation per bank shape, so a
    plan build compiles a handful of them rather than every layout op.

    Takes ``feat_oh [K, I, v]``, ``thr [K, I]``, ``lut [K, C, N]`` (f32 or
    int8), ``bias [N]`` and, for int8, ``scales [K]``. Returns the kernel's
    operands in its argument order: ``(sel [K·C, W], thr [K·C, 1], lut_t
    [W, K·C], bias [W, 1])``, the scales as ``[1, K·C]`` before ``bias``
    when given.
    """
    k, c = lut.shape[:2]
    pad = (-k) % bank_tiles(k, c)[1]

    def groups(a, value=0):
        return jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1),
                       constant_values=value)[None]

    ops = (groups(feat_oh), groups(thr, jnp.inf), groups(lut), bias[None])
    if scales is not None:
        ops += (groups(scales),)
    out = tuple(a[0] for a in stack_layout(*ops))
    return out[:3] + out[4:] + out[3:4]


# ---------------------------------------------------------------------------
# Kernel bodies
# ---------------------------------------------------------------------------


def descent_windows(depth: int) -> tuple[tuple[int, int], ...]:
    """Static row window ``(lo, hi)`` of each level of a heap-ordered tree
    of depth ``depth`` over ``C = 2**depth`` node rows: level ``l``'s nodes
    ``2**l - 1 … 2**(l+1) - 2``, rounded out to whole 8-row sublane tiles
    and clamped to C. At depth 8 the descent touches 312 of 8 × 256 rows."""
    c = 1 << depth
    return tuple(((2 ** level - 1) // 8 * 8,
                  min(c, -(-(2 ** (level + 1) - 1) // 8) * 8))
                 for level in range(depth))


def _stack_layer_mxu(h, sel, thr, tab, *, depth: int):
    """One bank in the transposed layout, gather-free: ``h [W, Tt]`` →
    ``[W, Tt]`` contributions (bias not yet added). Each level's
    compare-select-sum reads only that level's window of node rows
    (:func:`descent_windows`); ``node`` stays the absolute heap index."""
    kc, tt = sel.shape[0], h.shape[1]
    c = 1 << depth
    k = kc // c
    vals = jax.lax.dot_general(                    # [K·C, Tt]: x at each node
        sel, h, (((1,), (0,)), ((), ())), precision=_HIGHEST,
        preferred_element_type=jnp.float32)
    bits = (vals > thr).astype(jnp.int32).reshape(k, c, tt)
    node = jnp.zeros((k, 1, tt), jnp.int32)
    for lo, hi in descent_windows(depth):
        rows = jax.lax.broadcasted_iota(jnp.int32, (k, hi - lo, tt), 1)
        bit = jnp.sum(jnp.where(rows == node - lo, bits[:, lo:hi], 0),
                      axis=1, keepdims=True)
        node = 2 * node + 1 + bit
    iota = jax.lax.broadcasted_iota(jnp.int32, (k, c, tt), 1)
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


def _bank_step(x_ref, sel_ref, thr_ref, lut_ref, scale_ref, bias_ref,
               out_ref, *, depth: int, strategy: str):
    """One (batch tile, group tile) step of a single bank: the group tiles'
    contributions accumulate in the resident ``[W, Tt]`` output block, and
    the bias joins after the last of them. ``scale_ref`` dequantizes the
    int8 table as in :func:`_stack_layers`; ``None`` on the fp path."""
    layer = _stack_layer_mxu if strategy == "mxu" else _stack_layer_lookup
    tab = lut_ref[...].astype(jnp.float32)                 # [W, Kt·C]
    if scale_ref is not None:
        tab = tab * scale_ref[...]                         # [1, Kt·C] row
    contrib = layer(x_ref[...], sel_ref[...], thr_ref[...], tab, depth=depth)
    g = pl.program_id(1)

    @pl.when(g == 0)
    def _init():
        out_ref[...] = contrib

    @pl.when(g != 0)
    def _accum():
        out_ref[...] += contrib

    @pl.when(g == pl.num_programs(1) - 1)
    def _bias():
        out_ref[...] += bias_ref[...]


def fuzzy_lut_kernel(x_ref, sel_ref, thr_ref, lut_ref, bias_ref, out_ref, *,
                     depth: int, strategy: str):
    """One (batch tile, group tile) step of the fp32 single-bank kernel."""
    _bank_step(x_ref, sel_ref, thr_ref, lut_ref, None, bias_ref, out_ref,
               depth=depth, strategy=strategy)


# ---------------------------------------------------------------------------
# Launchers
# ---------------------------------------------------------------------------


def _whole_block(ndim: int):
    """Index map of an operand that rides whole in every grid step."""
    return lambda *_: (0,) * ndim


def _transposed_call(kernel, x, operands, in_specs, *, w: int, bt: int,
                     grid: tuple[int, ...], semantics: tuple[str, ...],
                     n_out: int, interpret: bool) -> jax.Array:
    """Transpose the ``[T, d]`` batch into ``[W, T]`` rows, padded to a
    whole number of ``bt``-lane tiles; run ``kernel`` over ``grid`` with
    the batch tile as grid axis 0; return ``[T, n_out]``."""
    t, d0 = x.shape
    tp = -(-t // bt) * bt
    xt = jnp.pad(x.astype(jnp.float32).T, ((0, w - d0), (0, tp - t)))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((w, bt), lambda i, *_: (0, i))] + in_specs,
        out_specs=pl.BlockSpec((w, bt), lambda i, *_: (0, i)),
        out_shape=jax.ShapeDtypeStruct((w, tp), jnp.float32),
        compiler_params=_tpu_compiler_params(semantics),
        interpret=interpret,
    )(xt, *operands)
    return out[:n_out, :t].T


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
    return _transposed_call(
        functools.partial(kernel, depth=depth, strategy=strategy), x,
        operands, whole, w=w, bt=bt, grid=(t // bt,), semantics=("parallel",),
        n_out=n_out, interpret=interpret)


def bank_call(kernel, x, operands, *, depth: int, n_out: int,
              interpret: bool | None, strategy: str, where: str) -> jax.Array:
    """Shared launcher of the fp32 and int8 single-bank kernels. Operands
    come from :func:`bank_layout`: ``(sel, thr, lut_t, bias)``, the int8
    scales between ``lut_t`` and ``bias``. Transposes the ``[T, K·v]``
    batch into the ``[W, T]`` layout, tiles it by :func:`bank_tiles`
    (padding the batch to the tile, or to whole 128-lane tiles under it),
    accumulates over group tiles, and returns ``[T, n_out]`` with the bias
    applied."""
    if interpret is None:
        interpret = default_interpret()
    strategy = resolve_strategy(strategy, interpret)
    sel, thr, *tables, bias = operands
    kc, w = sel.shape
    c = 1 << depth
    t, d0 = x.shape
    if d0 > w or n_out > w or kc % c:
        raise ValueError(
            f"{where}: x carries {d0} features and n_out={n_out}, but the "
            f"bank holds {kc} tree nodes of depth {depth} over {w} "
            "activation rows")
    bt, kt = bank_tiles(kc // c, c)
    _check_divisible(where, K=(kc // c, kt))
    nodes = kt * c
    specs = ([pl.BlockSpec((nodes, op.shape[1]), lambda i, g: (g, 0))
              for op in (sel, thr)]
             + [pl.BlockSpec((op.shape[0], nodes), lambda i, g: (0, g))
                for op in tables]
             + [pl.BlockSpec(bias.shape, _whole_block(2))])
    # a batch under the tile runs as whole 128-lane tiles, the width a vreg
    # holds anyway: so a flow's sums never depend on how many flows share
    # its call (a sharded plan's per-device slice included)
    bt = min(bt, -(-t // 128) * 128)
    return _transposed_call(
        functools.partial(kernel, depth=depth, strategy=strategy), x,
        operands, specs, w=w, bt=bt, grid=(-(-t // bt), kc // nodes),
        semantics=("parallel", "arbitrary"), n_out=n_out,
        interpret=interpret)


@functools.partial(
    jax.jit, static_argnames=("depth", "n_out", "interpret", "strategy"))
def fuzzy_lut_pallas(
    x: jax.Array,          # [T, K·v]
    sel: jax.Array,        # [K·C, W] one-hot feature select (bank_layout)
    thr: jax.Array,        # [K·C, 1]
    lut: jax.Array,        # [W, K·C] f32, transposed
    bias: jax.Array,       # [W, 1] (zeros where the bank has no bias)
    *,
    depth: int,
    n_out: int,            # true out_features (≤ W)
    interpret: bool | None = None,
    strategy: str = "auto",
) -> jax.Array:
    """One Pegasus bank, ONE ``pallas_call``: returns ``[T, n_out]`` f32,
    bias applied.

    Fully traceable inside an outer ``jax.jit`` (the engine jits whole-plan
    forwards through here); ``interpret`` and ``strategy`` are static args,
    ``None``/``"auto"`` resolve via :func:`default_interpret` /
    :func:`resolve_strategy`. Tiles follow from the operand shapes
    (:func:`bank_tiles`); groups not padded to the group tile raise
    ``ValueError``.
    """
    return bank_call(fuzzy_lut_kernel, x, (sel, thr, lut, bias), depth=depth,
                     n_out=n_out, interpret=interpret, strategy=strategy,
                     where="fuzzy_lut_pallas")


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
