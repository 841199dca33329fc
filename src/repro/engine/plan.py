"""ExecutionPlan: compile a pegasusified model once, call it many times.

The hand-rolled apply paths in ``repro.nets.*`` re-derived the kernel layout
on every invocation — feature one-hots, block padding, and (for the q8 path)
int8 quantization of the whole LUT bank. Quark-style all-on-dataplane designs
and FENIX's offload pipeline both treat that state as *precompiled*; this
module does the same for the TPU realization:

  * :class:`CompiledBank` — one ``PegasusLinear`` plus every tensor the fused
    Pallas kernel needs, built exactly once in the kernel's transposed layout
    (one-hot feature select, +inf-padded thresholds, LUT, int8 LUT +
    per-group scales). Registered as a jax pytree so a whole plan's banks
    flow through ``jax.jit`` as traced state rather than baked-in constants.
  * :class:`FusedBankStack` / :func:`fuse_banks` — Cross-bank Primitive
    Fusion: a maximal run of shape-compatible consecutive banks (same group
    width and centroid count, each bank's output feeding the next's input)
    compiles into ONE stacked Pallas kernel invocation
    (``fuzzy_lut_stack_pallas`` / ``..._q8``) — operands stacked into the
    kernel's transposed ``[L, W, Kmax·C]`` layout at plan build, the
    inter-bank re-partition + bias (+ q8 dequant) folded into the kernel
    loop so activations never leave VMEM between banks. Incompatible runs, the ``gather``/``onehot``
    backends, and the RNN/CNN structural steps fall back to the per-bank
    path; ``fuse=False`` on :func:`build_plan` disables grouping entirely
    (the fusion config participates in plan_for's memo key).
  * :class:`ExecutionPlan` — the whole model: compiled banks + a structural
    forward (sequential stack, windowed CNN, unrolled RNN, two-level NAM)
    that is a *pure function* of ``(state, inputs)`` closed over static
    shapes, so the entire forward traces into ONE jitted XLA computation per
    ``(backend, batch-bucket)``.
  * **Batch bucketing** — request batches are zero-padded up to a bounded
    set of bucket sizes (powers of two by default, multiples of the largest
    bucket beyond it), so varying request sizes hit a warm compile cache
    instead of retracing per shape. ``EngineStats.jit_traces`` counts actual
    XLA traces; the compile-count tests pin the invariants.
  * :func:`build_plan` — compile a model into a plan. Memoization lives in
    :mod:`repro.engine.registry` (:class:`PlanRegistry` / :func:`plan_for`):
    weakref-watched, bounded, explicitly evictable entries. To support that,
    a plan holds a *detached replica* of each bank layer (same arrays, new
    dataclass instance) — compiling a model never pins the caller's model
    objects, so dropping the model lets the registry reclaim its plan.

Backends are semantics-identical up to quantization:
  ``gather``    — take_along_axis reference (XLA)
  ``onehot``    — one-hot × LUT matmul (MXU-friendly XLA)
  ``kernel``    — fused Pallas fuzzy-LUT kernel
  ``kernel_q8`` — fused Pallas kernel over the cached int8 LUT
"""

from __future__ import annotations

import dataclasses
import threading
import warnings
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.analysis.sanitizer import make_lock

from repro.core.amm import PegasusLinear, apply_gather, apply_onehot
from repro.core.fuzzy_tree import hard_index
from repro.kernels.fuzzy_lut.kernel import (
    bank_tiles,
    default_interpret,
    fuzzy_lut_pallas,
    fuzzy_lut_stack_pallas,
    resolve_strategy,
    stack_block_t,
    stack_layout,
)
from repro.kernels.fuzzy_lut.ops import (
    bank_operands,
    prepare_feat_onehot,
    quantized_lut_cached,
)
from repro.kernels.fuzzy_lut.quantized import (
    fuzzy_lut_q8_pallas,
    fuzzy_lut_stack_q8_pallas,
)

__all__ = [
    "BACKENDS",
    "DEFAULT_BUCKETS",
    "DEFAULT_FUSE_NMAX_CAP",
    "STATS",
    "CompiledBank",
    "EngineStats",
    "ExecutionPlan",
    "FusedBankStack",
    "bucket_batch",
    "bucket_chunks",
    "build_plan",
    "fuse_banks",
    "resolve_devices",
]

# Per-group cap on a fused stack's padded output width: the stacked LUTs
# are [L, W, Kmax·C] with W ≥ Nmax, so one wide bank joining a narrow run
# multiplies EVERY member's LUT (and the kernel's VMEM working set) by Nmax/N.
# Groups split rather than pad past this; equal-width wide banks may still
# fuse above it because they add no padding (see fuse_banks). 2048 clears
# every paper-scale head (N ≤ a few hundred) while bounding worst-case
# stack VMEM to a few MiB at C=32.
DEFAULT_FUSE_NMAX_CAP = 2048

BACKENDS = ("gather", "onehot", "kernel", "kernel_q8")

# The jitted forwards donate their (plan-owned) input buffers so XLA may
# recycle the storage. When a model's output is smaller than its input —
# most classifiers — no alias exists and jax warns per executable; that is
# the expected shape here, not an error worth one warning per compile.
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")

# Bounded bucket set: odd batch sizes round UP to the nearest bucket (zero
# rows are sliced off after the call), so the jit cache holds at most
# ``len(DEFAULT_BUCKETS)`` entries per backend for any batch ≤ the largest
# bucket; beyond it, batches round to multiples of the largest bucket.
DEFAULT_BUCKETS: tuple[int, ...] = (8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096)


def bucket_batch(b: int, buckets: Sequence[int] = DEFAULT_BUCKETS) -> int:
    """Round a batch size up to its compile bucket (smallest bucket ≥ b;
    beyond the largest, the next multiple of it)."""
    if b <= 0:
        raise ValueError(f"batch must be positive, got {b}")
    for s in sorted(buckets):
        if b <= s:
            return int(s)
    top = int(max(buckets))
    return -(-b // top) * top


def bucket_chunks(
    total: int,
    buckets: Sequence[int] = DEFAULT_BUCKETS,
    max_batch: int | None = None,
) -> list[int]:
    """Split ``total`` coalesced flows into bucket-aligned micro-batch sizes.

    Full chunks are exact bucket sizes (zero pad rows); the tail dispatches
    either as one padded chunk or as an exact bucket plus a smaller padded
    chunk — whichever wastes fewer padded rows. This replaces fixed-stride
    chunking (the old ``max_batch=1024`` slicing), which ignored the bucket
    ladder and could split a 2048-flow batch that has its own exact bucket.
    """
    if total <= 0:
        raise ValueError(f"total must be positive, got {total}")
    bs = sorted(int(b) for b in buckets)
    if max_batch is None:
        top = bs[-1]
    else:
        fits = [b for b in bs if b <= max_batch]
        # max_batch below the smallest bucket cannot bound anything: every
        # dispatch pads up to bs[0] anyway, so sub-bucket chunking would
        # only multiply padded work — clamp to one smallest-bucket chunk
        top = fits[-1] if fits else bs[0]
    sizes = []
    remaining = total
    while remaining > top:
        sizes.append(top)
        remaining -= top
    if remaining:
        fit = max((b for b in bs if b <= remaining), default=0)
        if 0 < fit < remaining:
            pad_whole = bucket_batch(remaining, bs) - remaining
            rest = remaining - fit
            pad_split = bucket_batch(rest, bs) - rest
            if pad_split < pad_whole:
                sizes.append(fit)
                remaining = rest
        sizes.append(remaining)
    return sizes


@dataclasses.dataclass
class EngineStats:
    """Global counters — the parity/caching tests assert layout work happens
    at plan-build time only, and whole-plan XLA traces happen at most once
    per (backend, batch-bucket), never per call."""

    layout_builds: int = 0   # CompiledBank layout preparations
    plan_builds: int = 0     # ExecutionPlan compilations
    plan_cache_hits: int = 0  # plan_for() served from the memo
    bank_calls: int = 0      # CompiledBank.apply invocations (eager or trace)
    jit_traces: int = 0      # whole-plan forward traces (one per compile)
    jit_calls: int = 0       # jitted plan dispatches (hits = calls - traces)

    def reset(self) -> None:
        self.layout_builds = 0
        self.plan_builds = 0
        self.plan_cache_hits = 0
        self.bank_calls = 0
        self.jit_traces = 0
        self.jit_calls = 0

    def snapshot(self) -> dict:
        return dataclasses.asdict(self)


STATS = EngineStats()


def _pad_to(x: jax.Array, axis: int, mult: int, value: float = 0.0) -> jax.Array:
    size = x.shape[axis]
    rem = (-size) % mult
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad, constant_values=value)


@jax.tree_util.register_pytree_node_class
class CompiledBank:
    """One PegasusLinear with its kernel layout precomputed and frozen.

    All layout work (one-hot of split features, the single-bank kernel's
    transposed ``[W, K·C]`` layout with +inf thresholds and zero LUT rows
    on padded groups, int8 quantization + scales) happens in ``__init__``
    (:func:`~repro.kernels.fuzzy_lut.ops.bank_operands`); ``apply`` only
    hands the activations to the kernel, whose launcher transposes and
    pads them. The batch and group tiles follow from the bank's shape
    (:attr:`tiles`).

    Pytree protocol: the tensors are leaves, the static kernel config is
    aux data — so banks can ride through ``jax.jit`` as arguments (shared
    across every compiled bucket) instead of being re-embedded as XLA
    constants in each executable.

    ``self.layer`` is a *detached replica* of the source layer (same arrays,
    fresh dataclass instance): a compiled bank must never pin the caller's
    model object, or the registry's drop-the-model-evict-the-plan weakref
    scheme could never fire (the registry keeps its own weakrefs to the
    source layers for staleness checks).
    """

    def __init__(
        self,
        layer: PegasusLinear,
        *,
        interpret: bool | None = None,
        strategy: str = "auto",
    ):
        # q8 memo keyed on the ORIGINAL layer id (shared across rebuilds of
        # the same model); the replica below is what the bank retains.
        lut_q8, scales = quantized_lut_cached(layer)
        self.layer = dataclasses.replace(layer)
        self.interpret = default_interpret() if interpret is None else interpret
        self.strategy = resolve_strategy(strategy, self.interpret)
        self.depth = int(np.log2(layer.num_centroids) + 0.5)

        # -- layout prep: done ONCE here, never on the call path -----------
        self.sel, self.thr, self.lut, self.bias = bank_operands(
            layer, layer.lut)
        self.lut_q8, self.scales = bank_operands(layer, lut_q8, scales)[2:4]
        STATS.layout_builds += 1

    @property
    def tiles(self) -> tuple[int, int]:
        """(batch tile, group tile) the single-bank kernel runs with."""
        c = self.layer.num_centroids
        return bank_tiles(self.sel.shape[0] // c, c)

    def tables(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """The f32 LUT and int8 LUT ``[K, C, N]`` and the per-group scales
        ``[K]``, read back out of the kernel layout at true size (host
        numpy; no requantization)."""
        p = self.layer
        k, c, n = p.num_groups, p.num_centroids, p.out_features
        lut, lut_q8 = (np.asarray(t)[:n, :k * c].T.reshape(k, c, n)
                       for t in (self.lut, self.lut_q8))
        return lut, lut_q8, np.asarray(self.scales)[0, :k * c:c]

    # -- pytree protocol ----------------------------------------------------

    def tree_flatten(self):
        children = (self.layer, self.sel, self.thr, self.lut, self.lut_q8,
                    self.scales, self.bias)
        aux = (self.depth, self.interpret, self.strategy)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        # bypass __init__: no layout work, no STATS increment — this path
        # runs on every jit flatten/unflatten round-trip
        obj = object.__new__(cls)
        (obj.layer, obj.sel, obj.thr, obj.lut, obj.lut_q8, obj.scales,
         obj.bias) = children
        obj.depth, obj.interpret, obj.strategy = aux
        return obj

    # -- backend dispatch ---------------------------------------------------

    def apply(self, x: jax.Array, backend: str) -> jax.Array:
        STATS.bank_calls += 1
        if backend == "gather":
            return apply_gather(self.layer, x)
        if backend == "onehot":
            return apply_onehot(self.layer, x)
        if backend not in ("kernel", "kernel_q8"):
            raise ValueError(
                f"unknown backend {backend!r}; expected one of {BACKENDS}")
        n = self.layer.out_features
        xf = x.reshape(-1, x.shape[-1])
        kw = dict(depth=self.depth, n_out=n, interpret=self.interpret,
                  strategy=self.strategy)
        if backend == "kernel":
            y = fuzzy_lut_pallas(xf, self.sel, self.thr, self.lut, self.bias,
                                 **kw)
        else:
            y = fuzzy_lut_q8_pallas(xf, self.sel, self.thr, self.lut_q8,
                                    self.scales, self.bias, **kw)
        return y.reshape(*x.shape[:-1], n)


# ---------------------------------------------------------------------------
# Cross-bank Primitive Fusion: compatible consecutive banks → one kernel
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
class FusedBankStack:
    """A run of L shape-compatible banks compiled into ONE stacked kernel.

    Operand stacks are built once here (plan build): each bank's true-size
    tensors are padded to the group's ``(Kmax, Nmax)`` — +inf thresholds and
    zero LUT rows on padded groups descend to leaf 0 and contribute nothing —
    then stacked along a leading L axis. On the ``kernel``/``kernel_q8``
    backends ``apply`` dispatches ``fuzzy_lut_stack_pallas`` /
    ``..._q8`` (re-partition, bias, and dequant all inside the kernel loop);
    ``gather``/``onehot`` and any stack the kernel rejects (``ValueError``
    on a mis-padded operand) fall back to the per-bank chain, which is
    semantics-identical.

    The member banks stay whole inside the stack (pytree children), so the
    fallback chain, ``plan.bank_inputs`` and the per-bank parity tests keep
    working on fused plans.
    """

    def __init__(self, banks: Sequence["CompiledBank"]):
        if len(banks) < 2:
            raise ValueError("a fused stack needs at least 2 banks")
        for a, b in zip(banks, banks[1:]):
            if not _fusable(a, b):
                raise ValueError("banks are not shape-compatible for fusion")
        self.banks = list(banks)
        layers = [b.layer for b in banks]
        self.v = layers[0].group_size
        self.depth = banks[0].depth
        self.ks = tuple(l.num_groups for l in layers)
        self.n_out = layers[-1].out_features
        self.interpret = banks[0].interpret
        self.strategy = banks[0].strategy

        kmax = max(self.ks)
        nmax = max(l.out_features for l in layers)
        c = layers[0].num_centroids
        i = c - 1
        feat_oh = jnp.zeros((len(layers), kmax, i, self.v), jnp.float32)
        thr = jnp.full((len(layers), kmax, i), jnp.inf, jnp.float32)
        lut = jnp.zeros((len(layers), kmax, c, nmax), jnp.float32)
        lut_q8 = jnp.zeros((len(layers), kmax, c, nmax), jnp.int8)
        scales = jnp.zeros((len(layers), kmax), jnp.float32)
        bias = jnp.zeros((len(layers), nmax), jnp.float32)
        for l, bank in enumerate(banks):
            p = bank.layer
            k, n = p.num_groups, p.out_features
            # true-size tables padded to the GROUP geometry — no new
            # quantization: the int8 rows are read back out of the bank
            _, q8, sc = bank.tables()
            feat_oh = feat_oh.at[l, :k].set(
                prepare_feat_onehot(p.trees.features, self.v))
            thr = thr.at[l, :k].set(p.trees.thresholds)
            lut = lut.at[l, :k, :, :n].set(p.lut)
            lut_q8 = lut_q8.at[l, :k, :, :n].set(q8)
            scales = scales.at[l, :k].set(sc)
            if p.bias is not None:
                bias = bias.at[l, :n].set(p.bias)
        # the kernel's transposed layout, built once here (stack_layout)
        self.sel, self.thr, self.lut, self.bias, self.scales = stack_layout(
            feat_oh, thr, lut, bias, scales)
        self.lut_q8 = stack_layout(feat_oh, thr, lut_q8, bias)[2]
        self.width = int(self.lut.shape[1])      # padded activation rows W
        self.block_t = stack_block_t(kmax * c)
        STATS.layout_builds += 1

    # -- pytree protocol ----------------------------------------------------

    def tree_flatten(self):
        children = (tuple(self.banks), self.sel, self.thr, self.lut,
                    self.lut_q8, self.scales, self.bias)
        aux = (self.ks, self.v, self.depth, self.n_out, self.width,
               self.block_t, self.interpret, self.strategy)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        obj = object.__new__(cls)
        (banks, obj.sel, obj.thr, obj.lut,
         obj.lut_q8, obj.scales, obj.bias) = children
        obj.banks = list(banks)
        (obj.ks, obj.v, obj.depth, obj.n_out, obj.width, obj.block_t,
         obj.interpret, obj.strategy) = aux
        return obj

    # -- dispatch -----------------------------------------------------------

    def _per_bank(self, x: jax.Array, backend: str) -> jax.Array:
        h = x
        for bank in self.banks:
            h = bank.apply(h, backend)
        return h

    def apply(self, x: jax.Array, backend: str) -> jax.Array:
        if backend not in ("kernel", "kernel_q8"):
            return self._per_bank(x, backend)
        lead = x.shape[:-1]
        xf = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
        t = xf.shape[0]
        bt = min(self.block_t, t)
        xf = _pad_to(xf, 0, bt)
        kw = dict(depth=self.depth, ks=self.ks, n_out=self.n_out, block_t=bt,
                  interpret=self.interpret, strategy=self.strategy)
        try:
            if backend == "kernel":
                y = fuzzy_lut_stack_pallas(
                    xf, self.sel, self.thr, self.lut, self.bias, **kw)
            else:
                y = fuzzy_lut_stack_q8_pallas(
                    xf, self.sel, self.thr, self.lut_q8, self.scales,
                    self.bias, **kw)
        except ValueError:
            # mis-padded operand stack (e.g. hand-built): the kernel refuses
            # loudly and the per-bank chain serves the call (and does its own
            # bank_calls accounting)
            return self._per_bank(x, backend)
        STATS.bank_calls += len(self.banks)   # fused path only: no double count
        return y[:t].reshape(*lead, self.n_out)


def _fusable(a: CompiledBank, b: CompiledBank) -> bool:
    """Can bank ``b`` consume bank ``a``'s output inside one stacked kernel?
    Same partition width and centroid count (stacked operands must share
    (v, C)), exact output→input chaining, and identical static kernel
    config."""
    return (a.layer.group_size == b.layer.group_size
            and a.layer.num_centroids == b.layer.num_centroids
            and a.layer.out_features == b.layer.in_features
            and a.interpret == b.interpret
            and a.strategy == b.strategy)


def _balloons(run: Sequence[CompiledBank], bank: CompiledBank,
              nmax_cap: int | None) -> bool:
    """Would adding ``bank`` to ``run`` pad some member's output rows past
    ``nmax_cap``? The stacked operands share one Nmax = max(out_features),
    so a single wide bank balloons every narrow member's padded [C, Nmax]
    LUT slab. Equal-width banks above the cap are NOT a balloon (no padding
    is added), so uniformly-wide runs still fuse."""
    if nmax_cap is None:
        return False
    ns = [b.layer.out_features for b in run] + [bank.layer.out_features]
    nmax = max(ns)
    return nmax > nmax_cap and min(ns) < nmax


def fuse_banks(banks: Sequence[CompiledBank], *,
               nmax_cap: int | None = DEFAULT_FUSE_NMAX_CAP) -> list:
    """Plan-build fusion pass: group maximal runs of compatible consecutive
    banks into :class:`FusedBankStack` steps; lone banks pass through.

    ``nmax_cap`` bounds each group's padded output width (``None`` = no
    cap): a run splits rather than letting one wide bank balloon a narrow
    stack's ``[L, W, Kmax·C]`` VMEM footprint — the wide bank starts
    its own run (and may still fuse with equally-wide neighbors, which add
    no padding).

    Purely structural — the returned step list is what the sequential
    forward iterates, and each step exposes the same
    ``apply(x, backend)`` contract, so fusing never changes trace counts
    (the whole forward is still one jitted computation per bucket)."""
    steps: list = []
    run: list[CompiledBank] = []

    def flush():
        if len(run) >= 2:
            steps.append(FusedBankStack(run))
        else:
            steps.extend(run)
        run.clear()

    for bank in banks:
        if run and (not _fusable(run[-1], bank)
                    or _balloons(run, bank, nmax_cap)):
            flush()
        run.append(bank)
    flush()
    return steps


# ---------------------------------------------------------------------------
# ExecutionPlan + per-family structural forwards
# ---------------------------------------------------------------------------


def resolve_devices(devices) -> tuple | None:
    """Normalize a ``devices=`` knob into a canonical device tuple.

    Accepts ``None`` (single-device, the default), an int ``k`` (the first
    ``k`` of ``jax.devices()``), or a sequence of ``jax.Device`` objects /
    integer device ids. The canonical form — ``None`` or a tuple of
    ``jax.Device`` — is what participates in ``plan_for``'s memo key, so
    ``devices=2`` and ``devices=jax.devices()[:2]`` memo-hit the same plan.
    """
    if devices is None:
        return None
    if isinstance(devices, int):
        avail = jax.devices()
        if devices < 1:
            raise ValueError(f"devices must be ≥ 1, got {devices}")
        if devices > len(avail):
            raise ValueError(
                f"devices={devices} but only {len(avail)} jax devices are "
                "visible (simulate more CPU devices with "
                "XLA_FLAGS=--xla_force_host_platform_device_count=N)")
        return tuple(avail[:devices])
    avail = None
    out = []
    for d in devices:
        if isinstance(d, int):
            avail = jax.devices() if avail is None else avail
            out.append(avail[d])
        else:
            out.append(d)
    return tuple(out) or None


class _PlanCounters:
    """Per-plan trace instrumentation, held OUTSIDE the plan so the jitted
    forward's closure never references the plan itself (see ExecutionPlan).

    Guarded by a small lock: the async serving runtime may call one plan
    from the drain thread while ``infer()`` runs on another — the counter
    read-modify-writes must not lose updates."""

    __slots__ = ("traces", "traced_buckets", "rows", "lock")

    def __init__(self):
        self.traces = 0                               # guarded-by: lock
        # distinct (backend, bucket) pairs ever traced — named so the
        # guarded-by map cannot collide with ExecutionPlan.buckets,
        # the immutable bucket LADDER
        self.traced_buckets: set[tuple[str, int]] = set()  # guarded-by: lock
        # (backend, bucket) → [requested rows, dispatched (padded) rows]:
        # the pad_waste surface — what fraction of every bucket's compute
        # went to filler rows (ladder efficiency, reported by the bench and
        # MultiModelServer.stats()).
        self.rows: dict[tuple[str, int], list] = {}   # guarded-by: lock
        self.lock = make_lock("plan._ctr.lock")


class ExecutionPlan:
    """Compiled model: banks + structural forward, backend bound globally.

    The forward is a pure function ``forward(apply, state, *inputs)`` where
    ``state`` is a jax pytree (banks + any captured arrays) and every other
    degree of freedom (window length, NAM flag, block geometry, interpret
    mode) is a static Python value closed over at plan-build. ``__call__``
    pads the batch up to its bucket, dispatches the jitted forward, and
    slices the padding back off — so the whole model is ONE XLA computation
    per ``(backend, bucket)`` and repeated calls at any batch size that maps
    to a warm bucket perform zero Python-per-bank dispatch and zero retraces.

    **Multi-device execution** comes in two flavors:

      * ``devices=`` (build-time) — SHARDED mode: the whole-plan forward is
        wrapped in ``shard_map`` over a 1-D ``("batch",)`` mesh, the padded
        batch split evenly across the devices and the bank operands
        replicated (they are small — KiB of LUT per bank). One call spreads
        one big batch over every device; outputs are bit-exact with the
        single-device plan because every row's compute is independent of
        the batch partition. Every bucket size must divide evenly by the
        device count (the default power-of-two ladder accepts 2/4/8).
      * ``device=`` (call-time, single-device plans only) — PLACED mode:
        the padded inputs and a cached replica of the bank state are
        committed to one specific device and the call executes entirely
        there. This is what the serving runtime's per-device executor
        streams use: N placed plans run concurrently, one stream per
        device.
    """

    def __init__(
        self,
        banks: Sequence[CompiledBank],
        forward: Callable[..., jax.Array],
        state: Any,
        *,
        backend: str = "onehot",
        family: str = "sequential",
        bucket_sizes: Sequence[int] | None = None,
        devices=None,
    ):
        if backend not in BACKENDS:
            raise ValueError(f"unknown backend {backend!r}; expected one of {BACKENDS}")
        self.banks = list(banks)
        self._forward = forward
        self._state = state
        self.backend = backend
        self.family = family
        self.buckets = tuple(sorted(bucket_sizes)) if bucket_sizes else DEFAULT_BUCKETS
        self.devices = resolve_devices(devices)
        # PLACED mode: per-device replicas of the bank state, built lazily
        # on first use (cross-device copies of KiB-scale LUT tables)
        self._replicas: dict = {}                # guarded-by: _replica_lock
        self._replica_lock = make_lock("plan._replica_lock")
        # what an unplaced call reads (immutable after construction)
        self._call_state = state
        mesh = None
        if self.devices is not None and len(self.devices) > 1:
            bad = [b for b in self.buckets if b % len(self.devices)]
            if bad:
                raise ValueError(
                    f"bucket sizes {bad} are not divisible by the "
                    f"{len(self.devices)}-device mesh — every bucket is "
                    "split evenly across the batch axis (pass bucket_sizes "
                    "that the device count divides)")
            mesh = Mesh(np.asarray(self.devices), ("batch",))
            # bank state replicated on the mesh once, here: left on the
            # default device it would be re-broadcast from device 0 per call
            self._call_state = jax.device_put(
                state, NamedSharding(mesh, PartitionSpec()))
        self._mesh = mesh
        self._batch_sharding = (None if mesh is None else
                                NamedSharding(mesh, PartitionSpec("batch")))
        # compile-cache instrumentation (per plan; STATS mirrors globally).
        # The counters live in a detached holder: _pure must not close over
        # `self`, or plan ↔ jit-closure would form a reference cycle and an
        # evicted plan's executables/tensors would linger until a gen-2 GC
        # pass instead of freeing on the registry's refcount drop.
        self._ctr = ctr = _PlanCounters()
        self.jit_calls = 0
        # set by the family builders after construction (sequential/CNN runs
        # may compile FusedBankStack steps; other families stay per-bank)
        self.fused_groups = 0
        self.fused_banks = 0
        self.fused_stacks: list = []
        # build-time fusion knobs + audit report, recorded by build_plan so
        # the plan auditor (repro.analysis.planaudit) can explain WHY pairs
        # stayed unfused and stats() can surface finding counts
        self.fuse_cfg = {"fuse": True, "nmax_cap": DEFAULT_FUSE_NMAX_CAP}
        self.audit_report = None

        def _pure(state, inputs, backend):
            # body runs at TRACE time only — this is the retrace counter the
            # bucketing tests assert on. PG004 is right that these are
            # trace-time side effects; here that is the POINT (they fire
            # once per compile, never per call), so they stay, justified:
            # pegasus-lint: disable=PG004 intentional trace-counter (fires once per compile)
            STATS.jit_traces += 1
            # pegasus-lint: disable-block=PG004 intentional compile-cache instrumentation under the innermost lock
            with ctr.lock:
                ctr.traces += 1
                ctr.traced_buckets.add((backend, int(inputs[0].shape[0])))

            def run(state, inputs):
                return forward(
                    lambda bank, x: bank.apply(x, backend), state, *inputs)

            if mesh is None:
                return run(state, inputs)
            # SHARDED mode: batch axis split across the mesh, bank state
            # replicated (P() prefix-spec broadcasts over the whole state
            # pytree). Rows never interact, so no collectives — check_vma
            # is off because the Pallas calls carry no replication rules.
            return jax.shard_map(
                run, mesh=mesh,
                in_specs=(PartitionSpec(), PartitionSpec("batch")),
                out_specs=PartitionSpec("batch"),
                check_vma=False)(state, inputs)

        # inputs (arg 1) are DONATED: the bucket ladder hands the jitted
        # forward a padded buffer the plan itself owns, so XLA may reuse its
        # storage for intermediates/outputs instead of the old pad-then-copy
        # pair. __call__ guarantees every donated leaf is plan-owned
        # (_owned_padded) — a caller's array is never invalidated.
        self._jit = jax.jit(_pure, static_argnames=("backend",),
                            donate_argnums=(1,))
        STATS.plan_builds += 1

    @property
    def trace_count(self) -> int:
        with self._ctr.lock:
            return self._ctr.traces

    @property
    def compiled_buckets(self) -> set:
        # snapshot, not the live set: callers iterate it while the drain
        # thread may be tracing a new bucket (set mutation during iteration
        # raises); the stats/bugfix sweep moved this read under the lock
        with self._ctr.lock:
            return set(self._ctr.traced_buckets)

    def __call__(
        self, *inputs: jax.Array, backend: str | None = None,
        jit: bool = True, device=None,
    ) -> jax.Array:
        be = self.backend if backend is None else backend
        if be not in BACKENDS:
            raise ValueError(f"unknown backend {be!r}; expected one of {BACKENDS}")
        if device is not None and self._mesh is not None:
            raise ValueError(
                "this plan is sharded across a device mesh at build time "
                "(devices=); per-call device placement applies only to "
                "single-device plans")
        if not jit:
            return self._forward(
                lambda bank, x: bank.apply(x, be), self._state, *inputs)
        b = int(np.shape(inputs[0])[0])
        bucket = bucket_batch(b, self.buckets)
        target = self._batch_sharding if device is None else device
        with TraceAnnotation("plan.call"):
            padded = tuple(self._owned_padded(x, bucket, target)
                           for x in inputs)
            STATS.jit_calls += 1
            with self._ctr.lock:
                self.jit_calls += 1
                rows = self._ctr.rows.setdefault((be, bucket), [0, 0])
                rows[0] += b
                rows[1] += bucket
            y = self._jit(self._state_for(device), padded, backend=be)
            return y if bucket == b else y[:b]

    def lower(self, *inputs, backend: str | None = None):
        """Ahead-of-time lowering of the jitted forward for inputs already
        at a bucket size (arrays or ``jax.ShapeDtypeStruct``s), without
        running it: ``.compile().as_text()`` shows what the device executes
        (a compiled Pallas kernel appears as ``tpu_custom_call``)."""
        be = self.backend if backend is None else backend
        return self._jit.lower(self._state_for(None), tuple(inputs), backend=be)

    def _state_for(self, device):
        """The bank state a call reads: with ``device=None`` the plan's own
        (replicated on the mesh in SHARDED mode), otherwise the replica
        committed to ``device`` (built once per device). Placed calls pass
        the replica so every operand of the jitted forward lives on one
        device — mixed-device arguments are a jit error, and replicating
        KiB-scale LUT tables once is far cheaper than shipping them per
        call."""
        if device is None:
            return self._call_state
        with self._replica_lock:
            st = self._replicas.get(device)
        if st is None:
            # device_put OUTSIDE the lock (PG001): a cross-device copy must
            # not stall concurrent placed calls to other devices. Racing
            # builders both pay the copy once; setdefault keeps the first.
            built = jax.device_put(self._state, device)
            with self._replica_lock:
                st = self._replicas.setdefault(device, built)
        return st

    @staticmethod
    def _owned_padded(x, bucket: int, target=None) -> jax.Array:
        """A plan-OWNED buffer at the bucket size — safe to donate.

        Host inputs are padded on the host and transferred once, which
        yields a fresh buffer. A jax array is padded where it lives (a fresh
        buffer too); one already at its bucket size is copied, because a
        donated buffer is deleted after the call.

        ``target`` is where the jitted call reads its inputs: a device
        (PLACED mode — the call then runs entirely on that device's
        stream), the batch sharding of the mesh (SHARDED mode — each device
        receives only its rows), or ``None`` (the default device).
        """
        b = np.shape(x)[0]
        pad = [(0, bucket - b)] + [(0, 0)] * (np.ndim(x) - 1)
        if not isinstance(x, jax.Array):
            x = np.asarray(x)
            if b != bucket:
                x = np.pad(x, pad)
            return jnp.asarray(x) if target is None else jax.device_put(x, target)
        owned = b != bucket
        if owned:
            x = jnp.pad(x, pad)
        if target is not None:
            # may_alias only for our own padded buffer: a caller's array is
            # always copied, never donated
            return jax.device_put(x, target, may_alias=owned)
        return x if owned else x.copy()

    def _lut_cell_stats(self) -> tuple[int, int]:
        """(useful, dispatched) LUT cells across the plan's kernel steps.

        A fused stack dispatches its whole padded ``[L, W, Kmax·C]``
        slab per call; only the member banks' true ``K·C·N`` cells carry
        signal. Standalone banks contribute their true cells to BOTH terms,
        so the ratio weights fused padding by its real share of the plan's
        LUT compute."""
        fused_members = {id(b) for s in self.fused_stacks for b in s.banks}
        useful = dispatched = 0
        for s in self.fused_stacks:
            c = s.banks[0].layer.num_centroids
            dispatched += len(s.banks) * max(s.ks) * c * s.width
            useful += sum(b.layer.num_groups * c * b.layer.out_features
                          for b in s.banks)
        for b in self.banks:
            if id(b) not in fused_members:
                cells = (b.layer.num_groups * b.layer.num_centroids
                         * b.layer.out_features)
                useful += cells
                dispatched += cells
        return useful, dispatched

    def compile_stats(self) -> dict:
        """Per-plan jit-cache counters (the serving stats surface)."""
        with self._ctr.lock:                     # consistent snapshot
            traces = self._ctr.traces
            jit_calls = self.jit_calls
            buckets = sorted(self._ctr.traced_buckets)
            rows = {k: list(v) for k, v in self._ctr.rows.items()}
        useful, dispatched = self._lut_cell_stats()
        # fused stacks dispatch Kmax/Nmax-padded operand slabs the batch
        # filler fraction alone never counted: fold the static operand
        # efficiency into the KERNEL backends' per-bucket waste (the
        # fallback backends run per-bank on true-size tables)
        fused_eff = useful / dispatched if dispatched else 1.0

        def _waste(be: str, req: int, disp: int) -> float:
            if not disp:
                return 0.0
            eff = fused_eff if be in ("kernel", "kernel_q8") else 1.0
            return round(1.0 - (req / disp) * eff, 4)

        return {
            "traces": traces,
            "jit_calls": jit_calls,
            "bucket_hits": jit_calls - traces,
            "buckets": buckets,
            # ladder efficiency: filler fraction of every dispatched bucket
            # (kernel backends include fused-stack operand padding)
            "pad_waste": {
                f"{be}@{bucket}": _waste(be, req, disp)
                for (be, bucket), (req, disp) in sorted(rows.items())
            },
            # static operand padding per fused group (batch-independent)
            "pad_waste_fused": {
                f"group{g}": {
                    "layers": len(s.banks),
                    "kmax": max(s.ks),
                    "width": s.width,
                    "frac": round(
                        1.0 - sum(b.layer.num_groups
                                  * b.layer.num_centroids
                                  * b.layer.out_features for b in s.banks)
                        / (len(s.banks) * max(s.ks)
                           * s.banks[0].layer.num_centroids
                           * s.width), 4),
                }
                for g, s in enumerate(self.fused_stacks)
            },
            # fusion coverage: how much of the plan runs as stacked kernels
            "fused_groups": self.fused_groups,
            "fused_banks": self.fused_banks,
            # sharded width: how many devices the batch axis splits across
            # (1 = single-device; placed calls don't change it)
            "devices": 1 if self.devices is None else len(self.devices),
            # plan-audit finding counts (repro.analysis.planaudit), None
            # when the plan was built with audit="off" and never audited
            "audit": None if self.audit_report is None
            else dict(self.audit_report.counts),
        }

    @property
    def num_banks(self) -> int:
        return len(self.banks)

    def bank_inputs(self, *inputs: jax.Array, backend: str = "gather") -> list:
        """Forward once (eagerly), recording the first activation each bank
        receives — a debugging/parity-test aid (None for unreached banks).
        Fused steps are walked per-bank so the recording stays per-bank."""
        rec: dict[int, jax.Array] = {}

        def apply(bank, x: jax.Array) -> jax.Array:
            if isinstance(bank, FusedBankStack):
                h = x
                for member in bank.banks:
                    rec.setdefault(id(member), h)
                    h = member.apply(h, backend)
                return h
            rec.setdefault(id(bank), x)
            return bank.apply(x, backend)

        self._forward(apply, self._state, *inputs)
        return [rec.get(id(b)) for b in self.banks]

    def table_bytes(self) -> int:
        """Total LUT bytes held by the plan (fp + q8 layouts)."""
        total = 0
        for b in self.banks:
            total += b.lut.size * b.lut.dtype.itemsize
            total += b.lut_q8.size * b.lut_q8.dtype.itemsize
        return total


def _compile_banks(layers: Sequence[PegasusLinear], **kw) -> list[CompiledBank]:
    return [CompiledBank(l, **kw) for l in layers]


def _note_fusion(plan: ExecutionPlan, steps: Sequence) -> None:
    for s in steps:
        if isinstance(s, FusedBankStack):
            plan.fused_groups += 1
            plan.fused_banks += len(s.banks)
            plan.fused_stacks.append(s)


def _sequential_plan(layers, backend, kw, buckets, fuse, nmax_cap,
                     devices=None) -> ExecutionPlan:
    banks = _compile_banks(layers, **kw)
    steps = fuse_banks(banks, nmax_cap=nmax_cap) if fuse else list(banks)

    def forward(apply, state, x):
        h = x.astype(jnp.float32)
        for step in state["steps"]:
            h = apply(step, h)
        return h

    plan = ExecutionPlan(banks, forward, {"steps": steps}, backend=backend,
                         family="sequential", bucket_sizes=buckets,
                         devices=devices)
    _note_fusion(plan, steps)
    return plan


def _rnn_plan(model, backend, kw, buckets, devices=None) -> ExecutionPlan:
    x_banks = _compile_banks(model.x_banks, **kw)
    h_banks = _compile_banks(model.h_banks, **kw)
    out_bank = CompiledBank(model.out_bank, **kw)
    window = int(model.window)   # static: the unroll length is frozen into
    # the plan (bank swaps after compilation are caught by plan_for's
    # _model_banks identity check, which rebuilds the plan)

    def forward(apply, state, x):
        xf = x.astype(jnp.float32)
        h_pre = apply(state["x"][0], xf[:, 0])
        for t in range(1, window):
            h_pre = apply(state["x"][t], xf[:, t]) + apply(state["h"][t - 1], h_pre)
        return apply(state["out"], h_pre)

    state = {"x": x_banks, "h": h_banks, "out": out_bank}
    return ExecutionPlan(x_banks + h_banks + [out_bank], forward, state,
                         backend=backend, family="rnn", bucket_sizes=buckets,
                         devices=devices)


def _cnn_plan(model, backend, kw, buckets, fuse, nmax_cap,
              devices=None) -> ExecutionPlan:
    from repro.nets.cnn import _windows  # structural helper, no cycle at call time

    window_bank = CompiledBank(model.window_bank, **kw)
    head_banks = _compile_banks(model.head_banks, **kw)
    # the head chain after the window pool is an ordinary sequential run —
    # fusable; the windowed step itself stays structural (per-window batch)
    head_steps = (fuse_banks(head_banks, nmax_cap=nmax_cap) if fuse
                  else list(head_banks))
    nam = bool(model.nam)        # static branch selector
    state = {
        "window": window_bank,
        "heads": head_steps,
        "out_bias": None if model.out_bias is None else jnp.asarray(model.out_bias),
    }

    def forward(apply, state, x):
        win = _windows(x.astype(jnp.float32))          # [B, P, KERNEL*f]
        b, pcount, wdim = win.shape
        contrib = apply(state["window"], win.reshape(-1, wdim)).reshape(b, pcount, -1)
        if nam:
            return contrib.sum(axis=1) + state["out_bias"]  # single SumReduce
        h = contrib.mean(axis=1)                       # rows already ReLU'd
        for bank in state["heads"]:
            h = apply(bank, h)
        return h

    plan = ExecutionPlan([window_bank] + head_banks, forward, state,
                         backend=backend, family="cnn", bucket_sizes=buckets,
                         devices=devices)
    _note_fusion(plan, head_steps)
    return plan


def _cnn_l_plan(model, backend, kw, buckets, devices=None) -> ExecutionPlan:
    from repro.nets.cnn import _packet_feats

    bank1 = CompiledBank(model.bank1, **kw)
    bank2 = CompiledBank(model.bank2, **kw)
    state = {
        "b1": bank1,
        "b2": bank2,
        "emb_tree": model.emb_tree,                    # FuzzyTree is a pytree
        "logit_lut": jnp.asarray(model.logit_lut),
        "bias": jnp.asarray(model.bias),
    }

    def forward(apply, state, seq, payload):
        x = _packet_feats(seq, payload) * 255.0        # [B, W, 62]
        b, w, d = x.shape
        h_pre = apply(state["b1"], x.reshape(-1, d))
        e_pre = apply(state["b2"], h_pre)
        emb = jnp.tanh(e_pre)
        idx = hard_index(state["emb_tree"], emb)
        contrib = state["logit_lut"][idx].reshape(b, w, -1)
        return contrib.sum(axis=1) + state["bias"]

    return ExecutionPlan([bank1, bank2], forward, state, backend=backend,
                         family="cnn_l", bucket_sizes=buckets,
                         devices=devices)


def build_plan(
    model: Any,
    *,
    backend: str = "onehot",
    interpret: bool | None = None,
    strategy: str = "auto",
    bucket_sizes: Sequence[int] | None = None,
    fuse: bool = True,
    fuse_nmax_cap: int | None = DEFAULT_FUSE_NMAX_CAP,
    devices=None,
    audit: str = "warn",
) -> ExecutionPlan:
    """Compile any pegasusified model into an ExecutionPlan.

    Dispatch is structural (no imports of the net modules at module scope):
      * list/tuple of PegasusLinear  → sequential stack (MLP, AutoEncoder)
      * ``.x_banks``/``.h_banks``    → PegasusRNN
      * ``.window_bank``             → PegasusCNN (B and M/NAM)
      * ``.emb_tree``/``.logit_lut`` → PegasusCNNL (two-level NAM)

    Args:
        model: any pegasusified model (see the dispatch table above). A
            bare ``PegasusLinear`` is treated as a one-bank stack.
            Unrecognized structures raise ``TypeError`` at build time,
            never at call time.
        backend: default execution backend for ``plan(x)`` calls —
            ``"gather"`` | ``"onehot"`` | ``"kernel"`` | ``"kernel_q8"``
            (:data:`BACKENDS`); overridable per call via
            ``plan(x, backend=...)``. Unknown names raise ``ValueError``.
        interpret: ``True`` forces Pallas interpret mode (slow, runs
            anywhere), ``False`` requires a compiled Pallas backend,
            ``None`` (default) resolves via :func:`default_interpret` —
            interpret everywhere except a real TPU backend.
        strategy: Map+SumReduce realization for the kernel backends —
            ``"mxu"`` (one-hot × LUT matmul), ``"lookup"`` (sparse
            gather descent), or ``"auto"`` (default: ``lookup`` under
            interpret mode, ``mxu`` on compiled TPU).
        bucket_sizes: overrides the batch-bucket ladder (default
            :data:`DEFAULT_BUCKETS`, 8…4096). Must be sorted ascending;
            batches above the top bucket round up to multiples of it.
            Fewer buckets ⇒ fewer traces but more padded compute
            (``compile_stats()["pad_waste"]`` reports the waste).
        fuse: ``False`` disables the cross-bank fusion pass
            (:func:`fuse_banks`) — the A/B switch and the escape hatch
            for a shape the stacked kernel mishandles (a stack the
            kernel refuses falls back per-bank instead of dying).
        fuse_nmax_cap: bounds each fused group's padded output width
            (:data:`DEFAULT_FUSE_NMAX_CAP` = 2048 columns; ``None``
            disables the cap) so one wide bank cannot balloon a narrow
            stack's padded ``[L, W, Kmax·C]`` VMEM footprint;
            uniformly-wide runs still fuse above the cap because they
            add no padding. Both fusion knobs participate in
            ``plan_for``'s memo key, so fused and unfused plans of one
            model coexist.
        devices: SHARDED execution mode — ``None`` (default) compiles a
            single-device plan; an int ``k`` or a sequence of
            ``jax.Device``/device ids (see :func:`resolve_devices`) wraps
            the whole-plan forward in ``shard_map`` over a 1-D batch
            mesh: the padded bucket splits evenly across the devices and
            the bank operands replicate (they are KiB-scale). Outputs
            are bit-exact with the single-device plan. Every bucket size
            must divide by the device count (``ValueError`` at build).
            Participates in ``plan_for``'s memo key, so sharded and
            single-device plans of one model coexist.
        audit: run the static plan auditor (:mod:`repro.analysis.planaudit`,
            PGA101-PGA106) over the freshly built plan — ``"warn"``
            (default) attaches the report and raises a ``UserWarning``
            when it carries error/warning findings, ``"error"`` raises
            :class:`repro.analysis.planaudit.PlanAuditError` on error
            findings, ``"off"`` skips the pass (``plan.audit_report``
            stays ``None``). The audit never dispatches jax computation;
            it reads the plan's host-side tables only.

    The plan freezes ALL model state at build time — banks and non-bank
    attributes alike (RNN window, CNN nam/out_bias, CNN-L
    emb_tree/logit_lut/bias). Mutating the model afterwards does NOT affect
    a plan you hold: rebuild it, or go through :func:`plan_for`, whose memo
    detects bank swaps and non-bank reassignment and recompiles.
    """
    if interpret is None:
        interpret = default_interpret()
    kw = dict(interpret=interpret, strategy=strategy)
    if isinstance(model, PegasusLinear):
        plan = _sequential_plan([model], backend, kw, bucket_sizes, fuse,
                                fuse_nmax_cap, devices)
    elif isinstance(model, (list, tuple)):
        if not all(isinstance(l, PegasusLinear) for l in model):
            raise TypeError("bank list must contain only PegasusLinear")
        plan = _sequential_plan(model, backend, kw, bucket_sizes, fuse,
                                fuse_nmax_cap, devices)
    elif hasattr(model, "x_banks") and hasattr(model, "h_banks"):
        plan = _rnn_plan(model, backend, kw, bucket_sizes, devices)
    elif hasattr(model, "emb_tree") and hasattr(model, "logit_lut"):
        plan = _cnn_l_plan(model, backend, kw, bucket_sizes, devices)
    elif hasattr(model, "window_bank"):
        plan = _cnn_plan(model, backend, kw, bucket_sizes, fuse,
                         fuse_nmax_cap, devices)
    else:
        raise TypeError(f"don't know how to compile {type(model).__name__} into a plan")
    # the non-bank state the plan froze at build — plan_for compares this
    # against the live model to catch attribute reassignment (see _model_aux)
    plan._aux_token = _model_aux(model)
    # record the fusion knobs so the auditor can explain WHY a pair of
    # banks runs unfused (PGA105) instead of guessing
    plan.fuse_cfg = {"fuse": fuse, "nmax_cap": fuse_nmax_cap}
    _run_build_audit(plan, audit)
    return plan


def _run_build_audit(plan: ExecutionPlan, audit: str) -> None:
    """Build-time hook into the plan auditor. Imported lazily: plan.py is
    imported by the analysis package's sanitizer consumers, so a module-
    scope import would be circular."""
    if audit == "off":
        return
    if audit not in ("warn", "error"):
        raise ValueError(f"audit must be 'off'|'warn'|'error', got {audit!r}")
    from repro.analysis.planaudit import PlanAuditError, audit_plan

    report = audit_plan(plan)
    plan.audit_report = report
    counts = report.counts
    if audit == "error" and counts["error"]:
        raise PlanAuditError(report)
    if counts["error"] or counts["warning"]:
        warnings.warn(
            f"plan audit: {counts['error']} error / {counts['warning']} "
            f"warning finding(s) — inspect plan.audit_report or rerun "
            f"`python -m repro.analysis plan`:\n{report}",
            stacklevel=3)


# ---------------------------------------------------------------------------
# Model-structure helpers shared with the registry (repro.engine.registry),
# which owns all plan memoization: weakref-watched, bounded, evictable.
# ---------------------------------------------------------------------------


def _model_key(model: Any, interpret: bool, kw: dict) -> tuple:
    if isinstance(model, (list, tuple)):
        ids: tuple = tuple(id(l) for l in model)
    else:
        ids = (id(model),)
    return (*ids, interpret, tuple(sorted(kw.items())))


def _model_aux(model: Any) -> tuple:
    """Non-bank model state a compiled plan froze at build time (window
    length, NAM flag, out-bias, embedding tree, logit LUT). The registry
    must rebuild when any of it is reassigned — the forwards no longer read
    these attributes live, so a stale memo hit would silently serve outputs
    from the pre-mutation tensors."""
    if hasattr(model, "x_banks") and hasattr(model, "h_banks"):
        return (int(model.window),)
    if hasattr(model, "emb_tree") and hasattr(model, "logit_lut"):
        return (model.emb_tree, model.logit_lut, model.bias)
    if hasattr(model, "window_bank"):
        return (bool(model.nam), model.out_bias)
    return ()


def _aux_matches(a: tuple, b: tuple) -> bool:
    """Identity for array-like entries (``==`` on jax arrays is elementwise),
    equality for plain scalars."""
    return len(a) == len(b) and all(
        x is y or (isinstance(x, (bool, int)) and isinstance(y, (bool, int))
                   and x == y)
        for x, y in zip(a, b))


def _model_banks(model: Any) -> tuple:
    """Current bank layers of a model, in plan construction order — used to
    detect in-place mutation (e.g. ``peg.window_bank = refine(...)``) that
    would otherwise hit the memo with a stale compiled plan."""
    if isinstance(model, PegasusLinear):
        return (model,)
    if isinstance(model, (list, tuple)):
        return tuple(model)
    if hasattr(model, "x_banks") and hasattr(model, "h_banks"):
        return (*model.x_banks, *model.h_banks, model.out_bank)
    if hasattr(model, "emb_tree") and hasattr(model, "logit_lut"):
        return (model.bank1, model.bank2)
    if hasattr(model, "window_bank"):
        return (model.window_bank, *model.head_banks)
    return ()
