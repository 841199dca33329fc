"""Compile the Pallas kernels and the MLP-B, RNN-B and CNN-L plans for a
described TPU v5e.

Interpret mode on the CPU runs the kernel bodies but never asks Mosaic, the
TPU kernel compiler, whether it accepts them. These tests do, with no chip
attached: the TPU compiler compiles for a ``v5e:2x2`` topology that is only
described, with ``interpret=False`` and ``strategy="mxu"`` at MLP-B's
widths (banks ks=(8, 16, 16, 16), v=2, depth 6, hidden 32, 3 classes),
RNN-B's (16 unfused banks of K=2 or 24 groups of 1, depth 8, hidden 24, 3
classes) and CNN-L's (per packet, 62 bytes → 64 → 16 in two unfused banks
of groups of 1 at depth 8, a 4-bit index, 3 classes). A kernel that Mosaic refuses fails here instead of on the chip,
where the servers' breakers would quietly fall back to the ``gather`` plan.

The topology is described inside a fixture, so only the worker that runs
this file loads the TPU compiler; it skips only when the description fails.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.amm import PegasusLinear
from repro.core.fuzzy_tree import FuzzyTree
from repro.engine import build_plan
from repro.nets.cnn import PACKET_FEATURES, PegasusCNNL
from repro.nets.rnn import PegasusRNN
from repro.kernels.fuzzy_lut.kernel import fuzzy_lut_pallas, fuzzy_lut_stack_pallas
from repro.kernels.fuzzy_lut.quantized import (
    fuzzy_lut_q8_pallas, fuzzy_lut_stack_q8_pallas,
)

KS, V, DEPTH, HIDDEN, N_OUT = (8, 16, 16, 16), 2, 6, 32, 3
C = 2 ** DEPTH
KC = max(KS) * C           # tree nodes per stacked layer, padded to C each
W = HIDDEN                 # stacked activation rows: max(16·2, 32)
RNN_C, RNN_HIDDEN = 256, 24    # RNN-B: depth 8, x/h groups of 1


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compile cache
    off: an entry written here could not be read back without a chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _pallas_grids(fn, *shapes) -> list[tuple]:
    """``(grid, batch block)`` of every ``pallas_call`` ``fn`` traces to."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                gm = eqn.params["grid_mapping"]
                block = gm.block_mappings[0].block_shape
                yield (tuple(gm.grid),
                       tuple(getattr(b, "block_size", b) for b in block))
            for v in eqn.params.values():
                for sub in v if isinstance(v, (list, tuple)) else (v,):
                    if isinstance(sub, ClosedJaxpr):
                        yield from walk(sub.jaxpr)
                    elif isinstance(sub, Jaxpr):
                        yield from walk(sub)

    return list(walk(jax.make_jaxpr(fn)(*shapes).jaxpr))


def _kernel_case(name: str, sds):
    """(fn, operand shapes) of one kernel at MLP-B or RNN-B widths."""
    f32, i8 = jnp.float32, jnp.int8
    if name.startswith(("single", "rnn_b")):
        # MLP-B's widest single bank (K=16 groups of 2 → 32 outputs) on one
        # 128-row tile, or RNN-B's recurrent bank (K=24 groups of 1 → 24
        # outputs: 3 group tiles) on the 4096-flow bucket
        k, v, c, n, t = ((max(KS), V, C, HIDDEN, 128) if name[0] == "s"
                         else (RNN_HIDDEN, 1, RNN_C, RNN_HIDDEN, 4096))
        w = -(-max(k * v, n) // 8) * 8
        kw = dict(depth=c.bit_length() - 1, n_out=n, interpret=False,
                  strategy="mxu")
        x, sel, thr = sds((t, k * v)), sds((k * c, w)), sds((k * c, 1))
        bias = sds((w, 1))
        if name.endswith("fp32"):
            return (lambda *a: fuzzy_lut_pallas(*a, **kw),
                    (x, sel, thr, sds((w, k * c)), bias))
        return (lambda *a: fuzzy_lut_q8_pallas(*a, **kw),
                (x, sel, thr, sds((w, k * c), i8), sds((1, k * c)), bias))
    # the whole fused MLP-B stack at bucket 1024
    kw = dict(depth=DEPTH, ks=KS, n_out=N_OUT, interpret=False, strategy="mxu")
    nl = len(KS)
    x, sel, thr = sds((1024, KS[0] * V)), sds((nl, KC, W)), sds((nl, KC, 1))
    bias = sds((nl, W, 1))
    if name == "stack_fp32":
        return (lambda *a: fuzzy_lut_stack_pallas(*a, **kw),
                (x, sel, thr, sds((nl, W, KC)), bias))
    return (lambda *a: fuzzy_lut_stack_q8_pallas(*a, **kw),
            (x, sel, thr, sds((nl, W, KC), i8), sds((nl, 1, KC)), bias))


@pytest.mark.parametrize(
    "name", ["single_fp32", "single_q8", "stack_fp32", "stack_q8",
             "rnn_b_fp32", "rnn_b_q8"])
def test_kernel_compiles_for_v5e(one_chip, name):
    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, shapes = _kernel_case(name, sds)
    assert "tpu_custom_call" in _compiled_text(fn, *shapes)


def _mlp_b_banks() -> list:
    """Random banks at MLP-B's widths (16 → 32 → 32 → 32 → 3)."""
    rng = np.random.default_rng(0)
    banks = []
    for k, n in zip(KS, (HIDDEN, HIDDEN, HIDDEN, N_OUT)):
        trees = FuzzyTree(
            jnp.asarray(rng.integers(0, V, (k, C - 1)), jnp.int32),
            jnp.asarray(rng.normal(size=(k, C - 1)), jnp.float32),
            jnp.asarray(rng.normal(size=(k, C, V)), jnp.float32))
        banks.append(PegasusLinear(
            trees, jnp.asarray(rng.normal(size=(k, C, n)), jnp.float32),
            jnp.asarray(rng.normal(size=n), jnp.float32), group_size=V))
    return banks


@pytest.mark.parametrize("backend", ["kernel", "kernel_q8"])
def test_mlp_b_plan_forward_compiles_for_v5e(one_chip, backend):
    """The whole jitted plan forward at bucket 1024, as a server runs it:
    one fused stack, compiled by Mosaic."""
    plan = build_plan(_mlp_b_banks(), backend=backend, interpret=False,
                      audit="off")
    assert plan.compile_stats()["fused_groups"] == 1

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    state = jax.tree.map(sds, plan._state)
    x = jax.ShapeDtypeStruct((1024, KS[0] * V), jnp.float32, sharding=one_chip)
    text = plan._jit.lower(state, (x,), backend=backend).compile().as_text()
    assert "tpu_custom_call" in text
    # the stack keeps the grid and tile it had before the single-bank
    # kernel shared its launcher: batch tiles of 256 flows, nothing else
    grids = _pallas_grids(
        lambda s, xx: plan._jit(s, (xx,), backend=backend), state, x)
    assert grids == [((4,), (W, 256))]


def _depth8_bank(rng, k: int, n: int) -> PegasusLinear:
    """A random bank of ``k`` groups of 1, depth 8, ``n`` outputs."""
    trees = FuzzyTree(
        jnp.zeros((k, RNN_C - 1), jnp.int32),
        jnp.asarray(rng.normal(size=(k, RNN_C - 1)), jnp.float32),
        jnp.zeros((k, RNN_C, 1), jnp.float32))
    return PegasusLinear(
        trees, jnp.asarray(rng.normal(size=(k, RNN_C, n)), jnp.float32),
        jnp.asarray(rng.normal(size=n), jnp.float32), group_size=1)


def _rnn_b_model() -> PegasusRNN:
    """Random banks at RNN-B's widths: 8 input banks (K=2 → 24), 7
    recurrent (K=24 → 24) and the classifier (K=24 → 3)."""
    rng = np.random.default_rng(0)
    bank = functools.partial(_depth8_bank, rng)
    return PegasusRNN(x_banks=[bank(2, RNN_HIDDEN) for _ in range(8)],
                      h_banks=[bank(RNN_HIDDEN, RNN_HIDDEN) for _ in range(7)],
                      out_bank=bank(RNN_HIDDEN, N_OUT), window=8)


def test_rnn_b_plan_forward_compiles_for_v5e(one_chip):
    """RNN-B's jitted plan forward at bucket 4096, as a server runs it:
    16 lane-dense single-bank kernels, compiled by Mosaic. Each custom call
    is named ``fuzzy_lut_pallas`` (the jitted launcher it was traced in),
    the name the benchmark's single-bank kernel reader looks for."""
    plan = build_plan(_rnn_b_model(), backend="kernel", interpret=False,
                      audit="off")

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    state = jax.tree.map(sds, plan._state)
    x = jax.ShapeDtypeStruct((4096, 8, 2), jnp.float32, sharding=one_chip)
    text = plan._jit.lower(state, (x,), backend="kernel").compile().as_text()
    calls = [line.split(" = ", 1)[0].strip().lstrip("%")
             for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 16
    assert all(c.rsplit(".", 1)[0] == "fuzzy_lut_pallas" for c in calls)
    # input banks: whole bank, 512-flow tiles; recurrent and classifier
    # banks: 128-flow tiles × 3 group tiles of 8 groups
    grids = _pallas_grids(
        lambda s, xx: plan._jit(s, (xx,), backend="kernel"), state, x)
    assert sorted(set(grids)) == [((8, 1), (24, 512)), ((32, 3), (24, 128))]
    assert sorted(g[0] for g in grids).count((32, 3)) == 8


def _cnn_l_model() -> PegasusCNNL:
    """Random banks at CNN-L's widths: per packet 62 bytes → 64 → 16, depth
    8, and a depth-4 index tree over the 16-dim embedding."""
    rng = np.random.default_rng(0)
    tree = FuzzyTree(jnp.asarray(rng.integers(0, 16, 15), jnp.int32),
                     jnp.asarray(rng.normal(size=15), jnp.float32),
                     jnp.zeros((16, 16), jnp.float32))
    return PegasusCNNL(bank1=_depth8_bank(rng, PACKET_FEATURES, 64),
                       bank2=_depth8_bank(rng, 64, 16),
                       emb_tree=tree,
                       logit_lut=jnp.asarray(rng.normal(size=(16, N_OUT)),
                                             jnp.float32),
                       bias=jnp.zeros(N_OUT, jnp.float32), index_bits=4)


@pytest.fixture(scope="module")
def cnn_l_forward(one_chip):
    """CNN-L's plan, its state and input as shapes on the described chip,
    and the text of its jitted forward compiled at bucket 4096."""
    plan = build_plan(_cnn_l_model(), backend="kernel", interpret=False,
                      audit="off")

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    state = jax.tree.map(sds, plan._state)
    x = jax.ShapeDtypeStruct((4096, 8, PACKET_FEATURES), jnp.uint8,
                             sharding=one_chip)
    text = plan._jit.lower(state, (x,), backend="kernel").compile().as_text()
    return plan, state, x, text


def test_cnn_l_plan_forward_compiles_for_v5e(cnn_l_forward):
    """CNN-L's jitted plan forward at bucket 4096 on the packed uint8
    records, as a server runs it: two lane-dense single-bank kernels over
    the 32768 packet rows of a chunk, named ``fuzzy_lut_pallas``. Both
    banks (K = 62 padded to 64, and K = 64; W = 64) run 128-flow tiles ×
    8 group tiles of 8 groups."""
    plan, state, x, text = cnn_l_forward
    calls = [line.split(" = ", 1)[0].strip().lstrip("%")
             for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 2
    assert all(c.rsplit(".", 1)[0] == "fuzzy_lut_pallas" for c in calls)
    grids = _pallas_grids(
        lambda s, xx: plan._jit(s, (xx,), backend="kernel"), state, x)
    assert grids == [((256, 8), (64, 128))] * 2


def test_cnn_l_index_ops_are_found_by_name(cnn_l_forward):
    """The chip's trace names a device operation by its HLO instruction
    text (operands included, metadata left out), with no name stack, so
    the benchmark's ``cnn_l_index_share`` finds the forward's
    ``cnn_l.index`` scope by instruction names. In the forward compiled for
    a v5e, every top-level instruction whose text its patterns match is in
    the scope, every fusion in the scope is matched, and what the scope
    holds besides is no larger than one value per packet row."""
    from bench.metrics.cnn_l_index_share import PATTERNS

    entry = cnn_l_forward[3].split("\nENTRY ", 1)[1]
    matched = 0
    for line in entry.splitlines()[1:]:
        if " = " not in line:
            continue
        name, rest = line.strip().removeprefix("ROOT ").split(" = ", 1)
        op = re.search(r'op_name="([^"]*)"', rest)
        in_scope = op is not None and "cnn_l.index" in op.group(1)
        opcode = re.search(r"\s([a-z][\w-]*)\(", rest).group(1)
        if opcode in ("bitcast", "get-tuple-element", "parameter",
                      "constant"):
            continue                  # views and literals: no device work
        if any(p in f"{name} = {rest.split(', metadata=')[0]}"
               for p in PATTERNS):
            assert in_scope, line
            matched += 1
        elif in_scope:
            assert opcode != "fusion", line
            dims = re.match(r"[a-z]\w*\[([\d,]*)\]", rest).group(1)
            assert np.prod([int(d) for d in dims.split(",") if d]) <= 32768, line
    assert matched > 0
