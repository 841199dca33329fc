"""Execution-engine tests: backend parity across every net family, plan
caching (no layout prep / quantization on the call path), and the q8 memo.

Parity contract (ISSUE acceptance): through one ExecutionPlan,
``gather == onehot == kernel`` bitwise-closely for MLP, RNN, CNN, CNN-L and
AutoEncoder pegasus variants, and ``kernel_q8`` matches within int8
quantization tolerance — exactly per-bank, and by prediction agreement at
the net level (index flips near thresholds compound across stacked banks,
so elementwise net-level bounds would be vacuous).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.data.synthetic_traffic import make_dataset
from repro.engine import (
    BACKENDS, STATS, CompiledBank, FusedBankStack, build_plan, fuse_banks,
    plan_for,
)
from repro.kernels.fuzzy_lut import ops

pytestmark = pytest.mark.kernel   # every case exercises the Pallas backends

FLOWS = 48
STEPS = 5          # parity needs a trained-enough model, not an accurate one
BATCH = 16


@pytest.fixture(scope="module")
def ds():
    return make_dataset("peerrush", flows_per_class=FLOWS)


def _mlp(ds):
    from repro.nets.mlp import pegasusify_mlp, train_mlp

    m = train_mlp(ds.train["stats"], ds.train["label"], ds.num_classes, steps=STEPS)
    banks = pegasusify_mlp(m, ds.train["stats"].astype(np.float32),
                           depth=3, refine_steps=0)
    return banks, (jnp.asarray(ds.test["stats"][:BATCH], jnp.float32),)


def _rnn(ds):
    from repro.nets.rnn import pegasusify_rnn, train_rnn

    m = train_rnn(ds.train["seq"], ds.train["label"], ds.num_classes, steps=STEPS)
    return pegasusify_rnn(m, ds.train["seq"], depth=4), (
        jnp.asarray(ds.test["seq"][:BATCH]),)


def _cnn(ds):
    from repro.nets.cnn import pegasusify_cnn, train_cnn

    m = train_cnn(ds.train["seq"], ds.train["label"], ds.num_classes,
                  size="B", steps=STEPS)
    return pegasusify_cnn(m, ds.train["seq"], depth=5), (
        jnp.asarray(ds.test["seq"][:BATCH]),)


def _cnn_l(ds):
    from repro.nets.cnn import pegasusify_cnn_l, train_cnn_l

    m = train_cnn_l(ds.train["seq"], ds.train["bytes"], ds.train["label"],
                    ds.num_classes, steps=STEPS)
    peg = pegasusify_cnn_l(m, ds.train["seq"], ds.train["bytes"],
                           enc_depth=4, index_bits=3)
    return peg, (jnp.asarray(ds.test["seq"][:BATCH]),
                 jnp.asarray(ds.test["bytes"][:BATCH]))


def _ae(ds):
    from repro.nets.autoencoder import anomaly_features, pegasusify_ae, train_autoencoder

    x = ds.train["seq"].reshape(len(ds.train["label"]), -1)
    m = train_autoencoder(x, steps=STEPS)
    banks = pegasusify_ae(m, x.astype(np.float32), depth=4)
    xt = ds.test["seq"][:BATCH].reshape(BATCH, -1)
    # the AE bank stack consumes the engineered feature view
    return banks, (anomaly_features(jnp.asarray(xt, jnp.float32)),)


FAMILIES = {"mlp": _mlp, "rnn": _rnn, "cnn": _cnn, "cnn_l": _cnn_l, "ae": _ae}

# mlp + ae are cheap enough for the fast CI lane; the windowed/unrolled
# families train + compile for tens of seconds and ride the full lane.
FAMILY_PARAMS = [
    pytest.param("mlp"),
    pytest.param("ae"),
    pytest.param("rnn", marks=pytest.mark.slow),
    pytest.param("cnn", marks=pytest.mark.slow),
    pytest.param("cnn_l", marks=pytest.mark.slow),
]

_COMPILED: dict[str, tuple] = {}


def _family(ds, family):
    """Lazy per-family (model, plan, inputs) — built once, on first use."""
    if family not in _COMPILED:
        model, inputs = FAMILIES[family](ds)
        _COMPILED[family] = (model, build_plan(model), inputs)
    return _COMPILED[family]


def _compiled(ds, family):
    _, plan, inputs = _family(ds, family)
    return plan, inputs


@pytest.mark.parametrize("family", FAMILY_PARAMS)
def test_backend_parity(ds, family):
    plan, inputs = _compiled(ds, family)
    ref = np.asarray(plan(*inputs, backend="gather"))
    assert np.isfinite(ref).all()

    # exact backends: identical up to fp32 accumulation order
    for be in ("onehot", "kernel"):
        out = np.asarray(plan(*inputs, backend=be))
        np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4,
                                   err_msg=f"{family}:{be}")

    # q8: quantization tolerance per bank, on the REAL activations each bank
    # sees (random inputs land in degenerate leaves and say nothing)
    for i, xb in enumerate(plan.bank_inputs(*inputs)):
        bank = plan.banks[i]
        yg = np.asarray(bank.apply(xb, "gather"))
        yq = np.asarray(bank.apply(xb, "kernel_q8"))
        denom = max(float(np.linalg.norm(yg)), 1e-6)
        rel = float(np.linalg.norm(yq - yg)) / denom
        assert rel < 0.12, (family, i, rel)
    # … and agreeing predictions end-to-end (flips compound across banks)
    outq = np.asarray(plan(*inputs, backend="kernel_q8"))
    assert np.isfinite(outq).all()
    if family != "ae":
        agree = float((outq.argmax(-1) == ref.argmax(-1)).mean())
        assert agree >= 0.75, (family, agree)
    else:
        rel = float(np.linalg.norm(outq - ref) / np.linalg.norm(ref))
        assert rel < 0.25, (family, rel)


@pytest.mark.parametrize("family", FAMILY_PARAMS)
def test_plan_call_does_no_layout_work(ds, family):
    """Acceptance: after one warm call, further calls perform ZERO layout
    prep and ZERO quantization on any backend."""
    plan, inputs = _compiled(ds, family)
    for be in BACKENDS:
        plan(*inputs, backend=be)            # warm (layouts were plan-time anyway)
    before_layout = STATS.layout_builds
    before_quant = ops.QUANT_STATS["quantize_calls"]
    for be in BACKENDS:
        plan(*inputs, backend=be)
    assert STATS.layout_builds == before_layout
    assert ops.QUANT_STATS["quantize_calls"] == before_quant


def test_bank_layout_built_once():
    """CompiledBank does its layout work in __init__, not in apply()."""
    from repro.core.amm import init_pegasus_linear

    rng = np.random.default_rng(0)
    layer = init_pegasus_linear(
        rng.normal(size=(8, 6)).astype(np.float32), None,
        rng.normal(size=(64, 8)).astype(np.float32), group_size=2, depth=3,
        lut_bits=None)
    before = STATS.layout_builds
    bank = CompiledBank(layer)
    assert STATS.layout_builds == before + 1
    x = jnp.asarray(rng.normal(size=(4, 8)).astype(np.float32))
    ref = np.asarray(bank.apply(x, "gather"))
    for be in ("onehot", "kernel"):
        np.testing.assert_allclose(np.asarray(bank.apply(x, be)), ref,
                                   rtol=1e-4, atol=1e-5)
    assert STATS.layout_builds == before + 1   # apply() never re-preps


def test_pegasus_linear_compile_method():
    """core/amm hook: PegasusLinear.compile() yields a single-bank plan."""
    from repro.core.amm import apply_gather, init_pegasus_linear

    rng = np.random.default_rng(3)
    layer = init_pegasus_linear(
        rng.normal(size=(8, 6)).astype(np.float32), None,
        rng.normal(size=(64, 8)).astype(np.float32), group_size=2, depth=3,
        lut_bits=None)
    plan = layer.compile()
    assert plan.num_banks == 1
    x = jnp.asarray(rng.normal(size=(4, 8)).astype(np.float32))
    ref = np.asarray(apply_gather(layer, x))
    for be in BACKENDS[:3]:
        np.testing.assert_allclose(np.asarray(plan(x, backend=be)), ref,
                                   rtol=1e-4, atol=1e-5)


def test_q8_memo_quantizes_once():
    """Satellite fix: fuzzy_lut_matmul_q8 must not re-quantize per call."""
    from repro.core.amm import init_pegasus_linear
    from repro.kernels.fuzzy_lut.ops import fuzzy_lut_matmul_q8

    rng = np.random.default_rng(1)
    layer = init_pegasus_linear(
        rng.normal(size=(8, 6)).astype(np.float32), None,
        rng.normal(size=(64, 8)).astype(np.float32), group_size=2, depth=3,
        lut_bits=None)
    x = jnp.asarray(rng.normal(size=(4, 8)).astype(np.float32))
    fuzzy_lut_matmul_q8(layer, x)
    calls = ops.QUANT_STATS["quantize_calls"]
    hits = ops.QUANT_STATS["cache_hits"]
    fuzzy_lut_matmul_q8(layer, x)
    fuzzy_lut_matmul_q8(layer, x)
    assert ops.QUANT_STATS["quantize_calls"] == calls        # no re-quant
    assert ops.QUANT_STATS["cache_hits"] >= hits + 2


def test_q8_memo_evicts_dead_layers():
    import gc

    from repro.core.amm import init_pegasus_linear

    rng = np.random.default_rng(2)
    layer = init_pegasus_linear(
        rng.normal(size=(4, 3)).astype(np.float32), None,
        rng.normal(size=(32, 4)).astype(np.float32), group_size=2, depth=2,
        lut_bits=None)
    ops.quantized_lut_cached(layer)
    key = id(layer)
    assert key in ops._Q8_MEMO
    del layer
    gc.collect()
    assert key not in ops._Q8_MEMO


def test_plan_for_memoizes(ds):
    banks, _, inputs = _family(ds, "mlp")
    hits = STATS.plan_cache_hits
    p1 = plan_for(banks)
    p2 = plan_for(banks)
    assert p1 is p2
    assert STATS.plan_cache_hits == hits + 1
    np.testing.assert_allclose(
        np.asarray(p1(*inputs, backend="onehot")),
        np.asarray(p1(*inputs, backend="gather")), rtol=1e-4, atol=1e-4)


def test_plan_for_detects_inplace_mutation(ds):
    """Reassigning a bank on the model must invalidate the memo — otherwise
    the engine would keep serving logits from the pre-mutation tables."""
    import dataclasses as dc

    banks, _, inputs = _family(ds, "mlp")
    model = list(banks)
    p1 = plan_for(model)
    y1 = np.asarray(p1(*inputs, backend="gather"))
    assert plan_for(model) is p1                    # unchanged → memo hit
    # simulate refine(): replace a bank with a copy (new object, same arrays)
    model[-1] = dc.replace(model[-1])
    p2 = plan_for(model)
    assert p2 is not p1                             # mutation → rebuilt
    np.testing.assert_allclose(
        np.asarray(p2(*inputs, backend="gather")), y1, rtol=1e-6, atol=1e-6)


@pytest.mark.slow
def test_plan_for_detects_wrapper_mutation(ds):
    """Same for attribute reassignment on a wrapper model (id-stable key):
    the memo must notice the compiled banks no longer match the model's."""
    import dataclasses as dc

    model, _, inputs = _family(ds, "cnn")
    p1 = plan_for(model)
    assert plan_for(model) is p1
    model.window_bank = dc.replace(model.window_bank)   # refine()-style swap
    p2 = plan_for(model)
    assert p2 is not p1
    np.testing.assert_allclose(
        np.asarray(p2(*inputs, backend="gather")),
        np.asarray(p1(*inputs, backend="gather")), rtol=1e-6, atol=1e-6)


def test_plan_for_detects_aux_mutation():
    """Non-bank attrs (NAM bias, logit LUT, window) are frozen into the plan
    at build; reassigning one on the model must invalidate the memo even
    though every bank is identity-unchanged."""
    import types

    from repro.core.amm import init_pegasus_linear

    rng = np.random.default_rng(7)
    layer = init_pegasus_linear(
        rng.normal(size=(6, 4)).astype(np.float32), None,
        rng.normal(size=(64, 6)).astype(np.float32), group_size=2, depth=3,
        lut_bits=None)
    model = types.SimpleNamespace(
        window_bank=layer, head_banks=[], nam=True,
        out_bias=jnp.zeros(4, jnp.float32), pool_windows=6)
    x = jnp.asarray(rng.normal(size=(4, 8, 2)).astype(np.float32))
    p1 = plan_for(model)
    y1 = np.asarray(p1(x, backend="gather"))
    assert plan_for(model) is p1                    # unchanged → memo hit
    model.out_bias = jnp.ones(4, jnp.float32)       # recalibrated bias
    p2 = plan_for(model)
    assert p2 is not p1                             # aux mutation → rebuilt
    np.testing.assert_allclose(np.asarray(p2(x, backend="gather")), y1 + 1.0,
                               rtol=1e-6, atol=1e-6)


def test_unknown_backend_rejected(ds):
    banks, plan, inputs = _family(ds, "mlp")
    with pytest.raises(ValueError, match="unknown backend"):
        plan(*inputs, backend="dense")
    with pytest.raises(ValueError, match="unknown backend"):
        build_plan(banks, backend="nope")


def test_pegasus_server_batches(ds):
    from repro.launch.serve import PegasusServer

    banks, plan, (x,) = _family(ds, "mlp")
    server = PegasusServer(banks, backend="onehot", max_batch=8)
    reqs = [np.asarray(x[i : i + 4]) for i in range(0, 16, 4)]
    outs = server.serve(reqs)
    assert len(outs) == 4 and all(o.shape[0] == 4 for o in outs)
    ref = np.asarray(plan(x, backend="onehot"))
    np.testing.assert_allclose(np.concatenate(outs), ref, rtol=1e-5, atol=1e-5)
    assert server.requests_served == 4
    assert server.batches_run == 2                 # 16 flows → buckets [8, 8]
    # second round reuses the SAME plan: no new layout/quant work
    before = STATS.layout_builds
    server.serve(reqs)
    assert STATS.layout_builds == before
    # both rounds hit ONE compiled bucket (8): 4 jit calls, 1 trace
    st = server.stats()["engine"]
    assert st["jit_calls"] == 4
    assert st["traces"] == 1
    assert st["bucket_hits"] == 3
    assert st["buckets"] == [("onehot", 8)]


def test_pegasus_server_counts_on_success_only(ds):
    """Satellite: a raising request must not corrupt the serving stats."""
    from repro.launch.serve import PegasusServer

    banks, _, (x,) = _family(ds, "mlp")
    server = PegasusServer(banks, backend="onehot", max_batch=8)
    server.serve([np.asarray(x[:4])])
    assert (server.requests_served, server.batches_run) == (1, 1)
    with pytest.raises(ValueError, match="unknown backend"):
        server.infer(x[:4], backend="dense")
    with pytest.raises(ValueError, match="unknown backend"):
        server.serve([np.asarray(x[:4])], backend="dense")
    assert (server.requests_served, server.batches_run) == (1, 1)
    # and the server still serves fine afterwards
    server.infer(x[:4])
    assert (server.requests_served, server.batches_run) == (2, 2)


def test_bucket_chunks_policy():
    from repro.engine import DEFAULT_BUCKETS, bucket_chunks

    assert bucket_chunks(16, max_batch=8) == [8, 8]
    assert bucket_chunks(256) == [256]              # exact bucket: one chunk
    assert bucket_chunks(300) == [256, 44]          # exact + minimal pad tail
    assert bucket_chunks(904) == [904]              # split wouldn't cut padding
    top = DEFAULT_BUCKETS[-1]
    assert bucket_chunks(top + 904) == [top, 904]
    assert bucket_chunks(2048, max_batch=4096) == [2048]  # the old fixed-1024
    # chunking split this despite its exact bucket
    assert bucket_chunks(3) == [3]
    assert sum(bucket_chunks(12345)) == 12345
    # a cap below the smallest bucket can't bound anything (dispatches pad
    # up to the smallest bucket regardless) — it must not multiply work
    assert bucket_chunks(8, max_batch=4) == [8]
    with pytest.raises(ValueError):
        bucket_chunks(0)


# ---------------------------------------------------------------------------
# Whole-plan jit + batch bucketing (ISSUE 2 tentpole)
# ---------------------------------------------------------------------------


def test_jit_bucket_compile_invariants(ds):
    """Acceptance: repeated calls at one bucket trigger ZERO retraces; a new
    batch size triggers at most one (its bucket's first compile); sub-bucket
    batches round up into already-warm buckets."""
    _, plan, (x,) = _family(ds, "mlp")             # BATCH=16 → bucket 16
    be = "onehot"
    plan(x, backend=be)                            # warm bucket 16
    t0 = STATS.jit_traces
    plan(x, backend=be)
    plan(x, backend=be)
    assert STATS.jit_traces == t0                  # same bucket: no retrace
    plan(x[:9], backend=be)                        # 9 → bucket 16: still warm
    assert STATS.jit_traces == t0
    plan(x[:4], backend=be)                        # 4 → bucket 8: ≤ 1 trace
    assert STATS.jit_traces <= t0 + 1
    traces_after_8 = STATS.jit_traces
    plan(x[:3], backend=be)                        # 3 → bucket 8: warm again
    plan(x[:7], backend=be)
    assert STATS.jit_traces == traces_after_8
    assert ("onehot", 16) in plan.compiled_buckets


def test_bucket_padding_roundtrip(ds):
    """Zero-row bucket padding must not leak into the sliced-off outputs."""
    _, plan, (x,) = _family(ds, "mlp")
    for be in BACKENDS:
        full = np.asarray(plan(x, backend=be))
        odd = np.asarray(plan(x[:11], backend=be))  # 11 → bucket 16
        assert odd.shape[0] == 11
        np.testing.assert_allclose(odd, full[:11], rtol=1e-5, atol=1e-5,
                                   err_msg=f"bucket padding corrupted {be}")


@pytest.mark.parametrize("family", FAMILY_PARAMS)
def test_jit_matches_eager(ds, family):
    """The jitted whole-plan forward is the same function as the eager
    per-bank dispatch — every backend, every family."""
    plan, inputs = _compiled(ds, family)
    for be in BACKENDS:
        np.testing.assert_allclose(
            np.asarray(plan(*inputs, backend=be)),
            np.asarray(plan(*inputs, backend=be, jit=False)),
            rtol=1e-4, atol=1e-4, err_msg=f"{family}:{be} jit != eager")


def test_kernel_strategy_parity(ds):
    """The MXU one-hot-matmul and interpreter gather-sum kernel strategies
    are semantics-identical (same descent bits, same rows accumulated)."""
    banks, _, (x,) = _family(ds, "mlp")
    p_mxu = build_plan(banks, strategy="mxu")
    p_lookup = build_plan(banks, strategy="lookup")
    for be in ("kernel", "kernel_q8"):
        np.testing.assert_allclose(
            np.asarray(p_mxu(x, backend=be)),
            np.asarray(p_lookup(x, backend=be)),
            rtol=1e-4, atol=1e-4, err_msg=f"strategy parity broke for {be}")


def test_bucket_batch_policy():
    from repro.engine import DEFAULT_BUCKETS, bucket_batch

    assert bucket_batch(1) == DEFAULT_BUCKETS[0]
    assert bucket_batch(8) == 8
    assert bucket_batch(9) == 16
    assert bucket_batch(1024) == 1024
    top = DEFAULT_BUCKETS[-1]
    assert bucket_batch(top + 1) == 2 * top       # beyond the ladder:
    assert bucket_batch(2 * top) == 2 * top       # multiples of the largest
    with pytest.raises(ValueError):
        bucket_batch(0)


# ---------------------------------------------------------------------------
# PlanRegistry + multi-model serving (ISSUE 3 tentpole)
# ---------------------------------------------------------------------------


def _fresh_banks(seed: int, n_out: int = 5) -> list:
    from repro.core.amm import init_pegasus_linear

    rng = np.random.default_rng(seed)
    return [init_pegasus_linear(
        rng.normal(size=(8, n_out)).astype(np.float32), None,
        rng.normal(size=(64, 8)).astype(np.float32), group_size=2, depth=3,
        lut_bits=None)]


def test_plan_registry_evicts_dropped_models():
    """Satellite regression: dropping a model must evict its memoized plan
    (the old memo's strong refs pinned models forever — and a recycled id()
    could then alias a stale plan)."""
    import gc

    from repro.engine import PlanRegistry

    reg = PlanRegistry()
    banks = _fresh_banks(11)
    plan = reg.plan_for(banks)
    assert reg.plan_for(banks) is plan
    assert len(reg) == 1
    del banks
    gc.collect()
    assert len(reg) == 0                          # dropped model → evicted
    # a plan the caller still holds keeps working after eviction
    x = jnp.asarray(np.random.default_rng(0).normal(size=(4, 8)), jnp.float32)
    assert np.isfinite(np.asarray(plan(x, backend="gather"))).all()


def test_plan_is_refcount_reclaimable():
    """An evicted plan must free on refcount drop, not wait for a gen-2 GC
    pass: the jitted forward's closure may not reference the plan object
    (the plan ↔ closure cycle this guards against once existed)."""
    import weakref

    from repro.engine import build_plan

    plan = build_plan(_fresh_banks(31))
    x = jnp.asarray(np.random.default_rng(0).normal(size=(4, 8)), jnp.float32)
    plan(x, backend="gather")                     # populate the jit cache
    ref = weakref.ref(plan)
    del plan
    assert ref() is None                          # no cycle: died on refcount


def test_plan_registry_bounded_and_explicit_eviction():
    from repro.engine import PlanRegistry

    reg = PlanRegistry(max_plans=2)
    keep = [_fresh_banks(s) for s in range(3)]
    plans = [reg.plan_for(m) for m in keep]
    assert len(reg) == 2                          # LRU-bounded
    assert reg.plan_for(keep[0]) is not plans[0]  # oldest was evicted → rebuilt
    assert reg.discard(keep[0]) == 1              # explicit eviction
    assert len(reg) == 1


def test_plan_registry_named_entries():
    from repro.engine import PlanRegistry

    reg = PlanRegistry()
    banks = _fresh_banks(21)
    plan = reg.register("mlp-a", banks, backend="gather")
    assert "mlp-a" in reg and reg.names() == ["mlp-a"]
    assert reg.get("mlp-a") is plan
    assert reg.model("mlp-a") is banks
    st = reg.stats()["mlp-a"]
    assert st["backend"] == "gather" and st["num_banks"] == 1
    assert reg.evict("mlp-a") and "mlp-a" not in reg
    assert not reg.evict("mlp-a")                 # double-evict is a no-op
    with pytest.raises(KeyError):
        reg.get("mlp-a")


def test_plan_registry_reregister_discards_replaced_memo():
    """Satellite: register() over an existing name must discard the replaced
    model's memo entry, exactly like evict() — otherwise the superseded
    plan lingered in the bounded memo until LRU churn or GC."""
    from repro.engine import PlanRegistry

    reg = PlanRegistry()
    a, b = _fresh_banks(51), _fresh_banks(52)
    plan_a = reg.register("m", a)
    assert len(reg) == 1
    plan_b = reg.register("m", b)               # replaces a
    assert plan_b is not plan_a
    assert reg.model("m") is b
    assert len(reg) == 1                        # a's memo entry discarded
    # b's memo entry intact (same build options as register's)
    assert reg.plan_for(b, backend="onehot") is plan_b
    # re-registering the SAME model must not discard its own entry
    assert reg.register("m", b) is plan_b
    assert len(reg) == 1


def test_plan_registry_reregister_same_banks_keeps_memo():
    """Re-registering a DIFFERENT wrapper over the SAME bank objects must
    not discard the (shared, bank-identity-keyed) memo entry the new model
    just produced — that discard would force a duplicate compile on the
    next plan_for."""
    from repro.engine import PlanRegistry

    reg = PlanRegistry()
    banks = _fresh_banks(55)
    a, b = list(banks), list(banks)            # distinct wrappers, same banks
    plan = reg.register("m", a)
    assert len(reg) == 1
    plan2 = reg.register("m", b)               # same key (element identity)
    assert plan2 is plan                       # memo hit, not a rebuild
    assert len(reg) == 1                       # and the entry survived


def test_plan_registry_recompile_refreshes_named_stats():
    """Satellite: the get() recompile-on-stale path must refresh the named
    entry's build stats (plan_build_ms re-timed, recompiles counted) —
    the old path left register()-time numbers on a replaced plan."""
    import dataclasses as dc

    from repro.engine import PlanRegistry

    reg = PlanRegistry()
    model = list(_fresh_banks(53))
    reg.register("m", model)
    st0 = reg.stats()["m"]
    assert st0["recompiles"] == 0
    p1 = reg.get("m")
    assert reg.stats()["m"]["recompiles"] == 0  # fresh get: no rebuild
    model[-1] = dc.replace(model[-1])           # refine()-style bank swap
    p2 = reg.get("m")
    assert p2 is not p1                         # stale → recompiled
    st1 = reg.stats()["m"]
    assert st1["recompiles"] == 1
    assert st1["plan_build_ms"] != st0["plan_build_ms"]   # re-timed
    assert reg.get("m") is p2                   # fresh again: stable


def test_plan_registry_concurrent_first_call_builds_once():
    """Tentpole thread-safety: N threads racing plan_for on one uncached
    model must serialize on the registry lock — exactly ONE build, every
    caller handed the same plan."""
    import threading

    from repro.engine import PlanRegistry

    reg = PlanRegistry()
    banks = _fresh_banks(54)
    before = STATS.plan_builds
    n = 4
    plans = [None] * n
    barrier = threading.Barrier(n)

    def first_call(i):
        barrier.wait()
        plans[i] = reg.plan_for(banks)

    threads = [threading.Thread(target=first_call, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert STATS.plan_builds == before + 1      # no double-compile
    assert all(p is plans[0] for p in plans)


def _multi_server(ds):
    """One server holding 3 mixed-family plans (mlp, ae fast; rnn cached)."""
    from repro.launch.serve import MultiModelServer

    server = MultiModelServer(backend="onehot")
    names = ("mlp", "ae", "rnn")
    for fam in names:
        model, _, _ = _family(ds, fam)
        server.add_model(fam, model)
    return server, names


@pytest.mark.slow
def test_multi_model_outputs_match_standalone_plans(ds):
    """N≥3 mixed-family models behind one server produce outputs identical
    to their standalone plans."""
    server, names = _multi_server(ds)
    reqs = []
    for fam in names:
        _, _, inputs = _family(ds, fam)
        reqs += [(fam, tuple(x[:8] for x in inputs)),
                 (fam, tuple(x[8:16] for x in inputs))]
    outs = server.serve(reqs)
    assert len(outs) == len(reqs)
    for (fam, inputs), out in zip(reqs, outs):
        _, plan, _ = _family(ds, fam)
        ref = np.asarray(plan(*inputs, backend="onehot"))
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5,
                                   err_msg=f"served {fam} != standalone plan")


@pytest.mark.slow
def test_multi_model_compile_caches_isolated(ds):
    """Serving model B must never retrace model A's plan: each plan compiles
    once per (backend, bucket) it actually serves, nothing more."""
    server, names = _multi_server(ds)
    for fam in names:
        _, _, inputs = _family(ds, fam)
        server.submit(fam, *(x[:8] for x in inputs))
    server.drain()
    per_plan = {f: server.registry.get(f).compile_stats()["traces"] for f in names}
    before = STATS.jit_traces
    for _ in range(2):                            # repeat rounds: all warm
        for fam in names:
            _, _, inputs = _family(ds, fam)
            server.submit(fam, *(x[:8] for x in inputs))
        server.drain()
    assert STATS.jit_traces == before             # zero cross-model retraces
    for fam in names:
        assert server.registry.get(fam).compile_stats()["traces"] == per_plan[fam]


@pytest.mark.slow
def test_multi_model_fair_scheduling_drains_all_queues(ds):
    """Round-robin: one micro-batch per pending model per turn — a burst on
    one model cannot monopolize the dispatch order — and every queue ends
    empty."""
    server, names = _multi_server(ds)
    server.max_batch = 8                          # force 2 chunks per model
    for fam in names:
        _, _, inputs = _family(ds, fam)
        for lo in (0, 8):
            server.submit(fam, *(x[lo : lo + 8] for x in inputs))
    assert server.pending() == {f: 2 for f in names}
    log_start = len(server.schedule_log)
    results = server.drain()
    assert server.pending() == {}                 # every queue drained
    assert sorted(results) == sorted(names)
    assert all(len(results[f]) == 2 for f in names)
    log = list(server.schedule_log)[log_start:]
    # 2 chunks per model, interleaved one-per-model per round
    assert log == list(names) + list(names)
    st = server.stats()["serving"]["models"]
    for fam in names:
        assert st[fam]["requests_served"] == 2
        assert st[fam]["batches_run"] == 2
        assert st[fam]["flows_served"] == 16


def test_multi_model_adopts_shared_registry(ds):
    """A server built on a pre-populated registry must serve its names
    (queues/counters adopted at construction, and lazily for names
    registered afterwards)."""
    from repro.engine import PlanRegistry
    from repro.launch.serve import MultiModelServer

    banks, _, (x,) = _family(ds, "mlp")
    reg = PlanRegistry()
    reg.register("pre", banks, backend="onehot")
    server = MultiModelServer(registry=reg, backend="onehot")
    assert server.models() == ["pre"]
    y = server.infer("pre", x[:4])
    assert np.asarray(y).shape[0] == 4
    reg.register("post", banks, backend="onehot")  # registered after init
    server.submit("post", x[:4])
    assert server.drain()["post"][0].shape[0] == 4
    st = server.stats()["serving"]["models"]
    assert st["pre"]["requests_served"] == 1
    assert st["post"]["requests_served"] == 1


def test_multi_model_unknown_name_and_success_only_stats(ds):
    from repro.launch.serve import MultiModelServer

    banks, _, (x,) = _family(ds, "mlp")
    server = MultiModelServer({"mlp": banks}, backend="onehot")
    with pytest.raises(KeyError, match="unknown model"):
        server.submit("nope", x[:4])
    server.submit("mlp", x[:4])
    with pytest.raises(ValueError, match="unknown backend"):
        server.drain(backend="dense")             # every model failed → raise
    st = server.stats()["serving"]["models"]["mlp"]
    assert (st["requests_served"], st["batches_run"]) == (0, 0)
    assert server.pending() == {"mlp": 1}         # failed drain is retryable
    out = server.drain()
    assert out["mlp"][0].shape[0] == 4
    st = server.stats()["serving"]["models"]["mlp"]
    assert (st["requests_served"], st["batches_run"]) == (1, 1)


# ---------------------------------------------------------------------------
# Cross-bank Primitive Fusion (ISSUE 4 tentpole)
# ---------------------------------------------------------------------------


def _chain_banks(seed: int, dims=(8, 8, 8, 5), group_size: int = 2,
                 depth: int = 3) -> list:
    """A sequential stack whose consecutive banks chain exactly (out == in):
    maximal fusion material."""
    from repro.core.amm import init_pegasus_linear

    rng = np.random.default_rng(seed)
    banks = []
    for d_in, d_out in zip(dims, dims[1:]):
        banks.append(init_pegasus_linear(
            rng.normal(size=(d_in, d_out)).astype(np.float32),
            rng.normal(size=d_out).astype(np.float32) * 0.1,
            rng.normal(size=(128, d_in)).astype(np.float32),
            group_size=group_size, depth=depth, lut_bits=None))
    return banks


def test_fuse_banks_groups_compatible_runs():
    """The planning pass groups maximal compatible runs and leaves anything
    incompatible (here: a different partition width v) as per-bank steps."""
    banks = [CompiledBank(l) for l in _chain_banks(40, dims=(8, 8, 8, 4))]
    steps = fuse_banks(banks)
    assert len(steps) == 1 and isinstance(steps[0], FusedBankStack)
    assert steps[0].banks == banks
    assert steps[0].ks == (4, 4, 4) and steps[0].n_out == 4

    # a bank with group_size=4 cannot join a v=2 run
    odd = CompiledBank(_chain_banks(41, dims=(4, 6), group_size=4)[0])
    mixed = fuse_banks([banks[0], banks[1], odd])
    assert len(mixed) == 2
    assert isinstance(mixed[0], FusedBankStack) and mixed[1] is odd

    # a lone bank (or a broken chain) stays per-bank
    assert fuse_banks([banks[0]]) == [banks[0]]


def test_fused_plan_parity_all_backends_and_strategies(ds):
    """Acceptance: the fused-stack output ≡ the per-bank output on every
    backend and both kernel strategies."""
    banks, _, (x,) = _family(ds, "mlp")
    for strategy in ("mxu", "lookup"):
        fused = build_plan(banks, strategy=strategy)
        unfused = build_plan(banks, strategy=strategy, fuse=False)
        assert fused.fused_groups >= 1 and unfused.fused_groups == 0
        for be in BACKENDS:
            np.testing.assert_allclose(
                np.asarray(fused(x, backend=be)),
                np.asarray(unfused(x, backend=be)),
                rtol=1e-4, atol=1e-4,
                err_msg=f"fused != per-bank for {be}/{strategy}")


def test_fused_synthetic_chain_parity():
    """K and N padding inside the stack (first bank wider, last bank
    narrower) must not leak into the output."""
    layers = _chain_banks(42, dims=(12, 8, 8, 3))
    x = jnp.asarray(np.random.default_rng(1).normal(size=(16, 12)), jnp.float32)
    fused = build_plan(layers)
    unfused = build_plan(layers, fuse=False)
    assert fused.fused_banks == 3
    for be in BACKENDS:
        np.testing.assert_allclose(
            np.asarray(fused(x, backend=be)),
            np.asarray(unfused(x, backend=be)), rtol=1e-4, atol=1e-4,
            err_msg=f"padded stack parity broke on {be}")


def test_fusion_does_not_add_traces(ds):
    """Acceptance: fusing never changes the compile count — one trace per
    (backend, bucket) on the fused plan, exactly like the per-bank plan."""
    banks, _, (x,) = _family(ds, "mlp")
    fused = build_plan(banks)
    unfused = build_plan(banks, fuse=False)
    for plan in (fused, unfused):
        for be in BACKENDS:
            plan(x, backend=be)
            plan(x, backend=be)            # warm: must not retrace
            plan(x[:9], backend=be)        # rounds into the same bucket
    assert fused.compile_stats()["traces"] == unfused.compile_stats()["traces"]
    assert fused.compiled_buckets == unfused.compiled_buckets
    for plan in (fused, unfused):
        assert plan.compile_stats()["traces"] == len(plan.compiled_buckets)


def test_fused_stack_falls_back_on_bad_operands(ds):
    """A stack the kernel refuses (ValueError, e.g. a mis-sized ks tuple)
    must fall back to the per-bank chain instead of raising."""
    banks, _, (x,) = _family(ds, "mlp")
    stack = fuse_banks([CompiledBank(l) for l in banks])[0]
    ref = np.asarray(stack.apply(x, "kernel"))
    stack.ks = stack.ks + (stack.ks[-1],)      # now inconsistent with L
    out = np.asarray(stack.apply(x, "kernel"))  # ValueError → per-bank path
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)


def test_compile_stats_reports_pad_waste_and_fusion(ds):
    banks, _, (x,) = _family(ds, "mlp")
    plan = build_plan(banks)
    plan(x[:11], backend="gather")             # 11 → bucket 16: 5 filler rows
    plan(x, backend="gather")                  # exact bucket: zero filler
    st = plan.compile_stats()
    assert st["fused_groups"] == 1 and st["fused_banks"] == len(banks)
    # cumulative per bucket: 11 + 16 requested over 2×16 dispatched
    assert abs(st["pad_waste"]["gather@16"] - (1 - 27 / 32)) < 1e-3
    # MultiModelServer surfaces the same counters per model
    from repro.launch.serve import MultiModelServer

    server = MultiModelServer({"mlp": banks}, backend="gather")
    server.infer("mlp", x[:11])
    mst = server.stats()["engine"]["models"]["mlp"]
    assert mst["fused_groups"] == 1
    assert mst["pad_waste"]["gather@16"] == round(5 / 16, 4)


def test_compile_stats_folds_fused_operand_padding_into_pad_waste():
    """Kernel-backend pad_waste must charge the fused stack's Kmax/width
    operand padding, not just batch filler; the fallback backends run on
    true-size tables and keep the batch-only number. Pinned on the
    (12, 8, 8, 3) chain: ks=(6, 4, 4), C=8, ns=(8, 8, 3), activation width
    W = round8(max(6·2, 8)) = 16 → useful = 6·8·8 + 4·8·8 + 4·8·3 = 736 LUT
    cells of the 3·6·8·16 = 2304 the stacked slab dispatches."""
    layers = _chain_banks(42, dims=(12, 8, 8, 3))
    plan = build_plan(layers)
    assert plan.fused_banks == 3
    st = plan.compile_stats()
    fused = st["pad_waste_fused"]["group0"]
    assert (fused["layers"], fused["kmax"], fused["width"]) == (3, 6, 16)
    assert fused["frac"] == round(1 - 736 / 2304, 4) == 0.6806

    x = jnp.asarray(np.random.default_rng(1).normal(size=(11, 12)), jnp.float32)
    plan(x, backend="kernel")
    plan(x, backend="gather")
    waste = plan.compile_stats()["pad_waste"]
    # gather dispatches per-bank true-size tables: batch filler only
    assert waste["gather@16"] == round(5 / 16, 4)
    # kernel dispatches the padded slab: batch filler × operand efficiency
    assert waste["kernel@16"] == round(1 - (11 / 16) * (736 / 2304), 4)
    # a fully-unfused plan has no operand padding: backends agree again
    unfused = build_plan(layers, fuse=False)
    unfused(x, backend="kernel")
    assert unfused.compile_stats()["pad_waste"]["kernel@16"] == \
        round(5 / 16, 4)
    assert unfused.compile_stats()["pad_waste_fused"] == {}


def test_fuse_flag_participates_in_plan_key(ds):
    banks, _, (x,) = _family(ds, "mlp")
    p_fused = plan_for(banks)
    p_unfused = plan_for(banks, fuse=False)
    assert p_fused is not p_unfused
    assert plan_for(banks) is p_fused           # both memoized independently
    assert plan_for(banks, fuse=False) is p_unfused
    assert p_unfused.fused_groups == 0


def test_donated_inputs_never_invalidate_caller_arrays(ds):
    """__call__ donates its padded buffers to the jitted forward; a caller's
    array must survive both the exact-bucket and the padded path."""
    banks, plan, (x,) = _family(ds, "mlp")
    x16 = jnp.asarray(x[:16])                  # exact bucket size
    y1 = np.asarray(plan(x16, backend="gather"))
    y2 = np.asarray(plan(x16, backend="gather"))
    np.testing.assert_allclose(y1, y2)
    assert not x16.is_deleted()
    _ = np.asarray(x16 + 1.0)                  # still usable
    x11 = jnp.asarray(x[:11])                  # padded up to bucket 16
    plan(x11, backend="gather")
    plan(x11, backend="gather")
    assert not x11.is_deleted()


def test_ops_layout_memo_pads_static_operands_once():
    """Satellite: the ops.py wrappers must not rebuild the kernel layout of
    lut/thr/feat_oh per call — one layout build per (layer, q8?), cache
    hits after."""
    from repro.core.amm import init_pegasus_linear
    from repro.kernels.fuzzy_lut.ops import (
        LAYOUT_STATS, fuzzy_lut_matmul, fuzzy_lut_matmul_q8)

    rng = np.random.default_rng(5)
    layer = init_pegasus_linear(
        rng.normal(size=(24, 10)).astype(np.float32), None,
        rng.normal(size=(256, 24)).astype(np.float32), group_size=4, depth=3,
        lut_bits=None)                          # K=6, N=10: both padded
    x = jnp.asarray(rng.normal(size=(5, 24)).astype(np.float32))
    fuzzy_lut_matmul(layer, x)
    builds = LAYOUT_STATS["layout_builds"]
    hits = LAYOUT_STATS["cache_hits"]
    fuzzy_lut_matmul(layer, x)
    fuzzy_lut_matmul(layer, x[:3])
    assert LAYOUT_STATS["layout_builds"] == builds       # no re-pad per call
    assert LAYOUT_STATS["cache_hits"] >= hits + 2
    # the q8 wrapper keeps its own (quantized) layout entry
    fuzzy_lut_matmul_q8(layer, x)
    builds_q8 = LAYOUT_STATS["layout_builds"]
    fuzzy_lut_matmul_q8(layer, x)
    assert LAYOUT_STATS["layout_builds"] == builds_q8


@pytest.mark.slow
def test_fuse_nmax_cap_splits_ballooning_groups():
    """Satellite: one wide bank must not balloon a narrow stack's padded
    [L, W, Kmax·C] footprint — the run splits at the cap (the wide bank
    stands alone), narrow neighbors still fuse, and outputs stay identical
    to the unfused path."""
    layers = _chain_banks(45, dims=(8, 8, 64, 8, 5))    # N = (8, 64, 8, 5)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(16, 8)), jnp.float32)
    wide = build_plan(layers)                   # default cap: all 4 banks fuse
    assert (wide.fused_groups, wide.fused_banks) == (1, 4)
    capped = build_plan(layers, fuse_nmax_cap=16)
    # b0 alone (joining N=64 would balloon it), b1 (N=64) alone, b2+b3 fuse
    assert (capped.fused_groups, capped.fused_banks) == (1, 2)
    unfused = build_plan(layers, fuse=False)
    for be in BACKENDS:
        np.testing.assert_allclose(
            np.asarray(capped(x, backend=be)),
            np.asarray(unfused(x, backend=be)), rtol=1e-4, atol=1e-4,
            err_msg=f"nmax-capped plan parity broke on {be}")
        np.testing.assert_allclose(
            np.asarray(wide(x, backend=be)),
            np.asarray(unfused(x, backend=be)), rtol=1e-4, atol=1e-4)


@pytest.mark.slow
def test_fuse_nmax_cap_allows_uniformly_wide_runs():
    """Equal-width banks above the cap add no padding — they still fuse."""
    layers = _chain_banks(46, dims=(64, 64, 64))        # N = (64, 64)
    plan = build_plan(layers, fuse_nmax_cap=16)
    assert (plan.fused_groups, plan.fused_banks) == (1, 2)
    x = jnp.asarray(np.random.default_rng(5).normal(size=(8, 64)), jnp.float32)
    unfused = build_plan(layers, fuse=False)
    for be in ("gather", "kernel"):
        np.testing.assert_allclose(
            np.asarray(plan(x, backend=be)),
            np.asarray(unfused(x, backend=be)), rtol=1e-4, atol=1e-4)


def test_fuse_nmax_cap_participates_in_plan_key(ds):
    from repro.engine import DEFAULT_FUSE_NMAX_CAP

    banks, _, _ = _family(ds, "mlp")
    p_default = plan_for(banks)                 # N=(32,32,32,3): one group of 4
    p_capped = plan_for(banks, fuse_nmax_cap=1)
    assert p_default is not p_capped
    # cap 1 splits the narrow classifier off; the equal-width hidden run
    # stays fused (uniform width adds no padding)
    assert (p_default.fused_groups, p_default.fused_banks) == (1, 4)
    assert (p_capped.fused_groups, p_capped.fused_banks) == (1, 3)
    # the default cap is normalized into the key: explicit == implicit
    assert plan_for(banks, fuse_nmax_cap=DEFAULT_FUSE_NMAX_CAP) is p_default
    assert plan_for(banks, fuse_nmax_cap=None) is not p_default


def test_multi_model_drain_isolates_failing_model(ds):
    """A model whose dispatch raises must not lose the other models'
    results, corrupt any counters, or drop its own (retryable) queue."""
    from repro.launch.serve import MultiModelServer

    banks, _, (x,) = _family(ds, "mlp")
    server = MultiModelServer({"good": banks, "bad": banks}, backend="onehot")
    server.submit("good", x[:4])
    server.submit("bad", x[:4, : x.shape[1] // 2])   # wrong feature width
    results = server.drain()                      # good drains, bad isolated
    assert list(results) == ["good"]
    assert results["good"][0].shape[0] == 4
    assert "bad" in server.last_drain_errors
    st = server.stats()["serving"]["models"]
    assert (st["good"]["requests_served"], st["good"]["batches_run"]) == (1, 1)
    assert (st["bad"]["requests_served"], st["bad"]["batches_run"]) == (0, 0)
    assert server.pending() == {"bad": 1}         # bad queue kept for retry
    # a permanently-bad request poisons its queue — discard_pending clears it
    assert server.discard_pending("bad") == 1
    assert server.pending() == {}
    # serve(): the failed model's error carries the served partial results
    with pytest.raises(Exception) as ei:
        server.serve([("good", x[:4]), ("bad", x[:4, : x.shape[1] // 2])])
    assert ei.value.partial_results["good"][0].shape[0] == 4
    server.discard_pending("bad")
