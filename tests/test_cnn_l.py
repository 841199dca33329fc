"""CNN-L, the raw-payload model, held to a plain reference.

The model is a two-level NAM over the 8 packets of a flow: per packet, bank
1 maps the 62 raw bytes (60 payload bytes, length, IPD) to a hidden
pre-activation, bank 2 maps that to an embedding, one fuzzy tree indexes
``tanh`` of the embedding, and a logit table read at that index is summed
over the packets, plus a bias. The plan takes the packed per-packet
records ``[B, 8, 62]`` uint8 (``nets.cnn.pack_packets``).

The reference below is that sentence in plain ``jax.numpy``, float32, with
no kernel, bucket or cache. The model's trees and tables are random, drawn
from a seed, at the published widths (62 → 64 → 16) and a small depth.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.amm import PegasusLinear
from repro.core.fuzzy_tree import FuzzyTree
from repro.engine import build_plan
from repro.nets.cnn import (
    PACKET_FEATURES, PegasusCNNL, pack_packets, pegasus_cnn_l_apply,
)

WINDOW, HIDDEN, EMB, CLASSES = 8, 64, 16, 3
DEPTH, INDEX_BITS, FLOWS = 4, 3, 32
# Only comparisons of computed values can route differently: bank 1 reads
# exact integers against half-integer thresholds. Bank 2 and the index tree
# compare sums of 62 or 64 unit-scale table rows, which the kernel adds in
# another order than jnp.sum. Float32 rounding of such a sum is of order
# sqrt(64) ulps, about 5e-7, so flows whose every computed comparison clears
# its threshold by 1e-5 (relative to 1 + |threshold|; twenty times that)
# must route alike and are compared.
NEAR = 1e-5
# Routed alike, a flow's logits are the same 8 table rows and the bias, of
# magnitude up to about 10, which a jitted reduction may add in another
# order: each of the 9 adds rounds by at most half an ulp (5e-7 at 10).
ATOL = 1e-5


def _inorder(depth: int) -> list[int]:
    """Heap indices of a complete tree of ``depth`` levels, in order."""
    def walk(n):
        if n >= (1 << depth) - 1:
            return []
        return walk(2 * n + 1) + [n] + walk(2 * n + 2)
    return walk(0)


def _bank(rng, k: int, n: int, lo: float, hi: float, *, integer: bool):
    """``k`` one-feature trees, each a search tree over ``[lo, hi]``, and
    a ``[k, C, n]`` table of variance ``1/k``."""
    c = 1 << DEPTH
    vals = np.sort(rng.uniform(lo, hi, (k, c - 1)), axis=1)
    if integer:
        vals = np.floor(vals) + 0.5
    thr = np.empty((k, c - 1), np.float32)
    thr[:, _inorder(DEPTH)] = vals
    trees = FuzzyTree(jnp.zeros((k, c - 1), jnp.int32), jnp.asarray(thr),
                      jnp.zeros((k, c, 1), jnp.float32))
    lut = rng.normal(size=(k, c, n)) / np.sqrt(k)
    return PegasusLinear(trees, jnp.asarray(lut, jnp.float32),
                         jnp.asarray(0.1 * rng.normal(size=n), jnp.float32),
                         group_size=1)


def _model(seed: int) -> PegasusCNNL:
    rng = np.random.default_rng(seed)
    nodes = (1 << INDEX_BITS) - 1
    tree = FuzzyTree(
        jnp.asarray(rng.integers(0, EMB, nodes), jnp.int32),
        jnp.asarray(rng.uniform(-0.8, 0.8, nodes), jnp.float32),
        jnp.zeros((1 << INDEX_BITS, EMB), jnp.float32))
    return PegasusCNNL(
        bank1=_bank(rng, PACKET_FEATURES, HIDDEN, 0, 255, integer=True),
        bank2=_bank(rng, HIDDEN, EMB, -2, 2, integer=False),
        emb_tree=tree,
        logit_lut=jnp.asarray(rng.normal(size=(1 << INDEX_BITS, CLASSES)),
                              jnp.float32),
        bias=jnp.asarray(rng.normal(size=CLASSES), jnp.float32),
        index_bits=INDEX_BITS)


def _flows(seed: int, n: int):
    rng = np.random.default_rng(seed)
    seq = rng.integers(0, 256, (n, WINDOW, 2), dtype=np.uint8)
    payload = rng.integers(0, 256, (n, WINDOW, 60), dtype=np.uint8)
    return seq, payload


def _ref_bank(p: PegasusLinear, x):
    """One bank: each group's tree descended, one table row per group,
    summed with the bias. Returns the output and, per row, the smallest
    relative distance of a compared value to its threshold."""
    feats, thrs = p.trees.features, p.trees.thresholds
    k, v = feats.shape[0], p.group_size
    xg = x.reshape(x.shape[0], k, v)
    group = jnp.arange(k)
    node = jnp.zeros((x.shape[0], k), jnp.int32)
    margin = jnp.full((x.shape[0],), jnp.inf)
    for _ in range(DEPTH):
        t = thrs[group, node]
        val = jnp.take_along_axis(xg, feats[group, node][..., None], -1)[..., 0]
        margin = jnp.minimum(margin, jnp.min(jnp.abs(val - t) / (1 + jnp.abs(t)), -1))
        node = 2 * node + 1 + (val > t).astype(jnp.int32)
    rows = p.lut[group, node - ((1 << DEPTH) - 1)]
    return rows.sum(axis=1) + p.bias, margin


def reference(m: PegasusCNNL, x: np.ndarray):
    """``x [B, 8, 62]`` uint8 → ``(logits [B, 3], margin [B])``; the
    margin covers bank 2 and the index tree (bank 1 compares integers)."""
    with jax.default_matmul_precision("highest"):
        b = x.shape[0]
        rows = jnp.asarray(x, jnp.float32).reshape(b * WINDOW, PACKET_FEATURES)
        h, _ = _ref_bank(m.bank1, rows)
        e, margin = _ref_bank(m.bank2, h)
        emb = jnp.tanh(e)
        tree = m.emb_tree
        node = jnp.zeros((b * WINDOW,), jnp.int32)
        for _ in range(INDEX_BITS):
            t = tree.thresholds[node]
            val = jnp.take_along_axis(emb, tree.features[node][:, None], 1)[:, 0]
            margin = jnp.minimum(margin, jnp.abs(val - t) / (1 + jnp.abs(t)))
            node = 2 * node + 1 + (val > t).astype(jnp.int32)
        contrib = m.logit_lut[node - ((1 << INDEX_BITS) - 1)]
        logits = contrib.reshape(b, WINDOW, CLASSES).sum(axis=1) + m.bias
        return (np.asarray(logits),
                np.asarray(margin.reshape(b, WINDOW).min(axis=1)))


def _assert_matches(got, want, margin):
    far = margin >= NEAR
    assert far.mean() >= 0.75, far.mean()     # most flows are compared
    assert got.shape == want.shape and np.isfinite(got).all()
    np.testing.assert_allclose(np.asarray(got)[far], want[far], rtol=0,
                               atol=ATOL)


def test_pack_packets_round_trips():
    seq, payload = _flows(1, 5)
    for s, p in ((seq, payload), (jnp.asarray(seq), jnp.asarray(payload))):
        x = pack_packets(s, p)
        assert x.shape == (5, WINDOW, PACKET_FEATURES) and x.dtype == np.uint8
        assert isinstance(x, jax.Array) == isinstance(s, jax.Array)
        np.testing.assert_array_equal(np.asarray(x)[..., :60], payload)
        np.testing.assert_array_equal(np.asarray(x)[..., 60:], seq)


@pytest.mark.parametrize("backend,strategy", [
    ("gather", "auto"), ("kernel", "lookup"), ("kernel", "mxu")])
def test_plan_matches_reference(backend, strategy):
    model = _model(7)
    seq, payload = _flows(8, FLOWS)
    x = pack_packets(seq, payload)
    plan = build_plan(model, backend=backend, interpret=True,
                      strategy=strategy, audit="off")
    want, margin = reference(model, x)
    _assert_matches(plan(x), want, margin)
    # the (seq, payload) entry point packs through the same helper
    np.testing.assert_array_equal(
        np.asarray(pegasus_cnn_l_apply(model, seq, payload, backend=backend)),
        np.asarray(plan(x, jit=False)))


def test_served_uint8_requests_match_reference(monkeypatch):
    """Ragged one-array uint8 requests through the async server on the
    kernel backend: each answer is the reference's, every input reaches
    the plan as uint8, and nothing falls back to gather."""
    from repro.engine.plan import ExecutionPlan
    from repro.launch.serve import AsyncMultiModelServer, InferRequest

    seen = []
    call = ExecutionPlan.__call__

    def recording(self, *inputs, **kw):
        seen.extend(x.dtype for x in inputs)
        return call(self, *inputs, **kw)

    monkeypatch.setattr(ExecutionPlan, "__call__", recording)
    model = _model(11)
    sizes = [1, 5, 13, 32, 7, 3]
    x = pack_packets(*_flows(12, sum(sizes)))
    want, margin = reference(model, x)
    with AsyncMultiModelServer(backend="kernel") as server:
        server.add_model("cnn_l", model, backend="kernel")
        starts = np.cumsum([0] + sizes[:-1])
        futs = [server.submit(InferRequest("cnn_l", x[s:s + n]))
                for s, n in zip(starts, sizes)]
        outs = [f.result(timeout=300) for f in futs]
        health = server.stats()["health"]
    for n, res in zip(sizes, outs):
        assert res.flows == n and np.shape(res.output) == (n, CLASSES)
    got = np.concatenate([np.asarray(r.output) for r in outs])
    _assert_matches(got, want, margin)
    assert seen and all(d == np.uint8 for d in seen), seen
    assert health["degraded_models"] == []
    assert health["models"]["cnn_l"].get("fallback_batches", 0) == 0
