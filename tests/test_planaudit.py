"""Tests for repro.analysis.planaudit — the PGA1xx plan auditor.

Every rule gets a seeded-violation fixture: a plan (or tampered plan)
constructed to trip exactly that invariant, plus the clean-plan side
showing the rule stays quiet on healthy builds. PGA101's analytic bound is
validated against brute-force enumeration of every leaf combination.
"""

from __future__ import annotations

import json
import warnings

import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.planaudit import (AuditConfig, PlanAuditError,
                                      accumulation_grid, audit_plan,
                                      overflow_bound)
from repro.core.amm import PegasusLinear, init_pegasus_linear
from repro.core.fuzzy_tree import FuzzyTree
from repro.dataplane.resources import SwitchBudget
from repro.engine import PlanRegistry, build_plan, plan_for
from repro.kernels.fuzzy_lut.quantized import quantize_lut_int8

RNG = np.random.default_rng(20250808)


def _chain_banks(seed, dims=(8, 8, 8, 5), group_size=2, depth=3,
                 row_scale=None):
    """Sequential chaining banks; ``row_scale[g]`` multiplies group ``g``'s
    weight rows of the FIRST bank (seeded per-group amax ladders)."""
    rng = np.random.default_rng(seed)
    banks = []
    for j, (a, b) in enumerate(zip(dims, dims[1:])):
        w = rng.normal(size=(a, b)).astype(np.float32)
        if j == 0 and row_scale is not None:
            for g, s in enumerate(row_scale):
                w[g * group_size:(g + 1) * group_size] *= s
        banks.append(init_pegasus_linear(
            w, rng.normal(size=b).astype(np.float32) * 0.1,
            rng.normal(size=(128, a)).astype(np.float32),
            group_size=group_size, depth=depth, lut_bits=None))
    return banks


def _rules(report, rule):
    return [f for f in report.findings if f.rule == rule]


# ---------------------------------------------------------------------------
# PGA101 — fixed-point overflow
# ---------------------------------------------------------------------------


def test_pga101_bound_matches_brute_force():
    """The separable bound IS the reachable worst case: enumerate every
    (c_1..c_K) leaf combination of a small random table and compare."""
    k, c, n = 3, 4, 2
    q8 = RNG.integers(-127, 128, size=(k, c, n)).astype(np.int64)
    scales = np.abs(RNG.normal(size=k)).astype(np.float64) + 1e-3
    bias = RNG.normal(size=n).astype(np.float64)

    grid = accumulation_grid(scales)
    contrib = np.rint(q8 * (scales[:, None, None] / grid))
    worst = 0.0
    for c0 in range(c):
        for c1 in range(c):
            for c2 in range(c):
                tot = (contrib[0, c0] + contrib[1, c1] + contrib[2, c2]
                       + np.rint(bias / grid))
                worst = max(worst, float(np.abs(tot).max()))
    assert overflow_bound(q8, scales, bias) == pytest.approx(worst)


def test_pga101_grid_flushes_dead_groups():
    """A dead group (scale floored near 1e-8/127) must not drag the grid
    1e7x below the live groups — its whole amplitude rounds to zero there."""
    live = np.array([1e-2, 3e-2, 2e-2])
    dead = np.array([1e-8 / 127.0])
    assert accumulation_grid(np.concatenate([live, dead])) == \
        pytest.approx(live.min())
    # but a gradual ladder (every step < 254x) keeps every group live
    ladder = np.array([1.0, 1e2, 1e4, 1e6])
    assert accumulation_grid(ladder) == pytest.approx(1.0)


def _overflow_banks(seed=3):
    """First bank carries a per-group amax ladder spanning 1e8 in factor-100
    steps — no group flushable, worst-case accumulator ~1e10 >> int32."""
    return _chain_banks(seed, dims=(10, 6), group_size=2,
                        row_scale=[100.0 ** g for g in range(5)])


def test_pga101_overflow_seeded_violation():
    plan = build_plan(_overflow_banks(), audit="off")
    found = _rules(audit_plan(plan), "PGA101")
    assert found and found[0].severity == "error"
    assert found[0].metrics["bound"] > 2**31 - 1
    # healthy chain: quiet
    clean = build_plan(_chain_banks(0), audit="off")
    assert not _rules(audit_plan(clean), "PGA101")


# ---------------------------------------------------------------------------
# PGA102 — quantization fidelity
# ---------------------------------------------------------------------------


def test_pga102_tampered_q8_table():
    plan = build_plan(_chain_banks(1), audit="off")
    assert not _rules(audit_plan(plan), "PGA102")
    # zero one bank's int8 table: dequant error becomes ~the group amax
    plan.banks[1].lut_q8 = jnp.zeros_like(plan.banks[1].lut_q8)
    found = _rules(audit_plan(plan), "PGA102")
    assert found and found[0].severity == "error"
    assert found[0].site == "bank[1]"
    assert found[0].metrics["rel_err"] > 0.5


# ---------------------------------------------------------------------------
# PGA103 — VMEM footprint
# ---------------------------------------------------------------------------


def test_pga103_vmem_budget():
    plan = build_plan(_chain_banks(2), audit="off")
    assert not _rules(audit_plan(plan), "PGA103")      # 16 MiB: plenty
    found = _rules(audit_plan(plan, AuditConfig(vmem_budget_bytes=4096)),
                   "PGA103")
    assert found and all(f.severity == "error" for f in found)
    assert all(f.metrics["bytes"] > 4096 for f in found)
    # warning band: budget between need and margin*need
    need = max(f.metrics["bytes"] for f in found)
    rep = audit_plan(plan, AuditConfig(vmem_budget_bytes=int(need * 1.5)))
    assert any(f.severity == "warning" for f in _rules(rep, "PGA103"))
    # a single bank split over group tiles is priced per grid step: RNN-B's
    # recurrent width (K=24 groups of 1, C=256) runs 8 groups × 128 flows,
    # [24, 128] activation blocks, [2048, 24] select and [24, 2048] LUT
    k, c, n = 24, 256, 24
    trees = FuzzyTree(jnp.zeros((k, c - 1), jnp.int32),
                      jnp.zeros((k, c - 1)), jnp.zeros((k, c, 1)))
    wide = build_plan(PegasusLinear(trees, jnp.zeros((k, c, n)), None,
                                    group_size=1), audit="off")
    (f,) = _rules(audit_plan(wide, AuditConfig(vmem_budget_bytes=4096)),
                  "PGA103")
    assert f.metrics["shape"] == "K=24, Kt=8, W=24, bt=128"
    nodes, w, bt = 8 * c, 24, 128
    floats = (2 * w * bt + nodes * (w + 1) + w * nodes + nodes + w
              + 4 * nodes * bt)
    assert f.metrics["bytes"] == 4 * floats + w * nodes


# ---------------------------------------------------------------------------
# PGA104 — tile / lane alignment
# ---------------------------------------------------------------------------


def test_pga104_hidden_pad_rows_and_mxu_lanes():
    # bucket 1536 vs single-bank batch tile 1024 (K·C = 32 nodes): 512
    # hidden rows per call
    plan = build_plan(_chain_banks(4), fuse=False,
                      bucket_sizes=(8, 1536), audit="off")
    assert {b.tiles[0] for b in plan.banks} == {1024}
    found = _rules(audit_plan(plan), "PGA104")
    hidden = [f for f in found if f.metrics.get("hidden_rows")]
    assert hidden and hidden[0].metrics["hidden_rows"] == 512
    assert hidden[0].severity == "warning"
    # power-of-two ladder: no hidden padding
    clean = build_plan(_chain_banks(4), audit="off")
    assert not _rules(audit_plan(clean), "PGA104")
    # mxu strategy, narrow banks: the batch rides the lanes, so no lane
    # finding whatever the LUT width
    mxu = build_plan(_chain_banks(4), strategy="mxu", fuse=False,
                     audit="off")
    assert not _rules(audit_plan(mxu), "PGA104")


# ---------------------------------------------------------------------------
# PGA105 — fusion-rejection explanations
# ---------------------------------------------------------------------------


def test_pga105_explanations():
    # fully fused chain: nothing to explain
    fused = build_plan(_chain_banks(5), audit="off")
    assert fused.fused_groups == 1
    assert not _rules(audit_plan(fused), "PGA105")

    # fuse=False: compatible pair, fusion disabled
    off = build_plan(_chain_banks(5), fuse=False, audit="off")
    found = _rules(audit_plan(off), "PGA105")
    assert found and all(f.severity == "info" for f in found)
    assert any("fuse=False" in f.message for f in found)

    # nmax_cap balloon split: widths (8, 4) with cap 4
    capped = build_plan(_chain_banks(6, dims=(8, 8, 4)), fuse_nmax_cap=4,
                        audit="off")
    assert capped.fused_groups == 0
    found = _rules(audit_plan(capped), "PGA105")
    assert found and "fuse_nmax_cap=4" in found[0].message

    # v-mismatch: a group_size=4 bank cannot join a v=2 chain
    v2 = _chain_banks(7, dims=(8, 8))
    v4 = _chain_banks(8, dims=(8, 5), group_size=4)
    mixed = build_plan(v2 + v4, audit="off")
    found = _rules(audit_plan(mixed), "PGA105")
    assert found and "partition width v 2 != 4" in found[0].message


def test_pga105_cnn_l_builder_note():
    """The CNN-L b1→b2 chain ROADMAP names: shape-compatible, but the
    builder compiles banks individually — surfaced as a ratchet candidate."""
    b1, b2 = _chain_banks(9, dims=(8, 8, 8))

    class _FakeCNNL:
        bank1, bank2 = b1, b2
        emb_tree = None
        logit_lut = np.zeros((4, 3), np.float32)
        bias = np.zeros(3, np.float32)

    plan = build_plan(_FakeCNNL(), audit="off")
    assert plan.family == "cnn_l"
    found = _rules(audit_plan(plan), "PGA105")
    assert found and found[0].site == "bank[0]→bank[1]"
    assert "fusion ratchet candidate" in found[0].message


# ---------------------------------------------------------------------------
# PGA106 — dataplane resource fit
# ---------------------------------------------------------------------------


def test_pga106_dataplane_target():
    plan = build_plan(_chain_banks(10), audit="off")
    # no target declared: rule is off entirely
    assert not _rules(audit_plan(plan), "PGA106")
    # tofino2: this toy plan fits — one info finding with utilization
    rep = audit_plan(plan, AuditConfig(target="tofino2"))
    found = _rules(rep, "PGA106")
    assert [f.severity for f in found] == ["info"]
    assert found[0].metrics["sram_pct"] < 100
    # a tiny budget: validate() errors + recirculation warning
    tiny = SwitchBudget(stages=2, sram_bits_per_stage=2048,
                        tcam_bits_per_stage=64, action_bus_bits=64,
                        phv_bits=256)
    found = _rules(audit_plan(plan, AuditConfig(target=tiny)), "PGA106")
    sev = [f.severity for f in found]
    assert "error" in sev and "warning" in sev
    with pytest.raises(ValueError, match="unknown dataplane target"):
        audit_plan(plan, AuditConfig(target="tofino9"))


# ---------------------------------------------------------------------------
# lifecycle: build_plan audit modes, registry caching, stats surfaces
# ---------------------------------------------------------------------------


def test_build_plan_audit_modes():
    bad = _overflow_banks()
    with pytest.raises(PlanAuditError, match="PGA101"):
        build_plan(bad, audit="error")
    with pytest.warns(UserWarning, match="plan audit"):
        plan = build_plan(bad, audit="warn")
    assert plan.audit_report is not None
    assert plan.audit_report.counts["error"] == 1
    off = build_plan(bad, audit="off")
    assert off.audit_report is None
    with pytest.raises(ValueError, match="audit must be"):
        build_plan(_chain_banks(11), audit="loud")


def test_clean_build_attaches_report_without_warning():
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        plan = build_plan(_chain_banks(12))           # default audit="warn"
    assert plan.audit_report is not None and plan.audit_report.ok
    st = plan.compile_stats()
    assert st["audit"] == {"error": 0, "warning": 0, "info": 0}
    assert build_plan(_chain_banks(12), audit="off").compile_stats()[
        "audit"] is None


def test_suppress_and_report_shape():
    plan = build_plan(_overflow_banks(), audit="off")
    rep = audit_plan(plan, AuditConfig(suppress=("PGA101",)))
    assert not _rules(rep, "PGA101") and rep.ok
    rep = audit_plan(plan)
    doc = rep.to_dict()
    assert doc["counts"]["error"] == 1 and doc["ok"] is False
    assert doc["summary"]["family"] == "sequential"
    assert json.dumps(doc)                            # JSON-serializable
    assert "PGA101" in str(rep)


def test_registry_audit_kwarg_and_lazy_report():
    banks = _chain_banks(13)
    # audit mode must NOT fork the memo key
    assert plan_for(banks) is plan_for(banks, audit="off")
    reg = PlanRegistry()
    reg.register("m", banks, backend="gather", audit="off")
    assert reg.get("m").audit_report is None
    rep = reg.audit_report("m")                       # lazy, then cached
    assert rep.ok and reg.get("m").audit_report is rep
    assert reg.stats()["m"]["audit"] == rep.counts


# ---------------------------------------------------------------------------
# satellite: quantize_lut_int8 round-trip property test
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", range(5))
def test_quantize_lut_int8_roundtrip_properties(seed):
    """Per-group symmetric int8: |q| ≤ 127, sign-symmetric, and every
    dequantized element within half a quantization step of the source."""
    rng = np.random.default_rng(seed)
    k, c, n = (int(rng.integers(1, 6)), 2 ** int(rng.integers(1, 4)),
               int(rng.integers(1, 9)))
    lut = (rng.normal(size=(k, c, n)) * 10.0 ** rng.integers(-3, 3)
           ).astype(np.float32)
    q, scale = quantize_lut_int8(jnp.asarray(lut))
    q, scale = np.asarray(q, np.int64), np.asarray(scale, np.float64)
    assert q.dtype == np.int64 and np.abs(q).max() <= 127
    assert scale.shape == (k,) and (scale > 0).all()
    err = np.abs(lut - q * scale[:, None, None])
    # round-to-nearest: err ≤ scale/2 per element, with fp32 slack
    assert (err <= scale[:, None, None] * 0.5 + 1e-6).all()
    # symmetric: quantizing -lut flips the codes, same scales
    q_neg, scale_neg = quantize_lut_int8(jnp.asarray(-lut))
    np.testing.assert_array_equal(np.asarray(q_neg, np.int64), -q)
    np.testing.assert_allclose(np.asarray(scale_neg, np.float64), scale)


def test_quantize_lut_int8_degenerate_group():
    """An all-zero group floors its scale instead of dividing by zero."""
    lut = np.zeros((2, 4, 3), np.float32)
    lut[1] = 5.0
    q, scale = quantize_lut_int8(jnp.asarray(lut))
    assert np.asarray(q)[0].max() == 0
    assert float(np.asarray(scale)[0]) == pytest.approx(1e-8 / 127.0)
    assert np.asarray(q)[1].max() == 127


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def test_cli_json_smoke(capsys, tmp_path):
    """One small family end-to-end through the CLI: exit 0, JSON parses,
    every rule documented, report file written."""
    from repro.analysis.planaudit import main

    out = tmp_path / "audit.json"
    rc = main(["--families", "mlp", "--backends", "gather", "--flows", "16",
               "--steps", "2", "--json", "--out", str(out)])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert doc["totals"]["error"] == 0 and doc["totals"]["warning"] == 0
    assert set(doc["plans"]) == {"mlp:gather"}
    assert doc["plans"]["mlp:gather"]["summary"]["family"] == "sequential"
    assert set(doc["rules"]) == {f"PGA10{i}" for i in range(1, 7)}
    assert json.loads(out.read_text())["totals"] == doc["totals"]


def test_cli_suppress_changes_exit_code():
    from repro.analysis.planaudit import main

    # seed an erroring family is expensive; instead check the flag plumbing
    # via AuditConfig: suppressed rules vanish from the report entirely
    plan = build_plan(_overflow_banks(), audit="off")
    assert not audit_plan(plan).ok
    assert audit_plan(plan, AuditConfig(suppress=("PGA101",))).ok
    assert callable(main)
