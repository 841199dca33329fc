"""Plan auditor: static numerics / VMEM / dataplane analysis of compiled plans.

The third analysis layer (PGA1xx, after the PG0xx AST lint and the runtime
sanitizer): :func:`audit_plan` walks a built ``ExecutionPlan`` — banks,
fused stacks, bucket ladder, backend/strategy, q8 tables — WITHOUT
dispatching any jax computation, and proves (or refutes) the invariants the
paper's compiler enforces on the P4 target before deployment:

* **PGA101** — fixed-point overflow: worst-case int32 accumulator bound of
  each bank's q8 tables, all groups rescaled to the finest group scale (the
  common fixed-point grid an integer dataplane would accumulate in). The
  bound is exact: per output column, each group independently contributes
  its most extreme row, so ``Σ_k max_c`` / ``Σ_k min_c`` IS the reachable
  worst case (validated against brute-force enumeration in the tests).
* **PGA102** — quantization fidelity: worst-case per-group dequantization
  error of the q8 table vs the f32 LUT it claims to quantize. Symmetric
  round-to-nearest guarantees ``err ≤ scale/2`` (~0.4% of the group amax);
  a violation means the q8 table is stale or tampered.
* **PGA103** — VMEM footprint per ``pallas_call``: operand blocks + stacked
  tables at the worst-case batch tile, against a per-target budget — the
  build-time version of the kernel docstring's working-set math.
* **PGA104** — tile alignment: ladder buckets that silently dispatch hidden
  pad rows (the kernel pads the batch up to its tile multiple, uncounted by
  ``pad_waste``).
* **PGA105** — fusion-rejection explanations: why each adjacent chained
  bank pair is NOT inside one :class:`FusedBankStack` (v/C mismatch,
  chaining break, ``nmax_cap`` split, ``fuse=False``, or a family builder
  that never runs the fusion pass — the CNN-L b1→b2 pair ROADMAP names).
  Info severity: explanations, not defects.
* **PGA106** — dataplane resource fit: the plan's banks lowered through
  ``repro.dataplane.compile`` to a MAT pipeline, charged against a declared
  :class:`SwitchBudget` (``AuditConfig.target``). Off unless a target is
  declared — serving on CPUs/TPUs carries no switch budget.

Everything here is host-side numpy over tensors the plan already
materialized at build time; no new XLA computation is traced or executed.

Lifecycle wiring: ``build_plan(..., audit="warn"|"error"|"off")`` runs this
at build, ``plan.audit_report`` / ``compile_stats()["audit"]`` carry the
result into every server ``stats()`` surface, and
``python -m repro.analysis plan [--json]`` audits the in-tree model zoo
(the static-analysis CI lane's zero-findings gate).
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any

import numpy as np

from . import rules as R

__all__ = [
    "AuditConfig", "AuditFinding", "AuditReport", "PlanAuditError",
    "audit_plan", "main",
]


class PlanAuditError(ValueError):
    """Raised by ``build_plan(..., audit="error")`` on error-severity
    findings; carries the full report as ``.report``."""

    def __init__(self, report: "AuditReport"):
        self.report = report
        bad = [f for f in report.findings if f.severity == "error"]
        super().__init__(
            f"plan audit failed with {len(bad)} error finding"
            f"{'s' if len(bad) != 1 else ''}:\n"
            + "\n".join(f"  {f}" for f in bad))


@dataclasses.dataclass(frozen=True)
class AuditFinding:
    """One typed finding: ``rule`` is a PGA1xx id, ``severity`` one of
    error/warning/info, ``site`` names the plan element (bank[i], stack[g],
    bucket, plan), ``metrics`` the numbers behind the verdict."""

    rule: str
    severity: str
    site: str
    message: str
    metrics: dict = dataclasses.field(default_factory=dict)

    def __str__(self) -> str:
        return f"{self.severity.upper():7s} {self.rule} {self.site}: {self.message}"

    def to_dict(self) -> dict:
        return {"rule": self.rule, "severity": self.severity,
                "site": self.site, "message": self.message,
                "metrics": self.metrics}


@dataclasses.dataclass(frozen=True)
class AuditConfig:
    """Audit policy knobs. Defaults come from :mod:`repro.analysis.rules`
    so the thresholds a finding enforces are reviewable as data."""

    q8_rel_tol: float = R.PGA102_REL_TOL
    vmem_budget_bytes: int = R.PGA103_VMEM_BUDGET
    vmem_margin: float = R.PGA103_MARGIN
    overflow_margin: float = R.PGA101_MARGIN
    # dataplane target for PGA106: None (off), "tofino2", or a SwitchBudget
    target: Any = None
    # PGA rule ids to drop entirely (CLI --suppress)
    suppress: tuple = ()


class AuditReport:
    """Findings + plan summary; the object ``plan.audit_report`` caches."""

    def __init__(self, findings: list[AuditFinding], summary: dict):
        self.findings = list(findings)
        self.summary = dict(summary)

    @property
    def counts(self) -> dict:
        c = {"error": 0, "warning": 0, "info": 0}
        for f in self.findings:
            c[f.severity] += 1
        return c

    @property
    def ok(self) -> bool:
        """No error- or warning-severity findings (info is explanatory)."""
        c = self.counts
        return c["error"] == 0 and c["warning"] == 0

    def to_dict(self) -> dict:
        return {"summary": self.summary, "counts": self.counts,
                "ok": self.ok,
                "findings": [f.to_dict() for f in self.findings]}

    def __str__(self) -> str:
        c = self.counts
        head = (f"plan audit [{self.summary.get('family')}] "
                f"{c['error']} error(s), {c['warning']} warning(s), "
                f"{c['info']} note(s)")
        return "\n".join([head] + [f"  {f}" for f in self.findings])


# ---------------------------------------------------------------------------
# Per-rule checks. Each takes the plan (duck-typed; engine imports stay
# lazy to keep repro.analysis import-light and cycle-free) and a config,
# and yields AuditFinding objects.
# ---------------------------------------------------------------------------


def _true_tables(bank) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(f32 LUT, q8 LUT, scales) read back out of the bank's kernel layout
    at its TRUE (K, C, N) — the padded rows are zeros/inf filler with no
    numeric content."""
    lut, q8, scales = bank.tables()
    return (lut.astype(np.float64), q8.astype(np.int64),
            scales.astype(np.float64))


def accumulation_grid(scales: np.ndarray) -> float:
    """The coarsest fixed-point grid step that loses no representable
    signal: the finest scale among SIGNIFICANT groups. A group whose whole
    amplitude (``amax ≈ 127·scale``) sits below half a step of a coarser
    grid rounds to zero in that grid anyway — quantization already
    discarded it — so it cannot force the grid finer. Without this flush
    rule a dead group (all-zero LUT, scale floored at ``1e-8/127``) would
    drag the grid ~1e7x below the live groups' scales and every healthy
    bank would "overflow" on paper.

    Formally: the largest candidate ``s ∈ scales`` such that every group
    is either representable (``scale_g ≥ s``) or flushable
    (``127·scale_g ≤ s/2``)."""
    if scales.size == 0:
        return 1.0
    ss = np.sort(scales.astype(np.float64))
    prefix = np.maximum.accumulate(ss)                  # coarsest so far
    for i in range(ss.size - 1, -1, -1):
        if i == 0 or prefix[i - 1] * 254.0 <= ss[i]:
            return max(float(ss[i]), 1e-30)
    return max(float(ss[0]), 1e-30)


def overflow_bound(q8: np.ndarray, scales: np.ndarray,
                   bias: np.ndarray | None = None) -> float:
    """Worst-case |int32 accumulator| for one bank's SumReduce, in units of
    the bank's accumulation grid (:func:`accumulation_grid` — the shared
    fixed-point step an integer dataplane accumulates in; groups finer
    than the grid flush to zero under ``rint``, exactly as the rescale
    hardware would).

    Exact, not just an upper bound: per output column the K groups choose
    leaves independently, so the extreme sum is separable —
    ``Σ_k max_c`` (and ``Σ_k min_c`` for the negative side).
    """
    smin = accumulation_grid(scales)
    contrib = np.rint(q8 * (scales[:, None, None] / smin))      # [K, C, N]
    pos = contrib.max(axis=1).sum(axis=0)                       # [N]
    neg = contrib.min(axis=1).sum(axis=0)
    if bias is not None:
        b = np.rint(np.asarray(bias, np.float64) / smin)
        pos = pos + b
        neg = neg + b
    if pos.size == 0:
        return 0.0
    return float(max(pos.max(), -neg.min(), 0.0))


def _check_overflow(plan, cfg: AuditConfig):
    for i, bank in enumerate(plan.banks):
        _, q8, scales = _true_tables(bank)
        bias = None if bank.layer.bias is None else np.asarray(bank.layer.bias)
        bound = overflow_bound(q8, scales, bias)
        grid = accumulation_grid(scales)
        metrics = {"bound": bound, "int32_max": R.INT32_MAX,
                   "k": bank.layer.num_groups, "grid": grid,
                   "scale_spread": float(scales.max() / grid)
                   if scales.size else 1.0}
        site = f"bank[{i}]"
        if bound > R.INT32_MAX:
            yield AuditFinding(
                "PGA101", "error", site,
                f"worst-case accumulator {bound:.3e} exceeds int32 "
                f"({R.INT32_MAX}) in the finest-scale fixed-point grid "
                f"(group scale spread {metrics['scale_spread']:.1e})",
                metrics)
        elif bound * cfg.overflow_margin > R.INT32_MAX:
            yield AuditFinding(
                "PGA101", "warning", site,
                f"worst-case accumulator {bound:.3e} is within "
                f"{cfg.overflow_margin:g}x of int32", metrics)


def _check_fidelity(plan, cfg: AuditConfig):
    for i, bank in enumerate(plan.banks):
        lut, q8, scales = _true_tables(bank)
        if lut.size == 0:
            continue
        dq = q8 * scales[:, None, None]
        amax = np.abs(lut).max(axis=(1, 2))                     # [K]
        rel = np.abs(lut - dq).max(axis=(1, 2)) / np.maximum(amax, 1e-8)
        worst = float(rel.max())
        if worst > cfg.q8_rel_tol:
            g = int(rel.argmax())
            yield AuditFinding(
                "PGA102", "error", f"bank[{i}]",
                f"q8 dequant error {worst:.4f} of group {g}'s amax exceeds "
                f"tol {cfg.q8_rel_tol:g} — the int8 table does not match "
                "the f32 LUT (stale or tampered quantization)",
                {"rel_err": worst, "group": g, "tol": cfg.q8_rel_tol})


def _vmem_bytes(layers: int, nodes: int, w: int, bt: int) -> int:
    """VMEM working set of one pallas_call step (kernel.py module
    docstring): the batch tile's ``[W, Tt]`` input/output blocks, per layer
    the ``[nodes, W]`` select, ``[nodes, 1]`` thresholds, ``[W, nodes]`` f32
    LUT (plus its int8 copy and scale row on the q8 path) and ``[W, 1]``
    bias, and the ``[nodes, Tt]`` descent intermediates of one layer."""
    floats = (2 * w * bt                                  # x^T + out^T tiles
              + layers * nodes * (w + 1)                  # select + thr
              + layers * w * nodes                        # f32 LUT
              + layers * nodes + layers * w               # scales + bias
              + 4 * nodes * bt)                           # vals/bits/one-hots
    return 4 * floats + layers * w * nodes                # + int8 LUT


def _single_vmem_bytes(bank, max_bucket: int) -> int:
    """Worst-case VMEM working set of the single-bank kernel: one
    (batch tile, group tile) step, its blocks cut to ``Kt·C`` nodes."""
    bt, kt = bank.tiles
    return _vmem_bytes(1, kt * bank.layer.num_centroids, bank.lut.shape[0],
                       min(bt, max(max_bucket, 1)))


def _stack_vmem_bytes(stack, max_bucket: int) -> int:
    """Worst-case VMEM working set of one stacked pallas_call: EVERY
    per-layer operand rides whole (that is the point of the fusion — the
    activation never leaves VMEM)."""
    kc = max(stack.ks) * stack.banks[0].layer.num_centroids
    return _vmem_bytes(len(stack.banks), kc, stack.width,
                       min(stack.block_t, max(max_bucket, 1)))


def _iter_steps(plan):
    """(site, step) over the plan's forward steps: fused stacks once each,
    banks not inside any stack individually."""
    fused_members = {id(b) for s in plan.fused_stacks for b in s.banks}
    for g, s in enumerate(plan.fused_stacks):
        lo = plan.banks.index(s.banks[0])
        yield f"stack[{g}]=banks[{lo}:{lo + len(s.banks)}]", s
    for i, b in enumerate(plan.banks):
        if id(b) not in fused_members:
            yield f"bank[{i}]", b


def _check_vmem(plan, cfg: AuditConfig):
    budget = cfg.vmem_budget_bytes
    max_bucket = max(plan.buckets)
    for site, step in _iter_steps(plan):
        if hasattr(step, "ks"):                      # FusedBankStack
            need = _stack_vmem_bytes(step, max_bucket)
            shape = (f"L={len(step.banks)}, Kmax={max(step.ks)}, "
                     f"W={step.width}, bt={step.block_t}")
        else:
            need = _single_vmem_bytes(step, max_bucket)
            bt, kt = step.tiles
            shape = (f"K={step.layer.num_groups}, Kt={kt}, "
                     f"W={step.lut.shape[0]}, bt={bt}")
        metrics = {"bytes": need, "budget": budget, "shape": shape}
        if need > budget:
            yield AuditFinding(
                "PGA103", "error", site,
                f"pallas_call working set ~{need / 2**20:.2f} MiB ({shape}) "
                f"exceeds the VMEM budget {budget / 2**20:.2f} MiB — the "
                "kernel would fail (or thrash) at runtime; shrink block_t "
                "or split the fused run (fuse_nmax_cap)", metrics)
        elif need * cfg.vmem_margin > budget:
            yield AuditFinding(
                "PGA103", "warning", site,
                f"pallas_call working set ~{need / 2**20:.2f} MiB ({shape}) "
                f"is within {cfg.vmem_margin:g}x of the VMEM budget "
                f"{budget / 2**20:.2f} MiB", metrics)


def _check_alignment(plan, cfg: AuditConfig):
    # hidden batch padding: __call__ pads up to the bucket, then the kernel
    # path pads AGAIN up to its batch-tile multiple — rows pad_waste never
    # sees. Flag every (bucket, tile) pair that re-pads.
    tiles = {}                          # (bt_limit, kind) -> example site
    for site, step in _iter_steps(plan):
        if hasattr(step, "ks"):
            tiles.setdefault((step.block_t, "stack"), site)
        else:
            tiles.setdefault((step.tiles[0], "bank"), site)
    for (limit, kind), site in sorted(tiles.items()):
        for bucket in plan.buckets:
            # a batch under the tile fills its 128-lane vregs either way:
            # only rows beyond the bucket's own lane tiles are hidden work
            bt = min(limit, bucket)
            hidden = (-bucket) % bt
            if hidden:
                yield AuditFinding(
                    "PGA104", "warning", f"bucket {bucket}",
                    f"bucket {bucket} is not a multiple of the {kind} batch "
                    f"tile {bt} ({site}): the kernel path silently pads "
                    f"{hidden} extra rows per call, uncounted by pad_waste",
                    {"bucket": bucket, "tile": bt, "hidden_rows": hidden})


def _unfused_reasons(a, b) -> list[str]:
    """Why ``_fusable(a, b)`` says no — one string per failed conjunct."""
    la, lb = a.layer, b.layer
    r = []
    if la.group_size != lb.group_size:
        r.append(f"partition width v {la.group_size} != {lb.group_size}")
    if la.num_centroids != lb.num_centroids:
        r.append(f"centroid count C {la.num_centroids} != {lb.num_centroids}")
    if la.out_features != lb.in_features:
        r.append(f"chaining break: out {la.out_features} != in {lb.in_features}")
    if a.interpret != b.interpret:
        r.append("interpret-mode mismatch")
    if a.strategy != b.strategy:
        r.append(f"strategy mismatch {a.strategy} != {b.strategy}")
    return r


def _chain_boundaries(plan):
    """Adjacent chained (tail bank, head bank, structural note) triples the
    forward actually executes back-to-back, by family."""
    st = plan._state
    fam = plan.family
    chains: list[tuple[list, str | None]] = []
    if fam == "sequential":
        chains.append((list(st["steps"]), None))
    elif fam == "cnn":
        heads = list(st["heads"])
        if heads:
            # window → first head crosses the per-window SumReduce/mean —
            # a structural break no fusion pass can cross
            chains.append(([st["window"], heads[0]],
                           "structural: the per-window SumReduce/mean "
                           "separates the pair"))
            chains.append((heads, None))
    elif fam == "cnn_l":
        chains.append(([st["b1"], st["b2"]],
                       "the cnn_l builder compiles banks individually "
                       "(no fusion pass over the b1→b2 chain)"))
    # rnn: recurrent structure — no two banks chain unconditionally
    for steps, note in chains:
        for prev, nxt in zip(steps, steps[1:]):
            same_stack = prev is nxt
            if same_stack:
                continue
            tail = prev.banks[-1] if hasattr(prev, "ks") else prev
            head = nxt.banks[0] if hasattr(nxt, "ks") else nxt
            yield tail, head, note


def _check_fusion(plan, cfg: AuditConfig):
    cap = plan.fuse_cfg.get("nmax_cap")
    fuse_on = plan.fuse_cfg.get("fuse", True)
    for tail, head, note in _chain_boundaries(plan):
        ti = plan.banks.index(tail)
        hi = plan.banks.index(head)
        site = f"bank[{ti}]→bank[{hi}]"
        reasons = _unfused_reasons(tail, head)
        if note is not None and "structural" in note:
            reasons = [note] + reasons
        elif not reasons:
            if not fuse_on:
                reasons = ["pair is shape-compatible but fusion is disabled "
                           "(fuse=False)"]
            elif note is not None:
                reasons = [note + " — pair is shape-compatible (fusion "
                           "ratchet candidate, see ROADMAP)"]
            else:
                widths = (tail.layer.out_features, head.layer.out_features)
                reasons = [
                    f"pair is shape-compatible but split by the "
                    f"fuse_nmax_cap={cap} balloon guard (member widths "
                    f"{widths} would pad a narrow stack to the run's Nmax)"]
        yield AuditFinding(
            "PGA105", "info", site,
            "unfused adjacent pair: " + "; ".join(reasons),
            {"tail": ti, "head": hi})


def _resolve_target(target):
    from repro.dataplane.resources import TOFINO2, SwitchBudget
    if target is None:
        return None, None
    if isinstance(target, SwitchBudget):
        return target, "custom"
    name = str(target).lower()
    if name in ("", "none", "off"):
        return None, None
    if name == "tofino2":
        return TOFINO2, "tofino2"
    raise ValueError(f"unknown dataplane target {target!r} (know: tofino2)")


def _check_dataplane(plan, cfg: AuditConfig):
    budget, name = _resolve_target(cfg.target)
    if budget is None:
        return
    from repro.dataplane.compile import compile_model
    pipe = compile_model([b.layer for b in plan.banks], budget=budget)
    rep = pipe.report()
    metrics = {"target": name, "stages_used": rep.stages_used,
               "sram_pct": round(rep.sram_pct, 2),
               "tcam_pct": round(rep.tcam_pct, 2),
               "bus_pct": round(rep.bus_pct, 2),
               "phv_bits_peak": rep.phv_bits_peak,
               "recirculations": rep.recirculations}
    for err in rep.validate():
        yield AuditFinding(
            "PGA106", "error", "plan",
            f"dataplane target '{name}' exceeded: {err}", metrics)
    if rep.recirculations:
        yield AuditFinding(
            "PGA106", "warning", "plan",
            f"{rep.stages_used} physical stages need "
            f"{rep.recirculations} recirculation pass(es) on '{name}' "
            f"({budget.stages} stages/pipeline) — line rate divides "
            "accordingly", metrics)
    yield AuditFinding(
        "PGA106", "info", "plan",
        f"dataplane fit on '{name}': {rep.stages_used} stages, "
        f"SRAM {rep.sram_pct:.2f}%, TCAM {rep.tcam_pct:.2f}%, "
        f"bus {rep.bus_pct:.2f}%", metrics)


_CHECKS = (_check_overflow, _check_fidelity, _check_vmem, _check_alignment,
           _check_fusion, _check_dataplane)


def audit_plan(plan, config: AuditConfig | None = None) -> AuditReport:
    """Statically audit a built ExecutionPlan (PGA101–PGA106).

    Pure host-side analysis: walks the plan structure and the numpy views
    of tensors the build already materialized; never traces or dispatches
    a jax computation. Returns an :class:`AuditReport`; attach it yourself
    or let ``build_plan(..., audit=...)`` do both.
    """
    cfg = config or AuditConfig()
    suppress = set(cfg.suppress)
    findings: list[AuditFinding] = []
    for check in _CHECKS:
        for f in check(plan, cfg):
            if f.rule not in suppress:
                findings.append(f)
    order = {"error": 0, "warning": 1, "info": 2}
    findings.sort(key=lambda f: (order[f.severity], f.rule, f.site))
    summary = {
        "family": plan.family,
        "backend": plan.backend,
        "num_banks": len(plan.banks),
        "fused_groups": len(plan.fused_stacks),
        "buckets": list(plan.buckets),
        "devices": 1 if plan.devices is None else len(plan.devices),
        "table_bytes": plan.table_bytes(),
    }
    return AuditReport(findings, summary)


# ---------------------------------------------------------------------------
# CLI: python -m repro.analysis plan [--json] — audits the in-tree zoo.
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis plan",
        description="Static plan audit (PGA101-PGA106) over the in-tree "
                    "model families; exit 1 on any unsuppressed "
                    "error/warning finding")
    ap.add_argument("--families", default="mlp,rnn,cnn,cnn_l,ae",
                    help="comma-separated families to build and audit")
    ap.add_argument("--backends", default="gather,kernel_q8",
                    help="comma-separated default backends to build per family")
    ap.add_argument("--json", action="store_true",
                    help="print the full report as JSON instead of text")
    ap.add_argument("--out", default=None,
                    help="also write the JSON report to this path")
    ap.add_argument("--target", default=None,
                    help="dataplane target for PGA106 (e.g. tofino2); "
                         "default: no target declared")
    ap.add_argument("--vmem-budget", type=int, default=None,
                    help="override the PGA103 VMEM budget (bytes)")
    ap.add_argument("--suppress", default="",
                    help="comma-separated PGA rule ids to suppress")
    ap.add_argument("--flows", type=int, default=48,
                    help="synthetic dataset flows per class (zoo size)")
    ap.add_argument("--steps", type=int, default=5,
                    help="training steps per zoo model")
    args = ap.parse_args(argv)

    cfg = AuditConfig(
        target=args.target,
        vmem_budget_bytes=args.vmem_budget or R.PGA103_VMEM_BUDGET,
        suppress=tuple(s for s in args.suppress.split(",") if s))

    from repro.engine import build_plan

    from .zoo import build_family

    reports: dict[str, AuditReport] = {}
    families = [f for f in args.families.split(",") if f]
    backends = [b for b in args.backends.split(",") if b]
    for fam in families:
        model = build_family(fam, flows=args.flows, steps=args.steps)
        for be in backends:
            plan = build_plan(model, backend=be, audit="off")
            reports[f"{fam}:{be}"] = audit_plan(plan, cfg)

    totals = {"error": 0, "warning": 0, "info": 0}
    for rep in reports.values():
        for sev, n in rep.counts.items():
            totals[sev] += n
    doc = {
        "config": {"target": args.target, "suppress": cfg.suppress,
                   "vmem_budget_bytes": cfg.vmem_budget_bytes,
                   "families": families, "backends": backends},
        "totals": totals,
        "plans": {name: rep.to_dict() for name, rep in reports.items()},
        "rules": R.PGA_RULES,
    }
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(doc, fh, indent=2, default=str)
    if args.json:
        print(json.dumps(doc, indent=2, default=str))
    else:
        for name, rep in reports.items():
            print(f"== {name} ==")
            print(rep)
        print(f"plan-audit: {totals['error']} error(s), "
              f"{totals['warning']} warning(s), {totals['info']} note(s) "
              f"over {len(reports)} plan(s)")
    return 1 if (totals["error"] or totals["warning"]) else 0
