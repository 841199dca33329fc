"""Shared arithmetic of the metric readers."""

from __future__ import annotations

import math

import numpy as np

# columns of ctx.records
RID, SENT, DONE, FLOWS, OK = range(5)


def nearest_rank(values, q: float) -> float:
    """The ``q``-quantile by nearest rank: a value that was measured, and
    ``inf`` where a failed request lands on it."""
    v = np.sort(np.asarray(values, np.float64))
    if not len(v):
        return float("nan")
    return float(v[max(0, math.ceil(q * len(v)) - 1)])


def traced_flows(ctx) -> float | None:
    """Flows answered inside the traced slice."""
    t0, t1 = ctx.traced
    if ctx.trace is None or t0 is None:
        return None
    r = ctx.records
    done = (r[:, OK] == 1) & (r[:, DONE] >= t0) & (r[:, DONE] <= t1)
    return float(r[done, FLOWS].sum())


def kernel_roofline(ctx, kernel: str) -> float | None:
    calls = ctx.kernel_calls.get(kernel)
    if calls is None or ctx.trace is None or ctx.peaks is None:
        return None
    t = ctx.trace["kernel_s"].get(kernel, 0.0)
    n = ctx.trace["kernel_calls"].get(kernel, 0)
    flows = traced_flows(ctx)
    if not t or not n or not flows:
        return None
    ops, nbytes = ctx.work.kernel_work(ctx.geometry, calls, flows, n)
    return ctx.work.roofline_share(ops, nbytes, t, ctx.peaks)[0]
