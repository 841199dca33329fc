"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, the sampled
requests' rows go through the configuration's plain reference (float32,
``jax.default_matmul_precision("highest")``), in blocks of rows, and each
answer the server gave is held against it. Two numbers are compared, each
with its limit:

* ``unanswered``: requests of the window that failed or never resolved,
  plus sampled answers of the wrong shape or not finite. Limit 0.
* ``gap``: the largest absolute difference between a served logit and the
  reference's, over the sampled flows whose every comparison along the
  reference's path clears its threshold by more than the configuration's
  ``near_margin`` (relative). Within that margin float32 rounding in the
  previous bank's sum may route a flow either way, and both answers are
  right; the share of such flows is printed (``near_share``) and not
  compared.

The control (``precision="high"``) puts the reference computed in the next
precision below the configuration's in the program's place.
"""

from __future__ import annotations

import numpy as np

BLOCK = 32768


def reference(ref_module, cfg: dict, banks, x: np.ndarray,
              precision: str = "highest") -> tuple[np.ndarray, np.ndarray]:
    """``(outputs, margins)`` of the plain reference over ``x``, computed in
    blocks of :data:`BLOCK` rows (one compiled shape)."""
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda b, xb: ref_module.forward(cfg, b, xb, precision))
    outs, margins = [], []
    with jax.default_matmul_precision("highest"):
        for i in range(0, len(x), BLOCK):
            xb = x[i:i + BLOCK]
            n = len(xb)
            if n < BLOCK:
                xb = np.concatenate(
                    [xb, np.zeros((BLOCK - n,) + xb.shape[1:], xb.dtype)])
            y, m = fn(banks, jnp.asarray(xb))
            outs.append(np.asarray(y)[:n])
            margins.append(np.asarray(m)[:n])
    if not outs:
        return np.zeros((0, 0), np.float32), np.zeros((0,), np.float32)
    return np.concatenate(outs), np.concatenate(margins)


def compare(cfg: dict, served: list, want: np.ndarray, margins: np.ndarray,
            *, failed: int) -> dict:
    """Hold ``served`` (one output per sampled request, in the order of the
    rows of ``want``; ``None`` for a request that failed) to the reference.
    Returns ``{"correct", "checks": {name: {"value", "limit"}},
    "near_share", "flows_checked"}``."""
    chk = cfg["check"]
    bad = int(failed)
    gap, near, checked, start = 0.0, 0, 0, 0
    for out, n in served:
        ref = want[start:start + n]
        mar = margins[start:start + n]
        start += n
        if (out is None or np.shape(out) != ref.shape
                or not np.isfinite(out).all()):
            bad += 1
            continue
        ok = mar >= chk["near_margin"]
        near += int((~ok).sum())
        checked += n
        if ok.any():
            gap = max(gap, float(np.abs(np.asarray(out)[ok] - ref[ok]).max()))
    limit = float(chk["limits"]["gap"])
    checks = {"unanswered": {"value": bad, "limit": 0},
              "gap": {"value": gap, "limit": limit}}
    return {"correct": bool(bad == 0 and checked > 0 and gap <= limit),
            "checks": checks,
            "near_share": near / checked if checked else 0.0,
            "flows_checked": checked}
