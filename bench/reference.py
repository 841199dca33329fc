"""Plain float32 forward of one Pegasus bank, for the configurations'
references. Imports nothing of the program.

Hard routing as the paper deploys it: each group of ``v`` features descends
its tree (``x[feature] > threshold`` goes right, ``depth`` times), the leaf
picks one table row per group, and the rows are summed with the bias. The
forward also returns, per flow, the smallest relative distance between a
compared value and its threshold along every path taken
(``|x - t| / (1 + |t|)``): where that margin is within float32 rounding, the
order in which an implementation sums a previous bank's rows may route the
flow either way, and both answers are right. A bank that reads the raw
8-bit flow fields (``raw_input=True``) compares exact integers, so its
comparisons never count toward the margin.

``precision="high"`` is the control of the comparison: the same forward with
every value that a one-hot matrix product would carry (the table rows and
the compared activations) rounded as TPU's three-pass bfloat16 product
(``jax.lax.Precision.HIGH``) rounds it for a one-hot operand, that is to the
sum of two bfloat16 numbers.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

PRECISIONS = ("highest", "high")


def bf16_round(x: jax.Array) -> jax.Array:
    """``x`` rounded to the nearest bfloat16 (ties to even), kept in
    float32. Done on the bits: a compiler allowed excess precision may
    drop a float32 → bfloat16 → float32 round trip of converts."""
    b = jax.lax.bitcast_convert_type(x, jnp.uint32)
    b = (b + jnp.uint32(0x7FFF) + ((b >> 16) & 1)) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(b, jnp.float32)


def bf16x2(x: jax.Array) -> jax.Array:
    """``x`` as a three-pass bfloat16 product with an exact 1.0 returns it:
    ``hi + lo`` with ``hi = bf16(x)`` and ``lo = bf16(x - hi)``."""
    hi = bf16_round(x)
    return hi + bf16_round(x - hi)


def bank_forward(bank: dict, x: jax.Array, *, v: int, depth: int,
                 precision: str = "highest", raw_input: bool = False
                 ) -> tuple[jax.Array, jax.Array]:
    """``x [T, K*v]`` → ``(y [T, N], margin [T])``."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}")
    feats, thrs, lut = bank["features"], bank["thresholds"], bank["lut"]
    if precision == "high":
        x, lut = bf16x2(x), bf16x2(lut)
    k = feats.shape[0]
    t = x.shape[0]
    xg = x.reshape(t, k, v)
    group = jnp.arange(k)
    node = jnp.zeros((t, k), jnp.int32)
    margin = jnp.full((t,), jnp.inf, jnp.float32)
    for _ in range(depth):
        f = feats[group, node]                                  # [T, K]
        thr = thrs[group, node]
        val = jnp.take_along_axis(xg, f[..., None], axis=-1)[..., 0]
        if not raw_input:
            margin = jnp.minimum(margin, jnp.min(
                jnp.abs(val - thr) / (1.0 + jnp.abs(thr)), -1))
        node = 2 * node + 1 + (val > thr).astype(jnp.int32)
    leaf = node - ((1 << depth) - 1)
    rows = lut[group, leaf]                                     # [T, K, N]
    y = jnp.sum(rows, axis=1)
    if bank.get("bias") is not None:
        y = y + bank["bias"]
    return y, margin
