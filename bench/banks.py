"""Pegasus lookup banks made from the seed, on the device, in one call.

A bank is the paper's Partition/Map/SumReduce form of one linear layer: the
input splits into ``K`` groups of ``v`` features, each group descends its own
complete clustering tree of depth ``d`` (``C = 2**d`` leaves; internal node
``n`` sends the flow right when ``x[feature[n]] > threshold[n]``), and the
output is the sum of the ``K`` table rows the leaves select, plus a bias.

The benchmark makes every bank itself, as plain arrays, so that the program
under test and the plain reference read the same tables and neither takes
anything the other made. Trees are random axis-aligned partitions of the
input's range (each split at a uniform point of the middle half of its
node's region, so every leaf is a box of that range); table rows are
normal with variance ``1/K`` so that a bank's output is of unit scale and
the next bank's trees split where its inputs lie.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _trees(key, lo, hi, *, k: int, v: int, depth: int) -> dict:
    """``k`` independent trees over groups of ``v`` features; ``lo``/``hi``
    ``[k*v]`` are each feature's range. Returns ``features [k, C-1]``,
    ``thresholds [k, C-1]`` (heap order) and ``centroids [k, C, v]``."""
    kf, ku = jax.random.split(key)
    c = 1 << depth
    # one draw for every node (heap order), sliced by level below
    f_all = jax.random.randint(kf, (k, c - 1), 0, v)
    u_all = jax.random.uniform(ku, (k, c - 1), minval=0.25, maxval=0.75)
    lo = lo.reshape(k, 1, v)
    hi = hi.reshape(k, 1, v)
    feats, thrs = [], []
    for level in range(depth):
        m = 1 << level
        f = f_all[:, m - 1:2 * m - 1]
        u = u_all[:, m - 1:2 * m - 1]
        oh = jax.nn.one_hot(f, v, dtype=bool)                  # [K, m, v]
        lo_f = jnp.sum(jnp.where(oh, lo, 0.0), -1)
        hi_f = jnp.sum(jnp.where(oh, hi, 0.0), -1)
        t = lo_f + u * (hi_f - lo_f)
        feats.append(f)
        thrs.append(t)
        # heap order: node j of this level has children 2j, 2j+1 below
        left_hi = jnp.where(oh, t[..., None], hi)
        right_lo = jnp.where(oh, t[..., None], lo)
        lo = jnp.stack([lo, right_lo], axis=2).reshape(k, 2 * m, v)
        hi = jnp.stack([left_hi, hi], axis=2).reshape(k, 2 * m, v)
    return {"features": jnp.concatenate(feats, axis=1).astype(jnp.int32),
            "thresholds": jnp.concatenate(thrs, axis=1).astype(jnp.float32),
            "centroids": 0.5 * (lo + hi)}


def random_banks(key, specs: list[dict], *, v: int, depth: int) -> list[dict]:
    """Banks that share the group width ``v`` and the depth. Groups are
    independent, so the trees of every bank's groups are drawn together in
    one pass over the levels (a small program to trace, whatever the
    number of banks); then each bank's table and bias.

    ``specs``: per bank ``k`` (groups), ``n`` (outputs), ``lo``/``hi`` (the
    input range, a scalar or ``[k*v]``), ``bias`` and optionally
    ``lut_scale`` (default ``1/sqrt(k)``). Each bank: ``features [K, C-1]``
    int32, ``thresholds [K, C-1]``, ``centroids [K, C, v]`` (the centre of
    each leaf's box), ``lut [K, C, N]`` and ``bias [N]`` (or ``None``)."""
    kt, kl = jax.random.split(key)

    def rng(s, name):
        return jnp.broadcast_to(jnp.asarray(s[name], jnp.float32),
                                (s["k"] * v,))

    lo = jnp.concatenate([rng(s, "lo") for s in specs])
    hi = jnp.concatenate([rng(s, "hi") for s in specs])
    trees = _trees(kt, lo, hi, k=sum(s["k"] for s in specs), v=v,
                   depth=depth)
    c = 1 << depth
    # one normal draw for every table row and bias, sliced per bank
    sizes = [s["k"] * c * s["n"] + s["n"] for s in specs]
    z = jax.random.normal(kl, (sum(sizes),), jnp.float32)
    out, start, at = [], 0, 0
    for s, size in zip(specs, sizes):
        k, n = s["k"], s["n"]
        g = slice(start, start + k)
        start += k
        zs = z[at:at + size]
        at += size
        scale = s.get("lut_scale")
        scale = 1.0 / float(k) ** 0.5 if scale is None else scale
        out.append({
            "features": trees["features"][g],
            "thresholds": trees["thresholds"][g],
            "centroids": trees["centroids"][g],
            "lut": scale * zs[:k * c * n].reshape(k, c, n),
            "bias": 0.1 * zs[k * c * n:] if s["bias"] else None,
        })
    return out
