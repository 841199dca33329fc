"""§4.3 ablation: lookups per inference before/after each fusion level,
plus wall-time of the three Pegasus apply paths (gather / one-hot / kernel).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    MapOp, PartitionOp, PrimitiveGraph, SumReduceOp,
    advanced_nam, advanced_remove_nonlinear, fuse_basic, init_pegasus_linear,
)
from repro.core.fusion import identity
from repro.core.amm import apply_gather, apply_onehot
from repro.kernels.fuzzy_lut.ops import fuzzy_lut_matmul


def _mlp_graph(d=16, h=32, o=4, seed=0):
    """Paper Fig. 5 'initial' layout: BN,FC,ReLU ×2 + head as primitives."""
    rng = np.random.default_rng(seed)
    k, v = d // 4, 4
    w1 = jnp.asarray(rng.normal(size=(d, h)), jnp.float32)
    b1 = jnp.asarray(rng.normal(size=(h,)), jnp.float32)
    w2 = jnp.asarray(rng.normal(size=(h, o)), jnp.float32)
    ops = [
        PartitionOp(dim=v),
        MapOp(fn=lambda xg: 1.1 * xg, linear=True, in_dim=v, out_dim=v,
              table_entries=64, bias=jnp.zeros((k, v)), name="bn1"),
        MapOp(fn=lambda xg: jnp.einsum("...kv,kvn->...kn", xg, w1.reshape(k, v, h)),
              linear=True, in_dim=v, out_dim=h, table_entries=64, name="fc1"),
        SumReduceOp(),
        MapOp(fn=identity, linear=True, in_dim=h, out_dim=h, table_entries=0,
              bias=b1, name="bias1"),
        MapOp(fn=jax.nn.relu, linear=False, in_dim=h, out_dim=h,
              table_entries=64, name="relu"),
        MapOp(fn=lambda x: x @ w2, linear=True, in_dim=h, out_dim=o,
              table_entries=64, name="fc2"),
    ]
    return PrimitiveGraph(ops)


def main(quick: bool = False):
    g = _mlp_graph()
    basic = fuse_basic(g)
    lin = advanced_remove_nonlinear(g)
    nam = advanced_nam(g)
    print(f"lookups initial={g.num_lookups()} basic={basic.num_lookups()} "
          f"adv-linear={lin.num_lookups()} adv-NAM={nam.num_lookups()}")

    # apply-path timing for one PegasusLinear
    rng = np.random.default_rng(0)
    d, n, t = 256, 256, 2048 if not quick else 256
    w = rng.normal(size=(d, n)).astype(np.float32) / np.sqrt(d)
    calib = rng.normal(size=(4096, d)).astype(np.float32)
    layer = init_pegasus_linear(w, None, calib, group_size=4, depth=4, lut_bits=None)
    x = jnp.asarray(rng.normal(size=(t, d)).astype(np.float32))

    for name, fn in [
        ("gather", jax.jit(lambda xb: apply_gather(layer, xb))),
        ("onehot", jax.jit(lambda xb: apply_onehot(layer, xb))),
        ("kernel(interp)", lambda xb: fuzzy_lut_matmul(layer, xb)),
    ]:
        fn(x).block_until_ready()
        t0 = time.perf_counter()
        iters = 3 if "kernel" in name else 20
        for _ in range(iters):
            fn(x).block_until_ready()
        us = (time.perf_counter() - t0) / iters * 1e6
        print(f"apply-path {name:<16} {us:10.1f} us/call  [T={t},D={d},N={n}]")


if __name__ == "__main__":
    main()
