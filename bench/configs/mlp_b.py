"""MLP-B: four banks chained, each bank's output the next one's input.

Bank 0 reads the 16 8-bit flow statistics (8 groups of 2), banks 1 and 2
the 32-wide hidden pre-activations (16 groups of 2), bank 3 maps them to
the 3 class logits; depth 6 (64 leaves) throughout. The program fuses the
four into one stacked kernel call per chunk.
"""

from __future__ import annotations

import functools

import jax
import numpy as np

from bench import banks as B
from bench.flows import make_flows

#: the program's kernels and the banks each call of it runs, per chunk
KERNEL_CALLS = {"fuzzy_lut_stack": [[0, 1, 2, 3]]}


def geometry(cfg: dict) -> list[dict]:
    """Per bank: groups ``k``, group width ``v``, leaves ``c``, outputs
    ``n``, and whether it has a bias."""
    v, c, h = cfg["group_size"], 1 << cfg["depth"], cfg["hidden"]
    dims = [cfg["in_features"]] + [h] * cfg["hidden_layers"] + [cfg["classes"]]
    return [{"k": dims[i] // v, "v": v, "c": c, "n": dims[i + 1],
             "bias": True} for i in range(len(dims) - 1)]


def kernel_calls(cfg: dict) -> dict:
    return KERNEL_CALLS if cfg.get("fused", True) else {
        "fuzzy_lut": [[i] for i in range(len(geometry(cfg)))]}


@functools.partial(jax.jit, static_argnums=(0,))
def _make_banks(shape: tuple, key, lo0, hi0):
    geo, depth = shape
    specs = [{"k": k, "n": n, "bias": True,
              "lo": lo0 if i == 0 else -2.0, "hi": hi0 if i == 0 else 2.0}
             for i, (k, _, n) in enumerate(geo)]
    return B.random_banks(key, specs, v=geo[0][1], depth=depth)


def make(cfg: dict, seed31: int, seed: int) -> tuple[list, dict]:
    """``(banks, pool)``: the banks as plain arrays on the device, made in
    one jitted call, and the flow pool the traffic draws requests from."""
    pool = make_flows(seed, cfg["pool_flows"], cfg["classes"])
    x = pool["stats"]
    geo = tuple((g["k"], g["v"], g["n"]) for g in geometry(cfg))
    # input range of bank 0: the pool's range per feature, widened by one
    lo = x.min(0).astype(np.float32) - 1.0
    hi = x.max(0).astype(np.float32) + 1.0
    banks = _make_banks((geo, cfg["depth"]), jax.random.PRNGKey(seed31),
                        lo, hi)
    return banks, pool


def inputs(cfg: dict, pool: dict) -> np.ndarray:
    """The request rows as the server receives them: float32 statistics."""
    return pool["stats"].astype(np.float32)


def program_model(cfg: dict, banks: list):
    """The banks as the program's model type: a list of PegasusLinear."""
    from repro.core.amm import PegasusLinear
    from repro.core.fuzzy_tree import FuzzyTree

    return [PegasusLinear(trees=FuzzyTree(b["features"], b["thresholds"],
                                          b["centroids"]),
                          lut=b["lut"], bias=b["bias"],
                          group_size=cfg["group_size"]) for b in banks]
