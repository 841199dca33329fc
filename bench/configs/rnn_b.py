"""RNN-B: the windowed recurrence unrolled over 8 packets.

Step ``t`` has an input bank on the raw (length, IPD) bytes (2 groups of 1,
24 outputs; only step 0's carries the bias) and, from step 1 on, a
recurrent bank on the previous step's 24 pre-activations (24 groups of 1,
with the bias); their outputs add. A classifier bank maps the last
pre-activation to 3 logits. Depth 8 (256 leaves) throughout. The banks do
not chain output to input alike, so the program runs 16 single-bank kernel
calls per chunk.
"""

from __future__ import annotations

import functools

import jax
import numpy as np

from bench import banks as B
from bench.flows import make_flows


def _layout(cfg: dict) -> list[tuple[str, int, int, int, bool]]:
    """``(role, k, v, n, bias)`` per bank in the order the program calls
    them: x_0, then (x_t, h_{t-1}) for t = 1..W-1, then the classifier."""
    w, h, c = cfg["window"], cfg["hidden"], cfg["classes"]
    xg, hg = cfg["x_group"], cfg["h_group"]
    xk, hk = cfg["step_features"] // xg, h // hg
    out = [("x0", xk, xg, h, True)]
    for t in range(1, w):
        out += [(f"x{t}", xk, xg, h, False), (f"h{t - 1}", hk, hg, h, True)]
    return out + [("out", hk, hg, c, True)]


def geometry(cfg: dict) -> list[dict]:
    c = 1 << cfg["depth"]
    return [{"k": k, "v": v, "c": c, "n": n, "bias": bias}
            for _, k, v, n, bias in _layout(cfg)]


def kernel_calls(cfg: dict) -> dict:
    return {"fuzzy_lut": [[i] for i in range(len(_layout(cfg)))]}


@functools.partial(jax.jit, static_argnums=(0,))
def _make_banks(shape: tuple, key):
    layout, depth, hidden = shape
    specs = []
    for role, k, _, n, bias in layout:
        if role.startswith("x"):
            lo, hi, scale = -1.0, 256.0, 0.5
        else:
            lo, hi, scale = -2.0, 2.0, float(np.sqrt(0.5 / hidden))
        specs.append({"k": k, "n": n, "bias": bias, "lo": lo, "hi": hi,
                      "lut_scale": scale})
    by_width: dict = {}
    for i, (_, _, v, _, _) in enumerate(layout):
        by_width.setdefault(v, []).append(i)
    out = {}
    for j, (v, idx) in enumerate(sorted(by_width.items())):
        banks = B.random_banks(jax.random.fold_in(key, j),
                               [specs[i] for i in idx], v=v, depth=depth)
        out.update((layout[i][0], b) for i, b in zip(idx, banks))
    return out


def make(cfg: dict, seed31: int, seed: int) -> tuple[dict, dict]:
    pool = make_flows(seed, cfg["pool_flows"], cfg["classes"])
    banks = _make_banks((tuple(_layout(cfg)), cfg["depth"], cfg["hidden"]),
                        jax.random.PRNGKey(seed31))
    return banks, pool


def inputs(cfg: dict, pool: dict) -> np.ndarray:
    """``[F, 8, 2]`` float32 (length, IPD) per packet."""
    return pool["seq"].astype(np.float32)


def program_model(cfg: dict, banks: dict):
    """The banks as the program's PegasusRNN."""
    from repro.core.amm import PegasusLinear
    from repro.core.fuzzy_tree import FuzzyTree
    from repro.nets.rnn import PegasusRNN

    def lin(b, v):
        return PegasusLinear(trees=FuzzyTree(b["features"], b["thresholds"],
                                             b["centroids"]),
                             lut=b["lut"], bias=b["bias"], group_size=v)

    w = cfg["window"]
    return PegasusRNN(
        x_banks=[lin(banks[f"x{t}"], cfg["x_group"]) for t in range(w)],
        h_banks=[lin(banks[f"h{t}"], cfg["h_group"]) for t in range(w - 1)],
        out_bank=lin(banks["out"], cfg["h_group"]), window=w)
