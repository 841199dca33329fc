"""CNN-L: a two-level NAM over the raw bytes of a flow's 8 packets.

Per packet, bank 1 maps the 62 bytes (60 payload bytes, length, IPD; groups
of 1, depth 8) to 64 hidden pre-activations, bank 2 maps those to a 16-wide
embedding (groups of 1, depth 8), one fuzzy tree of depth ``index_bits``
indexes ``tanh`` of the embedding, and a ``[2**index_bits, 3]`` logit table
read at that index is summed over the 8 packets, plus a bias. The program
runs each bank as one single-bank kernel call over the chunk's 8 packet
rows per flow: 2 calls per chunk.
"""

from __future__ import annotations

import functools

import jax
import numpy as np

from bench import banks as B
from bench.flows import make_flows


def _banks(cfg: dict) -> list[dict]:
    """Bank 1, bank 2 and the index tree as banks: ``k`` groups of ``v``,
    ``c`` leaves, ``n`` outputs."""
    c, d = 1 << cfg["enc_depth"], cfg["packet_features"]
    h, e = cfg["hidden"], cfg["embedding"]
    return [{"k": d, "v": 1, "c": c, "n": h, "bias": True},
            {"k": h, "v": 1, "c": c, "n": e, "bias": True},
            {"k": 1, "v": e, "c": 1 << cfg["index_bits"],
             "n": cfg["classes"], "bias": False}]


def geometry(cfg: dict) -> list[dict]:
    """The three stages once per packet slot, since ``bench/work.py``
    counts work per flow and each flow puts 8 packet rows through each
    stage. The index stage's logit rows are the sum over packets; the
    logit bias, added once per flow, rides on the last slot's entry."""
    w = cfg["window"]
    out = [dict(g) for _ in range(w) for g in _banks(cfg)]
    out[-1]["bias"] = True
    return out


def kernel_calls(cfg: dict) -> dict:
    """The two banks of every packet slot as 16 single-bank calls per
    chunk. The program makes 2 real calls per chunk, each over all 8 slots'
    rows; the roofline reader scales each call's tables by the calls it
    counts in the trace over the 16 declared here, so each bank's tables
    count once per real call. The index stage is XLA work, in no kernel."""
    return {"fuzzy_lut": [[3 * t + i] for t in range(cfg["window"])
                          for i in (0, 1)]}


@functools.partial(jax.jit, static_argnums=(0,))
def _make_banks(shape: tuple, key):
    (k1, n1), (k2, n2), (ki, vi, ni), depth, bits, window = shape
    kb, ki_ = jax.random.split(key)
    b1, b2 = B.random_banks(kb, [
        {"k": k1, "n": n1, "bias": True, "lo": -1.0, "hi": 256.0},
        {"k": k2, "n": n2, "bias": True, "lo": -2.0, "hi": 2.0}],
        v=1, depth=depth)
    # the logit rows are summed over the window's packets: variance
    # 1/window keeps the logits of unit scale, as 1/K does for a bank
    (index,) = B.random_banks(ki_, [
        {"k": ki, "n": ni, "bias": True, "lo": -1.0, "hi": 1.0,
         "lut_scale": float(window) ** -0.5}], v=vi, depth=bits)
    return {"b1": b1, "b2": b2, "index": index}


def _payload(seed: int, label: np.ndarray, n_bytes: int, classes: int,
             window: int) -> np.ndarray:
    """``[F, window, n_bytes]`` uint8: each class draws its bytes from its
    own profile, a sparse Dirichlet over the 256 values (a few bytes salient
    per class), as the repository's synthetic traffic does."""
    rng = np.random.default_rng([seed & ((1 << 64) - 1), 0xB17E5])
    profiles = rng.dirichlet(np.full(256, 0.08), size=classes)
    out = np.empty((len(label), window, n_bytes), np.uint8)
    for c in range(classes):
        rows = np.flatnonzero(label == c)
        out[rows] = rng.choice(256, size=(len(rows), window, n_bytes),
                               p=profiles[c])
    return out


def make(cfg: dict, seed31: int, seed: int) -> tuple[dict, dict]:
    pool = make_flows(seed, cfg["pool_flows"], cfg["classes"])
    payload = _payload(seed, pool["label"], cfg["payload_bytes"],
                       cfg["classes"], cfg["window"])
    # the packed per-packet record: payload bytes, then length, then IPD
    pool["x"] = np.concatenate([payload, pool["seq"]], axis=-1)
    g = _banks(cfg)
    shape = ((g[0]["k"], g[0]["n"]), (g[1]["k"], g[1]["n"]),
             (g[2]["k"], g[2]["v"], g[2]["n"]), cfg["enc_depth"],
             cfg["index_bits"], cfg["window"])
    return _make_banks(shape, jax.random.PRNGKey(seed31)), pool


def inputs(cfg: dict, pool: dict) -> np.ndarray:
    """``[F, 8, 62]`` uint8 packed per-packet records."""
    return pool["x"]


def program_model(cfg: dict, banks: dict):
    """The banks as the program's PegasusCNNL."""
    from repro.core.amm import PegasusLinear
    from repro.core.fuzzy_tree import FuzzyTree
    from repro.nets.cnn import PegasusCNNL

    def lin(b):
        return PegasusLinear(trees=FuzzyTree(b["features"], b["thresholds"],
                                             b["centroids"]),
                             lut=b["lut"], bias=b["bias"],
                             group_size=cfg["enc_group"])

    ix = banks["index"]
    return PegasusCNNL(
        bank1=lin(banks["b1"]), bank2=lin(banks["b2"]),
        emb_tree=FuzzyTree(ix["features"][0], ix["thresholds"][0],
                           ix["centroids"][0]),
        logit_lut=ix["lut"][0], bias=ix["bias"],
        index_bits=cfg["index_bits"])
