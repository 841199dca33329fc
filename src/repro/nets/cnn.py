"""CNN-B / CNN-M / CNN-L (paper §6.3): 1-D textcnn-style classifiers.

  * CNN-B: Basic Fusion only — conv windows over the (len, IPD) sequence,
    each window position a fused table bank, ReLU folded forward, avg-pool +
    FC head.
  * CNN-M: same input, Advanced Primitive Fusion (NAM): ALL intermediate
    SumReduces removed — each window's whole sub-network folds into ONE
    lookup; a single final SumReduce mixes window contributions. Bigger
    effective model (deeper per-window sub-nets) at LOWER lookup cost.
  * CNN-L: NAM over PACKETS with raw 60-byte payloads (+len,ipd): a
    per-packet encoder (trained jointly) produces a compact embedding that
    is fuzzy-indexed to a few bits — this is the paper's per-flow
    "fuzzy index per packet" storage trick (§7.3, Fig. 7) — and a second
    level maps (packet-slot, index) → class-logit contributions.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.amm import (
    PegasusLinear,
    init_pegasus_bank,
    init_pegasus_linear,
)
from repro.core.fuzzy_tree import FuzzyTree, fit_tree
from repro.engine import plan_for

from .common import train_classifier

__all__ = [
    "CNNModel", "train_cnn", "cnn_apply",
    "pegasusify_cnn", "pegasus_cnn_apply",
    "CNNL", "train_cnn_l", "cnn_l_apply", "pegasusify_cnn_l", "pegasus_cnn_l_apply",
    "PACKET_FEATURES", "pack_packets",
]


# ---------------------------------------------------------------------------
# CNN-B / CNN-M: conv over the 8×2 sequence
# ---------------------------------------------------------------------------

KERNEL = 3  # conv window length (time steps)


@dataclasses.dataclass
class CNNModel:
    params: dict
    num_classes: int
    channels: int
    hidden: int
    size: str  # "B" | "M"


def init_cnn(num_classes: int, channels: int, hidden: int, seed: int = 0) -> dict:
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    in_w = KERNEL * 2  # window of 3 steps × (len, ipd)
    return {
        "w_conv": jax.random.normal(ks[0], (in_w, channels)) / np.sqrt(in_w),
        "b_conv": jnp.zeros(channels),
        "w_h": jax.random.normal(ks[1], (channels, hidden)) / np.sqrt(channels),
        "b_h": jnp.zeros(hidden),
        "w_o": jax.random.normal(ks[2], (hidden, num_classes)) / np.sqrt(hidden),
        "b_o": jnp.zeros(num_classes),
    }


def _windows(x: jax.Array) -> jax.Array:
    """[B, W, 2] → [B, P, KERNEL*2] sliding windows (stride 1)."""
    b, w, f = x.shape
    p = w - KERNEL + 1
    idx = jnp.arange(p)[:, None] + jnp.arange(KERNEL)[None, :]
    return x[:, idx].reshape(b, p, KERNEL * f)


def cnn_apply(m_or_p, x: jax.Array) -> jax.Array:
    p = m_or_p.params if isinstance(m_or_p, CNNModel) else m_or_p
    xf = x.astype(jnp.float32) / 255.0
    win = _windows(xf)                                   # [B, P, 6]
    h = jax.nn.relu(win @ p["w_conv"] + p["b_conv"])     # conv as per-window FC
    h = h.mean(axis=1)                                   # avg pool over time
    h = jax.nn.relu(h @ p["w_h"] + p["b_h"])
    return h @ p["w_o"] + p["b_o"]


def train_cnn(
    x: np.ndarray, y: np.ndarray, num_classes: int, *, size: str = "B", steps=900, seed=0
) -> CNNModel:
    channels, hidden = (16, 24) if size == "B" else (48, 64)
    params = init_cnn(num_classes, channels, hidden, seed=seed)
    params = train_classifier(params, cnn_apply, x, y, steps=steps, lr=2e-3, seed=seed)
    return CNNModel(params=params, num_classes=num_classes, channels=channels, hidden=hidden, size=size)


@dataclasses.dataclass
class PegasusCNN:
    """CNN-B: fused banks. CNN-M (NAM): window_bank covers the whole
    per-window sub-model in ONE lookup per window."""

    window_bank: PegasusLinear      # [B,P,6] windows → per-window contribution
    head_banks: list[PegasusLinear]  # empty for NAM (M); B keeps FC head banks
    out_bias: jax.Array | None
    nam: bool
    pool_windows: int


def pegasusify_cnn(
    m: CNNModel, x_calib: np.ndarray, *, depth: int = 12, refine_steps: int = 0
) -> PegasusCNN:
    p = m.params
    xf = x_calib.astype(np.float32)
    win = np.asarray(_windows(jnp.asarray(xf)))          # [B, P, 6]
    flat = win.reshape(-1, KERNEL * 2)
    n_pool = win.shape[1]

    if m.size == "M":
        # NAM (Advanced Fusion ③): the per-window sub-model — conv, ReLU, FC,
        # ReLU, FC head — folds into ONE lookup; only the final SumReduce
        # over windows survives.
        def submodel(c):  # c: [1, C, 6] centroids → [1, C, classes]
            h = jax.nn.relu(c / 255.0 @ p["w_conv"] + p["b_conv"])
            h = jax.nn.relu(h @ p["w_h"] + p["b_h"]) / n_pool
            return h @ p["w_o"]

        bank = init_pegasus_bank(
            submodel, flat, group_size=KERNEL * 2, depth=depth, bias=None
        )
        peg = PegasusCNN(
            window_bank=bank, head_banks=[], out_bias=p["b_o"],
            nam=True, pool_windows=n_pool,
        )
        if refine_steps:
            from repro.core.finetune import refine

            # per-window distillation target through the NAM decomposition
            per_win_tgt = (
                jax.nn.relu(
                    jax.nn.relu(jnp.asarray(flat) / 255.0 @ p["w_conv"] + p["b_conv"])
                    @ p["w_h"] + p["b_h"]
                ) / n_pool
            ) @ p["w_o"]
            peg.window_bank = refine(bank, jnp.asarray(flat), per_win_tgt, steps=refine_steps)
        return peg

    # CNN-B (Basic Fusion): conv window is ONE group (K=1) → the ReLU folds
    # directly into the rows: rows = relu(c@W + b).
    conv_bank = init_pegasus_bank(
        lambda c: jax.nn.relu(c / 255.0 @ p["w_conv"] + p["b_conv"]),
        flat, group_size=KERNEL * 2, depth=depth, bias=None,
    )
    pooled = np.asarray(
        jax.nn.relu(jnp.asarray(flat) / 255.0 @ p["w_conv"] + p["b_conv"])
    ).reshape(win.shape[0], n_pool, -1).mean(1)          # post-relu avg pool
    h_bank = init_pegasus_linear(
        np.asarray(p["w_h"], np.float32), np.asarray(p["b_h"], np.float32),
        pooled, group_size=1, depth=8, lut_bits=None,
    )
    h_pre = np.asarray(jnp.asarray(pooled) @ p["w_h"] + p["b_h"])
    # head banks: 1-D groups — exact for the linear part (a table per
    # scalar unit, 2^8 entries: the paper's fixed-point activation story)
    o_bank = init_pegasus_linear(
        np.asarray(p["w_o"], np.float32), np.asarray(p["b_o"], np.float32),
        h_pre, group_size=1, depth=8, lut_bits=None,
        act_fn=lambda c: jnp.maximum(c, 0),
    )
    return PegasusCNN(
        window_bank=conv_bank, head_banks=[h_bank, o_bank], out_bias=None,
        nam=False, pool_windows=n_pool,
    )


def pegasus_cnn_apply(peg: PegasusCNN, x: jax.Array, *, backend: str = "gather",
                      jit: bool = False) -> jax.Array:
    """Windowed deployment forward via the engine (B and M/NAM variants).
    Eager by default — one-shot evaluation entry point; serving call sites
    (PegasusServer / build_plan) get the jitted path."""
    return plan_for(peg)(x, backend=backend, jit=jit)


# ---------------------------------------------------------------------------
# CNN-L: NAM over packets with raw payload bytes
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class CNNL:
    params: dict
    num_classes: int
    emb_dim: int


def init_cnn_l(num_classes: int, emb_dim: int = 16, seed: int = 0) -> dict:
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    in_dim = 62  # 60 payload bytes + len + ipd
    return {
        "w_e1": jax.random.normal(ks[0], (in_dim, 64)) / np.sqrt(in_dim),
        "b_e1": jnp.zeros(64),
        "w_e2": jax.random.normal(ks[1], (64, emb_dim)) / np.sqrt(64.0),
        "b_e2": jnp.zeros(emb_dim),
        "w_o": jax.random.normal(ks[2], (emb_dim, num_classes)) / np.sqrt(float(emb_dim)),
        "b_o": jnp.zeros(num_classes),
    }


#: bytes of one packet's record: 60 payload bytes, then length, then IPD
PACKET_FEATURES = 62


def pack_packets(seq, payload):
    """``seq [B, W, 2]`` (length, IPD) and ``payload [B, W, 60]`` → the
    packed per-packet record ``[B, W, 62]`` uint8: the payload bytes, then
    the length, then the IPD, as a switch's parser emits one record per
    packet. The one input CNN-L's plan takes. NumPy in, NumPy out; a jax
    array in either place gives a jax array."""
    xp = jnp if isinstance(seq, jax.Array) or isinstance(payload, jax.Array) else np
    return xp.concatenate([xp.asarray(payload).astype(xp.uint8),
                           xp.asarray(seq).astype(xp.uint8)], axis=-1)


def _packet_feats(seq: jax.Array, payload: jax.Array) -> jax.Array:
    """[B,W,2]+[B,W,60] → [B, W, 62] float in [0,1]."""
    return jnp.asarray(pack_packets(seq, payload), jnp.float32) / 255.0


def cnn_l_apply(m_or_p, seq: jax.Array, payload: jax.Array) -> jax.Array:
    p = m_or_p.params if isinstance(m_or_p, CNNL) else m_or_p
    x = _packet_feats(seq, payload)                       # [B, W, 62]
    h = jax.nn.relu(x @ p["w_e1"] + p["b_e1"])
    e = jnp.tanh(h @ p["w_e2"] + p["b_e2"])               # per-packet embedding
    logits_per_pkt = e @ p["w_o"]                         # NAM contributions
    return logits_per_pkt.sum(axis=1) + p["b_o"]


def train_cnn_l(
    seq: np.ndarray, payload: np.ndarray, y: np.ndarray, num_classes: int,
    *, steps=1000, seed=0,
) -> CNNL:
    params = init_cnn_l(num_classes, seed=seed)
    x_pack = np.concatenate([seq.reshape(len(y), -1), payload.reshape(len(y), -1)], axis=1)
    w = seq.shape[1]

    def apply_packed(p, xb):
        s = xb[:, : w * 2].reshape(-1, w, 2)
        pl = xb[:, w * 2 :].reshape(-1, w, 60)
        return cnn_l_apply(p, s, pl)

    params = train_classifier(params, apply_packed, x_pack, y, steps=steps, lr=2e-3, seed=seed)
    return CNNL(params=params, num_classes=num_classes, emb_dim=16)


@dataclasses.dataclass
class PegasusCNNL:
    """Two-level NAM: per-packet encoder banks → fuzzy index (stored per
    flow, 4–8 bits, the §7.3 flow-storage trick) → logit LUT, final SumReduce.
    Its plan takes the packed per-packet records ``[B, W, 62]`` uint8 of
    :func:`pack_packets`."""

    bank1: PegasusLinear           # raw 62 bytes → encoder layer-1 pre-act
    bank2: PegasusLinear           # layer-1 pre-act → embedding pre-act (ReLU folded)
    emb_tree: FuzzyTree            # fuzzy index over tanh(embedding)
    logit_lut: jax.Array           # [2^index_bits, num_classes]
    bias: jax.Array
    index_bits: int


def pegasusify_cnn_l(
    m: CNNL, seq: np.ndarray, payload: np.ndarray, *,
    enc_group: int = 1, enc_depth: int = 8, index_bits: int = 4,
) -> PegasusCNNL:
    p = m.params
    # raw byte domain for the tables: the packed records the plan reads
    flat = np.asarray(pack_packets(seq, payload), np.float32).reshape(
        -1, PACKET_FEATURES)

    # level-1 bank: raw packet bytes (groups of enc_group bytes) → layer-1 pre-act
    bank1 = init_pegasus_linear(
        np.asarray(p["w_e1"], np.float32) / 255.0, np.asarray(p["b_e1"], np.float32),
        flat, group_size=enc_group, depth=enc_depth, lut_bits=None,
    )
    h_pre = np.asarray(jnp.asarray(flat) / 255.0 @ p["w_e1"] + p["b_e1"])
    # level-1b bank: pre-act → embedding pre-act, ReLU folded into LUT rows
    bank2 = init_pegasus_linear(
        np.asarray(p["w_e2"], np.float32), np.asarray(p["b_e2"], np.float32),
        h_pre, group_size=enc_group, depth=enc_depth, lut_bits=None,
        act_fn=lambda c: jnp.maximum(c, 0.0),
    )

    # level-2: fuzzy-index tanh(embedding) to ``index_bits`` bits per packet;
    # the per-flow register stores ONLY this index (Fig. 7 storage model).
    emb = np.asarray(
        jnp.tanh(jax.nn.relu(jnp.asarray(h_pre)) @ p["w_e2"] + p["b_e2"])
    )
    emb_tree = fit_tree(emb, depth=index_bits)
    logit_lut = jnp.asarray(emb_tree.centroids) @ p["w_o"]
    return PegasusCNNL(
        bank1=bank1, bank2=bank2, emb_tree=emb_tree, logit_lut=logit_lut,
        bias=p["b_o"], index_bits=index_bits,
    )


def pegasus_cnn_l_apply(
    peg: PegasusCNNL, seq: jax.Array, payload: jax.Array, *,
    backend: str = "gather", jit: bool = False
) -> jax.Array:
    """Deployment forward via the engine: all-table encoding → fuzzy index →
    LUT sum (the two-level NAM), on the packed record of
    :func:`pack_packets`. Eager by default — one-shot evaluation
    entry point; serving call sites get the jitted path."""
    return plan_for(peg)(pack_packets(seq, payload), backend=backend, jit=jit)
