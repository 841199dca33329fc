"""Synthetic flow records, made from the seed.

PeerRush, the capture MLP-B and RNN-B are evaluated on in the paper, is not
in the repository, so the benchmark draws flows with the same structure the
models read: per class a Markov chain over packet-length states and a
log-normal inter-packet delay (IPD), over a window of 8 packets. Two views
of each flow, as a switch's parser carries them (8-bit unsigned values):

* ``seq``   ``[F, 8, 2]``: (length, IPD) per packet, RNN-B's input;
* ``stats`` ``[F, 16]``: max/min/mean/std of length and IPD, mean absolute
  change of each, counts of long packets and long gaps (x16), and the first
  and last length and IPD, MLP-B's input.

The class parameters are drawn from the seed as well; the distributions
follow the repository's synthetic PeerRush model (3 classes).
"""

from __future__ import annotations

import numpy as np

WINDOW = 8
N_STATES = 6


def make_flows(seed: int, n: int, classes: int = 3) -> dict:
    """``n`` flows drawn from ``seed``: ``{"seq", "stats", "label"}``."""
    rng = np.random.default_rng(seed)
    base = rng.dirichlet(np.full(N_STATES, 2.0), size=(classes, N_STATES))
    ident = np.stack([np.roll(np.eye(N_STATES), c, axis=1)
                      for c in range(classes)])
    trans = 0.55 * ident + 0.45 * base
    cdf = np.cumsum(trans / trans.sum(-1, keepdims=True), axis=-1)
    means = (np.linspace(40, 250, N_STATES)[None]
             + rng.normal(0, 10, (classes, N_STATES))
             + 6 * np.arange(classes)[:, None])
    stds = rng.uniform(5, 25, (classes, N_STATES))
    ipd_mu = rng.uniform(1.0, 3.5, classes) + 0.25 * np.arange(classes)
    ipd_sigma = rng.uniform(0.3, 0.9, classes)

    label = rng.integers(0, classes, n)
    state = rng.integers(0, N_STATES, n)
    lens = np.empty((n, WINDOW), np.float32)
    ipds = np.empty((n, WINDOW), np.float32)
    for t in range(WINDOW):
        lens[:, t] = np.clip(rng.normal(means[label, state],
                                        stds[label, state]), 0, 255)
        ipds[:, t] = np.clip(rng.lognormal(ipd_mu[label], ipd_sigma[label]),
                             0, 255)
        u = rng.random(n)
        state = np.minimum((u[:, None] < cdf[label, state]).argmax(-1),
                           N_STATES - 1)
    seq = np.stack([lens, ipds], axis=-1).astype(np.uint8)
    stats = np.stack([
        lens.max(1), lens.min(1), lens.mean(1), lens.std(1),
        ipds.max(1), ipds.min(1), ipds.mean(1), ipds.std(1),
        np.abs(np.diff(lens, axis=1)).mean(1),
        np.abs(np.diff(ipds, axis=1)).mean(1),
        (lens > 128).sum(1) * 16.0, (ipds > 32).sum(1) * 16.0,
        lens[:, 0], lens[:, -1], ipds[:, 0], ipds[:, -1],
    ], axis=1)
    stats = np.clip(stats, 0, 255).astype(np.uint8)
    return {"seq": seq, "stats": stats, "label": label.astype(np.int32)}
