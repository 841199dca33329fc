"""Plain reference of RNN-B: ``h_0 = X_0(x_0)``,
``h_t = X_t(x_t) + H_{t-1}(h_{t-1})``, logits ``= O(h_{W-1})``, in float32.
Imports nothing of the program."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import bank_forward


def forward(cfg: dict, banks: dict, x: jax.Array, precision: str = "highest"):
    """``x [T, W, 2]`` → ``(logits [T, 3], margin [T])``."""
    d = cfg["depth"]
    xf = x.astype(jnp.float32)
    h, margin = bank_forward(banks["x0"], xf[:, 0], v=cfg["x_group"], depth=d,
                             precision=precision, raw_input=True)
    for t in range(1, cfg["window"]):
        a, ma = bank_forward(banks[f"x{t}"], xf[:, t], v=cfg["x_group"],
                             depth=d, precision=precision, raw_input=True)
        b, mb = bank_forward(banks[f"h{t - 1}"], h, v=cfg["h_group"], depth=d,
                             precision=precision)
        h = a + b
        margin = jnp.minimum(margin, jnp.minimum(ma, mb))
    y, m = bank_forward(banks["out"], h, v=cfg["h_group"], depth=d,
                        precision=precision)
    return y, jnp.minimum(margin, m)
