"""Plain reference of MLP-B: the four banks in sequence, in float32.
Imports nothing of the program."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import bank_forward


def forward(cfg: dict, banks: list, x: jax.Array, precision: str = "highest"):
    """``x [T, 16]`` → ``(logits [T, 3], margin [T])``."""
    h = x.astype(jnp.float32)
    margin = jnp.full((x.shape[0],), jnp.inf, jnp.float32)
    for i, bank in enumerate(banks):
        h, m = bank_forward(bank, h, v=cfg["group_size"], depth=cfg["depth"],
                            precision=precision, raw_input=i == 0)
        margin = jnp.minimum(margin, m)
    return h, margin
