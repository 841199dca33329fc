"""Plain reference of CNN-L, in float32: per packet, bank 1 on its 62 bytes,
bank 2 on bank 1's output, ``tanh``, the index tree's descent, the logit
table's row; the rows summed over the 8 packets, plus the bias. Imports
nothing of the program."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import bank_forward


def forward(cfg: dict, banks: dict, x: jax.Array, precision: str = "highest"):
    """``x [T, 8, 62]`` → ``(logits [T, 3], margin [T])``. The margin is
    the least over the 8 packets of every computed comparison (bank 2's and
    the index tree's; bank 1 compares exact integers)."""
    d, v = cfg["enc_depth"], cfg["enc_group"]
    index = dict(banks["index"], bias=None)

    def packet(xp):                       # [T, 62] bytes of one slot
        h, _ = bank_forward(banks["b1"], xp.astype(jnp.float32), v=v,
                            depth=d, precision=precision, raw_input=True)
        e, m2 = bank_forward(banks["b2"], h, v=v, depth=d,
                             precision=precision)
        # the index tree: one group of the whole embedding; its leaf's
        # table row is the packet's logit row
        y, m3 = bank_forward(index, jnp.tanh(e), v=cfg["embedding"],
                             depth=cfg["index_bits"], precision=precision)
        return y, jnp.minimum(m2, m3)

    # one packet slot at a time: bank 1's gathered rows are [T, 62, 64]
    ys, ms = jax.lax.map(packet, jnp.swapaxes(x, 0, 1))
    return ys.sum(axis=0) + banks["index"]["bias"], ms.min(axis=0)
