"""MLP-B (paper §6.3): BN→FC→ReLU ×3 + classifier head, on statistical
features — with its fully fused Pegasus form.

Fusion layout (Basic Primitive Fusion, Fig. 5 ①): each deployed table bank i
is indexed by layer i-1's PRE-activation and folds
`[ReLU →] BN-affine → FC` into its LUT rows; the switch executes
K lookups + a SumReduce per bank — nothing else.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.amm import PegasusLinear, init_pegasus_linear
from repro.engine import plan_for

from .common import train_classifier

__all__ = ["MLPB", "init_mlp", "mlp_apply", "mlp_b_recipe", "train_mlp",
           "pegasusify_mlp", "pegasus_mlp_apply"]

HIDDEN = 32


@dataclasses.dataclass
class MLPB:
    """Dense teacher + feature-normalization constants."""

    params: dict
    mu: np.ndarray
    sigma: np.ndarray
    num_classes: int


def init_mlp(in_dim: int, num_classes: int, hidden: int = HIDDEN, seed: int = 0) -> dict:
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    dims = [in_dim, hidden, hidden, hidden]
    params = {}
    for i in range(3):
        params[f"w{i}"] = jax.random.normal(ks[2 * i], (dims[i], dims[i + 1])) / np.sqrt(dims[i])
        params[f"b{i}"] = jnp.zeros(dims[i + 1])
        params[f"gamma{i}"] = jnp.ones(dims[i])
        params[f"beta{i}"] = jnp.zeros(dims[i])
    params["w_out"] = jax.random.normal(ks[6], (hidden, num_classes)) / np.sqrt(hidden)
    params["b_out"] = jnp.zeros(num_classes)
    return params


def mlp_apply(bundle_or_params, x: jax.Array, mu=None, sigma=None) -> jax.Array:
    """Forward. Accepts (params, mu, sigma) or an MLPB bundle."""
    if isinstance(bundle_or_params, MLPB):
        p, mu, sigma = bundle_or_params.params, bundle_or_params.mu, bundle_or_params.sigma
    else:
        p = bundle_or_params
    h = (x.astype(jnp.float32) - mu) / sigma  # dataset-stat normalization
    for i in range(3):
        h = p[f"gamma{i}"] * h + p[f"beta{i}"]          # BN affine (folded)
        h = h @ p[f"w{i}"] + p[f"b{i}"]                 # FC
        if True:
            h_pre = h
        h = jax.nn.relu(h)                              # ReLU
    return h @ p["w_out"] + p["b_out"]


def train_mlp(x: np.ndarray, y: np.ndarray, num_classes: int, *, steps=800, seed=0) -> MLPB:
    mu = x.astype(np.float32).mean(0)
    sigma = x.astype(np.float32).std(0) + 1e-3
    params = init_mlp(x.shape[1], num_classes, seed=seed)
    params = train_classifier(
        params,
        lambda p, xb: mlp_apply(p, xb, mu, sigma),
        x, y, steps=steps, seed=seed,
    )
    return MLPB(params=params, mu=mu, sigma=sigma, num_classes=num_classes)


# ---------------------------------------------------------------------------
# Pegasusification: dense teacher → fused LUT banks
# ---------------------------------------------------------------------------


def _activations(bundle: MLPB, x: np.ndarray) -> list[np.ndarray]:
    """Per-bank calibration inputs: raw x, then each FC's pre-activation."""
    p, mu, sigma = bundle.params, bundle.mu, bundle.sigma
    acts = [x.astype(np.float32)]
    h = (jnp.asarray(x, jnp.float32) - mu) / sigma
    for i in range(3):
        h = p[f"gamma{i}"] * h + p[f"beta{i}"]
        h = h @ p[f"w{i}"] + p[f"b{i}"]
        acts.append(np.asarray(h))
        h = jax.nn.relu(h)
    return acts  # [x, pre1, pre2, pre3]


def pegasusify_mlp(
    bundle: MLPB,
    x_calib: np.ndarray,
    *,
    group_size: int = 2,
    depth: int = 6,
    refine_steps: int = 100,
) -> list[PegasusLinear]:
    """Lower the trained MLP to 4 fused Pegasus banks (Fig. 5 ① result).

    Bank 0: idx on raw 8-bit stats; LUT = (norm·BN0 affine)(c) @ W0 + b0.
    Bank i: idx on pre-act i;       LUT = (BNi affine ∘ ReLU)(c) @ Wi + bi.
    Bank 3: classifier;             LUT = ReLU(c) @ W_out + b_out.
    """
    p, mu, sigma = bundle.params, bundle.mu, bundle.sigma
    acts = _activations(bundle, x_calib)
    layers = []

    def affine_fold(i, include_norm: bool):
        g = np.asarray(p[f"gamma{i}"], np.float32)
        b = np.asarray(p[f"beta{i}"], np.float32)
        if include_norm:
            scale = g / sigma
            shift = b - g * mu / sigma
        else:
            scale, shift = g, b

        def fn(c):  # c: [K, C, v] stacked centroids; slice per group
            k, _, v = c.shape
            s = scale.reshape(k, 1, v)
            t = shift.reshape(k, 1, v)
            return s * c + t

        return fn

    # bank 0: raw input → FC0 pre-activation
    layers.append(
        init_pegasus_linear(
            np.asarray(p["w0"]), np.asarray(p["b0"]), acts[0],
            group_size=group_size, depth=depth, lut_bits=None,
            act_fn=affine_fold(0, include_norm=True),
        )
    )
    # banks 1..2: pre-act i → pre-act i+1 (fold ReLU + BN affine)
    for i in (1, 2):
        aff = affine_fold(i, include_norm=False)
        layers.append(
            init_pegasus_linear(
                np.asarray(p[f"w{i}"]), np.asarray(p[f"b{i}"]), acts[i],
                group_size=group_size, depth=depth, lut_bits=None,
                act_fn=lambda c, aff=aff: aff(jnp.maximum(c, 0.0)),
            )
        )
    # classifier bank
    layers.append(
        init_pegasus_linear(
            np.asarray(p["w_out"]), np.asarray(p["b_out"]), acts[3],
            group_size=group_size, depth=depth, lut_bits=None,
            act_fn=lambda c: jnp.maximum(c, 0.0),
        )
    )

    if refine_steps:
        from repro.core.finetune import refine

        refined = []
        for i, layer in enumerate(layers):
            xb = jnp.asarray(acts[i])
            if i == 0:
                tgt = jnp.asarray(acts[1])
            elif i < 3:
                tgt = jnp.asarray(acts[i + 1])
            else:
                tgt = mlp_apply(bundle, jnp.asarray(x_calib))
            refined.append(refine(layer, xb, tgt, steps=refine_steps))
        layers = refined
    return layers


def mlp_b_recipe(seed: int = 0, *, flows_per_class: int = 1500,
                 steps: int = 120):
    """MLP-B at its published widths, served as users serve it: 16 8-bit
    flow statistics, ``group_size=2``, ``depth=6`` (64 centroids), hidden
    32, 4 banks, PeerRush's 3 classes. The dense teacher trains in-process
    on the synthetic PeerRush flows, all drawn from ``seed``; nothing is read
    from disk. Returns ``(banks, dataset)`` — the banks go to ``build_plan``
    or a server's ``add_model``."""
    from repro.data.synthetic_traffic import make_dataset

    ds = make_dataset("peerrush", flows_per_class=flows_per_class, seed=seed)
    teacher = train_mlp(ds.train["stats"], ds.train["label"], ds.num_classes,
                        steps=steps, seed=seed)
    banks = pegasusify_mlp(teacher, ds.train["stats"].astype(np.float32),
                           refine_steps=0)
    return banks, ds


def pegasus_mlp_apply(
    layers: list[PegasusLinear], x: jax.Array, *,
    backend: str = "gather", path: str | None = None, jit: bool = False,
) -> jax.Array:
    """Run the fused bank stack via the execution engine (hard routing,
    deployment semantics). ``path`` is a deprecated alias for ``backend``.
    Eager by default — one-shot evaluation entry point; serving call sites
    (PegasusServer / build_plan) get the jitted path."""
    return plan_for(layers)(x, backend=path if path is not None else backend,
                            jit=jit)
