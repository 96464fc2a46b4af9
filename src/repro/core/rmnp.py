"""RMNP — Row-Momentum Normalized Preconditioning (the paper's contribution).

Algorithm 2:
    V_t = beta * V_{t-1} + (1 - beta) * G_t
    D_t = RN(V_t) = (diag(V_t V_t^T))^{-1/2} V_t      (row-wise l2 normalize)
    W_{t+1} = W_t - eta * (D_t + wd * W_t)

Storage convention: every matmul parameter in this framework is stored as
(..., d_in, d_out); the paper's "row" (one output neuron's fan-in vector,
normalized along d_in) is therefore a *column* of the stored matrix, i.e. we
normalize along axis -2.  Leading axes (scan layer stacks, MoE expert stacks)
are treated as independent matrices.

Per-iteration cost is O(mn) — a single elementwise pass + a row reduction —
versus Muon's O(mn * min(m, n)) Newton-Schulz matmuls.

The optimizer itself is ``make_optimizer("rmnp", ...)`` (core/registry.py):
the rule lives in core/rules.py and runs on the bucketed engine
(core/engine.py).  This module keeps the two primitives it is built from.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def row_normalize(v: jax.Array, eps: float = 1e-8, in_axis: int = -2) -> jax.Array:
    """(diag(V V^T))^{-1/2} V: l2-normalize each output neuron's fan-in."""
    norm = jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)), axis=in_axis, keepdims=True))
    return (v / (norm + eps)).astype(v.dtype)


def rms_lr_scale(shape) -> float:
    """Muon/RMNP RMS scaling: lr * max(1, sqrt(d_out / d_in)) (Eq. 17/18)."""
    d_in, d_out = shape[-2], shape[-1]
    return max(1.0, (d_out / d_in) ** 0.5)
