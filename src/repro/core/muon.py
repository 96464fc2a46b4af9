"""Muon baseline (Algorithm 1): Newton-Schulz orthogonalization of momentum.

Reference coefficients from Jordan et al. [11]; 5 iterations by default.
The NS iteration costs O(mn * min(m, n)) per step — the quantity RMNP removes.
The optimizer is ``make_optimizer("muon", ...)``; its rule is
``rules.MuonRule``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_NS_COEFFS = (3.4445, -4.7750, 2.0315)


def newton_schulz(v: jax.Array, steps: int = 5, eps: float = 1e-7,
                  use_kernel: bool = False) -> jax.Array:
    """Approximate (V V^T)^{-1/2} V via the quintic Newton-Schulz iteration.

    Operates on the last two dims; leading dims are batched. Always iterates
    on the smaller Gram side (transpose if rows > cols).
    """
    a, b, c = _NS_COEFFS
    orig_dtype = v.dtype
    x = v.astype(jnp.float32)
    transpose = x.shape[-2] > x.shape[-1]
    if transpose:
        x = jnp.swapaxes(x, -1, -2)
    x = x / (jnp.linalg.norm(x, axis=(-2, -1), keepdims=True) + eps)

    if use_kernel:
        from repro.kernels import ops as kops
        for _ in range(steps):
            x = kops.ns_step(x, a, b, c)
    else:
        for _ in range(steps):
            g = x @ jnp.swapaxes(x, -1, -2)          # (m, m) Gram
            x = a * x + (b * g + c * (g @ g)) @ x    # quintic polynomial
    if transpose:
        x = jnp.swapaxes(x, -1, -2)
    return x.astype(orig_dtype)
