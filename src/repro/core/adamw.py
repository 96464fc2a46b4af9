"""AdamW for non-matrix parameters (and as a paper baseline)."""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.types import Optimizer, PyTree, Schedule, update_then_apply


class AdamWState(NamedTuple):
    mu: PyTree
    nu: PyTree


def adamw(lr: Schedule, b1: float = 0.9, b2: float = 0.95,
          eps: float = 1e-8, weight_decay: float = 0.1) -> Optimizer:
    def init(params):
        def z(p):
            return jnp.zeros(p.shape, jnp.float32)
        return AdamWState(mu=jax.tree_util.tree_map(z, params),
                          nu=jax.tree_util.tree_map(z, params))

    def update(grads, state, params, step):
        eta = lr(step)
        t = jnp.asarray(step, jnp.float32) + 1.0
        bc1 = 1.0 - b1 ** t
        bc2 = 1.0 - b2 ** t

        def upd(g, mu, nu, p):
            g = g.astype(jnp.float32)
            mu_new = b1 * mu + (1 - b1) * g
            nu_new = b2 * nu + (1 - b2) * jnp.square(g)
            d = (mu_new / bc1) / (jnp.sqrt(nu_new / bc2) + eps)
            return (-eta * (d + weight_decay * p.astype(jnp.float32))), mu_new, nu_new

        out = jax.tree_util.tree_map(upd, grads, state.mu, state.nu, params)
        def pick(i):
            return jax.tree_util.tree_map(
                lambda x: x[i], out, is_leaf=lambda x: isinstance(x, tuple))
        return pick(0), AdamWState(mu=pick(1), nu=pick(2))

    return Optimizer(init=init, update=update,
                     update_apply=update_then_apply(update))
