"""One quintic Newton-Schulz step as a fused Pallas pipeline — the Muon
baseline's O(mn * min(m,n)) hot loop, built on the tiled matmul kernel:

    G = X X^T                (m x m)
    P = b*G + c*(G @ G)      (m x m)
    Y = a*X + P @ X          (m x n)

Kept as three kernel launches (Gram, polynomial, apply): the Gram result is
reused twice, so fusing further would re-stream it from HBM anyway.

``ns_step3`` is the batched form for a stacked ``(L, m, n)`` shape bucket:
the same three-launch pipeline on the batched matmul kernel, so a whole
bucket costs one launch sequence instead of one per matrix.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.matmul import matmul, matmul3


def _poly_kernel(g_ref, gg_ref, o_ref, *, b: float, c: float):
    o_ref[...] = b * g_ref[...] + c * gg_ref[...]


@functools.partial(jax.jit, static_argnames=("a", "b", "c", "interpret"))
def ns_step(x, a: float, b: float, c: float, interpret: bool = False):
    """x: (m, n) fp32, m <= n assumed by the caller (transpose outside)."""
    m, n = x.shape
    g = matmul(x, x.T, interpret=interpret)            # (m, m)
    gg = matmul(g, g, interpret=interpret)             # (m, m)
    bm = min(256, m) if m % min(256, m) == 0 else m
    poly = pl.pallas_call(
        functools.partial(_poly_kernel, b=b, c=c),
        grid=(max(1, m // bm),),
        in_specs=[pl.BlockSpec((bm, m), lambda i: (i, 0)),
                  pl.BlockSpec((bm, m), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bm, m), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, m), jnp.float32),
        interpret=interpret,
        name="ns_poly",
    )(g, gg)
    return a * x + matmul(poly, x, interpret=interpret)


def _poly_kernel3(g_ref, gg_ref, o_ref, *, b: float, c: float):
    o_ref[0] = b * g_ref[0] + c * gg_ref[0]


@functools.partial(jax.jit, static_argnames=("a", "b", "c", "interpret"))
def ns_step3(x, a: float, b: float, c: float, interpret: bool = False):
    """Batched x: (L, m, n) fp32, m <= n assumed by the caller."""
    L, m, n = x.shape
    xt = jnp.swapaxes(x, -1, -2)
    g = matmul3(x, xt, interpret=interpret)            # (L, m, m)
    gg = matmul3(g, g, interpret=interpret)            # (L, m, m)
    bm = min(256, m) if m % min(256, m) == 0 else m
    poly = pl.pallas_call(
        functools.partial(_poly_kernel3, b=b, c=c),
        grid=(L, max(1, m // bm)),
        in_specs=[pl.BlockSpec((1, bm, m), lambda b, i: (b, i, 0)),
                  pl.BlockSpec((1, bm, m), lambda b, i: (b, i, 0))],
        out_specs=pl.BlockSpec((1, bm, m), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((L, m, m), jnp.float32),
        interpret=interpret,
        name="ns_poly3",
    )(g, gg)
    return a * x + matmul3(poly, x, interpret=interpret)
