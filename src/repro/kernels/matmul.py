"""Tiled MXU matmul kernel — the building block for the Muon Newton-Schulz
baseline.  Grid (m/bm, n/bn, k/bk) with an fp32 VMEM accumulator revisited
along the k axis (classic TPU matmul shape: 128-aligned tiles feed the MXU).

``matmul3`` is the batched form for stacked ``(L, m, k) @ (L, k, n)``
operands: the same tiling with a leading grid axis over ``L``, so one
``pallas_call`` covers a whole shape bucket instead of one launch per slice.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _pick(d, pref):
    for b in (pref, 256, 128, 64, 32, 16, 8):
        if b <= pref and d % b == 0:
            return b
    return d


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def matmul(a, b, *, bm: int = 256, bn: int = 256, bk: int = 256,
           interpret: bool = False):
    """a: (m, k) @ b: (k, n) -> fp32 (m, n)."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2
    bm, bn, bk = _pick(m, bm), _pick(n, bn), _pick(k, bk)
    pm, pn, pk = (-m) % bm, (-n) % bn, (-k) % bk
    if pm or pk:
        a = jnp.pad(a, ((0, pm), (0, pk)))
    if pk or pn:
        b = jnp.pad(b, ((0, pk), (0, pn)))
    M, K, N = m + pm, k + pk, n + pn
    grid = (M // bm, N // bn, K // bk)
    out = pl.pallas_call(
        functools.partial(_kernel, n_k=grid[2]),
        grid=grid,
        in_specs=[pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
                  pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        name="ns_matmul",
    )(a, b)
    return out[:m, :n]


def _kernel3(a_ref, b_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[0], b_ref[0],
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def matmul3(a, b, *, bm: int = 256, bn: int = 256, bk: int = 256,
            interpret: bool = False):
    """Batched a: (L, m, k) @ b: (L, k, n) -> fp32 (L, m, n).

    One launch for the whole stack: grid (L, m/bm, n/bn, k/bk) with the k
    axis innermost so the VMEM accumulator pattern is identical to the 2-D
    kernel — each (l, i, j) output tile revisits the accumulator along k.
    """
    L, m, k = a.shape
    L2, k2, n = b.shape
    assert k == k2 and L == L2, (a.shape, b.shape)
    bm, bn, bk = _pick(m, bm), _pick(n, bn), _pick(k, bk)
    pm, pn, pk = (-m) % bm, (-n) % bn, (-k) % bk
    if pm or pk:
        a = jnp.pad(a, ((0, 0), (0, pm), (0, pk)))
    if pk or pn:
        b = jnp.pad(b, ((0, 0), (0, pk), (0, pn)))
    M, K, N = m + pm, k + pk, n + pn
    grid = (L, M // bm, N // bn, K // bk)
    out = pl.pallas_call(
        functools.partial(_kernel3, n_k=grid[3]),
        grid=grid,
        in_specs=[pl.BlockSpec((1, bm, bk), lambda b, i, j, kk: (b, i, kk)),
                  pl.BlockSpec((1, bk, bn), lambda b, i, j, kk: (b, kk, j))],
        out_specs=pl.BlockSpec((1, bm, bn), lambda b, i, j, kk: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((L, M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        name="ns_matmul3",
    )(a, b)
    return out[:, :m, :n]
