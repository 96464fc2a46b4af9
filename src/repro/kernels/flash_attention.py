"""Flash attention (forward) as a Pallas TPU kernel.

The dominant memory term in the train_4k / prefill_32k roofline is the
(B, H, S, S) attention-score traffic of the XLA paths (see EXPERIMENTS.md
§Perf).  On TPU the fix is structural: tile Q into (block_q, hd) VMEM
blocks, stream K/V through VMEM in (block_k, hd) blocks on an inner grid
axis, and keep the online-softmax state (acc, m, l) in VMEM scratch — the
S x S score matrix never exists in HBM, so attention HBM traffic collapses
to O(S*hd) reads of Q/K/V plus one O(S*hd) write of the output.

Grid: (batch*kv_head, q_blocks, kv_blocks); the kv axis is the innermost
("arbitrary") dimension so the scratch accumulator carries across it.
Causal masking is positional, and fully-masked kv blocks are skipped via
pl.when (the compiler still schedules them, but they cost no MXU work).

GQA is handled by folding the group dimension into block rows: a kv head's
G query heads share its K/V stream, so q blocks are (G * block_q, hd).

The backward pass uses the recompute strategy: jax.custom_vjp whose bwd
re-runs the memory-efficient chunked reference (ref.py) under jax.vjp —
exactly flash-attention-2's recomputation, expressed at the XLA level.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
NEG_INF = -1e30


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                block_q: int, block_k: int, scale: float, causal: bool,
                n_kv_blocks: int):
    """One (q_block, kv_block) cell.  Scratch persists across the kv axis."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    should_run = True
    if causal:
        # kv block strictly after the q block: fully masked, skip
        should_run = ki * block_k <= (qi + 1) * block_q - 1

    @pl.when(should_run)
    def _body():
        q = q_ref[0].astype(jnp.float32)                 # (bq, hd)
        k = k_ref[0].astype(jnp.float32)                 # (bk, hd)
        v = v_ref[0].astype(jnp.float32)                 # (bk, hdv)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == n_kv_blocks - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / (l_ref[...] + 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret"))
def flash_attention_fwd(q, k, v, *, causal: bool = True,
                        block_q: int = DEFAULT_BLOCK_Q,
                        block_k: int = DEFAULT_BLOCK_K,
                        interpret: bool = False):
    """q: (B, S, H, hd); k, v: (B, S, K, hd/hdv), H % K == 0 (GQA).

    Returns (B, S, H, hdv).  S must divide by the block sizes (callers pad;
    the model's shapes are all powers of two).
    """
    B, S, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    hdv = v.shape[-1]
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    nq = S // block_q
    nk = S // block_k
    scale = 1.0 / (hd ** 0.5)

    # fold (B, K) into the leading grid axis; queries grouped per kv head
    # q -> (B*K, S*G?, ...): keep G inside the row dim so one kv stream
    # serves its G query heads: rows are (q_pos, g) pairs.
    qg = (q.reshape(B, S, K, G, hd).transpose(0, 2, 3, 1, 4)
          .reshape(B * K * G, S, hd))
    kg = jnp.repeat(k.transpose(0, 2, 1, 3).reshape(B * K, S, hd), G, axis=0)
    vg = jnp.repeat(v.transpose(0, 2, 1, 3).reshape(B * K, S, hdv), G, axis=0)

    grid = (B * K * G, nq, nk)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, block_q=block_q, block_k=block_k,
                          scale=scale, causal=causal, n_kv_blocks=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_k, hdv), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hdv), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * K * G, S, hdv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hdv), jnp.float32),   # acc
            pltpu.VMEM((block_q, 1), jnp.float32),     # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),     # running denom l
        ],
        interpret=interpret,
        name="flash_attention_fwd",
    )(qg, kg, vg)
    return (out.reshape(B, K, G, S, hdv).transpose(0, 3, 1, 2, 4)
            .reshape(B, S, H, hdv))


# ---------------------------------------------------------------------------
# differentiable wrapper: Pallas forward, recompute backward
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal: bool = True,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool = False):
    return flash_attention_fwd(q, k, v, causal=causal, block_q=block_q,
                               block_k=block_k, interpret=interpret)


def _fa_fwd(q, k, v, causal, block_q, block_k, interpret):
    out = flash_attention_fwd(q, k, v, causal=causal, block_q=block_q,
                              block_k=block_k, interpret=interpret)
    return out, (q, k, v)


def _fa_bwd(causal, block_q, block_k, interpret, res, g):
    # flash-attention-2 recompute strategy: the O(S^2) tensors are rebuilt
    # chunk-by-chunk in the backward; we express it as jax.vjp of the
    # memory-efficient chunked reference so XLA emits the chunked backward.
    q, k, v = res
    from repro.kernels.ref import chunked_attention_ref
    _, vjp = jax.vjp(
        lambda q_, k_, v_: chunked_attention_ref(
            q_, k_, v_, causal=causal, chunk_q=block_q, chunk_k=block_k),
        q, k, v)
    return vjp(g)


flash_attention.defvjp(_fa_fwd, _fa_bwd)
