"""Flash attention as Pallas TPU kernels: forward and backward.

Dense attention writes the float32 ``(B, H, S, S)`` scores to HBM and
reads them back for the softmax and again in the backward.  Here neither
pass ever holds more than a ``(block_q, block_k)`` tile of them, in VMEM.
Every operand and result is folded to ``(B*H, hd, S)``, the sequence
minor: the layout the compiler gives these activations in the model, so
folding costs no copy (a ``(B*H, S, hd)`` fold cost twelve layout copies
per layer), and a head dim under 128 pads nothing in HBM.

* **Forward** (``_fwd_kernel``), grid ``(B*H, q_blocks, kv_blocks)``: K/V
  stream through VMEM on the innermost ("arbitrary") axis while the
  online-softmax state (running max ``m``, sum ``l``, accumulator) stays
  in VMEM scratch.  It writes the output and the per-row logsumexp
  ``lse = m + log(l)``, float32, ``(B*H, 1, S)``.
* **Backward** (``_bwd_kernel``), one fused kernel, grid
  ``(B*H, kv_blocks, q_blocks)``: per kv block it recomputes the
  transposed probabilities ``P^T = exp(K Q^T * scale - lse)`` from q, k
  and the saved logsumexp, and with ``D = rowsum(dO * O)`` (float32,
  computed once in XLA) accumulates dV and dK over the q blocks in VMEM,
  and dQ^T for the whole sequence of the head in VMEM, written once.  The
  transposed form keeps the row statistics on the lane axis, where they
  broadcast without a relayout, and with k and v turned once per kv
  block every product takes its operands as they are.

``custom_vjp`` saves ``(q, k, v, o, lse)``: nothing of size S x S.

Precision, per product (operands in the input dtype, bfloat16 in
training, every product accumulated in float32), matching the dense path
(``models/layers.py:_dense_attention``):

* ``Q K^T``: input-dtype operands; scale, mask, max, exp, sum and
  logsumexp in float32;
* ``P V``: probabilities cast to ``v.dtype`` (the dense path casts its
  softmax to ``v.dtype`` too); output cast to ``q.dtype``;
* ``P^T dO`` (dV) and ``dO V^T``: ``P`` in ``v.dtype``, the cotangent in
  its own dtype (the output's, as the dense path's autodiff has it);
* ``dS^T Q`` (dK) and ``dS K`` (dQ): ``dS = P * (dO V^T - D)`` in float32,
  cast to the input dtype for the MXU -- the rounding the dense path's
  float32 x bfloat16 products get at the TPU's default precision.

Causal masking is positional.  Blocks wholly above the diagonal are
skipped (``pl.when``) and their index maps are clamped to the last block
that is needed, so a skipped step fetches nothing new; only blocks that
straddle the diagonal build a mask.

GQA: a kv head's G query heads read the same K/V blocks (the index map
divides the query-head index by G); dK and dV are summed over the group
in float32 after the backward kernel.

Block sizes come from the shape (:func:`block_sizes`); S must divide by
them.  :func:`flash_route` is the one rule that decides whether a call
takes these kernels (``models/layers.py:attention``, ``impl="auto"``).
"""
from __future__ import annotations

import functools
from typing import Tuple, Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.rmnp_update import LANE, VMEM_LIMIT_CAP, _vmem_limit

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
MIN_FLASH_SEQ = 256   # shortest self-attention the route sends here: flash
#                       beat dense from S 256 at head dims 64 and 96 on a
#                       TPU v5e (the shortest measured)
NEG_INF = -1e30
_NT = (((1,), (1,)), ((), ()))   # contract the last dims: A @ B^T


def block_sizes(seq: int, head_dim: int) -> Tuple[int, int]:
    """``(block_q, block_k)`` for a sequence of ``seq`` at ``head_dim``:
    512 where it divides ``seq``, else 256.  On a TPU v5e, forward and
    VJP at both benchmark shapes (head dims 64 and 96), square 512 blocks
    came within 3 % of 1024 (one block at S 1024, no causal skip), ahead
    of rectangular 256/512 pairs by 15-20 %; 128 took over three times as
    long (``benchmarks/attention_sweep.py``)."""
    del head_dim
    b = DEFAULT_BLOCK_Q if seq % DEFAULT_BLOCK_Q == 0 else 256
    return b, b


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def bwd_vmem_bytes(seq: int, hd: int, hdv: int, block_q: int,
                   block_k: int, itemsize: int) -> int:
    """VMEM the backward kernel holds: its pipelined sequence-minor blocks
    double-buffered (q, dO, k, v, the two row statistics, dQ for the
    whole sequence, dK, dV), k and v turned, the float32 dQ/dK/dV
    accumulators and six float32 ``(block_k, block_q)`` tile
    temporaries."""
    rows, rows_v = _round_up(hd, 16), _round_up(hdv, 16)   # sublanes
    lanes, lanes_v = _round_up(hd, LANE), _round_up(hdv, LANE)
    blocks = ((rows + rows_v) * (block_q + block_k) * itemsize
              + 2 * 8 * block_q * 4
              + (rows * seq + (rows + rows_v) * block_k) * itemsize)
    scratch = (block_k * (lanes + lanes_v) * (itemsize + 4)
               + rows * seq * 4)
    return 2 * blocks + scratch + 6 * block_q * block_k * 4


def flash_route(backend: str, seq_q: int, seq_kv: int, q_offset: int,
                hd: int, hdv: int, causal: bool,
                devices: int = 1) -> Union[bool, str]:
    """The one rule that sends an attention call to these kernels: ``True``,
    or why not.  Flash needs the TPU, self-attention from position 0, no
    GSPMD mesh to split it over (a Mosaic kernel cannot be partitioned;
    inside ``shard_map`` each device sees its own shard), S at least
    :data:`MIN_FLASH_SEQ` and a multiple of its block, head dims the
    MXU tiles (at most 128, or a multiple of 128), and a backward that
    fits VMEM."""
    del causal  # both masks take the same kernels
    if backend != "tpu":
        return f"backend {backend}"
    if seq_q != seq_kv or q_offset:
        return "not self-attention from position 0"
    if devices > 1:
        return f"GSPMD over {devices} devices"
    if seq_q < MIN_FLASH_SEQ:
        return f"S {seq_q} < {MIN_FLASH_SEQ}"
    bq, bk = block_sizes(seq_q, hd)
    if seq_q % bq:
        return f"S {seq_q} not a multiple of {bq}"
    for d in (hd, hdv):
        if d > LANE and d % LANE:
            return f"head dim {d}"
    if _vmem_limit(bwd_vmem_bytes(seq_q, hd, hdv, bq, bk, 2)) > \
            VMEM_LIMIT_CAP:
        return f"S {seq_q} backward over {VMEM_LIMIT_CAP >> 20} MiB VMEM"
    return True


def _lanes(col, n: int):
    """A lane-replicated ``(rows, LANE)`` column, as ``n`` lanes."""
    if n <= LANE:
        return col[:, :n]
    return jnp.tile(col, (1, n // LANE))


def _diag_mask(q0, k0, shape, transposed: bool):
    """Causal mask of a tile whose rows start at position ``q0`` (``k0``
    when ``transposed``: rows are keys) and columns at ``k0`` (``q0``)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    if transposed:
        return k0 + rows <= q0 + cols
    return k0 + cols <= q0 + rows


def _causal_steps(causal, q0, k0, block_q, block_k, body):
    """Run ``body(masked)`` for a (q block, kv block) tile: not at all
    above the diagonal, with the mask where the tile straddles it."""
    if not causal:
        body(False)
        return
    needed = k0 <= q0 + block_q - 1
    straddles = k0 + block_k - 1 > q0

    @pl.when(needed & straddles)
    def _():
        body(True)

    @pl.when(needed & jnp.logical_not(straddles))
    def _():
        body(False)


def _fwd_kernel(qt_ref, kt_ref, vt_ref, ot_ref, lse_ref, q_ref, acc_ref,
                m_ref, l_ref, *, block_q: int, block_k: int, scale: float,
                causal: bool, n_kv_blocks: int):
    """One (q block, kv block) step; the scratch carries across kv.
    Operands arrive sequence-minor, ``(hd, block)``: q is turned once per
    q block, k is the product's right operand as it is, v its
    transposed one."""
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        q_ref[...] = qt_ref[0].T
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def body(masked: bool):
        vt = vt_ref[0]
        s = jax.lax.dot(q_ref[...], kt_ref[0],
                        preferred_element_type=jnp.float32) * scale
        if masked:
            s = jnp.where(_diag_mask(qi * block_q, ki * block_k, s.shape,
                                     False), s, NEG_INF)
        m_prev = m_ref[...]                                # (bq, LANE)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, block_k))
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = corr * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        pv = jax.lax.dot_general(p.astype(vt.dtype), vt, _NT,
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * _lanes(corr, pv.shape[1]) + pv

    _causal_steps(causal, qi * block_q, ki * block_k, block_q, block_k, body)

    @pl.when(ki == n_kv_blocks - 1)
    def _finalize():
        l = l_ref[...]
        o = acc_ref[...] / _lanes(l, acc_ref.shape[1])     # (bq, hdv)
        ot_ref[0] = o.T.astype(ot_ref.dtype)
        lse = m_ref[...] + jnp.log(l)                      # (bq, LANE)
        lse_ref[0] = lse.T[:1]                             # (1, bq)


def _bwd_kernel(qt_ref, kt_ref, vt_ref, dot_ref, lse_ref, d_ref,
                dqt_ref, dkt_ref, dvt_ref, k_ref, v_ref, dq_acc, dk_acc,
                dv_acc, *, block_q: int, block_k: int, scale: float,
                causal: bool, n_q_blocks: int, n_kv_blocks: int):
    """One (kv block, q block) step in the transposed form: tiles are
    (block_k, block_q), row statistics broadcast down the sublanes.  k
    and v are turned once per kv block; every product then takes its
    operands as they are."""
    ki, qi = pl.program_id(1), pl.program_id(2)

    @pl.when((ki == 0) & (qi == 0))
    def _init_dq():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(qi == 0)
    def _init_dkv():
        k_ref[...] = kt_ref[0].T
        v_ref[...] = vt_ref[0].T
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def body(masked: bool):
        qt, dot = qt_ref[0], dot_ref[0]
        st = jax.lax.dot(k_ref[...], qt,
                         preferred_element_type=jnp.float32) * scale
        if masked:
            st = jnp.where(_diag_mask(qi * block_q, ki * block_k, st.shape,
                                      True), st, NEG_INF)
        pt = jnp.exp(st - lse_ref[0])                      # (bk, bq)
        dv_acc[...] += jax.lax.dot_general(
            pt.astype(dot.dtype), dot, _NT,
            preferred_element_type=jnp.float32)
        dpt = jax.lax.dot(v_ref[...], dot, preferred_element_type=jnp.float32)
        dst = pt * (dpt - d_ref[0])
        dk_acc[...] += jax.lax.dot_general(
            dst.astype(qt.dtype), qt, _NT,
            preferred_element_type=jnp.float32)
        dq_acc[qi] += jax.lax.dot(kt_ref[0], dst.astype(qt.dtype),
                                  preferred_element_type=jnp.float32)

    _causal_steps(causal, qi * block_q, ki * block_k, block_q, block_k, body)

    @pl.when(qi == n_q_blocks - 1)
    def _write_dkv():
        dkt_ref[0] = (dk_acc[...] * scale).T.astype(dkt_ref.dtype)
        dvt_ref[0] = dv_acc[...].T.astype(dvt_ref.dtype)

    @pl.when((ki == n_kv_blocks - 1) & (qi == n_q_blocks - 1))
    def _write_dq():
        for j in range(n_q_blocks):
            dqt_ref[0, :, j * block_q:(j + 1) * block_q] = (
                dq_acc[j] * scale).astype(dqt_ref.dtype)


def _fold(x):
    """(B, S, N, d) -> (B*N, d, S): one row of the grid per head, the
    sequence minor -- the layout the compiler gives these activations, so
    the fold is free."""
    B, S, N, d = x.shape
    return x.transpose(0, 2, 3, 1).reshape(B * N, d, S)


def _unfold(x, B: int):
    BN, d, S = x.shape
    return x.reshape(B, BN // B, d, S).transpose(0, 3, 1, 2)


def _check_blocks(S: int, block_q: int, block_k: int) -> Tuple[int, int]:
    block_q, block_k = min(block_q, S), min(block_k, S)
    if S % block_q or S % block_k:
        raise ValueError(f"S {S} must divide by the blocks "
                         f"({block_q}, {block_k})")
    return block_q, block_k


def _last_kv_block(qi, block_q: int, block_k: int):
    return (qi * block_q + block_q - 1) // block_k


def _flash_fwd(q, k, v, causal: bool, block_q: int, block_k: int,
               interpret: bool):
    """Folded output ``(B*H, hdv, S)`` and logsumexp ``(B*H, 1, S)``."""
    B, S, H, hd = q.shape
    K, hdv = k.shape[2], v.shape[-1]
    G = H // K
    block_q, block_k = _check_blocks(S, block_q, block_k)
    nq, nk = S // block_q, S // block_k

    def kv_map(bh, qi, ki):
        if causal:   # a skipped step keeps the block it has
            ki = jnp.minimum(ki, _last_kv_block(qi, block_q, block_k))
        return bh // G, 0, ki

    return pl.pallas_call(
        functools.partial(_fwd_kernel, block_q=block_q, block_k=block_k,
                          scale=1.0 / (hd ** 0.5), causal=causal,
                          n_kv_blocks=nk),
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, hd, block_q), lambda bh, qi, ki: (bh, 0, qi)),
            pl.BlockSpec((1, hd, block_k), kv_map),
            pl.BlockSpec((1, hdv, block_k), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, hdv, block_q), lambda bh, qi, ki: (bh, 0, qi)),
            pl.BlockSpec((1, 1, block_q), lambda bh, qi, ki: (bh, 0, qi)),
        ],
        out_shape=[jax.ShapeDtypeStruct((B * H, hdv, S), q.dtype),
                   jax.ShapeDtypeStruct((B * H, 1, S), jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), q.dtype),        # the q block, turned
            pltpu.VMEM((block_q, hdv), jnp.float32),   # accumulator
            pltpu.VMEM((block_q, LANE), jnp.float32),  # running max m
            pltpu.VMEM((block_q, LANE), jnp.float32),  # running sum l
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention_fwd",
    )(_fold(q), _fold(k), _fold(v))


def _flash_bwd(q, k, v, o, lse, do, causal: bool, block_q: int,
               block_k: int, interpret: bool):
    """dq, dk, dv in the inputs' layout and dtypes; ``o`` folded."""
    B, S, H, hd = q.shape
    K, hdv = k.shape[2], v.shape[-1]
    G = H // K
    block_q, block_k = _check_blocks(S, block_q, block_k)
    nq, nk = S // block_q, S // block_k
    dof = _fold(do)
    d = jnp.sum(dof.astype(jnp.float32) * o.astype(jnp.float32),
                axis=1, keepdims=True)                     # (B*H, 1, S)

    def q_map(bh, ki, qi):
        if causal:   # the first q block this kv block is seen from
            qi = jnp.maximum(qi, (ki * block_k) // block_q)
        return bh, 0, qi

    def kv_map(bh, ki, qi):
        return bh // G, 0, ki

    def kv_out_map(bh, ki, qi):
        return bh, 0, ki

    # dK/dV per query head; a group's heads are summed in float32 below
    dkv_dtype = (lambda t: t.dtype) if G == 1 else (lambda t: jnp.float32)
    itemsize = jnp.dtype(q.dtype).itemsize
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, block_q=block_q, block_k=block_k,
                          scale=1.0 / (hd ** 0.5), causal=causal,
                          n_q_blocks=nq, n_kv_blocks=nk),
        grid=(B * H, nk, nq),
        in_specs=[
            pl.BlockSpec((1, hd, block_q), q_map),
            pl.BlockSpec((1, hd, block_k), kv_map),
            pl.BlockSpec((1, hdv, block_k), kv_map),
            pl.BlockSpec((1, hdv, block_q), q_map),
            pl.BlockSpec((1, 1, block_q), q_map),
            pl.BlockSpec((1, 1, block_q), q_map),
        ],
        out_specs=[
            pl.BlockSpec((1, hd, S), lambda bh, ki, qi: (bh, 0, 0)),
            pl.BlockSpec((1, hd, block_k), kv_out_map),
            pl.BlockSpec((1, hdv, block_k), kv_out_map),
        ],
        out_shape=[jax.ShapeDtypeStruct((B * H, hd, S), q.dtype),
                   jax.ShapeDtypeStruct((B * H, hd, S), dkv_dtype(k)),
                   jax.ShapeDtypeStruct((B * H, hdv, S), dkv_dtype(v))],
        scratch_shapes=[
            pltpu.VMEM((block_k, hd), k.dtype),        # the k block, turned
            pltpu.VMEM((block_k, hdv), v.dtype),       # the v block, turned
            pltpu.VMEM((nq, hd, block_q), jnp.float32),  # dq, whole head
            pltpu.VMEM((block_k, hd), jnp.float32),    # dk
            pltpu.VMEM((block_k, hdv), jnp.float32),   # dv
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(bwd_vmem_bytes(
                S, hd, hdv, block_q, block_k, itemsize))),
        interpret=interpret,
        name="flash_attention_bwd",
    )(_fold(q), _fold(k), _fold(v), dof, lse, d)
    if G > 1:
        dk = dk.reshape(B * K, G, hd, S).sum(1).astype(k.dtype)
        dv = dv.reshape(B * K, G, hdv, S).sum(1).astype(v.dtype)
    return _unfold(dq, B), _unfold(dk, B), _unfold(dv, B)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret"))
def flash_attention_fwd(q, k, v, *, causal: bool = True,
                        block_q: int = DEFAULT_BLOCK_Q,
                        block_k: int = DEFAULT_BLOCK_K,
                        interpret: bool = False):
    """q: (B, S, H, hd); k, v: (B, S, K, hd/hdv), H % K == 0 (GQA).

    Returns (B, S, H, hdv).  S must divide by the block sizes (callers pad;
    the model's shapes are all powers of two)."""
    o, _ = _flash_fwd(q, k, v, causal, block_q, block_k, interpret)
    return _unfold(o, q.shape[0])


# ---------------------------------------------------------------------------
# differentiable wrapper: Pallas forward, Pallas backward
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal: bool = True,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool = False):
    return flash_attention_fwd(q, k, v, causal=causal, block_q=block_q,
                               block_k=block_k, interpret=interpret)


def _fa_fwd(q, k, v, causal, block_q, block_k, interpret):
    o, lse = _flash_fwd(q, k, v, causal, block_q, block_k, interpret)
    return _unfold(o, q.shape[0]), (q, k, v, o, lse)


def _fa_bwd(causal, block_q, block_k, interpret, res, g):
    q, k, v, o, lse = res
    return _flash_bwd(q, k, v, o, lse, g, causal, block_q, block_k,
                      interpret)


flash_attention.defvjp(_fa_fwd, _fa_bwd)
