"""Gradient compression for the cross-replica reduction.

Two mechanisms, composable with the mixed optimizer:

1. ``grad_dtype="bfloat16"`` on the train step (implicit XLA reduction in
   bf16 — halves all-reduce wire bytes, zero code at the collective site).

2. Explicit int8 error-feedback compression (this module), used on a pure
   data-parallel axis via ``shard_map``.  A ring fp32 all-reduce moves
   ``2 * 4n * (g-1)/g`` wire bytes; the compressed schedule is

       a) quantize (g + error) to blockwise-int8            [local]
       b) all_to_all the int8 chunks + fp32 block scales    [n int8 bytes]
       c) dequantize + sum the received chunks in fp32      [local]
       d) all_gather the summed chunk in bf16               [2n bytes]

   ~2.7x fewer wire bytes than fp32 ring all-reduce, ~1.4x fewer than
   bf16.  *Both* lossy stages feed back into the next step's error
   accumulator (error feedback, Seide et al. lineage): the local int8
   quantization residual of (a), and — because this rank is the one that
   computed chunk ``r``'s fp32 sum before broadcasting it in bf16 — the
   bf16 rounding residual of (d) for this rank's own chunk.  The
   *accumulated* update is therefore unbiased and convergence is
   preserved (tests/test_compression.py, including a long-run
   no-drift regression against ``exact_mean``).

3. ZeRO-2 reduce-scatter (``exact_reduce_scatter`` /
   ``compressed_reduce_scatter_leaf``): the stacked-bucket gradient is
   reduced *into its shard* — stage (d) disappears entirely (the result
   stays sharded; rank ``r`` keeps chunk ``r`` in fp32), so the wire
   schedule is the int8 a2a alone and the full mean-gradient bucket
   never exists on any rank.

   Rounding is deterministic (ties-to-even): with error feedback,
   stochastic rounding adds nothing and would break bitwise restart
   reproducibility.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.types import PyTree, path_str

_BLOCK = 512  # quantization block (elements) — one fp32 scale per block


class CompressionState(NamedTuple):
    error: PyTree  # fp32 error-feedback accumulators, like-params


def init_compression_state(params: PyTree,
                           n_dev: Optional[int] = None) -> CompressionState:
    """Zero error-feedback accumulators.

    ``n_dev=None`` (legacy / inside-shard_map view): leaves are
    like-params.  With an int ``n_dev``, every leaf gains an explicit
    leading *device* axis — ``(n_dev, *p.shape)`` — sharded ``P("data")``
    across the mesh so host checkpoints capture every rank's residual
    (not just rank 0's replica), making int8-wire restores bitwise.
    Inside the step the per-rank slice is ``local_view``; the train-step
    wrappers rewrap with ``from_local``."""
    if n_dev is None:
        return CompressionState(error=jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params))
    return CompressionState(error=jax.tree_util.tree_map(
        lambda p: jnp.zeros((n_dev,) + p.shape, jnp.float32), params))


def local_view(state: CompressionState) -> CompressionState:
    """Strip the leading device axis inside shard_map: each rank's
    ``(1, *shape)`` block becomes the like-params local residual."""
    return CompressionState(error=jax.tree_util.tree_map(
        lambda e: e[0], state.error))


def from_local(state: CompressionState) -> CompressionState:
    """Re-add the leading device axis (length 1 per rank) so shard_map's
    ``P("data")`` out-spec reassembles the global ``(n_dev, ...)`` array."""
    return CompressionState(error=jax.tree_util.tree_map(
        lambda e: e[None], state.error))


def reshard_error(state: CompressionState, n_old: int,
                  n_new: int) -> CompressionState:
    """Re-lay the device-axis EF residual for an elastic N -> N' restart.

    The *applied* compression bias at any instant is
    ``sum_r err_r / n_dev`` in mean-gradient units (each rank's residual
    is folded into its addend before the /n_dev wire mean).  Moving to a
    new mesh therefore puts ``sum(err) * (n_new / n_old)`` on rank 0 and
    zeros elsewhere — the outstanding mass is preserved exactly, and when
    the residuals are identically zero (as after any exactly-representable
    step) the reshard is bitwise zero -> zero."""
    host = jax.tree_util.tree_map(lambda e: np.asarray(e), state.error)

    def leaf(e):
        out = np.zeros((n_new,) + e.shape[1:], np.float32)
        out[0] = e.sum(axis=0) * (float(n_new) / float(n_old))
        return out

    return CompressionState(error=jax.tree_util.tree_map(leaf, host))


# ---------------------------------------------------------------------------
# quantizer
# ---------------------------------------------------------------------------

def _quantize_blocks(xb: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """fp32 ``(..., _BLOCK)`` -> (int8 ``(..., _BLOCK)``, fp32 scales
    ``(...)``): one scale per trailing block."""
    scale = jnp.max(jnp.abs(xb), axis=-1, keepdims=True) / 127.0
    q = jnp.clip(jnp.round(xb / jnp.maximum(scale, 1e-30)), -127, 127)
    return q.astype(jnp.int8), scale[..., 0]


def _dequantize_blocks(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale[..., None]


def quantize_blockwise(flat: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """fp32 (n,) with n % _BLOCK == 0 -> (int8 (n,), fp32 scales (n/_BLOCK,))."""
    q, scale = _quantize_blocks(flat.reshape(-1, _BLOCK))
    return q.reshape(-1), scale


def dequantize_blockwise(q: jax.Array, scale: jax.Array) -> jax.Array:
    return _dequantize_blocks(q.reshape(-1, _BLOCK), scale).reshape(-1)


# ---------------------------------------------------------------------------
# compressed mean over a mesh axis (call inside shard_map)
# ---------------------------------------------------------------------------

def _pad_to(x: jax.Array, mult: int) -> jax.Array:
    pad = (-x.shape[0]) % mult
    return jnp.pad(x, (0, pad)) if pad else x


def compressed_mean_leaf(g: jax.Array, err: jax.Array, axis_name: str,
                         n_dev: int):
    """Mean of ``g`` over ``axis_name`` with int8 a2a + bf16 gather.

    Returns (mean (g.shape fp32), new_err)."""
    v = g.astype(jnp.float32) + err
    n = v.size
    flat = _pad_to(v.reshape(-1), n_dev * _BLOCK)
    q, scale = quantize_blockwise(flat)
    deq = dequantize_blockwise(q, scale)
    err_flat = flat - deq  # stage-(a) residual: local int8 quantization

    # b) exchange chunks: row j of the result is sender-j's chunk for us
    qs = q.reshape(n_dev, -1)
    ss = scale.reshape(n_dev, -1)
    q_recv = jax.lax.all_to_all(qs, axis_name, split_axis=0, concat_axis=0,
                                tiled=False)
    s_recv = jax.lax.all_to_all(ss, axis_name, split_axis=0, concat_axis=0,
                                tiled=False)

    # c) dequantize + sum in fp32 (the "server" accumulation)
    chunk_sum = jnp.sum(
        jax.vmap(dequantize_blockwise)(q_recv, s_recv), axis=0)

    # d) share the result in bf16.  The bf16 rounding of chunk_sum is the
    # second lossy stage, and this rank is the only one that knows the fp32
    # value it rounded — so the rounding residual is folded into this rank's
    # error accumulator at its own chunk's positions.  Next step the chunk
    # sum carries it (+rho, exactly once), keeping the accumulated mean
    # unbiased; without it the bias compounds one bf16 ulp per step.
    chunk_bf16 = chunk_sum.astype(jnp.bfloat16)
    rounding = chunk_sum - chunk_bf16.astype(jnp.float32)
    clen = flat.size // n_dev
    idx = jax.lax.axis_index(axis_name)
    own = jax.lax.dynamic_slice(err_flat, (idx * clen,), (clen,))
    err_flat = jax.lax.dynamic_update_slice(err_flat, own + rounding,
                                            (idx * clen,))
    new_err = err_flat[:n].reshape(g.shape)

    gathered = jax.lax.all_gather(chunk_bf16, axis_name,
                                  tiled=True).astype(jnp.float32)
    mean = gathered[:n].reshape(g.shape) / n_dev
    return mean, new_err


def compressed_mean(grads: PyTree, state: CompressionState, axis_name: str,
                    n_dev: int, skip: Optional[Callable[[str], bool]] = None):
    """Tree-wide compressed mean; call inside shard_map over ``axis_name``.
    ``n_dev`` is the (static) size of the mesh axis.  Leaves whose path
    matches ``skip`` pass through unreduced with their error untouched —
    the ZeRO-2 step uses this to carve out the matrix leaves it
    reduce-scatters bucket-wise instead."""

    def leaf(kp, g, e):
        if skip is not None and skip(path_str(kp)):
            return g, e
        return compressed_mean_leaf(g, e, axis_name, n_dev)

    out = jax.tree_util.tree_map_with_path(leaf, grads, state.error)
    def pick(i):
        return jax.tree_util.tree_map(
            lambda x: x[i], out, is_leaf=lambda x: isinstance(x, tuple))
    return pick(0), CompressionState(error=pick(1))


# reference (uncompressed) mean, for the tests' convergence comparison
def exact_mean(grads: PyTree, axis_name: str,
               skip: Optional[Callable[[str], bool]] = None):
    def leaf(kp, g):
        if skip is not None and skip(path_str(kp)):
            return g
        return jax.lax.pmean(g.astype(jnp.float32), axis_name)

    return jax.tree_util.tree_map_with_path(leaf, grads)


# ---------------------------------------------------------------------------
# ZeRO-2: reduce-scatter straight into the bucket shard (call inside
# shard_map).  Operands are the (n_dev, chunk, d_in, d_out) chunked bucket
# layout of repro.core.bucketing.gather_chunks — chunk j is rank j's shard.
# ---------------------------------------------------------------------------

def exact_reduce_scatter(chunks: jax.Array, axis_name: str) -> jax.Array:
    """fp32 mean of a chunked bucket operand, left scattered: rank ``r``
    returns chunk ``r`` of the cross-replica mean, shape ``chunks.shape[1:]``.
    The full mean bucket never exists on any rank."""
    n_dev = chunks.shape[0]
    summed = jax.lax.psum_scatter(chunks.astype(jnp.float32), axis_name,
                                  scatter_dimension=0, tiled=False)
    return summed / n_dev


def fold_error_chunks(plan, chunk_means, state: CompressionState,
                      n_dev: int):
    """Fold the per-leaf fp32 error-feedback accumulators into already-
    chunked per-bucket mean-gradient operands.

    The microbatch-accumulation path (train/pipeline.py) never holds the
    matrix gradients per leaf — they are accumulated straight into the
    ``(n_dev, chunk, d_in, d_out)`` layout — so the ``g + err`` fold of
    :func:`compressed_mean_leaf` stage (a) happens here, in chunked form.
    Chunking is pure slicing (linear) and pad-slice error is identically
    zero, so this is bitwise the chunking of the per-leaf ``g + err``."""
    from repro.core.bucketing import gather_chunks

    err = gather_chunks(plan, state.error, n_dev, dtype=jnp.float32)
    return {k: chunk_means[k] + err[k] for k in chunk_means}


def rollback_fold(ok, new_state: CompressionState,
                  old_state: CompressionState) -> CompressionState:
    """Undo the error-feedback fold of a rejected step.

    The int8 schedule *consumes* the error accumulator before the wire
    (:func:`fold_error_chunks` / stage (a)) and writes the fresh residual
    after it — so by the time the non-finite guard has a verdict, the EF
    state has already turned over.  Applying the step's params/momentum
    rollback without also rolling the residual back would smuggle a
    poisoned (or simply wrong-epoch) residual into the next step's fold.
    ``jnp.where(ok, new, old)`` per leaf keeps the healthy path bitwise
    (select of the new value) and the skip path bitwise pre-step."""
    return CompressionState(error=jax.tree_util.tree_map(
        lambda n, o: jnp.where(ok, n, o), new_state.error, old_state.error))


def compressed_reduce_scatter_leaf(v_chunks: jax.Array, axis_name: str,
                                   n_dev: int, wire_fault=None):
    """int8 error-feedback reduce-scatter of one chunked bucket operand.

    ``v_chunks``: ``(n_dev, chunk, d_in, d_out)`` fp32 — this rank's local
    addend with the error accumulator already folded in (``g + err``),
    pre-split into per-destination chunks.  The schedule is stages (a)-(c)
    of :func:`compressed_mean_leaf` only: quantize, a2a the int8 chunks +
    fp32 block scales, dequantize + fp32 local sum.  Stage (d) — the bf16
    all-gather and its rounding bias — disappears because the result *stays
    sharded*: rank ``r`` keeps its fp32 chunk sum.

    ``wire_fault`` (fault-injection plumbing, ``repro.train.faults``) is an
    optional ``(q, scale) -> (q, scale)`` hook applied to the *outgoing*
    wire data — after the sender's residual is computed, so error feedback
    stays honest and only the receivers see the corruption, exactly like a
    real link fault.

    Returns ``(mean_shard fp32 (chunk, d_in, d_out), resid like v_chunks)``
    where ``resid`` is the rank-local quantization residual to scatter back
    into the error state (error feedback)."""
    if v_chunks.shape[0] != n_dev:
        raise ValueError(
            f"chunked operand has leading dim {v_chunks.shape[0]}, expected "
            f"the axis size {n_dev} — gather_chunks(n_chunks=n_dev)?")
    cshape = v_chunks.shape[1:]
    n = 1
    for s in cshape:
        n *= s
    v = v_chunks.astype(jnp.float32)
    pad = (-n) % _BLOCK
    if pad:
        xb = jnp.pad(v.reshape(n_dev, n), ((0, 0), (0, pad)))
        xb = xb.reshape(n_dev, -1, _BLOCK)
    else:
        # blocks straight from the chunked layout, never through a 1-D
        # flatten: the TPU compiler lowers a tiled -> 1-D reshape piece by
        # piece, so its compile time and host memory grow with the bucket
        # (minutes and tens of GB for a gpt2-large FFN bucket)
        xb = v.reshape(n_dev, n // _BLOCK, _BLOCK)
    q, scale = _quantize_blocks(xb)
    resid = xb - _dequantize_blocks(q, scale)
    if pad:
        resid = resid.reshape(n_dev, -1)[:, :n]
    resid = resid.reshape(v_chunks.shape)

    if wire_fault is not None:
        q, scale = wire_fault(q, scale)
    q_recv = jax.lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0,
                                tiled=False)
    s_recv = jax.lax.all_to_all(scale, axis_name, split_axis=0,
                                concat_axis=0, tiled=False)
    chunk_sum = jnp.sum(_dequantize_blocks(q_recv, s_recv), axis=0)
    if pad:
        chunk_sum = chunk_sum.reshape(-1)[:n]
    mean_shard = chunk_sum.reshape(cshape) / n_dev
    return mean_shard, resid
