"""Bucket-pipelined ZeRO-2 step machinery.

The serialized ZeRO-2 step (train/dp_step.py history) is one long chain:
full backward -> all-bucket reduce-scatter -> all-bucket update.  With the
RMNP preconditioner a single O(mn) memory pass, wall-clock lives in that
serialization, not in math.  This module breaks the chain in two places:

1. **Microbatch gradient accumulation** (:func:`microbatch_grads_chunked`):
   the local batch is split into ``accum`` microbatches and the backward
   runs as a ``jax.lax.scan``.  Matrix gradients are accumulated *directly
   in the chunked per-destination-rank layout* (``core/bucketing.py
   accumulate_chunks`` applied per microbatch), so the monolithic
   ``(padded_L, d_in, d_out)`` fp32 gradient bucket still never exists on
   any rank, ``accum > 1`` included.  Chunking is linear (pure slicing), so
   accumulate-then-reduce is bitwise the reduce of the per-leaf
   accumulation.  Non-matrix leaves accumulate per leaf in fp32.

2. **Per-bucket interleave** (:func:`make_pipelined_zero2_step`): instead
   of reduce-scattering every bucket and then updating every bucket,
   bucket *k*'s reduce-scatter and bucket *k-1*'s fused update are issued
   as independent chains — no cross-bucket data dependence — so XLA's
   latency-hiding scheduler can double-buffer communication against
   compute.  The global-norm clip, previously a full-width barrier (scaled
   gradient-shard buffers between the collectives and every update), moves
   to a two-phase scheme (:func:`two_phase_clip`): per-leaf partial sums
   of squares are psum'd **once**, and the resulting scalar scale is folded
   into each bucket's update chain (``Optimizer.update_apply_bucket``
   ``clip_scale``), keeping the inter-bucket dependence down to one scalar.

The structure is verified, not vibed: ``launch/hlo_cost.py
collective_overlap_report`` asserts on the compiled HLO that no bucket's
collective data-depends on another bucket's update output, and the
traced-buffer count (``kernels/ops.py count_buffer_eqns``) stays at zero
full-bucket fp32 gradient intermediates with ``accum > 1``
(tests/_zero_shard_worker.py).

The two-phase clip also carries the **in-graph non-finite guard**: the
per-leaf partial sums of squares it already psums are exactly the
reduction a finite-ness check needs (any NaN/Inf anywhere in a leaf makes
that leaf's sum non-finite), so :class:`GuardInfo` costs one ``isfinite``
over scalars that already exist — no extra collective, no extra pass over
the gradients.  ``guard=True`` on the step then masks the *entire* update
with ``jnp.where(ok, new, old)`` (:func:`mask_updates`): params, momentum,
slot stripes and the folded int8 error-feedback residual
(``compression.rollback_fold``) are bitwise-unchanged on a bad step, and
bitwise the unguarded step on a healthy one.  The selects sit strictly
*after* every collective and update, so the pipelined schedule keeps its
zero serialization edges (analysis/overlap verifies the guarded combos).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import bucketing
from repro.core.mixed import ClipStats
from repro.core.types import Optimizer, PyTree, map_with_path, path_str, tree_paths
from repro.distributed.compression import (
    CompressionState, compressed_mean, compressed_reduce_scatter_leaf,
    exact_mean, exact_reduce_scatter, fold_error_chunks, rollback_fold,
)
from repro.models.model import loss_fn
from repro.train import faults as faults_mod

# above this axis size, two_phase_clip drops from per-leaf to per-bucket
# partials: the per-leaf scheme traces one lax.switch branch per rank (exact
# replicated summation order, the bit-for-bit grad_norm guarantee), which is
# cheap on CPU-scale meshes but would bloat trace time on pod-scale axes.
_EXACT_CLIP_MAX_RANKS = 32


def split_microbatches(batch: PyTree, accum: int) -> PyTree:
    """(B_loc, ...) leaves -> (accum, B_loc/accum, ...) for the scan."""

    def split(x):
        if x.shape[0] % accum:
            raise ValueError(
                f"accum={accum} does not divide the local batch "
                f"{x.shape[0]} (global batch / data-axis size); pick a "
                f"batch divisible by accum * n_dev")
        return x.reshape((accum, x.shape[0] // accum) + x.shape[1:])

    return jax.tree_util.tree_map(split, batch)


def _grads_of(cfg: ModelConfig, params, batch, remat: str):
    (_, metrics), grads = jax.value_and_grad(
        lambda p: loss_fn(cfg, p, batch, remat=remat), has_aux=True)(params)
    return grads, metrics


def microbatch_grads_chunked(cfg: ModelConfig, plan, params, batch,
                             accum: int, n_chunks: int, remat: str = "none",
                             fault=None, step=None):
    """Backward pass with the matrix gradients accumulated in the chunked
    per-destination-rank ZeRO-2 layout.

    Returns ``(chunk_means, rest_grads, metrics)``:

    * ``chunk_means``: bucket key -> ``(n_chunks, padded_L / n_chunks,
      d_in, d_out)`` fp32 — the local *mean* (over microbatches) matrix
      gradient, already chunked for ``psum_scatter`` / the int8 a2a.  The
      monolithic bucket never exists, ``accum > 1`` included.
    * ``rest_grads``: a params-structured tree carrying the fp32 local mean
      gradient on non-matrix leaves; matrix leaves hold inert ``(1,)*ndim``
      placeholders for ``accum > 1`` (their gradient only exists chunked)
      and the raw backward leaves for ``accum == 1`` (both are ignored by
      every consumer — the reduce skips them, the clip skips them, the
      optimizer reads the shards).
    * ``metrics``: microbatch-mean metrics (identical to the full-batch
      metrics when every microbatch carries the same token count).

    ``accum == 1`` skips the scan entirely and is bitwise the un-accumulated
    step.

    ``fault`` (:class:`repro.train.faults.FaultSpec`, needs ``step``)
    poisons the backward output at the chosen step/microbatch — upstream of
    chunking, the wire and the clip.  ``fault=None`` leaves the trace
    byte-identical to before the injector existed (no scanned index).
    """
    mat = plan.paths
    if accum == 1:
        grads, metrics = _grads_of(cfg, params, batch, remat)
        grads = faults_mod.apply_grad_fault(fault, grads, step, 0)
        chunks = bucketing.gather_chunks(plan, grads, n_chunks,
                                         dtype=jnp.float32)
        return chunks, grads, metrics

    split = split_microbatches(batch, accum)

    def mb(carry, xs):
        mb_batch, mb_idx = xs if fault is not None else (xs, 0)
        chunk_acc, rest_acc = carry
        grads, metrics = _grads_of(cfg, params, mb_batch, remat)
        grads = faults_mod.apply_grad_fault(fault, grads, step, mb_idx)
        chunk_acc = bucketing.accumulate_chunks(plan, grads, chunk_acc,
                                                n_chunks)
        rest_acc = jax.tree_util.tree_map_with_path(
            lambda kp, a, g: a if path_str(kp) in mat
            else a + g.astype(jnp.float32), rest_acc, grads)
        return (chunk_acc, rest_acc), metrics

    chunk0 = bucketing.init_chunk_acc(plan, n_chunks)
    rest0 = map_with_path(
        lambda path, p: jnp.zeros((1,) * p.ndim if path in mat else p.shape,
                                  jnp.float32), params)
    xs = (split, jnp.arange(accum)) if fault is not None else split
    (chunk_sum, rest_sum), ms = jax.lax.scan(mb, (chunk0, rest0), xs)
    chunk_means = {k: v / accum for k, v in chunk_sum.items()}
    rest_grads = map_with_path(
        lambda path, g: g if path in mat else g / accum, rest_sum)
    metrics = jax.tree_util.tree_map(lambda m: jnp.mean(m, axis=0), ms)
    return chunk_means, rest_grads, metrics


def microbatch_grads(cfg: ModelConfig, params, batch, accum: int,
                     remat: str = "none", fault=None, step=None):
    """Per-leaf microbatch accumulation (the serialized baseline): fp32
    accumulators shaped like ``params``, mean over ``accum`` microbatches.
    ``accum == 1`` skips the scan and returns the raw backward leaves.
    ``fault`` injects as in :func:`microbatch_grads_chunked`."""
    if accum == 1:
        grads, metrics = _grads_of(cfg, params, batch, remat)
        return faults_mod.apply_grad_fault(fault, grads, step, 0), metrics
    split = split_microbatches(batch, accum)

    def mb(acc, xs):
        mb_batch, mb_idx = xs if fault is not None else (xs, 0)
        grads, metrics = _grads_of(cfg, params, mb_batch, remat)
        grads = faults_mod.apply_grad_fault(fault, grads, step, mb_idx)
        acc = jax.tree_util.tree_map(
            lambda a, g: a + g.astype(jnp.float32), acc, grads)
        return acc, metrics

    zero = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)
    xs = (split, jnp.arange(accum)) if fault is not None else split
    gsum, ms = jax.lax.scan(mb, zero, xs)
    grads = jax.tree_util.tree_map(lambda g: g / accum, gsum)
    metrics = jax.tree_util.tree_map(lambda m: jnp.mean(m, axis=0), ms)
    return grads, metrics


def _matrix_leaf_sq(plan, g_shards, axis_name: str, n_dev: int):
    """Per-leaf sums of squares of the sharded matrix partition, as one
    psum'd ``{path: scalar}`` map.

    Each rank reduces the slices it holds of each leaf (``lax.switch`` over
    the rank index picks this rank's *static* slice pattern, so every
    branch has static shapes) and one psum over the stacked per-leaf
    partials combines them.  A leaf whose slices live entirely on one rank
    is reduced over the same ``(lead, d_in, d_out)`` block the replicated
    step reduces — the other ranks contribute exact zeros — so its scalar
    is bit-for-bit the replicated leaf's."""
    partials, order = [], []
    idx = jax.lax.axis_index(axis_name)
    for b in plan.buckets:
        shard = g_shards[b.key]
        csize = shard.shape[0]

        def branch(r, b=b, csize=csize):
            lo, hi = r * csize, (r + 1) * csize

            def br(sh):
                outs = []
                for e in b.entries:
                    s, t = max(lo, e.offset), min(hi, e.offset + e.lead)
                    if s < t:
                        outs.append(jnp.sum(jnp.square(sh[s - lo:t - lo])))
                    else:
                        outs.append(jnp.zeros((), jnp.float32))
                return jnp.stack(outs)

            return br

        vec = jax.lax.switch(idx, [branch(r) for r in range(n_dev)], shard)
        partials.append(vec)
        order += [e.path for e in b.entries]
    if not partials:
        return {}
    stacked = jax.lax.psum(jnp.concatenate(partials), axis_name)
    return {path: stacked[i] for i, path in enumerate(order)}


class GuardInfo(NamedTuple):
    """Per-step finite-ness verdict, read off the clip partials for free.

    ``flags[i]`` is True when flag unit ``i``'s sum of squares is finite
    (units and order: :func:`guard_flag_names` — per gradient leaf on the
    exact per-leaf clip scheme, per bucket + rest leaf beyond
    ``_EXACT_CLIP_MAX_RANKS`` ranks).  ``ok`` folds every flag AND the
    global norm itself (a finite-per-leaf sum can still overflow when
    accumulated), so ``ok=False`` <=> the update must not be applied."""
    ok: jax.Array     # () bool
    flags: jax.Array  # (n_flags,) bool


def guard_flag_names(plan, tree, n_dev: int):
    """Static names for ``GuardInfo.flags``, index-aligned: gradient-leaf
    paths in tree-flatten order up to ``_EXACT_CLIP_MAX_RANKS`` ranks,
    else ``bucket:<key>`` per bucket followed by the rest-leaf paths."""
    if n_dev <= _EXACT_CLIP_MAX_RANKS:
        return [path for path, _ in tree_paths(tree)]
    mat = plan.paths
    return ([f"bucket:{b.key}" for b in plan.buckets]
            + [p for p, _ in tree_paths(tree) if p not in mat])


@jax.named_scope("guard")
def finite_guard(grads) -> GuardInfo:
    """Per-leaf finite flags for the replicated (non-two-phase) paths: one
    sum of squares per leaf — the same per-leaf partials
    ``clip_by_global_norm`` computes, so XLA CSEs the extra traversal away
    and the guard costs one ``isfinite`` over scalars."""
    sqs = [jnp.sum(jnp.square(g.astype(jnp.float32)))
           for g in jax.tree_util.tree_leaves(grads)]
    flags = (jnp.isfinite(jnp.stack(sqs)) if sqs
             else jnp.ones((0,), jnp.bool_))
    return GuardInfo(ok=jnp.all(flags), flags=flags)


@jax.named_scope("guard")
def mask_updates(ok, new, old):
    """Bitwise step skip: ``jnp.where(ok, new, old)`` on every leaf.
    Select is an elementwise pick — ``ok=True`` yields bitwise ``new``
    (a guarded healthy step is indistinguishable from an unguarded one),
    ``ok=False`` bitwise ``old`` (a skipped step leaves every buffer
    exactly as it was).  Applied strictly after the update, so no
    collective depends on the verdict."""
    return jax.tree_util.tree_map(lambda n, o: jnp.where(ok, n, o), new, old)


@jax.named_scope("clip")
def two_phase_clip(plan, g_shards, grads, clip_norm: float, axis_name: str,
                   n_dev: int):
    """Two-phase global-norm clip over the ZeRO-2 sharded matrix partition
    plus the replicated rest.

    Phase 1: per-rank partial sums of squares — per *leaf* (up to
    ``_EXACT_CLIP_MAX_RANKS`` ranks) so the final accumulation can replay
    ``clip_by_global_norm``'s exact tree order, else per bucket — are
    psum'd **once**.  Non-fp32 rest leaves are cast to fp32 exactly once
    (the cast feeding both the norm and the caller's scaling); matrix
    leaves of ``grads`` (stale local grads or placeholders the sharded
    optimizer ignores) never contribute.

    Phase 2 is the caller's: the returned ``scale`` is folded into each
    bucket's update chain (``Optimizer.update_apply_bucket clip_scale``),
    so no scaled-shard buffers sit between the collectives and the updates
    — the only cross-bucket dependence is this one scalar.

    ``clip_norm <= 0`` disables clipping: ``scale`` is pinned to exactly
    1.0 (folding it is bitwise identity) and ``clipped`` to 0.0, while
    ``global_norm`` is still measured — metrics and the guard keep working
    with the clip off.

    The per-unit partials double as the non-finite guard: ``guard.flags``
    is ``isfinite`` over the already-psum'd scalars (order:
    :func:`guard_flag_names`), one OR-reduction riding the psum we already
    pay.

    Returns ``(scale, rest32, stats, guard)`` where ``rest32`` maps
    rest-leaf path -> the once-cast fp32 leaf (matrix paths absent) and
    ``guard`` is the :class:`GuardInfo`."""
    mat = plan.paths
    rest32 = {path: g.astype(jnp.float32)
              for path, g in tree_paths(grads) if path not in mat}
    if n_dev <= _EXACT_CLIP_MAX_RANKS:
        leaf_sq = _matrix_leaf_sq(plan, g_shards, axis_name, n_dev)
        # exact replicated accumulation order: one scalar per leaf, summed
        # in tree-flatten order, starting from int 0 like clip_by_global_norm
        sqs = [leaf_sq[path] if path in mat else
               jnp.sum(jnp.square(rest32[path]))
               for path, _ in tree_paths(grads)]
        sq = sum(sqs)
        flags = (jnp.isfinite(jnp.stack(sqs)) if sqs
                 else jnp.ones((0,), jnp.bool_))
    else:
        # per-bucket partials, still one psum (a stacked vector instead of
        # a scalar) so the guard keeps bucket granularity at pod scale
        sq_mat = (jax.lax.psum(jnp.stack(
            [jnp.sum(jnp.square(g_shards[b.key])) for b in plan.buckets]),
            axis_name) if plan.buckets else jnp.zeros((0,), jnp.float32))
        rest_sqs = [jnp.sum(jnp.square(g)) for g in rest32.values()]
        sq = sum(rest_sqs) + jnp.sum(sq_mat)
        flags = jnp.isfinite(
            jnp.concatenate([sq_mat] + ([jnp.stack(rest_sqs)]
                                        if rest_sqs else [])))
    gnorm = jnp.sqrt(sq)
    if clip_norm > 0:
        scale = jnp.minimum(1.0, clip_norm / (gnorm + 1e-12))
        clipped = (gnorm > clip_norm).astype(jnp.float32)
    else:
        scale = jnp.ones((), jnp.float32)
        clipped = jnp.zeros((), jnp.float32)
    stats = ClipStats(global_norm=gnorm, clipped=clipped)
    guard = GuardInfo(ok=jnp.logical_and(jnp.all(flags), jnp.isfinite(gnorm)),
                      flags=flags)
    return scale, rest32, stats, guard


def scale_rest(grads, rest32, scale):
    """Apply the clip scale to the once-cast fp32 rest leaves (matrix
    leaves pass through untouched — dead values the sharded optimizer
    ignores, scaling them would be wasted work)."""
    return map_with_path(
        lambda path, g: rest32[path] * scale if path in rest32 else g, grads)


def make_pipelined_zero2_step(cfg: ModelConfig, opt: Optimizer, *,
                              axis_name: str, n_dev: int, clip_norm: float,
                              compress: bool, remat: str, accum: int,
                              guard: bool = False,
                              fault: Optional["faults_mod.FaultSpec"] = None):
    """The bucket-pipelined ZeRO-2 local step (call inside ``shard_map``
    over ``axis_name``): microbatch-accumulated chunked backward, one
    independent reduce-scatter -> clip-partial -> update chain per bucket,
    two-phase clip, updates entered through ``update_apply_sharded`` with
    the clip scale folded per bucket.

    ``guard=True`` masks the whole update (params, optimizer state, and on
    the int8 wire the folded error-feedback residual) with the
    :func:`two_phase_clip` finite verdict — a non-finite step leaves every
    buffer bitwise-unchanged and reports ``skipped=1`` plus the per-leaf
    ``guard_flags``.  ``fault`` injects a :mod:`repro.train.faults` fault
    into the backward output or the int8 wire (test/proof plumbing)."""

    def local_step(params, opt_state, comp_state, batch, step):
        plan = opt.bucket_plan(params)
        mat = plan.paths
        prev = (params, opt_state, comp_state)
        chunk_means, rest, metrics = microbatch_grads_chunked(
            cfg, plan, params, batch, accum, n_dev, remat,
            fault=fault, step=step)

        # per-bucket reduce chains: each bucket's collective depends only on
        # its own accumulated chunks (+ the shared error state), never on
        # another bucket's update
        g_shards = {}
        def skip(path):
            return path in mat
        if compress:
            v_chunks = fold_error_chunks(plan, chunk_means, comp_state, n_dev)
            resid = {}
            for b in plan.buckets:
                with jax.named_scope(f"reduce_scatter_{b.key}"):
                    g_shards[b.key], resid[b.key] = \
                        compressed_reduce_scatter_leaf(
                            v_chunks[b.key], axis_name, n_dev,
                            wire_fault=faults_mod.wire_fault_for(
                                fault, b.key, step, axis_name))
            rest, comp_state = compressed_mean(
                rest, comp_state, axis_name, n_dev, skip=skip)
            comp_state = CompressionState(
                error=bucketing.scatter_chunks(plan, resid, comp_state.error))
        else:
            for b in plan.buckets:
                with jax.named_scope(f"reduce_scatter_{b.key}"):
                    g_shards[b.key] = exact_reduce_scatter(
                        chunk_means[b.key], axis_name)
            rest = exact_mean(rest, axis_name, skip=skip)
        metrics = jax.tree_util.tree_map(
            lambda m: jax.lax.pmean(m, axis_name), metrics)

        scale, rest32, clip_stats, ginfo = two_phase_clip(
            plan, g_shards, rest, clip_norm, axis_name, n_dev)
        rest = scale_rest(rest, rest32, scale)
        with jax.named_scope("optimizer"):
            params, opt_state = opt.update_apply_sharded(
                g_shards, rest, opt_state, params, step, clip_scale=scale)
        metrics = dict(metrics, grad_norm=clip_stats.global_norm,
                       clip_rate=clip_stats.clipped)
        if guard:
            # post-update, post-collective selects: the pipelined schedule
            # (0 serialization edges) is untouched, only the final writes
            # pick between new and prev
            params = mask_updates(ginfo.ok, params, prev[0])
            opt_state = mask_updates(ginfo.ok, opt_state, prev[1])
            if compress:
                comp_state = rollback_fold(ginfo.ok, comp_state, prev[2])
            metrics["skipped"] = (~ginfo.ok).astype(jnp.float32)
            metrics["guard_flags"] = ginfo.flags.astype(jnp.float32)
        return params, opt_state, comp_state, metrics

    return local_step
