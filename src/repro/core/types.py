"""Optimizer interface.

All optimizers are pure-pytree transformations compatible with jit / pjit:

    state = opt.init(params)
    params, state = opt.update_apply(grads, state, params, step)

Per-leaf optimizers also expose the updates tree they apply:

    updates, state = opt.update(grads, state, params, step)

``updates`` already contain the (negative) learning-rate scaling, i.e. the
new parameters are ``params + updates``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional

import jax

PyTree = Any
Schedule = Callable[[jax.Array], jax.Array]  # step -> lr


@dataclasses.dataclass(frozen=True)
class Optimizer:
    init: Callable[[PyTree], PyTree]
    # (grads, state, params, step) -> (new_params, state): the one step every
    # train step calls.  On the bucketed engine the weight update is folded
    # into the per-bucket rule pass, so no updates tree ever exists.
    update_apply: Callable[..., Any]
    # (grads, state, params, step) -> (updates, state): the per-leaf
    # optimizers' updates tree (None on the bucketed engine)
    update: Optional[Callable[..., Any]] = None
    # ZeRO-2 fused apply: (g_shards, grads, state, params, step, *,
    # clip_scale=None) -> (new_params, state).  ``g_shards`` maps bucket key
    # -> this rank's (padded L / N, d_in, d_out) fp32 *mean-gradient shard*
    # (from a reduce-scatter inside shard_map); matrix leaves of ``grads``
    # are ignored, non-matrix leaves must already be mean-reduced (and
    # clip-scaled — ``clip_scale`` applies only to the matrix shards, folded
    # into each bucket's chain so no pre-scaled shard buffers serialize the
    # buckets).  Exposed by the bucketed engine when built with
    # shard_axis + shard_size.
    update_apply_sharded: Optional[Callable[..., Any]] = None
    # per-bucket ZeRO-2 entry point: (bucket, g_shard, v_shard, w_chunks,
    # step, clip_scale=None, *, slots=None) -> (w_new full padded bucket,
    # v_new shard, slots_new shard).  ``slots`` maps slot name -> this
    # rank's stripe shard of the rule's extra per-bucket state (None/{} for
    # slotless rules like RMNP/Muon).  One bucket's whole chain — clip
    # scale, the rule's fused apply, updated-weight all-gather — with no
    # dependence on any other bucket.
    # ``update_apply_sharded`` IS a loop over this plus the non-matrix
    # sweep (the pipelined dp step enters through it); the per-bucket form
    # is public for steps that need to drive buckets individually, e.g.
    # emitting a bucket's update from inside the backward scan (ROADMAP:
    # intra-backward streaming).  Contract-tested against
    # update_apply_sharded in tests/test_pipeline.py.
    update_apply_bucket: Optional[Callable[..., Any]] = None
    # params -> repro.core.bucketing.BucketPlan of the matrix partition
    # (same cached plan the update fns use).  The ZeRO-2 dp step needs it
    # to chunk the gradient buckets before the reduce-scatter.
    bucket_plan: Optional[Callable[[PyTree], Any]] = None
    # the shard_size the optimizer was built with (pad multiple of every
    # bucket's stacked L == the intended ZeRO shard-axis size).  The dp step
    # validates it against the mesh axis up front — a mismatch otherwise
    # surfaces as a shape error deep inside the bucket apply.
    shard_size: int = 1
    # params -> tuple of repro.core.engine.BucketStateMeta: static
    # per-bucket state-layout metadata (momentum + slot-stripe full shapes
    # and dtypes).  Consumed by repro.analysis to police lowered steps for
    # full-bucket materialization / silent state replication; None for
    # optimizers with no bucketed state (per-leaf AdamW, references).
    state_meta: Optional[Callable[[PyTree], Any]] = None


class MixedState(NamedTuple):
    matrix: PyTree
    other: PyTree


def apply_updates(params: PyTree, updates: PyTree) -> PyTree:
    return jax.tree_util.tree_map(
        lambda p, u: (p + u.astype(p.dtype)) if u is not None else p,
        params, updates, is_leaf=lambda x: x is None)


def update_then_apply(update: Callable[..., Any]) -> Callable[..., Any]:
    """``update_apply`` of a per-leaf optimizer: its ``update``, then
    :func:`apply_updates`."""
    def update_apply(grads, state, params, step):
        updates, state = update(grads, state, params, step)
        return apply_updates(params, updates), state
    return update_apply


def path_str(keypath) -> str:
    """'/'-joined string form of a jax KeyPath (dict keys, sequence indices,
    NamedTuple fields)."""
    keys = []
    for p in keypath:
        if hasattr(p, "key"):
            keys.append(str(p.key))
        elif hasattr(p, "idx"):
            keys.append(str(p.idx))
        else:
            keys.append(str(p))
    return "/".join(keys)


def tree_paths(tree: PyTree):
    """[(path_string, leaf)] with '/'-joined dict keys."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [(path_str(path), leaf) for path, leaf in flat]


def map_with_path(fn: Callable[[str, Any], Any], tree: PyTree) -> PyTree:
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: fn(path_str(path), leaf), tree)
