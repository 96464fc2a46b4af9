"""Generic shape-bucketed optimizer engine, parameterized by a
:class:`repro.core.rules.MatrixUpdateRule`.

This module owns the rule-agnostic machinery of the matrix partition: the
cached leaf->bucket plan, stacked momentum (+ per-rule slot stripes)
initialization, the ZeRO-1-aware single-pass apply, and the ZeRO-2
per-bucket sharded apply with the clip scale folded into each chain.
``core/mixed.py`` composes it with the per-leaf AdamW sweep, so a new
update rule inherits ZeRO-1/2 sharding, padded uneven buckets, int8
error-feedback and pipelined overlap with zero new distributed code.

State layout (:class:`BucketedState`): ``buckets`` maps bucket key -> the
stacked ``(padded L, d_in, d_out)`` momentum; ``slots`` maps slot name ->
bucket key -> the rule's extra ``(padded L, 1, d_out)`` stripes.  Both
shard along their leading ``L`` axis via
``repro.distributed.sharding.bucket_specs`` (the ``slots`` top-level field
is recognized exactly like ``buckets``), so every rule in the family goes
through one checkpoint / elastic-reshard / dp-step code path.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import bucketing
from repro.core.rules import MatrixUpdateRule
from repro.core.types import Schedule


class BucketedState(NamedTuple):
    """Uniform bucketed optimizer state for the whole rule family."""
    buckets: Dict[str, jax.Array]
    slots: Dict[str, Dict[str, jax.Array]] = {}


class BucketStateMeta(NamedTuple):
    """Static per-bucket state metadata for external inspectors.

    Everything ``repro.analysis`` needs to police a lowered step without
    re-deriving the engine's layout: the full stacked momentum shape is
    ``(padded, d_in, d_out)`` in ``momentum_dtype``; each slot stripe's
    *full* (unsharded) shape/dtype comes from the rule's ``slot_shapes``;
    ``leaf_shapes`` are the planned leaves so shape-collision heuristics
    (a leaf as large as its bucket) can be applied uniformly."""
    key: str
    d_in: int
    d_out: int
    size: int
    padded: int
    momentum_dtype: str
    slot_shapes: Dict[str, Tuple[Tuple[int, ...], str]]
    leaf_shapes: Tuple[Tuple[int, ...], ...]

    @property
    def full_shape(self) -> Tuple[int, int, int]:
        return (self.padded, self.d_in, self.d_out)


class BucketedEngine:
    """The rule-agnostic machinery of a bucketed matrix optimizer.

    ``core/mixed.py`` composes the :class:`Optimizer` from these methods
    and its AdamW sweep.
    """

    def __init__(self, rule: MatrixUpdateRule, lr: Schedule, *,
                 use_kernel: bool = False, momentum_dtype: str = "float32",
                 shard_axis: Optional[str] = None, shard_size: int = 1,
                 predicate=None):
        mdtype = jnp.dtype(momentum_dtype)
        if mdtype not in (jnp.float32, jnp.bfloat16):
            raise ValueError(f"momentum_dtype must be float32 or bfloat16, "
                             f"got {momentum_dtype!r}")
        self.rule = rule
        self.lr = lr
        self.use_kernel = use_kernel
        self.mdtype = mdtype
        self.shard_axis = shard_axis
        self.shard_size = shard_size
        self.predicate = predicate
        # static metadata, computed once and reused by every trace (bounded
        # LRU keyed on leaf paths/shapes — one optimizer can serve several
        # models without leaking plan metadata)
        self.plans = bucketing.PlanCache()

    # -- plan / state ---------------------------------------------------
    def plan(self, params) -> bucketing.BucketPlan:
        return self.plans.get(
            bucketing.plan_signature(params, self.predicate),
            lambda: bucketing.build_plan(params, predicate=self.predicate,
                                         pad_multiple=self.shard_size))

    def init_state(self, plan: bucketing.BucketPlan) -> BucketedState:
        buckets = bucketing.init_buckets(plan, self.mdtype)
        slots: Dict[str, Dict[str, jax.Array]] = {}
        for b in plan.buckets:
            for name, (shape, dtype) in self.rule.slot_shapes(
                    b.padded, b.d_in, b.d_out).items():
                slots.setdefault(name, {})[b.key] = jnp.zeros(shape, dtype)
        return BucketedState(buckets=buckets, slots=slots)

    def state_meta(self, params) -> Tuple[BucketStateMeta, ...]:
        """Per-bucket :class:`BucketStateMeta` for ``params`` (same cached
        plan the update fns use; pure metadata, no arrays touched)."""
        plan = self.plan(params)
        return tuple(
            BucketStateMeta(
                key=b.key, d_in=b.d_in, d_out=b.d_out, size=b.size,
                padded=b.padded, momentum_dtype=str(self.mdtype),
                slot_shapes={
                    name: (tuple(shape), str(jnp.dtype(dtype)))
                    for name, (shape, dtype) in self.rule.slot_shapes(
                        b.padded, b.d_in, b.d_out).items()},
                leaf_shapes=tuple(tuple(e.shape) for e in b.entries))
            for b in plan.buckets)

    def scale(self, bucket: bucketing.Bucket, step):
        from repro.core.rmnp import rms_lr_scale
        return self.lr(step) * rms_lr_scale((bucket.d_in, bucket.d_out))

    def _slots_of(self, slots, key) -> Dict[str, jax.Array]:
        return {name: per_bucket[key] for name, per_bucket in slots.items()}

    # -- single-pass fused apply (replicated / ZeRO-1) ------------------
    def bucket_apply(self, bucket, g, v, sl, w, step):
        """Fused apply of one stacked bucket, ZeRO-1 aware: ``g`` / ``w``
        are full ``(padded L, ...)`` operands; ``v`` and the slot stripes
        are either full or this rank's ``L/N`` shard (the per-bucket
        decision of ``bucket_specs``).  On a shard the rule runs over the
        local slices and the updated weights are all-gathered; momentum
        and slots stay sharded.  Returns ``(w_new full, v_new, sl_new)``."""
        l_loc = v.shape[0]
        n_shards = bucketing.shard_count(bucket, l_loc)
        if g.shape[0] != bucket.padded or w.shape[0] != bucket.padded:
            raise ValueError(
                f"bucket {bucket.key!r}: gradient/weight operands have "
                f"{g.shape[0]}/{w.shape[0]} slices, expected the padded "
                f"bucket size {bucket.padded}")
        if n_shards > 1:
            if self.shard_axis is None:
                raise ValueError(
                    f"bucket {bucket.key!r}: momentum holds {l_loc} of "
                    f"{bucket.padded} slices but no shard_axis was given")
            idx = jax.lax.axis_index(self.shard_axis)
            g = jax.lax.dynamic_slice_in_dim(g, idx * l_loc, l_loc, axis=0)
            w_loc = jax.lax.dynamic_slice_in_dim(w, idx * l_loc, l_loc,
                                                 axis=0)
        else:
            w_loc = w
        w_new, v_new, sl_new = self.rule.apply(
            g, v, w_loc, sl, scale=self.scale(bucket, step), step=step,
            use_kernel=self.use_kernel)
        if n_shards > 1:
            with jax.named_scope(f"all_gather_{bucket.key}"):
                w_new = jax.lax.all_gather(w_new, self.shard_axis, axis=0,
                                           tiled=True)
        return w_new, v_new, sl_new

    def apply_buckets(self, plan, g_b, p_b, buckets, slots, step):
        """Loop :meth:`bucket_apply` over the plan: ``(w_b, v_b,
        slots_b)``."""
        w_b, v_b = {}, {}
        slots_b: Dict[str, Dict[str, jax.Array]] = {n: {} for n in slots}
        for b in plan.buckets:
            with jax.named_scope(f"bucket_{b.key}"):
                w_b[b.key], v_new, sl_new = self.bucket_apply(
                    b, g_b[b.key], buckets[b.key],
                    self._slots_of(slots, b.key), p_b[b.key], step)
            v_b[b.key] = v_new
            for name in sl_new:
                slots_b[name][b.key] = sl_new[name]
        return w_b, v_b, slots_b

    # -- ZeRO-2 ---------------------------------------------------------
    def bucket_apply_sharded(self, bucket, g_shard, v, sl, w_chunks, step,
                             clip_scale=None):
        """One bucket's whole ZeRO-2 chain — optional clip scale folded
        into the gradient shard, the rule's fused apply on the local
        slices, updated-weight all-gather — independent of every other
        bucket (the pipelined dp step's per-bucket entry point).  The
        gradient arrives already reduced and sharded; ``w_chunks`` is the
        ``(N, padded L / N, d_in, d_out)`` chunked weight operand from
        ``gather_chunks``.  Returns ``(w_new full padded bucket, v_new
        shard, sl_new shard)``."""
        l_loc = v.shape[0]
        n_shards = bucketing.shard_count(bucket, l_loc)
        if g_shard.shape[0] != l_loc:
            raise ValueError(
                f"bucket {bucket.key!r}: gradient shard has "
                f"{g_shard.shape[0]} slices but the momentum shard has "
                f"{l_loc}")
        if w_chunks.shape[:2] != (n_shards, l_loc):
            raise ValueError(
                f"bucket {bucket.key!r}: weight chunks have shape "
                f"{w_chunks.shape[:2]}, expected ({n_shards}, {l_loc}) — "
                f"gather_chunks n_chunks must equal the shard count")
        g = g_shard if clip_scale is None else g_shard * clip_scale
        idx = jax.lax.axis_index(self.shard_axis)
        w_loc = jax.lax.dynamic_index_in_dim(w_chunks, idx, axis=0,
                                             keepdims=False)
        w_new, v_new, sl_new = self.rule.apply(
            g, v, w_loc, sl, scale=self.scale(bucket, step), step=step,
            use_kernel=self.use_kernel)
        with jax.named_scope(f"all_gather_{bucket.key}"):
            w_new = jax.lax.all_gather(w_new, self.shard_axis, axis=0,
                                       tiled=True)
        return w_new, v_new, sl_new

    def sharded_n_dev(self, plan, buckets) -> Optional[int]:
        """Shard count implied by the momentum buffers (consistency-checked
        across buckets); None for an empty plan."""
        n_dev = None
        for b in plan.buckets:
            n_b = bucketing.shard_count(b, buckets[b.key].shape[0])
            if n_dev is None:
                n_dev = n_b
            elif n_b != n_dev:
                raise ValueError(
                    f"inconsistent shard counts across buckets: "
                    f"{n_dev} vs {n_b} (bucket {b.key!r})")
        return n_dev

    def sharded_apply(self, plan, g_shards, buckets, slots, params, step,
                      clip_scale=None):
        """Loop :meth:`bucket_apply_sharded` over the plan.  Returns
        ``(w_b, v_b, slots_b)``, or None when the plan has no buckets."""
        n_dev = self.sharded_n_dev(plan, buckets)
        if n_dev is None:
            return None
        w_chunks = bucketing.gather_chunks(plan, params, n_dev)
        w_b, v_b = {}, {}
        slots_b: Dict[str, Dict[str, jax.Array]] = {n: {} for n in slots}
        for b in plan.buckets:
            with jax.named_scope(f"bucket_{b.key}"):
                w_b[b.key], v_new, sl_new = self.bucket_apply_sharded(
                    b, g_shards[b.key], buckets[b.key],
                    self._slots_of(slots, b.key), w_chunks[b.key], step,
                    clip_scale)
            v_b[b.key] = v_new
            for name in sl_new:
                slots_b[name][b.key] = sl_new[name]
        return w_b, v_b, slots_b

