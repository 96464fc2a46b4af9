"""Generic shape-bucketed optimizer engine, parameterized by a
:class:`repro.core.rules.MatrixUpdateRule`.

This module owns everything the RMNP and mixed fused optimizers used to
duplicate: the cached leaf->bucket plan, stacked momentum (+ per-rule slot
stripes) initialization, the two-pass bucket update, the ZeRO-1-aware fused
apply, and the ZeRO-2 per-bucket sharded apply with the clip scale folded
into each chain.  ``core/rmnp.py``, ``core/muon.py`` and ``core/mixed.py``
are thin compositions over it, so a new update rule inherits ZeRO-1/2
sharding, padded uneven buckets, int8 error-feedback and pipelined overlap
with zero new distributed code.

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
from repro.core.types import Optimizer, Schedule


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

    Callers compose an :class:`Optimizer` from these methods (see
    :func:`matrix_optimizer` for the pure-matrix form and
    ``core/mixed.py`` for the mixed form with its AdamW sweep).
    """

    def __init__(self, rule: MatrixUpdateRule, lr: Schedule, *,
                 use_kernel: bool = False, momentum_dtype: str = "float32",
                 shard_axis: Optional[str] = None, shard_size: int = 1,
                 predicate=None, strict: bool = False):
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
        self.strict = strict
        # static metadata, computed once and reused by every trace (bounded
        # LRU keyed on leaf paths/shapes — one optimizer can serve several
        # models without leaking plan metadata)
        self.plans = bucketing.PlanCache()

    # -- plan / state ---------------------------------------------------
    def plan(self, params) -> bucketing.BucketPlan:
        return self.plans.get(
            bucketing.plan_signature(params, self.predicate),
            lambda: bucketing.build_plan(params, predicate=self.predicate,
                                         strict=self.strict,
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

    # -- two-pass (update + apply_updates) ------------------------------
    def update_buckets(self, plan, g_b, p32_b, buckets, slots, step):
        """Per-bucket fp32 updates for the two-pass path: ``(upd_b, v_b,
        slots_b)``.  Additive rules go through ``precondition`` with the
        canonical op order; non-additive rules apply onto the fp32 params
        and return the difference (documented as allclose-only vs the
        fused path)."""
        upd_b, v_b = {}, {}
        slots_b: Dict[str, Dict[str, jax.Array]] = {n: {} for n in slots}
        for b in plan.buckets:
            sl = self._slots_of(slots, b.key)
            with jax.named_scope(f"bucket_{b.key}"):
                scale = self.scale(b, step)
                if self.rule.additive:
                    d, v_new, sl_new = self.rule.precondition(
                        g_b[b.key], buckets[b.key], sl, step=step,
                        use_kernel=self.use_kernel)
                    upd = -scale * (d + self.rule.weight_decay * p32_b[b.key])
                else:
                    w_new, v_new, sl_new = self.rule.apply(
                        g_b[b.key], buckets[b.key], p32_b[b.key], sl,
                        scale=scale, step=step, use_kernel=self.use_kernel)
                    upd = w_new - p32_b[b.key]
            upd_b[b.key], v_b[b.key] = upd, v_new
            for name in sl_new:
                slots_b[name][b.key] = sl_new[name]
        return upd_b, v_b, slots_b

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


def matrix_optimizer(rule: MatrixUpdateRule, lr: Schedule, *,
                     use_kernel: bool = False,
                     momentum_dtype: str = "float32",
                     fused_apply: bool = False,
                     shard_axis: Optional[str] = None,
                     shard_size: int = 1) -> Optimizer:
    """Bucketed optimizer over a pure-matrix tree for any registered rule —
    the engine behind ``rmnp(fused=True)`` and ``muon(fused=True)``.  The
    flag semantics (``fused_apply`` unlocking ``update_apply``,
    ``shard_axis``/``shard_size`` unlocking the ZeRO-2 entry points) match
    the historical RMNP constructor exactly."""
    eng = BucketedEngine(rule, lr, use_kernel=use_kernel,
                         momentum_dtype=momentum_dtype,
                         shard_axis=shard_axis, shard_size=shard_size,
                         strict=True)

    def init(params):
        return eng.init_state(eng.plan(params))

    def update(grads, state, params, step):
        plan = eng.plan(params)
        g_b = bucketing.gather(plan, grads, dtype=jnp.float32)
        p_b = bucketing.gather(plan, params, dtype=jnp.float32)
        upd_b, v_b, s_b = eng.update_buckets(plan, g_b, p_b, state.buckets,
                                             state.slots, step)
        updates = bucketing.scatter(plan, upd_b, params)
        return updates, BucketedState(buckets=v_b, slots=s_b)

    def update_apply(grads, state, params, step):
        """Single-pass fused apply: params are gathered per bucket in their
        native dtype, updated in one rule pass, and scattered back — no
        fp32 ``d`` bucket and no separate ``apply_updates`` pass."""
        plan = eng.plan(params)
        g_b = bucketing.gather(plan, grads, dtype=jnp.float32)
        p_b = bucketing.gather(plan, params)
        w_b, v_b, s_b = eng.apply_buckets(plan, g_b, p_b, state.buckets,
                                          state.slots, step)
        new_params = bucketing.scatter(plan, w_b, params, cast=True)
        return new_params, BucketedState(buckets=v_b, slots=s_b)

    def update_apply_bucket(bucket, g_shard, v_shard, w_chunks, step,
                            clip_scale=None, *, slots=None):
        """Public per-bucket ZeRO-2 entry point; ``slots`` maps slot name
        -> this rank's stripe shard (None/{} for slotless rules).  Returns
        ``(w_new full padded bucket, v_new shard, slots_new shard)``."""
        return eng.bucket_apply_sharded(bucket, g_shard, v_shard,
                                        slots or {}, w_chunks, step,
                                        clip_scale)

    def update_apply_sharded(g_shards, grads, state, params, step,
                             clip_scale=None):
        """ZeRO-2 single-pass apply (call inside ``shard_map``): a loop of
        independent per-bucket chains; ``grads`` is unused (pure-matrix
        optimizer); ``clip_scale`` folds the global-norm clip into each
        chain instead of pre-scaling the shards."""
        del grads
        plan = eng.plan(params)
        out = eng.sharded_apply(plan, g_shards, state.buckets, state.slots,
                                params, step, clip_scale)
        if out is None:
            return params, state
        w_b, v_b, s_b = out
        new_params = bucketing.scatter(plan, w_b, params, cast=True)
        return new_params, BucketedState(buckets=v_b, slots=s_b)

    zero2 = fused_apply and shard_axis is not None
    return Optimizer(init=init, update=update,
                     update_apply=update_apply if fused_apply else None,
                     update_apply_sharded=update_apply_sharded if zero2 else None,
                     update_apply_bucket=update_apply_bucket if zero2 else None,
                     bucket_plan=eng.plan, shard_size=shard_size,
                     state_meta=eng.state_meta)
