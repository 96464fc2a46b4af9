"""Explicit data-parallel train step with compressed gradient reduction,
optional ZeRO optimizer-state / gradient sharding, and an optionally
bucket-pipelined ZeRO-2 schedule.

The pjit train step (train/step.py) lets XLA choose the gradient
reduction; this variant takes control of the cross-replica collective via
``shard_map`` over the data axis so the int8 error-feedback schedule
(distributed/compression.py) replaces the fp32 ring all-reduce.  Params
are replicated across the axis.

Optimizer state has three modes:

* ``shard_state=False`` (ZeRO-0): state replicated, any optimizer works.
* ``shard_state=True`` (ZeRO-1): the stacked per-bucket matrix momentum
  (core/bucketing.py) is sharded along its leading ``L`` axis — each rank
  holds ``L/N`` slices, runs the single-pass apply kernel on its shard,
  and all-gathers only the updated param slices.  Per-rank stacked
  momentum bytes drop by the data-axis size.  Requires an optimizer built
  with ``shard_axis=axis_name``; with ``shard_size=N`` the
  buckets are padded so *every* bucket shards (uneven ``L`` included),
  without it uneven buckets fall back to replication individually
  (distributed/sharding.py ``bucket_specs``).
* ``zero2=True`` (implies ``shard_state``): additionally the matrix
  *gradient* reduction is a reduce-scatter straight into each rank's
  bucket shard — the gradient buckets are chunked per destination rank
  (core/bucketing.py ``gather_chunks``), reduced via ``psum_scatter`` (or
  the int8 a2a error-feedback schedule, with no bf16 all-gather stage),
  and fed to ``Optimizer.update_apply_sharded``, so the full
  ``(L, d_in, d_out)`` mean-gradient bucket never exists on any rank:
  per-rank gradient-bucket bytes drop by the axis size alongside the
  momentum, and only the updated param slices are all-gathered.

Two knobs control the ZeRO-2 schedule (train/pipeline.py):

* ``accum > 1`` splits the local batch into microbatches and runs the
  backward as a ``lax.scan``, accumulating matrix gradients directly in
  the chunked per-destination-rank layout — the monolithic fp32 gradient
  bucket never exists even while accumulating.
* ``overlap`` issues each bucket's reduce-scatter and each bucket's fused
  update as independent per-bucket chains with the global-norm clip
  reduced to a single psum'd scalar folded into every bucket's update
  (two-phase clip) — no scaled-shard buffers or cross-bucket data
  dependence between the collectives and the updates, so XLA's
  latency-hiding scheduler can overlap them.  ``overlap=False`` keeps the
  serialized all-reduce-then-all-update order (the benchmark baseline;
  per-leaf fp32 accumulation, pre-scaled gradient shards).  The default
  (``overlap=None``) resolves automatically via :func:`resolve_overlap`:
  pipelined everywhere except ``accum == 1`` with the exact fp32
  collectives, the one measured configuration where the pipelined
  schedule regresses (BENCH_overlap: the scan-free backward leaves no
  compute to hide the chunked layout's extra reshapes behind, 0.70x).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core import clip_by_global_norm
from repro.core.types import Optimizer, PyTree
from repro.distributed.compression import (
    CompressionState, compressed_mean, compressed_reduce_scatter_leaf,
    exact_mean, exact_reduce_scatter, init_compression_state, rollback_fold,
)
from repro.distributed.compression import (
    from_local as compression_from_local,
    local_view as compression_local_view,
)
from repro.distributed.sharding import axis_rules, bucket_specs
from repro.train import faults, pipeline


def resolve_overlap(overlap: Optional[bool], *, accum: int,
                    compress: bool) -> bool:
    """Resolve the tri-state ``overlap`` knob.  Explicit True/False wins;
    None picks the pipelined schedule except in the one measured regression
    case — ``accum == 1`` with exact fp32 collectives, where the backward
    is scan-free and there is no accumulation compute to hide the chunked
    layout's extra reshapes behind (BENCH_overlap: 0.70x vs serialized)."""
    if overlap is not None:
        return overlap
    return not (accum == 1 and not compress)


def make_dp_train_step(cfg: ModelConfig, opt: Optimizer, mesh: Mesh,
                       *, axis_name: str = "data", clip_norm: float = 1.0,
                       compress: bool = True, remat: str = "none",
                       shard_state: bool = False, zero2: bool = False,
                       accum: int = 1, overlap: Optional[bool] = None,
                       opt_state: PyTree = None, guard: bool = False,
                       fault=None):
    """(params, opt_state, comp_state, batch, step) -> (params, opt_state,
    comp_state, metrics).  Batch is sharded along ``axis_name``; params
    replicated; optimizer state replicated (default) or ZeRO-sharded along
    the stacked-bucket ``L`` axis (``shard_state=True``, which needs
    ``opt_state`` — real or ``jax.eval_shape`` abstract — to derive the
    per-bucket specs, and an optimizer built with
    ``shard_axis=axis_name``).  ``zero2=True`` (implies ``shard_state``)
    reduce-scatters the matrix gradient buckets straight into the shard;
    it needs the optimizer built with ``shard_size == the axis size``
    (padded buckets + ``update_apply_sharded``).  ``accum`` splits the
    local batch into that many microbatches (scan accumulation);
    ``overlap`` picks the bucket-pipelined ZeRO-2 schedule over the
    serialized baseline (no effect off the ZeRO-2 path) — None (default)
    auto-resolves via :func:`resolve_overlap`.

    ``clip_norm <= 0`` disables clipping while ``grad_norm``/``clip_rate``
    metrics keep reporting (``clip_rate`` pinned to 0).  ``guard=True``
    adds the in-graph non-finite guard (train/pipeline.py): a step whose
    gradient carries a NaN/Inf anywhere is skipped with params, optimizer
    state and the int8 error-feedback residual left bitwise-unchanged, and
    the metrics grow ``skipped`` (0/1) and per-leaf ``guard_flags``.
    ``fault`` (a ``repro.train.faults.FaultSpec``) injects a fault for the
    resilience proofs."""
    n_dev = mesh.shape[axis_name]
    overlap = resolve_overlap(overlap, accum=accum, compress=compress)
    if zero2:
        shard_state = True
    if accum < 1:
        raise ValueError(f"accum must be >= 1, got {accum}")
    state_spec = P()
    if shard_state:
        if opt.bucket_plan is None:
            raise ValueError(
                "shard_state=True requires the bucketed engine (an optimizer "
                "built with shard_axis=axis_name): the sharded step runs the "
                "update kernel on local momentum slices and all-gathers the "
                "updated param slices")
        if opt_state is None:
            raise ValueError(
                "shard_state=True needs opt_state (the real state or its "
                "jax.eval_shape) to derive per-bucket partition specs")
        state_spec = bucket_specs(opt_state, mesh, {"bucket": axis_name})
    if zero2:
        if opt.update_apply_sharded is None or opt.bucket_plan is None:
            raise ValueError(
                "zero2=True requires an optimizer exposing "
                "update_apply_sharded (make_optimizer built with "
                "shard_axis=axis_name and shard_size=the axis size): the "
                "ZeRO-2 step reduce-scatters gradient buckets straight "
                "into the momentum shard")
        if opt.shard_size != n_dev:
            # caught here, up front — a mismatch otherwise surfaces as an
            # opaque shape error deep inside bucket_update_apply once the
            # padded buckets fail to divide the mesh axis
            raise ValueError(
                f"zero2=True: the optimizer was built with shard_size="
                f"{opt.shard_size} but mesh axis {axis_name!r} has {n_dev} "
                f"devices — ZeRO-2 reduce-scatters each gradient bucket "
                f"into exactly one chunk per rank, so the optimizer must "
                f"be built with shard_size={n_dev}")

    if zero2 and overlap:
        local_step = pipeline.make_pipelined_zero2_step(
            cfg, opt, axis_name=axis_name, n_dev=n_dev, clip_norm=clip_norm,
            compress=compress, remat=remat, accum=accum, guard=guard,
            fault=fault)
        return _wrap(local_step, mesh, axis_name, state_spec)

    def zero2_reduce(grads, comp_state, step):
        """Serialized baseline: chunked reduce-scatter of every bucket's
        mean gradient (full mean bucket never materializes), then everything
        else as the usual per-leaf mean.  Returns (g_shards, rest-mean
        grads, comp_state, matrix paths)."""
        plan = opt.bucket_plan(grads)
        mat = plan.paths
        def skip(path):
            return path in mat
        g_shards = {}
        if compress:
            # fold the rank-local error accumulator in before chunking; the
            # residual of the int8 quantization goes back into the per-leaf
            # error state (pad-slice residuals are zero and are dropped)
            from repro.core.bucketing import gather_chunks, scatter_chunks
            v_tree = jax.tree_util.tree_map(
                lambda g, e: g.astype(jnp.float32) + e, grads,
                comp_state.error)
            chunks = gather_chunks(plan, v_tree, n_dev, dtype=jnp.float32)
            resid = {}
            for b in plan.buckets:
                with jax.named_scope(f"reduce_scatter_{b.key}"):
                    g_shards[b.key], resid[b.key] = \
                        compressed_reduce_scatter_leaf(
                            chunks[b.key], axis_name, n_dev,
                            wire_fault=faults.wire_fault_for(
                                fault, b.key, step, axis_name))
            grads, comp_state = compressed_mean(
                grads, comp_state, axis_name, n_dev, skip=skip)
            comp_state = CompressionState(
                error=scatter_chunks(plan, resid, comp_state.error))
        else:
            from repro.core.bucketing import gather_chunks
            chunks = gather_chunks(plan, grads, n_dev, dtype=jnp.float32)
            for b in plan.buckets:
                with jax.named_scope(f"reduce_scatter_{b.key}"):
                    g_shards[b.key] = exact_reduce_scatter(chunks[b.key],
                                                           axis_name)
            grads = exact_mean(grads, axis_name, skip=skip)
        return g_shards, grads, comp_state, plan

    def local_step(params, opt_state, comp_state, batch, step):
        prev = (params, opt_state, comp_state)
        grads, metrics = pipeline.microbatch_grads(cfg, params, batch, accum,
                                                   remat, fault=fault,
                                                   step=step)
        ginfo = None
        if zero2:
            g_shards, grads, comp_state, plan = zero2_reduce(grads,
                                                             comp_state, step)
            metrics = jax.tree_util.tree_map(
                lambda m: jax.lax.pmean(m, axis_name), metrics)
            # same two-phase norm as the pipelined path (per-leaf partials,
            # one psum, replicated summation order — satellite fix: stale
            # matrix leaves never enter sq_rest and rest leaves are cast to
            # fp32 exactly once), but the scale is applied the serialized
            # way: pre-scaled shard buffers between collectives and updates
            scale, rest32, clip_stats, ginfo = pipeline.two_phase_clip(
                plan, g_shards, grads, clip_norm, axis_name, n_dev)
            g_shards = {k: s * scale for k, s in g_shards.items()}
            grads = pipeline.scale_rest(grads, rest32, scale)
            with jax.named_scope("optimizer"):
                params, opt_state = opt.update_apply_sharded(
                    g_shards, grads, opt_state, params, step)
        else:
            if compress:
                grads, comp_state = compressed_mean(
                    grads, comp_state, axis_name, n_dev)
            else:
                grads = exact_mean(grads, axis_name)
            metrics = jax.tree_util.tree_map(
                lambda m: jax.lax.pmean(m, axis_name), metrics)
            if guard:
                # flags off the post-reduce mean grads — same coverage as
                # the two-phase scheme (wire faults included), and the
                # per-leaf partials CSE with clip_by_global_norm's
                ginfo = pipeline.finite_guard(grads)
            grads, clip_stats = clip_by_global_norm(grads, clip_norm)
            with jax.named_scope("optimizer"):
                params, opt_state = opt.update_apply(grads, opt_state,
                                                     params, step)
        metrics = dict(metrics, grad_norm=clip_stats.global_norm,
                       clip_rate=clip_stats.clipped)
        if guard:
            params = pipeline.mask_updates(ginfo.ok, params, prev[0])
            opt_state = pipeline.mask_updates(ginfo.ok, opt_state, prev[1])
            if compress:
                comp_state = rollback_fold(ginfo.ok, comp_state, prev[2])
            metrics["skipped"] = (~ginfo.ok).astype(jnp.float32)
            metrics["guard_flags"] = ginfo.flags.astype(jnp.float32)
        return params, opt_state, comp_state, metrics

    return _wrap(local_step, mesh, axis_name, state_spec)


def _wrap(local_step, mesh, axis_name, state_spec):
    rep = P()
    batch_spec = P(axis_name)
    comp_spec = P(axis_name)  # EF residual: explicit leading device axis

    def sharded_step(params, opt_state, comp_state, batch, step):
        # inside shard_map each rank sees its (1, *shape) residual block;
        # the step logic runs on the like-params local view and the
        # device axis is re-added so the P(axis_name) out-spec reassembles
        # the global (n_dev, ...) array — host saves then carry every
        # rank's residual, making int8-wire restores bitwise
        comp_state = compression_local_view(comp_state)
        # the body is per-rank code inside the manual region: the model's
        # logical sharding constraints must not name the mesh's axes there
        with axis_rules(None):
            params, opt_state, comp_state, metrics = local_step(
                params, opt_state, comp_state, batch, step)
        return params, opt_state, compression_from_local(comp_state), metrics

    return jax.shard_map(
        sharded_step, mesh=mesh,
        in_specs=(rep, state_spec, comp_spec, batch_spec, rep),
        out_specs=(rep, state_spec, comp_spec, rep),
        check_vma=False)


def init_dp_state(params, n_dev: int = 1):
    """Device-axis EF state for the dp train step: leaves are
    ``(n_dev, *p.shape)``, sharded ``P("data")`` by ``_wrap``."""
    return init_compression_state(params, n_dev)
