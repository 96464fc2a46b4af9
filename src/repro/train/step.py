"""Training / serving step functions (pjit-ready, donate-friendly).

``make_train_step`` builds a pure (params, opt_state, batch, step) ->
(params, opt_state, metrics) function with optional microbatch gradient
accumulation (lax.scan, fp32 accumulators) and global-norm clipping.
``make_serve_step`` / ``make_prefill_step`` build the inference paths that
decode_* / prefill_* shapes lower.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import clip_by_global_norm
from repro.core.types import Optimizer
from repro.models.model import forward, loss_fn
from repro.train import faults
from repro.train import pipeline as pipeline_mod


def _optimizer_call(opt: Optimizer, params, step: int = 0):
    """``(opt.update_apply, args)`` of one optimizer step over abstract
    values.  For tracing; nothing is compiled or executed."""
    def abstract(t):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t)
    state = jax.eval_shape(opt.init, params)
    return opt.update_apply, (abstract(params), state, abstract(params),
                              jnp.int32(step))


def optimizer_kernel_launches(opt: Optimizer, params, step: int = 0) -> list:
    """Every ``pallas_call`` launch (``kernels.introspect.KernelLaunch``)
    one optimizer step traces to: one trace gives both
    :func:`optimizer_launches` and :func:`kernel_routes`."""
    from repro.kernels import introspect

    fn, args = _optimizer_call(opt, params, step)
    return introspect.collect_kernel_launches(fn, *args)


def optimizer_launches(opt: Optimizer, params, step: int = 0) -> int:
    """Kernel (``pallas_call``) launches one optimizer step costs — the
    quantity the shape-bucketed engine minimises: one launch per shape
    bucket, not per matrix parameter.
    Pure tracing (abstract values); nothing is compiled or executed."""
    return len(optimizer_kernel_launches(opt, params, step))


def kernel_routes(opt: Optimizer, params, launches) -> dict:
    """Per shape bucket of ``opt``'s plan, the ``rmnp_rownorm_apply``
    kernel launch among ``launches`` (:func:`optimizer_kernel_launches`)
    its update traces to, or ``None`` where the kernel's VMEM plan sent
    the bucket to the XLA path."""
    from repro.kernels.rmnp_update import MAX_BLOCK_N

    rmnp = [ln for ln in launches if ln.name == "rmnp_rownorm_apply"]

    def of_bucket(ln, b):
        # stripe operands are (L, d_in, d_out padded to the lane block)
        L, d_in, n_p = ln.in_blocks[-1].array_shape
        return (L == b.padded and d_in == b.d_in
                and b.d_out <= n_p < b.d_out + MAX_BLOCK_N)
    return {b.key: next((ln for ln in rmnp if of_bucket(ln, b)), None)
            for b in opt.bucket_plan(params).buckets}


def optimizer_fp32_buffers(opt: Optimizer, params, shape,
                           step: int = 0) -> int:
    """Number of full-size fp32 buffers of exactly ``shape`` the optimizer
    step materializes (jaxpr equation outputs, recursive) — used to verify
    the engine never writes an fp32 ``d`` bucket."""
    from repro.kernels.ops import count_buffer_eqns

    fn, args = _optimizer_call(opt, params, step)
    return count_buffer_eqns(fn, shape, jnp.float32, *args)


def make_train_step(cfg: ModelConfig, opt: Optimizer, *, clip_norm: float = 1.0,
                    remat: str = "full", num_microbatches: int = 1,
                    grad_dtype: Optional[str] = None, guard: bool = False,
                    fault=None):
    """grad_dtype='bfloat16' compresses the cross-replica gradient reduction
    (the all-reduce moves half the bytes); accumulation stays fp32.

    ``clip_norm <= 0`` disables clipping bitwise (``core.mixed
    clip_by_global_norm``) while ``grad_norm``/``clip_rate`` keep
    reporting.  ``guard=True`` adds the in-graph non-finite guard: a step
    with any NaN/Inf gradient leaf is skipped with params and optimizer
    state bitwise-unchanged, plus ``skipped``/``guard_flags`` metrics
    (flags in gradient-leaf tree order).  ``fault``
    (``repro.train.faults.FaultSpec``) injects faults for the proofs."""

    def grads_of(params, batch, step, mb_idx=0):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, batch, remat=remat), has_aux=True)(params)
        grads = faults.apply_grad_fault(fault, grads, step, mb_idx)
        if grad_dtype:
            grads = jax.tree_util.tree_map(
                lambda g: g.astype(jnp.dtype(grad_dtype)), grads)
        return grads, metrics

    def train_step(params, opt_state, batch, step):
        prev = (params, opt_state)
        if num_microbatches > 1:
            # same split/validation and microbatch-mean metrics as the dp
            # pipeline (train/pipeline.py), so --accum means one thing
            from repro.train.pipeline import split_microbatches

            def mb(carry, xs):
                mb_batch, mb_idx = xs if fault is not None else (xs, 0)
                acc = carry
                g, m = grads_of(params, mb_batch, step, mb_idx)
                acc = jax.tree_util.tree_map(
                    lambda a, x: a + x.astype(jnp.float32), acc, g)
                return acc, m

            split = split_microbatches(batch, num_microbatches)
            zero = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            xs = ((split, jnp.arange(num_microbatches))
                  if fault is not None else split)
            gsum, ms = jax.lax.scan(mb, zero, xs)
            grads = jax.tree_util.tree_map(lambda g: g / num_microbatches, gsum)
            metrics = jax.tree_util.tree_map(lambda m: jnp.mean(m, axis=0), ms)
        else:
            grads, metrics = grads_of(params, batch, step)

        ginfo = pipeline_mod.finite_guard(grads) if guard else None
        grads, clip_stats = clip_by_global_norm(grads, clip_norm)
        with jax.named_scope("optimizer"):
            params, opt_state = opt.update_apply(grads, opt_state, params,
                                                 step)
        metrics = dict(metrics, grad_norm=clip_stats.global_norm,
                       clip_rate=clip_stats.clipped)
        if guard:
            params = pipeline_mod.mask_updates(ginfo.ok, params, prev[0])
            opt_state = pipeline_mod.mask_updates(ginfo.ok, opt_state, prev[1])
            metrics["skipped"] = (~ginfo.ok).astype(jnp.float32)
            metrics["guard_flags"] = ginfo.flags.astype(jnp.float32)
        return params, opt_state, metrics

    return train_step


def make_serve_step(cfg: ModelConfig):
    """One decode step: (params, cache, tokens (B,1), pos) ->
    (next_token (B,1), logits, cache)."""

    def serve_step(params, cache, tokens, pos):
        logits, new_cache, _ = forward(cfg, params, {"tokens": tokens},
                                       "decode", cache=cache, pos=pos)
        next_tok = jnp.argmax(logits[:, -1, :cfg.vocab], axis=-1).astype(jnp.int32)
        return next_tok[:, None], logits, new_cache

    return serve_step


def make_prefill_step(cfg: ModelConfig):
    """Prompt ingestion: (params, batch) -> (last-token logits, prompt cache)."""

    def prefill_step(params, batch):
        logits, cache, _ = forward(cfg, params, batch, "prefill")
        return logits[:, -1], cache

    return prefill_step


def eval_step(cfg: ModelConfig, params, batch):
    loss, metrics = loss_fn(cfg, params, batch, remat="none")
    return metrics
