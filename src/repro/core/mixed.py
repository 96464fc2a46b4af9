"""The paper's mixed update strategy: matrix parameters -> any registered
matrix update rule (RMNP, Muon, NorMuon, Muown, Nora — core/rules.py),
everything else (norms, biases, 1-D SSM params, optionally embeddings and the
LM head) -> AdamW.  Includes global-norm gradient clipping with clip-rate
tracking (paper Appendix E.7).

Implemented as a single per-leaf-dispatch optimizer so the whole state is one
pytree (momentum for matrix leaves, Adam (mu, nu) for the rest) — this keeps
pjit sharding of optimizer state trivially aligned with parameter sharding.
The bucketed path (``fused=True``) composes the generic bucketed engine
(core/engine.py) with the per-leaf AdamW sweep, so every rule in the
family inherits ZeRO-1/2 sharding, padded uneven buckets and the
pipelined dp step unchanged.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import bucketing
from repro.core.muon import newton_schulz
from repro.core.rmnp import rms_lr_scale, row_normalize
from repro.core.rules import MatrixUpdateRule, make_rule, rule_names
from repro.core.types import (Optimizer, PyTree, Schedule, map_with_path,
                              update_then_apply)

# parameter path fragments always handled by AdamW regardless of rank
_NON_MATRIX_TOKENS = ("norm", "bias", "scale", "a_log", "dt_", "conv")


def is_matrix_param(path: str, leaf, matrix_embed: bool = True) -> bool:
    """True when the leaf gets the matrix (RMNP/Muon) optimizer."""
    lp = path.lower()
    if any(tok in lp for tok in _NON_MATRIX_TOKENS):
        return False
    if not matrix_embed and ("embed" in lp or "lm_head" in lp):
        return False
    if not hasattr(leaf, "ndim") or leaf.ndim < 2:
        return False
    return leaf.shape[-1] > 1 and leaf.shape[-2] > 1


class ClipStats(NamedTuple):
    global_norm: jax.Array
    clipped: jax.Array  # 1.0 when the step was clipped


@jax.named_scope("clip")
def clip_by_global_norm(grads: PyTree, max_norm: float):
    """Global-norm clip.  ``max_norm <= 0`` disables clipping: the grads
    pass through *bitwise untouched* (no cast round-trip, no scale-by-1
    multiply) while ``global_norm`` is still measured and ``clipped`` pins
    to 0.0 — so metrics and the non-finite guard keep working with the
    clip off and no special-cased step is needed."""
    sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
             for g in jax.tree_util.tree_leaves(grads))
    gnorm = jnp.sqrt(sq)
    if max_norm <= 0:
        return grads, ClipStats(global_norm=gnorm,
                                clipped=jnp.zeros((), jnp.float32))
    scale = jnp.minimum(1.0, max_norm / (gnorm + 1e-12))
    clipped = jax.tree_util.tree_map(
        lambda g: (g.astype(jnp.float32) * scale).astype(g.dtype), grads)
    return clipped, ClipStats(global_norm=gnorm, clipped=(gnorm > max_norm).astype(jnp.float32))


class MixedState(NamedTuple):
    momentum: PyTree  # fp32; matrix-optimizer momentum OR Adam mu per leaf
    nu: PyTree        # fp32; Adam second moment (zero-size unused for matrix leaves)


class FusedMixedState(NamedTuple):
    """State for the shape-bucketed fused path: matrix momentum lives stacked
    per bucket; the per-leaf trees keep (1,)*ndim placeholders on matrix
    leaves so their structure still mirrors ``params`` (simple sharding).
    ``slots`` carries the rule's extra per-bucket stripes (e.g. NorMuon's
    neuron-wise second moment) in the same slot-major layout as
    ``engine.BucketedState`` — its top-level field name is what
    ``distributed.sharding.bucket_specs`` keys ZeRO sharding on, so every
    family member shares one checkpoint / reshard / dp-step path."""
    momentum: PyTree               # AdamW first moment (placeholders on matrix leaves)
    nu: PyTree                     # AdamW second moment (ditto)
    buckets: Dict[str, jax.Array]  # stacked matrix momentum, one per shape bucket
    slots: Dict[str, Dict[str, jax.Array]] = {}  # rule stripes: slot -> bucket key


def mixed_optimizer(
    matrix_kind: str,                      # any rules.rule_names() | "adamw"
    lr_matrix: Schedule,
    lr_adamw: Schedule,
    beta: float = 0.95,
    weight_decay: float = 0.1,
    adam_betas=(0.9, 0.95),
    adam_eps: float = 1e-8,
    rn_eps: float = 1e-8,
    matrix_embed: bool = True,
    ns_steps: int = 5,
    use_kernel: bool = False,
    fused: bool = False,
    momentum_dtype: str = "float32",
    shard_axis: Optional[str] = None,
    shard_size: int = 1,
) -> Optimizer:
    """Build the paper's mixed optimizer.  ``matrix_kind`` is any registered
    matrix update rule (``rules.rule_names()``: rmnp, muon, normuon, muown,
    nora) or ``'adamw'``, which degrades to plain AdamW on everything (the
    paper's AdamW baseline).

    ``fused=True`` routes the matrix partition through the shape-bucketed
    engine (core/engine.py): one single-pass rule apply per distinct
    ``(d_in, d_out)`` bucket — RMNP runs its Pallas apply kernel when
    ``use_kernel`` is set (no fp32 ``d`` bucket, no updates tree), the NS
    family batches Newton-Schulz over the bucket's stacked ``L`` axis.
    Rules beyond rmnp/muon carry extra per-bucket state stripes or a
    non-additive apply, which exist only in the bucketed layout, so they
    imply ``fused=True``.  ``momentum_dtype`` ('float32' | 'bfloat16') sets
    the bucketed matrix-momentum storage dtype (math is always fp32).
    Without ``fused`` the matrix leaves take the per-leaf path, plain
    ``jax.numpy`` — the reference the engine is tested against — which
    runs no kernel, so ``use_kernel`` there raises.

    ``shard_axis`` (implies ``fused``) names the mesh axis the stacked
    matrix momentum may be ZeRO-sharded over (consulted only when a bucket
    arrives as an ``L/N`` shard inside ``shard_map``).  ``shard_size`` (the
    size of ``shard_axis``) pads bucket ``L`` to a multiple so uneven
    buckets shard too, and unlocks ``Optimizer.update_apply_sharded`` — the
    ZeRO-2 entry point taking reduce-scattered per-bucket mean-gradient
    shards (AdamW leaves still read their mean grads from the per-leaf
    tree)."""
    if matrix_kind not in rule_names() + ("adamw",):
        raise ValueError(
            f"unknown matrix optimizer {matrix_kind!r}; expected one of "
            f"{', '.join(rule_names() + ('adamw',))}")
    if shard_size < 1:
        raise ValueError(f"shard_size must be >= 1, got {shard_size}")
    if shard_size > 1 and shard_axis is None:
        raise ValueError("shard_size > 1 needs shard_axis (the mesh axis "
                         "the padded buckets shard over)")
    if shard_axis is not None:
        fused = True  # sharded state lives in the bucketed layout
    if matrix_kind not in ("rmnp", "muon", "adamw"):
        fused = True  # slot stripes / non-additive apply are bucketed-only
    if use_kernel and not fused:
        raise ValueError(
            "use_kernel needs the bucketed engine: pass fused=True "
            "(launch/train: --engine single-pass); the per-leaf path is "
            "the plain jax.numpy reference and runs no kernel")
    b1, b2 = adam_betas

    def _is_mat(path, leaf):
        return matrix_kind != "adamw" and is_matrix_param(path, leaf, matrix_embed)

    if fused:
        # adamw buckets nothing (_is_mat is always False -> empty plan), so
        # any rule works as the engine's placeholder; rmnp is the cheapest
        rule = make_rule("rmnp" if matrix_kind == "adamw" else matrix_kind,
                         beta=beta, weight_decay=weight_decay, eps=rn_eps,
                         ns_steps=ns_steps)
        return _fused_mixed(
            rule, lr_matrix, lr_adamw, is_mat=_is_mat,
            weight_decay=weight_decay, b1=b1, b2=b2, adam_eps=adam_eps,
            use_kernel=use_kernel, momentum_dtype=momentum_dtype,
            shard_axis=shard_axis, shard_size=shard_size)

    def init(params):
        momentum = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        # second moment only needed on AdamW leaves; keep zeros elsewhere so
        # the state tree structure matches params everywhere (simple sharding)
        nu = map_with_path(
            lambda path, p: jnp.zeros(p.shape if not _is_mat(path, p) else (1,) * p.ndim,
                                      jnp.float32), params)
        return MixedState(momentum=momentum, nu=nu)

    def update(grads, state, params, step):
        eta_m = lr_matrix(step)
        eta_a = lr_adamw(step)
        t = jnp.asarray(step, jnp.float32) + 1.0
        bc1 = 1.0 - b1 ** t
        bc2 = 1.0 - b2 ** t

        def upd(path, g, v, nu, p):
            g32 = g.astype(jnp.float32)
            p32 = p.astype(jnp.float32)
            if _is_mat(path, p):
                v_new = beta * v + (1.0 - beta) * g32
                if matrix_kind == "rmnp":
                    d = row_normalize(v_new, rn_eps)
                else:
                    d = newton_schulz(v_new, steps=ns_steps)
                scale = eta_m * rms_lr_scale(p.shape)
                return -scale * (d + weight_decay * p32), v_new, nu
            # AdamW leaf
            mu_new = b1 * v + (1 - b1) * g32
            nu_new = b2 * nu + (1 - b2) * jnp.square(g32)
            d = (mu_new / bc1) / (jnp.sqrt(nu_new / bc2) + adam_eps)
            return -eta_a * (d + weight_decay * p32), mu_new, nu_new

        paths_tree = map_with_path(lambda path, _: path, params)
        out = jax.tree_util.tree_map(upd, paths_tree, grads, state.momentum, state.nu, params)
        def pick(i):
            return jax.tree_util.tree_map(
                lambda x: x[i], out, is_leaf=lambda x: isinstance(x, tuple))
        return pick(0), MixedState(momentum=pick(1), nu=pick(2))

    return Optimizer(init=init, update=update,
                     update_apply=update_then_apply(update))


def momentum_for_diagnostics(opt_state, params, matrix_embed: bool = True) -> PyTree:
    """Per-leaf momentum tree for dominance logging (paper Eq. 14-16 averages
    *per parameter*).  The fused state keeps matrix momentum stacked per
    bucket; averaging bucket-wise would re-weight the statistic, so scatter
    the buckets back onto the parameter tree first.  Non-fused states pass
    through unchanged."""
    if not hasattr(opt_state, "buckets"):
        return opt_state.momentum
    plan = bucketing.build_plan(
        params, predicate=lambda path, leaf: is_matrix_param(path, leaf, matrix_embed))
    return bucketing.scatter(plan, opt_state.buckets, opt_state.momentum)


def _fused_mixed(rule: MatrixUpdateRule, lr_matrix: Schedule,
                 lr_adamw: Schedule, *, is_mat,
                 weight_decay: float, b1: float, b2: float,
                 adam_eps: float, use_kernel: bool,
                 momentum_dtype: str,
                 shard_axis: Optional[str] = None,
                 shard_size: int = 1) -> Optimizer:
    """Mixed optimizer with the matrix partition running through the
    generic bucketed engine under ``rule``; AdamW leaves stay per-leaf
    (they are cheap elementwise updates XLA fuses on its own)."""
    from repro.core.engine import BucketedEngine

    eng = BucketedEngine(rule, lr_matrix, use_kernel=use_kernel,
                         momentum_dtype=momentum_dtype,
                         shard_axis=shard_axis, shard_size=shard_size,
                         predicate=is_mat)

    def init(params):
        bucketed = eng.init_state(eng.plan(params))
        momentum = map_with_path(
            lambda path, p: jnp.zeros(
                (1,) * p.ndim if is_mat(path, p) else p.shape, jnp.float32),
            params)
        nu = map_with_path(
            lambda path, p: jnp.zeros(
                (1,) * p.ndim if is_mat(path, p) else p.shape, jnp.float32),
            params)
        return FusedMixedState(momentum=momentum, nu=nu,
                               buckets=bucketed.buckets,
                               slots=bucketed.slots)

    @jax.named_scope("adamw")
    def adam_sweep(grads, state, params, step):
        """Per-leaf AdamW pass, new params computed in place; matrix leaves
        pass through (the bucket scatter overwrites them).  Returns (new
        params, momentum, nu)."""
        eta_a = lr_adamw(step)
        t = jnp.asarray(step, jnp.float32) + 1.0
        bc1 = 1.0 - b1 ** t
        bc2 = 1.0 - b2 ** t

        def upd_adam(path, g, mu, nu, p):
            if is_mat(path, p):
                return p, mu, nu
            g32 = g.astype(jnp.float32)
            mu_new = b1 * mu + (1 - b1) * g32
            nu_new = b2 * nu + (1 - b2) * jnp.square(g32)
            d = (mu_new / bc1) / (jnp.sqrt(nu_new / bc2) + adam_eps)
            u = -eta_a * (d + weight_decay * p.astype(jnp.float32))
            return p + u.astype(p.dtype), mu_new, nu_new

        paths_tree = map_with_path(lambda path, _: path, params)
        out = jax.tree_util.tree_map(upd_adam, paths_tree, grads,
                                     state.momentum, state.nu, params)
        def pick(i):
            return jax.tree_util.tree_map(
                lambda x: x[i], out, is_leaf=lambda x: isinstance(x, tuple))
        return pick(0), pick(1), pick(2)

    def update_apply(grads, state, params, step):
        """-> (new_params, state).  AdamW leaves compute their new params in
        place (same op order as apply_updates); matrix buckets run the
        rule's single-pass apply — gather (g, v, w), one pass, scatter the
        updated weights — with no fp32 ``d`` bucket and no updates tree."""
        plan = eng.plan(params)
        new_params, momentum, nu = adam_sweep(grads, state, params, step)

        # matrix partition: one single-pass rule apply per bucket
        g_b = bucketing.gather(plan, grads, dtype=jnp.float32)
        p_b = bucketing.gather(plan, params)
        w_b, v_b, s_b = eng.apply_buckets(plan, g_b, p_b, state.buckets,
                                          state.slots, step)
        new_params = bucketing.scatter(plan, w_b, new_params, cast=True)
        return new_params, FusedMixedState(momentum=momentum, nu=nu,
                                           buckets=v_b, slots=s_b)

    def update_apply_bucket(bucket, g_shard, v_shard, w_chunks, step,
                            clip_scale=None, *, slots=None):
        """One matrix bucket's whole ZeRO-2 chain — optional clip scale
        folded into the gradient shard, the rule's fused apply,
        updated-weight all-gather — independent of every other bucket (the
        pipelined dp step's per-bucket entry point).  ``slots`` maps slot
        name -> this rank's stripe shard (None/{} for slotless rules).
        Returns ``(w_new full padded bucket, v_new shard, slots_new
        shard)``."""
        return eng.bucket_apply_sharded(bucket, g_shard, v_shard,
                                        slots or {}, w_chunks, step,
                                        clip_scale)

    def update_apply_sharded(g_shards, grads, state, params, step,
                             clip_scale=None):
        """ZeRO-2 single-pass apply (call inside ``shard_map``): matrix
        buckets consume this rank's reduce-scattered ``(padded L / N, d_in,
        d_out)`` fp32 mean-gradient shards from ``g_shards`` (their leaves
        in ``grads`` are ignored); AdamW leaves read their mean grads from
        ``grads`` as usual — already clip-scaled by the caller — and update
        in place.  The matrix partition is a loop over
        ``update_apply_bucket`` (independent per-bucket chains;
        ``clip_scale`` folds the global-norm clip into each chain).  Only
        the updated weight slices are all-gathered — no full gradient
        bucket per rank."""
        plan = eng.plan(params)
        new_params, momentum, nu = adam_sweep(grads, state, params, step)

        out = eng.sharded_apply(plan, g_shards, state.buckets, state.slots,
                                params, step, clip_scale)
        if out is None:
            return new_params, FusedMixedState(momentum=momentum, nu=nu,
                                               buckets={}, slots={})
        w_b, v_b, s_b = out
        new_params = bucketing.scatter(plan, w_b, new_params, cast=True)
        return new_params, FusedMixedState(momentum=momentum, nu=nu,
                                           buckets=v_b, slots=s_b)

    zero2 = shard_axis is not None
    return Optimizer(init=init, update_apply=update_apply,
                     update_apply_sharded=update_apply_sharded if zero2 else None,
                     update_apply_bucket=update_apply_bucket if zero2 else None,
                     bucket_plan=eng.plan, shard_size=shard_size,
                     state_meta=eng.state_meta)
