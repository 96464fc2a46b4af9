"""Pluggable matrix-update rules for the shape-bucketed engine.

The bucketed engine (core/engine.py) owns everything *generic* about the
matrix partition — leaf->bucket plans, momentum stacking, shard padding,
ZeRO-1/2 slicing and the updated-weight all-gather.  What varies between
optimizers is only the per-bucket math, captured here as a
:class:`MatrixUpdateRule`:

* ``slot_shapes`` — extra per-bucket state beyond the stacked momentum
  (e.g. NorMuon's neuron-wise second moment), stored as ``(L, 1, d_out)``
  stripes that shard along ``L`` exactly like the momentum;
* ``precondition`` — the rule's direction ``d``;
* ``apply`` — the single-pass form ``(g, v, w) -> (w_new, v_new)`` the
  engine runs.  The default derives it from ``precondition`` with the
  exact op order of the RMNP apply kernel (``w32 + (-scale) * (d + wd *
  w32)``); RMNP's apply is the kernel itself (or its jnp reference), so
  RMNP has no separate ``precondition``, and Muown's multiplicative norm
  control overrides apply with its own update.

Every rule operates on stacked ``(L, d_in, d_out)`` operands where each
``L`` slice is an independent matrix — row reductions run along axis -2
(the stored matrix's fan-in; the paper's "row") and the NS family batches
its matmuls over ``L`` — so a ``(l_loc, ...)`` ZeRO shard computes exactly
what its slices would compute in the full bucket, and zero pad slices stay
identically zero through every rule (zero grad -> zero momentum -> zero
slots -> zero direction; Muown rescales a zero weight by a finite factor).

The rules are documented proxy reproductions of their sources (PAPERS.md):
Muon (Jordan et al.), NorMuon (arXiv 2510.05491, neuron-wise second
moment), Muown (arXiv 2605.10797, weight-norm control), Nora (row-norm
EMA variant of the RMNP family).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core.types import Optimizer, PyTree, Schedule

# rule name -> class; filled by @_register below.  ``adamw`` is not a matrix
# rule — the registry's mixed constructor (core.make_optimizer) special-cases
# it as the everything-through-AdamW baseline.
RULES: Dict[str, type] = {}


def _register(cls):
    RULES[cls.name] = cls
    return cls


def rule_names() -> Tuple[str, ...]:
    return tuple(sorted(RULES))


def make_rule(name: str, **hyper) -> "MatrixUpdateRule":
    """Construct a registered rule, keeping only the hyperparameters the
    rule declares (callers pass the shared pool: beta, weight_decay, eps,
    ns_steps, ...)."""
    if name not in RULES:
        raise ValueError(
            f"unknown matrix update rule {name!r}; registered: "
            f"{', '.join(rule_names())}")
    cls = RULES[name]
    fields = {f.name for f in dataclasses.fields(cls)}
    return cls(**{k: v for k, v in hyper.items() if k in fields})


def _ema32(g: jax.Array, v: jax.Array, beta: float) -> jax.Array:
    """Momentum EMA in fp32 — the shared first stage of every rule, spelled
    once so all paths (and the per-leaf references) share the op order."""
    return beta * v.astype(jnp.float32) + (1.0 - beta) * g.astype(jnp.float32)


@dataclasses.dataclass(frozen=True)
class MatrixUpdateRule:
    """Base rule: hyperparameters shared by the whole family."""
    beta: float = 0.95
    weight_decay: float = 0.1
    eps: float = 1e-8

    name = "base"

    def slot_shapes(self, rows: int, d_in: int,
                    d_out: int) -> Dict[str, Tuple[Tuple[int, ...], jnp.dtype]]:
        """Extra per-bucket state: slot name -> (shape, dtype) for a bucket
        holding ``rows`` stacked slices.  Shapes lead with ``rows`` so slots
        shard along ``L`` with the momentum."""
        del rows, d_in, d_out
        return {}

    def precondition(self, g: jax.Array, v: jax.Array,
                     slots: Dict[str, jax.Array], *, step,
                     use_kernel: bool = False):
        """(d fp32, v_new in v.dtype, slots_new) from a stacked fp32
        gradient ``g`` and stacked momentum ``v`` (fp32 or bf16 storage;
        math fp32).  ``step`` is the traced step index (bias corrections)."""
        raise NotImplementedError

    def apply(self, g: jax.Array, v: jax.Array, w: jax.Array,
              slots: Dict[str, jax.Array], *, scale, step,
              use_kernel: bool = False):
        """Single-pass per-bucket apply: ``(w_new in w.dtype, v_new,
        slots_new)``.  ``scale`` already folds lr * rms_lr_scale.  Default:
        the canonical additive form ``w - scale * (d + wd * w)``."""
        d, v_new, slots_new = self.precondition(g, v, slots, step=step,
                                                use_kernel=use_kernel)
        w32 = w.astype(jnp.float32)
        w_new = w32 + (-scale) * (d + self.weight_decay * w32)
        return w_new.astype(w.dtype), v_new, slots_new


@_register
@dataclasses.dataclass(frozen=True)
class RmnpRule(MatrixUpdateRule):
    """The paper's rule: momentum EMA + row (fan-in) l2 normalize.  Its
    apply is the single-pass Pallas stripe kernel (kernels/rmnp_update.py)
    when ``use_kernel`` is set, else the kernel's jnp reference."""
    name = "rmnp"

    def apply(self, g, v, w, slots, *, scale, step, use_kernel=False):
        del step
        if use_kernel:
            from repro.kernels import ops as kops
            v_new, w_new = kops.rmnp_bucket_update_apply(
                g, v, w, scale, self.weight_decay, beta=self.beta,
                eps=self.eps)
        else:
            from repro.kernels.ref import rmnp_rownorm_apply_ref
            v_new, w_new = rmnp_rownorm_apply_ref(
                g, v, w, scale, self.weight_decay, beta=self.beta,
                eps=self.eps)
        return w_new, v_new, {}


@_register
@dataclasses.dataclass(frozen=True)
class MuonRule(MatrixUpdateRule):
    """Muon: momentum EMA + quintic Newton-Schulz orthogonalization, batched
    over the bucket's leading ``L`` axis — one 3-launch NS sequence per
    bucket per iteration instead of one per leaf."""
    ns_steps: int = 5

    name = "muon"

    def precondition(self, g, v, slots, *, step, use_kernel=False):
        del step
        from repro.core.muon import newton_schulz
        v32 = _ema32(g, v, self.beta)
        d = newton_schulz(v32, steps=self.ns_steps, use_kernel=use_kernel)
        return d, v32.astype(v.dtype), {}


@_register
@dataclasses.dataclass(frozen=True)
class NorMuonRule(MuonRule):
    """NorMuon (arXiv 2510.05491, proxy): Muon plus a neuron-wise second
    moment of the orthogonalized update — one ``(L, 1, d_out)`` stripe per
    bucket, EMA of the per-output-neuron mean square of ``O = NS(V)``.  The
    normalized update is rescaled to preserve each matrix's update norm, so
    the rms lr scale keeps its meaning."""
    beta2: float = 0.999

    name = "normuon"

    def slot_shapes(self, rows, d_in, d_out):
        del d_in
        return {"nu": ((rows, 1, d_out), jnp.float32)}

    def precondition(self, g, v, slots, *, step, use_kernel=False):
        o, v_new, _ = super().precondition(g, v, slots, step=step,
                                           use_kernel=use_kernel)
        nu = self.beta2 * slots["nu"] + (1.0 - self.beta2) * jnp.mean(
            jnp.square(o), axis=-2, keepdims=True)
        t = jnp.asarray(step, jnp.float32) + 1.0
        nu_hat = nu / (1.0 - self.beta2 ** t)
        o_norm = o / (jnp.sqrt(nu_hat) + self.eps)
        # preserve each matrix's update norm (per L slice); the tiny floor
        # keeps zero pad slices at exactly 0/(0 + floor) == 0
        num = jnp.linalg.norm(o, axis=(-2, -1), keepdims=True)
        den = jnp.linalg.norm(o_norm, axis=(-2, -1), keepdims=True)
        d = o_norm * (num / (den + 1e-12))
        return d, v_new, {"nu": nu}


@_register
@dataclasses.dataclass(frozen=True)
class MuownRule(MuonRule):
    """Muown (arXiv 2605.10797, proxy): Muon with multiplicative weight-norm
    control — after the orthogonalized step, each output neuron's fan-in
    vector is rescaled back to its pre-step norm decayed by
    ``1 - scale * wd``, replacing additive weight decay.  Stateless beyond
    momentum."""
    name = "muown"

    def apply(self, g, v, w, slots, *, scale, step, use_kernel=False):
        d, v_new, _ = self.precondition(g, v, slots, step=step,
                                        use_kernel=use_kernel)
        w32 = w.astype(jnp.float32)
        n_old = jnp.sqrt(jnp.sum(jnp.square(w32), axis=-2, keepdims=True))
        w_tmp = w32 + (-scale) * d
        n_new = jnp.sqrt(jnp.sum(jnp.square(w_tmp), axis=-2, keepdims=True))
        decay = 1.0 - scale * self.weight_decay
        w_out = w_tmp * (decay * n_old / (n_new + self.eps))
        return w_out.astype(w.dtype), v_new, {}


@_register
@dataclasses.dataclass(frozen=True)
class NoraRule(MatrixUpdateRule):
    """Nora: the RMNP row-norm family with a *temporal* EMA of the row
    norms — one ``(L, 1, d_out)`` stripe per bucket tracking each output
    neuron's momentum norm over time, so a transient norm spike does not
    instantly rescale the direction (bias-corrected like Adam's second
    moment)."""
    beta2: float = 0.999

    name = "nora"

    def slot_shapes(self, rows, d_in, d_out):
        del d_in
        return {"r": ((rows, 1, d_out), jnp.float32)}

    def precondition(self, g, v, slots, *, step, use_kernel=False):
        v32 = _ema32(g, v, self.beta)
        rn = jnp.sqrt(jnp.sum(jnp.square(v32), axis=-2, keepdims=True))
        r = self.beta2 * slots["r"] + (1.0 - self.beta2) * rn
        t = jnp.asarray(step, jnp.float32) + 1.0
        r_hat = r / (1.0 - self.beta2 ** t)
        d = v32 / (r_hat + self.eps)
        return d, v32.astype(v.dtype), {"r": r}


# ---------------------------------------------------------------------------
# Per-leaf reference implementations.
#
# The bitwise anchor for the bucketed engine: the same rule math, tree-mapped
# over individual leaves (each reshaped to (lead, d_in, d_out)).  Stacking
# slices into a bucket changes no values — row ops are per-slice and the NS
# matmuls batch per-slice — so reference and engine must agree bit-for-bit
# on fp32 params (tests/test_rules.py, tests/_zero_shard_worker.py).
# ---------------------------------------------------------------------------

class PerLeafRefState(NamedTuple):
    momentum: PyTree                     # fp32, leaf-shaped
    slots: Dict[str, PyTree]             # slot name -> leaf-shaped stripes


def per_leaf_reference(rule: MatrixUpdateRule, lr: Schedule, *,
                       use_kernel: bool = False) -> Optimizer:
    """Per-leaf reference optimizer for ``rule`` (pure matrix trees)."""
    from repro.core.rmnp import rms_lr_scale

    def _as3(x):
        return x.reshape((-1,) + x.shape[-2:])

    def init(params):
        momentum = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)

        def slot_leaf(name):
            def build(p):
                shape, dtype = rule.slot_shapes(
                    _as3(p).shape[0], p.shape[-2], p.shape[-1])[name]
                return jnp.zeros(shape, dtype)
            return build

        slots = {name: jax.tree_util.tree_map(slot_leaf(name), params)
                 for name in rule.slot_shapes(1, 2, 2)}
        return PerLeafRefState(momentum=momentum, slots=slots)

    def update_apply(grads, state, params, step):
        from repro.core.types import tree_paths
        eta = lr(step)
        g_flat = tree_paths(grads)
        v_flat = tree_paths(state.momentum)
        p_flat = tree_paths(params)
        new_p, new_v = {}, {}
        new_s = {name: {} for name in state.slots}
        s_flat = {name: dict(tree_paths(state.slots[name]))
                  for name in state.slots}
        for (path, g), (_, v), (_, p) in zip(g_flat, v_flat, p_flat, strict=False):
            scale = eta * rms_lr_scale(p.shape)
            sl = {name: s_flat[name][path] for name in s_flat}
            w_new, v_new, sl_new = rule.apply(
                _as3(g).astype(jnp.float32), _as3(v), _as3(p), sl,
                scale=scale, step=step, use_kernel=use_kernel)
            new_p[path] = w_new.reshape(p.shape).astype(p.dtype)
            new_v[path] = v_new.reshape(v.shape)
            for name in sl_new:
                new_s[name][path] = sl_new[name]
        def rebuild(tmpl, vals):
            return jax.tree_util.tree_unflatten(
                jax.tree_util.tree_structure(tmpl),
                [vals[path] for path, _ in tree_paths(tmpl)])
        return (rebuild(params, new_p),
                PerLeafRefState(
                    momentum=rebuild(state.momentum, new_v),
                    slots={name: rebuild(state.slots[name], new_s[name])
                           for name in state.slots}))

    def update(grads, state, params, step):
        p32 = jax.tree_util.tree_map(
            lambda p: p.astype(jnp.float32), params)
        new_p, new_state = update_apply(grads, state, p32, step)
        updates = jax.tree_util.tree_map(lambda a, b: a - b, new_p, p32)
        return updates, new_state

    return Optimizer(init=init, update=update, update_apply=update_apply)
