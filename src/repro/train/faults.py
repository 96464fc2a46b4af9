"""Numerical fault injection for the resilience proofs.

The guard (train/pipeline.py ``two_phase_clip`` finite flags + bitwise
step skip) is only trustworthy if it is exercised against *real* faults in
the *real* step — not against hand-poisoned state.  This module injects
them in-graph, so the corruption flows through the same backward /
quantize / collective / clip path a production fault would:

* ``nan`` / ``inf``: poison one element of a chosen gradient leaf at a
  chosen step (and optionally a chosen microbatch of the accumulation
  scan), straight out of the backward pass — upstream of the wire, the
  error-feedback fold and the clip, exactly where a bad loss kernel or an
  overflowed bf16 activation would land it.

* ``bitflip``: flip the top exponent bit of the first fp32 *block scale*
  of a chosen bucket's int8 reduce-scatter payload, on rank 0's outgoing
  wire data.  The int8 payload itself is deliberately NOT the target: a
  flipped int8 sample is bounded by its block scale (error <= 254*scale),
  stays finite, and is invisible to a finite-ness guard — that residual
  risk belongs to the loss-spike ladder (distributed/monitor.py
  ``AnomalyMonitor``).  A flipped *scale* is unbounded (exponent bit 30
  turns a normal scale into ~1e38 * its mantissa; dequantize then
  overflows to inf), which is exactly the class the in-graph guard must
  catch.  Caveat: a block whose scale is exactly 0.0 flips to 2.0 and
  dequantizes 0 * 2.0 = 0 — target a bucket with live gradient data.

Faults parse from one CLI string (``launch/train.py --inject-fault``):

    kind:leaf:step[:microbatch]

    nan:blocks_0/attn/wq:5       NaN into that leaf's gradient at step 5
    inf:tok_embed/w:3:1          Inf at step 3, microbatch 1 only
    nan:*:6+                     NaN into the first leaf, every step >= 6
                                 (sticky — a persistent fault, the input
                                 that walks the rewind ladder to abort)
    bitflip:8x16:4               wire-scale bit-flip on bucket 8x16, step 4

A trailing ``+`` on the step makes the fault *sticky* (fires every step
>= ``step``); the launch driver disarms injected faults on rewind, so a
sticky fault models a transient that a rewind clears, while the abort
rung covers anomalies that keep firing.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.types import PyTree, tree_paths

_KINDS = ("nan", "inf", "bitflip")


@dataclasses.dataclass(frozen=True)
class FaultSpec:
    """One injected fault.  ``leaf`` is a gradient-leaf path for nan/inf
    (``*`` = the tree's first leaf) or a bucket key (e.g. ``8x16``) for
    bitflip; ``microbatch`` of -1 fires on every microbatch; ``sticky``
    fires at every step >= ``step`` instead of exactly at it."""
    kind: str
    leaf: str
    step: int
    microbatch: int = -1
    sticky: bool = False

    def __post_init__(self):
        if self.kind not in _KINDS:
            raise ValueError(f"fault kind must be one of {_KINDS}, "
                             f"got {self.kind!r}")
        if self.kind == "bitflip" and self.microbatch != -1:
            raise ValueError("bitflip is a wire fault — it has no "
                             "microbatch (the wire sees the accumulated "
                             "gradient)")

    def describe(self) -> str:
        when = f"step >= {self.step}" if self.sticky else f"step {self.step}"
        mb = f", microbatch {self.microbatch}" if self.microbatch >= 0 else ""
        return f"{self.kind} into {self.leaf!r} at {when}{mb}"


def parse_fault(spec: str) -> FaultSpec:
    """Parse ``kind:leaf:step[:microbatch]`` (see module docstring)."""
    parts = spec.split(":")
    if len(parts) not in (3, 4):
        raise ValueError(
            f"--inject-fault expects kind:leaf:step[:microbatch], "
            f"got {spec!r}")
    kind, leaf, step_s = parts[0], parts[1], parts[2]
    sticky = step_s.endswith("+")
    try:
        step = int(step_s[:-1] if sticky else step_s)
        mb = int(parts[3]) if len(parts) == 4 else -1
    except ValueError:
        raise ValueError(f"--inject-fault {spec!r}: step/microbatch must "
                         f"be integers") from None
    return FaultSpec(kind=kind, leaf=leaf, step=step, microbatch=mb,
                     sticky=sticky)


def _hit(spec: FaultSpec, step) -> jax.Array:
    step = jnp.asarray(step, jnp.int32)
    return step >= spec.step if spec.sticky else step == spec.step


def apply_grad_fault(spec: Optional[FaultSpec], grads: PyTree, step,
                     microbatch=0) -> PyTree:
    """Poison element ``[0, ..., 0]`` of the named gradient leaf when the
    traced ``step`` (and microbatch, if pinned) matches.  A Python no-op
    (identical trace) for ``spec=None`` or wire-fault specs.  One element
    is enough: any non-finite value makes the leaf's clip partial sum of
    squares non-finite, which is precisely the signal the guard reads."""
    if spec is None or spec.kind not in ("nan", "inf"):
        return grads
    flat = tree_paths(grads)
    target = spec.leaf if spec.leaf != "*" else flat[0][0]
    if target not in {p for p, _ in flat}:
        raise ValueError(
            f"--inject-fault leaf {spec.leaf!r} is not a gradient leaf; "
            f"available: {', '.join(p for p, _ in flat)}")
    hit = _hit(spec, step)
    if spec.microbatch >= 0:
        hit = jnp.logical_and(
            hit, jnp.asarray(microbatch, jnp.int32) == spec.microbatch)
    bad = float("nan") if spec.kind == "nan" else float("inf")

    def poison(path, g):
        if path != target:
            return g
        idx = (0,) * g.ndim
        # at[idx].set with a where keeps the no-fire branch bitwise: the
        # stored value is the element's own value unless the step matches
        return g.at[idx].set(jnp.where(hit, jnp.asarray(bad, g.dtype),
                                       g[idx]))

    from repro.core.types import map_with_path
    return map_with_path(poison, grads)


def wire_fault_for(spec: Optional[FaultSpec], bucket_key: str, step,
                   axis_name: str):
    """The ``wire_fault`` hook for ``compressed_reduce_scatter_leaf``:
    None unless ``spec`` is a bitflip aimed at ``bucket_key``; otherwise a
    ``(q, scale) -> (q, scale)`` callable that flips bit 30 (the top
    exponent bit) of the first outgoing fp32 block scale on rank 0 when
    the step matches.  Applied after the sender computed its quantization
    residual — the corruption is *on the wire*, so the sender's error
    feedback is honest and only the receiver sees garbage."""
    if spec is None or spec.kind != "bitflip" or spec.leaf != bucket_key:
        return None

    def corrupt(q, scale):
        hit = jnp.logical_and(_hit(spec, step),
                              jax.lax.axis_index(axis_name) == 0)
        first = (0,) * scale.ndim
        s0 = scale[first]
        flipped = jax.lax.bitcast_convert_type(
            jax.lax.bitcast_convert_type(s0, jnp.uint32)
            ^ jnp.uint32(1 << 30), jnp.float32)
        return q, scale.at[first].set(jnp.where(hit, flipped, s0))

    return corrupt
