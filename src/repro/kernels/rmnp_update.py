"""Single-pass RMNP apply kernel (the paper's O(mn) hot loop).

One pass over the gradient/momentum/weight triple per column stripe:
    v_new = beta * v + (1 - beta) * g
    d     = v_new / (||v_new||_col + eps)
    w_new = w - scale * (d + wd * w)

Grid is over (bucket slice, d_out column stripe); each program holds a full
(d_in, block_n) stripe in VMEM — the column reduction is local, so no
cross-program accumulation is needed.  This is the TPU-native shape of the
paper's row-normalization: the reduction runs down the sublane axis while
the 128-wide lane axis streams output neurons.

The batched (leading-axis) form is the engine behind the shape-bucketed
optimizer (core/engine.py): a whole (L, d_in, d_out) bucket of stacked
parameter slices is one ``pallas_call``.  Momentum may be stored in bf16
(``v`` dtype is preserved on output); math is always fp32.  Scalar
(lr-scale, weight-decay) arrive in SMEM, and the fp32 ``d`` bucket is
never materialized in HBM: the optimizer is a single memory pass over
(g, v, w).

The kernel writes its state in place: ``v`` is aliased to ``v_new`` and
``w`` to ``w_new``.  Where the caller donates the momentum and weights
(the train step's ``donate_argnums``), the kernel reads and writes the
donated buffers directly; where it does not, XLA copies the operand first
and the caller's arrays are left unchanged.

Every launch is planned by :func:`plan_stripes`: its VMEM accounting sets
the lane block (never below 128 lanes), the launch's scoped-VMEM limit,
and whether a shape takes the kernel at all — a stripe that cannot fit
at 128 lanes (an embedding-sized fan-in) takes the XLA path instead
(``kernels/ops.py``).
"""
from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128                    # Mosaic's lane tile: every block_n is a multiple
MAX_BLOCK_N = 512             # widest lane block the grow phase goes to
GROW_BUDGET = 16 * 2**20      # grow the block only while it needs at most this
VMEM_LIMIT_CAP = 96 * 2**20   # most scoped VMEM a launch may ask for (v5e
#                               has 128 MiB per core)

# fp32 (d_in, block_n) temporaries the kernel body holds beyond its
# pipelined blocks (the upcast loads, v_new, d and w_new) — measured
# against Mosaic's own VMEM demand in v5e compiles
APPLY_TEMPS = 2


class StripePlan(NamedTuple):
    block_n: int
    vmem_limit: int   # bytes, passed as the launch's vmem_limit_bytes


def stripe_vmem_bytes(d_in: int, bn: int, itemsizes: Sequence[int],
                      temps: int) -> int:
    """VMEM one grid program of a column-stripe kernel holds: every
    pipelined ``(d_in, bn)`` block (inputs and outputs, ``itemsizes`` in
    bytes per element) double-buffered at its own dtype, plus ``temps``
    fp32 body temporaries of the same shape."""
    return d_in * bn * (2 * sum(itemsizes) + 4 * temps)


def _vmem_limit(need: int) -> int:
    # headroom for Mosaic's internal scratch and layout padding
    return need + need // 4 + 2 * 2**20


def plan_stripes(d_in: int, n: int, itemsizes: Sequence[int],
                 temps: int) -> Optional[StripePlan]:
    """The one rule that routes a ``(d_in, n)`` stripe kernel: ``None`` when
    even a ``LANE``-wide block cannot fit ``VMEM_LIMIT_CAP`` (the caller
    then takes the XLA path), else the widest lane-aligned block that stays
    within ``GROW_BUDGET`` and divides ``n`` (growth never adds padding),
    with the scoped-VMEM limit its launch needs."""
    bn = LANE
    if _vmem_limit(stripe_vmem_bytes(d_in, bn, itemsizes, temps)) > \
            VMEM_LIMIT_CAP:
        return None
    while (bn * 2 <= MAX_BLOCK_N and n % (bn * 2) == 0
           and stripe_vmem_bytes(d_in, bn * 2, itemsizes, temps)
           <= GROW_BUDGET):
        bn *= 2
    return StripePlan(bn, _vmem_limit(
        stripe_vmem_bytes(d_in, bn, itemsizes, temps)))


def _itemsizes(*dtypes) -> tuple:
    return tuple(jnp.dtype(d).itemsize for d in dtypes)


def rownorm_apply_plan(g, v, w) -> Optional[StripePlan]:
    """Plan of the apply kernel: g, v, w in; v_new, w_new out."""
    return plan_stripes(g.shape[-2], g.shape[-1],
                        _itemsizes(g.dtype, v.dtype, w.dtype, v.dtype,
                                   w.dtype),
                        APPLY_TEMPS)


def _stripe_call(kernel, name: str, operands, out_dtypes,
                 plan: Optional[StripePlan], *, interpret: bool,
                 aliases: Dict[int, int], scalars=None):
    """Scaffolding of a column-stripe kernel: flatten leading
    dims (layer / expert stacks, bucket slices) into the outer grid axis,
    zero-pad d_out to the block, run one program per (l, stripe), slice the
    pad back off.  ``scalars`` (optional (k,) fp32) is prepended as a
    whole-array SMEM operand.  Padded columns are self-contained (their
    norm is local garbage) and never escape the slice.

    ``aliases`` maps an index into ``operands`` to the output written over
    it (same shape and dtype).  The ``(L, d_in, n)`` reshape is a bitcast,
    so an unpadded operand is still the caller's buffer; where d_out is
    padded, the aliased operand is ``jnp.pad``'s fresh buffer instead, and
    the kernel writes into that."""
    lead = operands[0].shape[:-2]
    d_in, n = operands[0].shape[-2:]
    if plan is None:
        raise ValueError(
            f"a ({d_in}, {n}) stripe does not fit {VMEM_LIMIT_CAP >> 20} MiB "
            f"of VMEM even at {LANE} lanes — route it to the XLA path "
            f"(kernels/ops.py)")
    L = 1
    for s in lead:
        L *= s
    ops3 = [o.reshape(L, d_in, n) for o in operands]
    bn = plan.block_n
    pad = (-n) % bn
    if pad:
        ops3 = [jnp.pad(o, ((0, 0), (0, 0), (0, pad))) for o in ops3]
    n_p = n + pad
    grid = (L, n_p // bn)
    spec = pl.BlockSpec((1, d_in, bn), lambda b, j: (b, 0, j))
    in_specs = [spec] * len(ops3)
    if scalars is not None:
        in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)] + in_specs
        ops3 = [scalars.astype(jnp.float32)] + ops3
    shift = 0 if scalars is None else 1
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[spec] * len(out_dtypes),
        out_shape=[jax.ShapeDtypeStruct((L, d_in, n_p), dt)
                   for dt in out_dtypes],
        input_output_aliases={i + shift: o for i, o in aliases.items()},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=plan.vmem_limit),
        interpret=interpret,
        name=name,
    )(*ops3)
    if pad:
        outs = [o[:, :, :n] for o in outs]
    return tuple(o.reshape(*lead, d_in, n) for o in outs)


def _kernel3d_apply(scal_ref, g_ref, v_ref, w_ref, v_out_ref, w_out_ref,
                    *, beta: float, eps: float):
    scale = scal_ref[0]
    wd = scal_ref[1]
    g = g_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    w = w_ref[0].astype(jnp.float32)
    v_new = beta * v + (1.0 - beta) * g
    norm = jnp.sqrt(jnp.sum(v_new * v_new, axis=0, keepdims=True))
    d = v_new / (norm + eps)
    v_out_ref[0] = v_new.astype(v_out_ref.dtype)
    # same op order as kernels/ref.rmnp_rownorm_apply_ref (update =
    # -scale*(d + wd*w), then w + update)
    w_out_ref[0] = (w + (-scale) * (d + wd * w)).astype(w_out_ref.dtype)


def _rownorm_apply_2d(g, v, w, scalars, *, beta: float, eps: float = 1e-8,
                      interpret: bool = False):
    """Single-pass fused apply.  g: (..., d_in, d_out) fp32; v: momentum in
    its storage dtype (fp32 or bf16); w: weights (any float dtype, math in
    fp32, output in w.dtype); scalars: (2,) fp32 ``[scale, weight_decay]``
    where scale already folds lr * rms_lr_scale.  Returns (v_new, w_new) —
    no fp32 ``d`` buffer is ever written."""
    return _stripe_call(
        functools.partial(_kernel3d_apply, beta=beta, eps=eps),
        "rmnp_rownorm_apply", [g, v, w], [v.dtype, w.dtype],
        rownorm_apply_plan(g, v, w), interpret=interpret,
        aliases={1: 0, 2: 1}, scalars=scalars)


# momentum and weight donation happens at the *train-step* jit boundary
# (donate_argnums on the outer step fn): a donate annotation on this nested
# jit would be dropped inside an outer jit.  Inside a donating step the
# kernel's aliases make the update in place; called eagerly, or from a jit
# that does not donate, XLA copies v and w first
rmnp_rownorm_apply_2d = functools.partial(
    jax.jit, static_argnames=("beta", "eps", "interpret"))(_rownorm_apply_2d)
