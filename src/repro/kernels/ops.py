"""jit'd public wrappers around the Pallas kernels.

Off the TPU the kernels run in interpret mode for correctness testing; on
TPU they compile to Mosaic.  ``_interpret()`` picks automatically.
Leading batch dims (layer stacks, expert stacks) are vmapped.  The RMNP
entry point routes a shape to the kernel or to the jnp reference by the
kernel's own VMEM plan (``rmnp_update.plan_stripes``): embedding-sized
fan-ins whose stripe cannot fit VMEM take the XLA path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import matmul as _mm
from repro.kernels import newton_schulz as _ns
from repro.kernels import rmnp_update as _rm


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def rmnp_bucket_update_apply(g, v, w, scale, wd, *, beta: float,
                             eps: float = 1e-8):
    """Single-pass apply over a stacked bucket: momentum EMA + row
    normalize + weight update in one ``pallas_call`` — no fp32 ``d`` buffer
    is ever materialized.

    g: (L, d_in, d_out) fp32 gradients; v: matching momentum in its storage
    dtype; w: matching weights (math fp32, output in w.dtype); scale/wd are
    traced fp32 scalars (scale folds lr * rms_lr_scale).  Returns
    (v_new, w_new).  The kernel writes v_new over v and w_new over w, so
    where the train step donates the momentum and weights
    (``donate_argnums`` on the outer step) their allocations are updated
    in place."""
    if _rm.rownorm_apply_plan(g, v, w) is None:
        from repro.kernels.ref import rmnp_rownorm_apply_ref
        return rmnp_rownorm_apply_ref(g, v, w, scale, wd, beta=beta, eps=eps)
    scalars = jnp.stack([jnp.asarray(scale, jnp.float32),
                         jnp.asarray(wd, jnp.float32)])
    return _rm.rmnp_rownorm_apply_2d(g, v, w, scalars, beta=beta, eps=eps,
                                     interpret=_interpret())


def _sub_jaxprs(param):
    # duck-typed: ClosedJaxpr carries .jaxpr, Jaxpr carries .eqns (the
    # concrete classes moved between jax.core and jax.extend.core)
    if hasattr(param, "jaxpr"):
        return _sub_jaxprs(param.jaxpr)
    if hasattr(param, "eqns"):
        return [param]
    if isinstance(param, (list, tuple)):
        return [j for p in param for j in _sub_jaxprs(p)]
    return []


def _walk_eqns(jaxpr, visit) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        n += visit(eqn)
        for param in eqn.params.values():
            n += sum(_walk_eqns(j, visit) for j in _sub_jaxprs(param))
    return n


def count_pallas_calls(fn, *args, **kwargs) -> int:
    """Number of ``pallas_call`` equations in ``fn``'s jaxpr (recursing into
    nested call/control-flow jaxprs) — i.e. kernel launches per execution.
    Traces but never runs ``fn``; used by the fused-engine tests and the
    launches-per-step benchmark column."""
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    return _walk_eqns(closed.jaxpr,
                      lambda eqn: int(eqn.primitive.name == "pallas_call"))


def count_buffer_eqns(fn, shape, dtype, *args, exclude_prims=(),
                      **kwargs) -> int:
    """Number of jaxpr equations in ``fn`` (recursive) producing an output of
    exactly ``(shape, dtype)`` — the tracer behind the single-pass engine's
    'no full-partition fp32 intermediate' claim: per bucket the apply emits
    only the updated weights, never an fp32 ``d`` buffer at the full bucket
    shape.  Traces but never runs ``fn``.

    ``exclude_prims`` names primitives whose outputs are not counted — the
    ZeRO-2 tests use it to discount the *intended* full-bucket buffer (the
    updated-weights ``all_gather``) when params are fp32, so the count
    isolates gradient-path intermediates."""
    shape = tuple(shape)
    dtype = jnp.dtype(dtype)
    exclude = frozenset(exclude_prims)
    closed = jax.make_jaxpr(fn)(*args, **kwargs)

    def visit(eqn):
        if eqn.primitive.name in exclude:
            return 0
        return sum(1 for v in eqn.outvars
                   if getattr(v.aval, "shape", None) == shape
                   and getattr(v.aval, "dtype", None) == dtype)

    return _walk_eqns(closed.jaxpr, visit)


def ns_step(x, a: float, b: float, c: float):
    """One Newton-Schulz iteration on (..., m, n) fp32.  Leading dims are
    batched through the stacked-bucket kernel: a whole ``(L, m, n)`` shape
    bucket costs one 3-launch sequence (Gram, polynomial, apply) instead of
    one per matrix — the bucketed-Muon analogue of
    ``rmnp_bucket_update_apply``."""
    if x.ndim == 2:
        return _ns.ns_step(x, a=a, b=b, c=c, interpret=_interpret())
    lead = x.shape[:-2]
    flat = x.reshape((-1,) + x.shape[-2:])
    out = _ns.ns_step3(flat, a=a, b=b, c=c, interpret=_interpret())
    return out.reshape(lead + x.shape[-2:])


def matmul(a, b):
    """Tiled fp32-accumulating matmul (2-D operands)."""
    return _mm.matmul(a, b, interpret=_interpret())
