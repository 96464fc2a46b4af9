"""Pallas kernel lint: VMEM budgets, grid coverage, dtype discipline.

Kernels are linted at the *trace* level (``kernels/introspect.py``
collects every ``pallas_call`` with its grid and block specs; nothing
executes), over a representative sweep of bucket shapes — square, wide,
tall, lane-unaligned d_out (the pad path), the widest fan-in a published
GPT-2 block bucket has, and an embedding-sized fan-in that the routing
rule sends to XLA.  Three checks per launch:

* **vmem-budget** — the launch's VMEM demand must fit its scoped limit.
  For the RMNP stripe kernels the demand is the kernel's own accounting
  (``rmnp_update.stripe_vmem_bytes``: blocks double-buffered at their
  dtypes plus the body's fp32 temporaries) read off the launch's block
  specs, and the plan recomputed from the launch's shapes must give the
  block the launch actually uses (128-lane aligned), so the accounting
  and the specs cannot drift apart.  Other kernels set no limit, so
  their double-buffered blocks must fit Mosaic's default.
* **grid-covers-array** — every non-SMEM operand's index map, evaluated
  over the grid, must tile the full array with no uncovered gap and no
  block starting fully out of bounds.
* **implicit-upcast** — widening ``convert_element_type`` ops inside the
  kernel body must take their input straight from a ref load (``get``):
  the deliberate load-and-upcast-to-fp32 pattern.  A widening convert in
  the middle of the arithmetic means mixed-dtype math snuck in.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

from repro.analysis.findings import Finding, Severity
from repro.analysis.framework import AnalysisPass, register_pass

# (L, d_in, d_out) stacked-bucket operand shapes the lint traces with:
# square, MLP-wide, MLP-tall, lane-unaligned d_out (pad path), the
# gpt2-large down-projection fan-in, and an embedding fan-in (XLA-routed)
LINT_SHAPES: Tuple[Tuple[int, int, int], ...] = (
    (4, 768, 768),
    (2, 768, 3072),
    (2, 3072, 768),
    (3, 64, 80),
    (1, 5120, 256),
    (1, 50432, 128),
)

# Mosaic's scoped-VMEM limit on v5e for a launch that sets none
DEFAULT_VMEM_LIMIT = 16 * 2**20


def _temps_for(name: str) -> Optional[int]:
    """Body temporaries the stripe accounting charges a kernel, by name."""
    from repro.kernels import rmnp_update as rm

    return {"rmnp_rownorm_apply": rm.APPLY_TEMPS}.get(name)


def _trace_targets():
    """(label, thunk, xla_routed) triples tracing each public kernel entry
    point over the lint shapes; ``xla_routed`` is the routing rule's own
    verdict, so an XLA-routed shape is expected to launch nothing.
    Imports live here so the analysis package imports without jax until a
    pass actually runs."""
    import jax.numpy as jnp

    from repro.kernels import ops as kops
    from repro.kernels import rmnp_update as rm

    targets = []
    for (ll, d_in, d_out) in LINT_SHAPES:
        g = jnp.zeros((ll, d_in, d_out), jnp.float32)
        targets.append((
            f"rmnp_bucket_update_apply[{ll}x{d_in}x{d_out}]",
            lambda g=g: kops.rmnp_bucket_update_apply(
                g, g, g, 0.1, 0.1, beta=0.95),
            rm.rownorm_apply_plan(g, g, g) is None))
    for (ll, m, _n) in ((4, 256, 0), (2, 512, 0)):
        x = jnp.zeros((ll, m, m), jnp.float32)
        targets.append((
            f"ns_step[{ll}x{m}x{m}]",
            lambda x=x: kops.ns_step(x, a=3.0, b=-4.0, c=1.2), False))
    a = jnp.zeros((256, 512), jnp.float32)
    b = jnp.zeros((512, 256), jnp.float32)
    targets.append(("matmul[256x512x256]", lambda: kops.matmul(a, b),
                    False))
    return targets


def _widening_converts_off_ref(kernel_jaxpr) -> List[str]:
    """Equation descriptions of widening converts whose input is NOT a
    direct ref load."""
    loaded = set()
    bad: List[str] = []
    for eqn in kernel_jaxpr.eqns:
        if eqn.primitive.name == "get":
            for v in eqn.outvars:
                loaded.add(v)
        elif eqn.primitive.name == "convert_element_type":
            src = eqn.invars[0]
            src_dt = getattr(getattr(src, "aval", None), "dtype", None)
            dst_dt = eqn.params.get("new_dtype")
            if src_dt is None or dst_dt is None:
                continue
            src_np, dst_np = np.dtype(src_dt), np.dtype(dst_dt)
            # bool/int widening is mask bookkeeping, not precision-
            # sensitive math; only float->float widening matters here
            if (src_np.kind == "f" and dst_np.kind == "f"
                    and dst_np.itemsize > src_np.itemsize
                    and src not in loaded):
                desc = f"{src_dt} -> {dst_dt}"
                if desc not in bad:
                    bad.append(desc)
    return bad


@register_pass
class KernelLintPass(AnalysisPass):
    name = "kernel-lint"
    description = ("Pallas launches fit the VMEM budget, tile their "
                   "arrays, and upcast only at ref loads")
    scope = "repo"

    def run(self, _artifacts=None) -> List[Finding]:
        from repro.kernels import introspect
        from repro.kernels import rmnp_update as rm

        out: List[Finding] = []
        n_launches = 0
        targets = _trace_targets()
        for label, thunk, xla_routed in targets:
            try:
                launches = introspect.collect_kernel_launches(thunk)
            except Exception as e:  # trace failure is itself a finding
                out.append(Finding(
                    pass_name=self.name, severity=Severity.ERROR,
                    code="trace-failed",
                    message=f"{label}: tracing raised {type(e).__name__}: "
                            f"{e}", location=label))
                continue
            if xla_routed:
                code, sev = (("xla-routed", Severity.INFO) if not launches
                             else ("routed-shape-launched", Severity.ERROR))
                out.append(Finding(
                    pass_name=self.name, severity=sev, code=code,
                    message=f"{label}: the VMEM plan routes this shape to "
                            f"XLA; {len(launches)} pallas_call traced",
                    location=label))
                continue
            if not launches:
                out.append(Finding(
                    pass_name=self.name, severity=Severity.WARNING,
                    code="no-launches",
                    message=f"{label}: no pallas_call traced for a shape "
                            f"the VMEM plan routes to the kernel",
                    location=label))
                continue
            for launch in launches:
                n_launches += 1
                where = f"{label}/{launch.name}"
                temps = _temps_for(launch.name)
                blocks3 = [b for b in launch.blocks
                           if b.memspace != "smem"
                           and len(b.block_shape) == 3]
                if temps is not None and blocks3:
                    d_in = blocks3[0].block_shape[-2] or 1
                    bn = blocks3[0].block_shape[-1] or 1
                    sizes = [np.dtype(b.dtype).itemsize for b in blocks3]
                    need = rm.stripe_vmem_bytes(d_in, bn, sizes, temps)
                    limit = rm.VMEM_LIMIT_CAP
                    plan = rm.plan_stripes(d_in, blocks3[0].array_shape[-1],
                                           sizes, temps)
                    if plan is None or plan.block_n != bn \
                            or bn % rm.LANE:
                        out.append(Finding(
                            pass_name=self.name,
                            severity=Severity.ERROR,
                            code="stripe-accounting-overrun",
                            message=(f"{where}: block ({d_in}, {bn}) is "
                                     f"not the {rm.LANE}-lane-aligned "
                                     f"block plan_stripes gives this "
                                     f"launch ({plan}) — the accounting "
                                     f"and the launch spec disagree"),
                            location=where))
                else:
                    need = launch.vmem_block_bytes()
                    limit = DEFAULT_VMEM_LIMIT
                if need > limit:
                    out.append(Finding(
                        pass_name=self.name, severity=Severity.ERROR,
                        code="vmem-over-budget",
                        message=(f"{where}: launch needs "
                                 f"{need / 2**20:.1f} MiB of VMEM per "
                                 f"program, over its "
                                 f"{limit / 2**20:.0f} MiB limit"),
                        location=where))
                for blk in launch.blocks:
                    if blk.memspace == "smem":
                        continue
                    cov = introspect.block_coverage(launch, blk)
                    for d, lo, hi in cov["uncovered"]:
                        out.append(Finding(
                            pass_name=self.name, severity=Severity.ERROR,
                            code="grid-gap",
                            message=(f"{where}: {blk.origin} dim {d} "
                                     f"[{lo}, {hi}) of "
                                     f"{blk.array_shape} is never "
                                     f"covered by any block"),
                            location=where))
                    for d, start in cov["out_of_bounds"]:
                        out.append(Finding(
                            pass_name=self.name, severity=Severity.ERROR,
                            code="block-out-of-bounds",
                            message=(f"{where}: {blk.origin} dim {d} "
                                     f"has a block starting at {start}, "
                                     f"past extent "
                                     f"{blk.array_shape[d]}"),
                            location=where))
                for desc in _widening_converts_off_ref(launch.kernel_jaxpr):
                    out.append(Finding(
                        pass_name=self.name, severity=Severity.WARNING,
                        code="implicit-upcast",
                        message=(f"{where}: widening convert {desc} not "
                                 f"fed by a ref load — mixed-dtype math "
                                 f"inside the kernel body"),
                        location=where))
        out.append(Finding(
            pass_name=self.name, severity=Severity.INFO, code="summary",
            message=f"linted {n_launches} launches across "
                    f"{len(targets)} trace targets"))
        return out
