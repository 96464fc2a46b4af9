"""Memory pass: no full-bucket fp32 intermediate in the ZeRO-2 jaxpr.

The single-pass ZeRO-2 engine's memory claim is that per rank, per
bucket, only ``1/N``-sized gradient/momentum/slot buffers and the one
*intended* full-size buffer (the updated-weight all-gather result) ever
exist.  This pass generalizes the ad-hoc ``count_buffer_eqns`` test
(``kernels/ops.py``) to every registered rule's full state surface:

* the full ``(padded, d_in, d_out)`` fp32 bucket (a gradient gather, a
  ``d`` buffer, or a replicated momentum buffer leaking in);
* every slot stripe at its FULL ``(padded, 1, d_out)`` shape — sharded
  rules (NorMuon's ``nu``, Nora's ``r``) must only ever hold the
  ``padded/N`` shard.

``all_gather`` / ``reshape`` / ``shard_map`` outputs are excluded, the
same discount the ZeRO-2 tests use for the intended updated-weight
gather; buckets where a planned leaf is itself bucket-sized are skipped
(the leaf's own gradient legitimately has the full shape).
"""
from __future__ import annotations

from typing import List, Tuple

from repro.analysis.findings import Finding, Severity
from repro.analysis.framework import (
    AnalysisPass, Artifacts, register_pass,
)

EXCLUDE_PRIMS = frozenset({"all_gather", "reshape", "shard_map"})


def count_jaxpr_buffers(jaxpr, shape: Tuple[int, ...], dtype: str,
                        exclude_prims=EXCLUDE_PRIMS) -> List[str]:
    """Primitive names of equations (recursing into sub-jaxprs) producing
    an output of exactly ``(shape, dtype)`` — the already-traced-jaxpr
    form of ``kernels.ops.count_buffer_eqns``."""
    import numpy as np

    from repro.kernels.ops import _sub_jaxprs, _walk_eqns

    shape = tuple(shape)
    dtype = np.dtype(dtype)
    hits: List[str] = []

    def visit(eqn):
        if eqn.primitive.name in exclude_prims:
            return 0
        n = 0
        for v in eqn.outvars:
            if (getattr(v.aval, "shape", None) == shape
                    and getattr(v.aval, "dtype", None) == dtype):
                hits.append(eqn.primitive.name)
                n += 1
        return n

    for j in _sub_jaxprs(jaxpr):
        _walk_eqns(j, visit)
    return hits


@register_pass
class MemoryPass(AnalysisPass):
    name = "memory"
    description = ("no full-bucket fp32 gradient/momentum/slot "
                   "intermediate in the ZeRO-2 step jaxpr")
    scope = "combo"

    def run(self, artifacts: Artifacts) -> List[Finding]:
        out: List[Finding] = []
        combo = artifacts.combo
        if artifacts.jaxpr is None:
            out.append(Finding(
                pass_name=self.name, severity=Severity.WARNING,
                code="no-jaxpr",
                message="combo lowered without a jaxpr; memory pass "
                        "cannot run", combo=combo.id))
            return out
        checked = 0
        for b in artifacts.buckets:
            if any(tuple(s) == b.full_shape for s in b.leaf_shapes):
                out.append(Finding(
                    pass_name=self.name, severity=Severity.INFO,
                    code="bucket-skipped",
                    message=(f"bucket {b.key}: a planned leaf is itself "
                             f"bucket-sized {b.full_shape}; full-shape "
                             f"counting would false-positive on the "
                             f"leaf's own gradient"),
                    combo=combo.id, location=b.key))
                continue
            checked += 1
            hits = count_jaxpr_buffers(artifacts.jaxpr, b.full_shape,
                                       "float32")
            for prim in hits:
                out.append(Finding(
                    pass_name=self.name, severity=Severity.ERROR,
                    code="full-bucket-fp32",
                    message=(f"bucket {b.key}: primitive {prim!r} "
                             f"materializes a full {b.full_shape} fp32 "
                             f"buffer — the ZeRO-2 path must only hold "
                             f"1/{artifacts.n_dev} shards (plus the "
                             f"excluded updated-weight all_gather)"),
                    combo=combo.id, location=b.key))
            for slot, (shape, dtype) in sorted(b.slot_shapes.items()):
                for prim in count_jaxpr_buffers(artifacts.jaxpr,
                                                shape, dtype):
                    out.append(Finding(
                        pass_name=self.name, severity=Severity.ERROR,
                        code="full-slot-stripe",
                        message=(f"bucket {b.key}: primitive {prim!r} "
                                 f"materializes slot {slot!r} at its "
                                 f"full shape {tuple(shape)} ({dtype}) "
                                 f"— slot stripes must stay sharded "
                                 f"along L"),
                        combo=combo.id, location=f"{b.key}/{slot}"))
        out.append(Finding(
            pass_name=self.name, severity=Severity.INFO, code="summary",
            message=f"checked {checked} buckets for full-shape fp32 "
                    f"buffers and slot stripes", combo=combo.id))
        return out
