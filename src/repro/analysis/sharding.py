"""Sharding pass: ZeRO-sharded state must never be silently replicated.

In the ZeRO-2 step the ONLY legitimate full-bucket-shaped ``all-gather``
is the updated-weight gather at the end of each bucket's chain — exactly
one per bucket.  Momentum and slot stripes live and die as ``L/N``
shards; an ``all-gather`` whose result matches a full momentum bucket
(beyond the one weight gather) or a full slot stripe means some future
change started replicating sharded state, which silently multiplies
optimizer memory by N and wire traffic per step.  This pass classifies
every HLO all-gather against the bucket plan and fails loudly on the
extra ones.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from repro.analysis import hlo as H
from repro.analysis.findings import Finding, Severity
from repro.analysis.framework import (
    AnalysisPass, Artifacts, register_pass,
)


def _gather_result_dims(op: H.Op) -> Optional[Tuple[int, ...]]:
    """The gathered (largest) result shape of an all-gather op.  The async
    ``-start`` form has a ``(operand, result)`` tuple type, so take the
    entry with the most elements."""
    shapes = H.all_shapes(op.type_str)
    if not shapes:
        return None

    def elems(dims: Tuple[int, ...]) -> int:
        n = 1
        for d in dims:
            n *= d
        return n

    return max((dims for _dt, dims in shapes), key=elems)


def classify_all_gathers(text: str, buckets) -> Dict[str, List[Tuple[str, str]]]:
    """Map ``bucket key -> [(computation, op name)]`` for every all-gather
    whose gathered result is exactly the bucket's full momentum shape,
    plus ``"slot:<bucket>/<slot>"`` entries for full-slot-stripe gathers
    and ``"?"`` for unclassified ones."""
    comps, _entry = H.parse_module(text)
    full_shapes = {b.full_shape: b.key for b in buckets}
    slot_shapes = {}
    for b in buckets:
        for slot, (shape, _dtype) in b.slot_shapes.items():
            slot_shapes[tuple(shape)] = f"slot:{b.key}/{slot}"
    out: Dict[str, List[Tuple[str, str]]] = {}
    for comp in comps.values():
        for op in comp.ops:
            base = (op.opcode[:-6] if op.opcode.endswith("-start")
                    else op.opcode)
            if base != "all-gather" or op.opcode.endswith("-done"):
                continue
            dims = _gather_result_dims(op)
            key = full_shapes.get(dims) or slot_shapes.get(dims) or "?"
            out.setdefault(key, []).append((comp.name, op.name))
    return out


@register_pass
class ShardingPass(AnalysisPass):
    name = "sharding"
    description = ("no all-gather replicates ZeRO-sharded momentum or "
                   "slot stripes (one weight gather per bucket)")
    scope = "combo"

    def run(self, artifacts: Artifacts) -> List[Finding]:
        out = artifacts.parse_findings(self.name)
        combo = artifacts.combo
        gathers = classify_all_gathers(artifacts.hlo_text, artifacts.buckets)
        for key, ops in sorted(gathers.items()):
            if key.startswith("slot:"):
                for cname, oname in ops:
                    out.append(Finding(
                        pass_name=self.name, severity=Severity.ERROR,
                        code="slot-stripe-gathered",
                        message=(f"all-gather %{oname} (in {cname}) "
                                 f"reconstructs the full {key[5:]} slot "
                                 f"stripe — slot state must stay "
                                 f"ZeRO-sharded"),
                        combo=combo.id, location=f"%{oname}"))
            elif key != "?" and len(ops) > 1:
                names = ", ".join(f"%{o}" for _c, o in ops)
                out.append(Finding(
                    pass_name=self.name, severity=Severity.ERROR,
                    code="state-replicated",
                    message=(f"bucket {key}: {len(ops)} full-bucket-shaped "
                             f"all-gathers ({names}); only the one "
                             f"updated-weight gather is allowed — an "
                             f"extra gather means momentum or another "
                             f"sharded buffer is being replicated"),
                    combo=combo.id, location=key))
        n_bucket = sum(len(v) for k, v in gathers.items()
                       if k != "?" and not k.startswith("slot:"))
        out.append(Finding(
            pass_name=self.name, severity=Severity.INFO, code="summary",
            message=(f"{n_bucket} bucket-shaped all-gathers across "
                     f"{len(artifacts.buckets)} buckets, "
                     f"{len(gathers.get('?', []))} unclassified"),
            combo=combo.id))
        return out
