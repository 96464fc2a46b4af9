"""Overlap pass: zero update->collective serialization edges.

The pipelined ZeRO-2 dp step's whole point is that every bucket's chain
(reduce collective -> fused apply -> updated-weight all-gather) is
independent of every other bucket, so XLA's latency-hiding scheduler can
overlap bucket i's collective with bucket j's compute.  A data dependence
from one bucket's update *output* back into any gradient collective
serializes communication behind compute and silently defeats the
scheduler; :func:`collective_overlap_report` (formerly in
``launch/hlo_cost.py``) detects exactly that edge in compiled HLO, and
:class:`OverlapPass` runs it for every ZeRO-2 combo in the registry — not
just the rules a test happens to name.
"""
from __future__ import annotations

from typing import Dict, List, Optional

from repro.analysis import hlo as H
from repro.analysis.findings import Finding, Severity
from repro.analysis.framework import (
    AnalysisPass, Artifacts, register_pass,
)


def collective_overlap_report(text: str, buckets) -> Dict:
    """Verify the bucket-pipelined ZeRO-2 structure in compiled HLO: no
    bucket's gradient collective may data-depend on another bucket's update
    output — that is the dependence that would serialize communication
    behind compute and defeat the latency-hiding scheduler.

    ``buckets``: iterable of ``(key, d_in, d_out)`` (e.g. from
    ``BucketPlan.buckets``).  Ops are classified by opcode + result shape:

    * *gradient collectives* — ``reduce-scatter`` / ``all-to-all`` ops
      (sync or ``-start`` async form; int8 a2a included).  A rank-3 result
      whose trailing dims match a bucket is attributed to it; int8/flat
      operands stay unattributed but are still checked.
    * *update outputs* — ``all-gather`` ops whose result trailing dims
      match a bucket (the updated-weight gather of
      ``bucket_update_apply_sharded``).  Flat bf16 gathers (the rest-leaf
      compressed-mean stage) don't match and are ignored.

    A *serialization edge* is (update-gather U, collective C) with U a
    transitive ancestor of C.  Ancestry is computed over operand edges in
    every computation, flowing through ``fusion`` / ``call`` / ``while`` /
    ``conditional`` ops into their called computations (conservative: any
    op inside a called computation is an ancestor of the caller's result).

    Returns ``{"collectives": [...], "update_gathers": [...],
    "serialization_edges": [(u, c, bucket_u, bucket_c), ...],
    "n_serialization_edges": int}``.
    """
    comps, _entry = H.parse_module(text)
    by_shape = {}
    for b in buckets:
        key, d_in, d_out = b[0], int(b[1]), int(b[2])
        by_shape[(d_in, d_out)] = key

    def bucket_of(type_str: str) -> Optional[str]:
        dims = H.first_shape_dims(type_str)
        if len(dims) >= 2:
            return by_shape.get((dims[-2], dims[-1]))
        return None

    # index ops, classify
    collectives, gathers = [], []
    for comp in comps.values():
        for op in comp.ops:
            base = op.opcode[:-6] if op.opcode.endswith("-start") else op.opcode
            if op.opcode.endswith("-done"):
                continue
            if base in ("reduce-scatter", "all-to-all"):
                collectives.append((comp.name, op, bucket_of(op.type_str)))
            elif base == "all-gather":
                bk = bucket_of(op.type_str)
                if bk is not None:
                    gathers.append((comp.name, op, bk))

    consumers = H.build_consumer_graph(comps)
    coll_ids = {(cname, op.name): (op.name, bk)
                for cname, op, bk in collectives}
    edges = []
    for cname, op, bk in gathers:  # BFS descendants of each update gather
        for node in H.reachable_from((cname, op.name), consumers):
            hit = coll_ids.get(node)
            if hit is not None and node != (cname, op.name):
                edges.append((op.name, hit[0], bk, hit[1]))
    return {
        "collectives": [
            {"name": op.name, "opcode": op.opcode, "bucket": bk,
             "computation": cname} for cname, op, bk in collectives],
        "update_gathers": [
            {"name": op.name, "opcode": op.opcode, "bucket": bk,
             "computation": cname} for cname, op, bk in gathers],
        "serialization_edges": edges,
        "n_serialization_edges": len(edges),
    }


@register_pass
class OverlapPass(AnalysisPass):
    name = "overlap"
    description = ("no update-output -> gradient-collective serialization "
                   "edge in the compiled ZeRO-2 step")
    scope = "combo"

    def run(self, artifacts: Artifacts) -> List[Finding]:
        out = artifacts.parse_findings(self.name)
        buckets = [(b.key, b.d_in, b.d_out) for b in artifacts.buckets]
        if not buckets:
            out.append(Finding(
                pass_name=self.name, severity=Severity.INFO,
                code="no-buckets",
                message="no matrix buckets in the plan; nothing to check",
                combo=artifacts.combo.id))
            return out
        rep = collective_overlap_report(artifacts.hlo_text, buckets)
        if not rep["update_gathers"]:
            # a ZeRO-2 combo with buckets MUST gather updated weights; the
            # classifier finding nothing means shapes drifted under it
            out.append(Finding(
                pass_name=self.name, severity=Severity.ERROR,
                code="no-update-gathers",
                message=("ZeRO-2 step compiled with no bucket-shaped "
                         "updated-weight all-gather — either weights are "
                         "not being gathered or the shape classifier no "
                         "longer matches the plan"),
                combo=artifacts.combo.id))
        for u, c, bk_u, bk_c in rep["serialization_edges"]:
            out.append(Finding(
                pass_name=self.name, severity=Severity.ERROR,
                code="serialization-edge",
                message=(f"update gather %{u} (bucket {bk_u}) is a "
                         f"transitive ancestor of gradient collective "
                         f"%{c} (bucket {bk_c}) — the bucket chains are "
                         f"serialized and the scheduler cannot overlap "
                         f"them"),
                combo=artifacts.combo.id, location=f"%{u} -> %{c}"))
        out.append(Finding(
            pass_name=self.name, severity=Severity.INFO, code="summary",
            message=(f"{len(rep['collectives'])} gradient collectives, "
                     f"{len(rep['update_gathers'])} update gathers, "
                     f"{rep['n_serialization_edges']} serialization edges"),
            combo=artifacts.combo.id))
        return out
