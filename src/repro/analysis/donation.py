"""Donation pass: donated buffers must really alias input to output.

``launch/train.py`` jits the step with ``donate_argnums=(0, 1)`` —
params and optimizer state are donated so the updated trees reuse the
same HBM.  Donation is only a *hint*: XLA records honored donations in
the module-level ``input_output_alias`` table, and a dropped one (shape
mismatch after a refactor, a consumer added after the update, a dtype
change) silently doubles the memory for that buffer.  This pass checks
every donated leaf against the compiled alias table and flags defensive
``copy`` ops of aliased parameters.

Small leaves (scalars, tiny norms) that XLA declines to alias are
surfaced as WARNINGs; a dropped alias on a big buffer (>= 1 MiB — a
bucket, a momentum shard, an embedding) is an ERROR.
"""
from __future__ import annotations

import re
from typing import Dict, List

from repro.analysis import hlo as H
from repro.analysis.findings import Finding, Severity
from repro.analysis.framework import (
    AnalysisPass, Artifacts, register_pass,
)

BIG_LEAF_BYTES = 1 << 20

_PARAM_NUM_RE = re.compile(r"parameter\((\d+)\)")


def _leaf_bytes(shape, dtype: str) -> int:
    n = 1
    for d in shape:
        n *= d
    itemsize = {"float32": 4, "float64": 8, "bfloat16": 2, "float16": 2,
                "int32": 4, "uint32": 4, "int64": 8, "int8": 1,
                "uint8": 1, "bool": 1}.get(str(dtype), 4)
    return n * itemsize


def entry_param_ops(text: str) -> Dict[int, str]:
    """Map flat entry parameter number -> op name in the ENTRY computation."""
    comps, entry = H.parse_module(text)
    out: Dict[int, str] = {}
    comp = comps.get(entry or "")
    if comp is None:
        return out
    for op in comp.ops:
        if op.opcode == "parameter":
            m = _PARAM_NUM_RE.search(op.raw)
            if m:
                out[int(m.group(1))] = op.name
    return out


def copied_params(text: str) -> Dict[int, List[str]]:
    """Parameter number -> names of ENTRY ``copy`` ops reading it directly
    (the defensive-copy signature of a degraded donation)."""
    comps, entry = H.parse_module(text)
    comp = comps.get(entry or "")
    if comp is None:
        return {}
    by_name = {name: num for num, name in entry_param_ops(text).items()}
    out: Dict[int, List[str]] = {}
    for op in comp.ops:
        if op.opcode == "copy" and op.operands:
            num = by_name.get(op.operands[0])
            if num is not None:
                out.setdefault(num, []).append(op.name)
    return out


def state_copies(text: str) -> int:
    """Number of ENTRY ``copy`` ops that move a donated parameter (one in
    the ``input_output_alias`` table) of at least ``BIG_LEAF_BYTES`` aside
    unchanged: the copy's type, layout and memory space included, is the
    parameter's own.  Such a copy exists because a consumer cannot write its
    output over the donated input in place; a copy to another layout or
    memory space (a transpose, a prefetch) is work of its own and is not
    counted."""
    comps, entry = H.parse_module(text)
    comp = comps.get(entry or "")
    if comp is None:
        return 0
    names = entry_param_ops(text)
    donated = {a.param_number for a in H.module_io_aliases(text)}
    ops = {op.name: op for op in comp.ops}
    return sum(
        1 for num, copies in copied_params(text).items() if num in donated
        for name in copies
        if ops[name].type_str == comp.symtab[names[num]]
        and H.shape_bytes(ops[name].type_str) >= BIG_LEAF_BYTES)


@register_pass
class DonationPass(AnalysisPass):
    name = "donation"
    description = ("every donated leaf appears in the compiled "
                   "input_output_alias table (no silent un-donation)")
    scope = "combo"

    def run(self, artifacts: Artifacts) -> List[Finding]:
        out = artifacts.parse_findings(self.name)
        combo = artifacts.combo
        if not artifacts.donated:
            out.append(Finding(
                pass_name=self.name, severity=Severity.WARNING,
                code="no-donations",
                message="combo lowered with no donated leaves recorded; "
                        "donation pass has nothing to verify",
                combo=combo.id))
            return out
        aliases = H.module_io_aliases(artifacts.hlo_text)
        aliased_params = {a.param_number for a in aliases}
        if not aliases:
            out.append(Finding(
                pass_name=self.name, severity=Severity.ERROR,
                code="no-alias-table",
                message=(f"{len(artifacts.donated)} leaves were donated "
                         f"but the compiled module has no "
                         f"input_output_alias table at all — donation "
                         f"is being dropped wholesale"),
                combo=combo.id))
            return out
        copies = copied_params(artifacts.hlo_text)
        for leaf in artifacts.donated:
            nbytes = _leaf_bytes(leaf.shape, leaf.dtype)
            if leaf.param_number not in aliased_params:
                sev = (Severity.ERROR if nbytes >= BIG_LEAF_BYTES
                       else Severity.WARNING)
                out.append(Finding(
                    pass_name=self.name, severity=sev,
                    code="donation-dropped",
                    message=(f"donated leaf {leaf.path} "
                             f"({tuple(leaf.shape)} {leaf.dtype}, "
                             f"{nbytes / 2**20:.2f} MiB) has no "
                             f"input_output_alias entry — XLA kept a "
                             f"second live copy"),
                    combo=combo.id, location=leaf.path))
            elif (leaf.param_number in copies
                  and nbytes >= BIG_LEAF_BYTES):
                names = ", ".join(f"%{n}" for n in copies[leaf.param_number])
                out.append(Finding(
                    pass_name=self.name, severity=Severity.WARNING,
                    code="defensive-copy",
                    message=(f"donated leaf {leaf.path} aliases but is "
                             f"also defensively copied ({names}) — the "
                             f"alias saves nothing for that use"),
                    combo=combo.id, location=leaf.path))
        donated_nums = {d.param_number for d in artifacts.donated}
        out.append(Finding(
            pass_name=self.name, severity=Severity.INFO, code="summary",
            message=(f"{len(aliases)} alias entries cover "
                     f"{len(aliased_params & donated_nums)}"
                     f"/{len(artifacts.donated)} donated leaves"),
            combo=combo.id))
        return out
