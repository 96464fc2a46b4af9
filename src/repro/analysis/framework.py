"""The pass framework: combos, per-combo artifacts, and the registry.

A *combo* is one point of the optimizer x wire x accum matrix, lowered
through the bucketed engine's ZeRO-2 path.
The lowering harness (:mod:`repro.analysis.lowering`) turns a combo into
:class:`Artifacts` — the traced jaxpr and AOT-compiled HLO of the real
``make_dp_train_step`` program, plus the static metadata the passes need
(bucket/slot-stripe shapes, expected donations) — WITHOUT ever executing
a step.  Each registered :class:`AnalysisPass` then inspects the
artifacts and returns :class:`Finding` objects.

Two scopes: ``combo`` passes run once per lowered combination; ``repo``
passes (the AST convention lint) run once per invocation with no
artifacts.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from repro.analysis import hlo as hlo_mod
from repro.analysis.findings import Finding, Severity

WIRES = ("fp32", "int8-ef")


@dataclasses.dataclass(frozen=True)
class Combo:
    """One optimizer x wire x accum point, lowered through the bucketed
    engine's ZeRO-2 path (``update_apply_sharded`` under ``shard_map``),
    where every memory/sharding/overlap invariant must hold."""
    optimizer: str
    wire: str              # "fp32" | "int8-ef"
    accum: int = 1
    guard: bool = False    # in-graph non-finite guard + bitwise step skip

    def __post_init__(self):
        if self.wire not in WIRES:
            raise ValueError(f"wire must be one of {WIRES}, "
                             f"got {self.wire!r}")
        if self.accum < 1:
            raise ValueError(f"accum must be >= 1, got {self.accum}")

    @property
    def compress(self) -> bool:
        return self.wire == "int8-ef"

    @property
    def id(self) -> str:
        base = f"{self.optimizer}/{self.wire}/accum{self.accum}"
        return base + "/guard" if self.guard else base


class BucketMeta:
    """Static per-bucket state metadata (from
    ``BucketedEngine.state_meta``): the stacked full shapes whose fp32
    materialization / all-gather the passes police."""

    def __init__(self, key: str, d_in: int, d_out: int, size: int,
                 padded: int, momentum_dtype,
                 slot_shapes: Dict[str, Tuple[Tuple[int, ...], object]],
                 leaf_shapes: Sequence[Tuple[int, ...]] = ()):
        self.key = key
        self.d_in = d_in
        self.d_out = d_out
        self.size = size
        self.padded = padded
        self.momentum_dtype = momentum_dtype
        self.slot_shapes = dict(slot_shapes)   # name -> (full shape, dtype)
        self.leaf_shapes = tuple(leaf_shapes)  # planned leaves' full shapes

    @property
    def full_shape(self) -> Tuple[int, int, int]:
        return (self.padded, self.d_in, self.d_out)

    def __repr__(self):
        return (f"BucketMeta({self.key!r}, padded={self.padded}, "
                f"slots={sorted(self.slot_shapes)})")


@dataclasses.dataclass
class DonatedLeaf:
    """One pytree leaf the step donates: its flat HLO entry parameter
    number plus enough identity to name it in a finding."""
    param_number: int
    path: str
    shape: Tuple[int, ...]
    dtype: str


@dataclasses.dataclass
class Artifacts:
    """Everything the combo-scope passes may consume.  ``jaxpr`` is the
    closed jaxpr of the jitted step; ``hlo_text`` the post-optimization
    HLO of its AOT compile; ``buckets`` the optimizer's bucket/slot
    metadata; ``donated`` the leaves the step donates."""
    combo: Combo
    jaxpr: object = None
    hlo_text: str = ""
    buckets: Tuple[BucketMeta, ...] = ()
    donated: Tuple[DonatedLeaf, ...] = ()
    n_dev: int = 4
    overlap: bool = False        # pipelined schedule requested
    _parsed: Optional[hlo_mod.ParsedModule] = None

    @property
    def parsed(self) -> hlo_mod.ParsedModule:
        if self._parsed is None:
            self._parsed = hlo_mod.parse_module_checked(self.hlo_text)
        return self._parsed

    def parse_findings(self, pass_name: str) -> List[Finding]:
        """The parser's issues as WARNING findings (shared by every
        HLO-level pass; deduplicated by the runner)."""
        return [Finding(pass_name=pass_name, severity=Severity.WARNING,
                        code=f"hlo-parse-{i.code}", message=i.message,
                        combo=self.combo.id, location=i.where)
                for i in self.parsed.issues]


class AnalysisPass:
    """Base checker.  Subclasses set ``name``/``description``/``scope``
    and implement ``run``."""

    name = "base"
    description = ""
    scope = "combo"            # "combo" | "repo"

    def run(self, artifacts: Optional[Artifacts]) -> List[Finding]:
        raise NotImplementedError


_REGISTRY: Dict[str, Callable[[], AnalysisPass]] = {}


def register_pass(cls):
    _REGISTRY[cls.name] = cls
    return cls


def registered_passes() -> Dict[str, Callable[[], AnalysisPass]]:
    """name -> pass class, import-complete (importing the pass modules
    here keeps registration a side-effect-free one-liner per module)."""
    from repro.analysis import (  # noqa: F401
        conventions, donation, kernel_lint, memory, overlap, sharding,
    )
    return dict(_REGISTRY)


def pass_catalog() -> List[Dict[str, str]]:
    return [{"name": name, "scope": cls.scope,
             "description": cls.description}
            for name, cls in sorted(registered_passes().items())]


def run_passes(artifacts_list: Sequence[Artifacts],
               only: Optional[Sequence[str]] = None) -> List[Finding]:
    """Run every registered pass over every combo's artifacts (repo-scope
    passes once), deduplicating the shared parse findings."""
    passes = registered_passes()
    names = list(only) if only else sorted(passes)
    unknown = [n for n in names if n not in passes]
    if unknown:
        raise ValueError(f"unknown pass(es) {unknown}; registered: "
                         f"{sorted(passes)}")
    findings: List[Finding] = []
    seen_parse = set()
    for name in names:
        p = passes[name]()
        if p.scope == "repo":
            findings.extend(p.run(None))
            continue
        for art in artifacts_list:
            for f in p.run(art):
                key = (f.code, f.combo, f.location)
                if f.code.startswith("hlo-parse-"):
                    if key in seen_parse:
                        continue
                    seen_parse.add(key)
                findings.append(f)
    return findings
