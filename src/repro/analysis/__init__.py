"""repro.analysis — compile-time invariant checking for the RMNP stack.

The systems claims this reproduction makes (no full-bucket fp32
intermediates, no silent replication of ZeRO-sharded state, donated
buffers really alias, zero update->collective serialization edges,
VMEM-safe kernel launches, repo conventions) are enforced as a standing
static analysis instead of ad-hoc per-PR checks:

* :mod:`repro.analysis.hlo` — the shared post-optimization-HLO parser
  (moved out of ``launch/hlo_cost.py``; hlo_cost and the overlap
  benchmark are now consumers), hardened to emit named parse findings
  instead of raising mid-analysis.
* :mod:`repro.analysis.framework` — pass framework: severity-ranked
  :class:`Finding`, the pass registry, and the per-combo runner.
* :mod:`repro.analysis.lowering` — lowers (never executes) every
  registry optimizer x wire x accum combination on an abstract
  4-device mesh via ``jax.eval_shape`` / AOT ``.lower()``.
* the passes — :mod:`memory`, :mod:`sharding`, :mod:`donation`,
  :mod:`overlap`, :mod:`kernel_lint`, :mod:`conventions`.
* ``python -m repro.analysis.check --all`` — the CI gate; writes a
  stable ``ANALYSIS_report.json``.
"""
from repro.analysis.findings import (  # noqa: F401
    Finding, Severity, load_allowlist, report_dict,
)
from repro.analysis.framework import (  # noqa: F401
    AnalysisPass, Artifacts, Combo, pass_catalog, registered_passes,
)
