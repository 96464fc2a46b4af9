"""Subprocess worker for the analysis-pass tests that need a 4-device
mesh (the env block must run before jax is imported, so this cannot live
in the pytest process).

Modes (argv[1]):

* ``sweep``  — lower every registry optimizer through the bucketed
  engine's ZeRO-2 path at fp32/accum1 and assert every pass is
  finding-free;
  prints ``ANALYSIS_SWEEP_OK``.
* ``broken`` — lower deliberately degraded rmnp variants and
  assert the passes catch them; prints ``ANALYSIS_BREAK_OK``.
"""
import os
import sys

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=4").strip()
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from repro.analysis import lowering  # noqa: E402
from repro.analysis.findings import Severity  # noqa: E402
from repro.analysis.framework import Combo, run_passes  # noqa: E402


def _gate(findings):
    return [f for f in findings if f.severity in (Severity.ERROR,
                                                  Severity.WARNING)]


def sweep():
    combos = lowering.build_combos(wires=["fp32"], accums=[1])
    arts = [lowering.lower_combo(c) for c in combos]
    bad = _gate(run_passes(arts))
    for f in bad:
        print(f"{f.severity.value} {f.pass_name} [{f.code}] "
              f"{f.combo or f.location}: {f.message}")
    assert not bad, f"{len(bad)} gate findings on the clean registry sweep"
    from repro.core import optimizer_names
    assert {c.optimizer for c in combos} == set(optimizer_names())
    print("ANALYSIS_SWEEP_OK")


def broken():
    combo = Combo("rmnp", "fp32", 1)

    art = lowering.lower_combo(combo, break_mode="gather-momentum")
    fs = run_passes([art], only=["sharding", "memory"])
    codes = {f.code for f in fs if f.severity is Severity.ERROR}
    assert "state-replicated" in codes, codes
    assert "full-bucket-fp32" in codes, codes

    art = lowering.lower_combo(combo, break_mode="drop-donation")
    fs = run_passes([art], only=["donation"])
    codes = {f.code for f in fs if f.severity is Severity.ERROR}
    assert codes == {"no-alias-table"}, codes

    # and the same combo lowered honestly is clean
    art = lowering.lower_combo(combo)
    bad = _gate(run_passes([art], only=["sharding", "memory", "donation"]))
    assert not bad, [f.code for f in bad]
    print("ANALYSIS_BREAK_OK")


if __name__ == "__main__":
    {"sweep": sweep, "broken": broken}[sys.argv[1]]()
