"""CLI: lower every combo, run every pass, emit ANALYSIS_report.json.

Run as ``python -m repro.analysis.check --all`` (CI does, after tier-1).
Exit status is 1 iff any ERROR finding survives the allowlist.

The environment block below runs before jax is imported anywhere (the
``repro`` package itself imports no jax): lowering needs a 4-device CPU
topology, and forcing the CPU platform keeps the checker deterministic on
accelerator hosts.
"""
from __future__ import annotations

import os

_FLAG = "--xla_force_host_platform_device_count=4"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " " + _FLAG).strip()
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
from typing import List  # noqa: E402

from repro.analysis.findings import (  # noqa: E402
    Severity, apply_allowlist, load_allowlist, report_dict,
)
from repro.analysis.framework import pass_catalog, run_passes  # noqa: E402


def _parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m repro.analysis.check",
        description="Static (lower-only) invariant checks over the "
                    "optimizer x wire matrix.")
    p.add_argument("--all", action="store_true",
                   help="check the full combo matrix (default when no "
                        "filter is given)")
    p.add_argument("--optimizer", action="append", default=None,
                   help="restrict to an optimizer (repeatable)")
    p.add_argument("--wire", action="append", default=None,
                   choices=["fp32", "int8-ef"],
                   help="restrict to a wire format (repeatable)")
    p.add_argument("--accum", action="append", type=int, default=None,
                   help="restrict to an accumulation factor (repeatable)")
    p.add_argument("--pass", dest="passes", action="append", default=None,
                   help="run only this pass (repeatable)")
    p.add_argument("--report", default="ANALYSIS_report.json",
                   help="report path (default: %(default)s)")
    p.add_argument("--allowlist", default=None,
                   help="JSON allowlist of findings to downgrade")
    p.add_argument("--list", action="store_true",
                   help="list passes and the selected combos, then exit")
    return p


def main(argv: List[str] | None = None) -> int:
    args = _parser().parse_args(argv)

    from repro.analysis import lowering

    combos = lowering.build_combos(
        optimizers=args.optimizer,
        wires=args.wire, accums=args.accum)
    catalog = pass_catalog()
    catalog_names = [entry["name"] for entry in catalog]
    if args.passes:
        unknown = set(args.passes) - set(catalog_names)
        if unknown:
            print(f"unknown pass(es): {', '.join(sorted(unknown))}; "
                  f"available: {', '.join(catalog_names)}",
                  file=sys.stderr)
            return 2

    if args.list:
        print("passes:")
        for entry in catalog:
            print(f"  {entry['name']:<12} ({entry['scope']}) "
                  f"{entry['description']}")
        print(f"combos ({len(combos)}):")
        for c in combos:
            print(f"  {c.id}")
        return 0

    artifacts = []
    for i, combo in enumerate(combos):
        t0 = time.monotonic()
        print(f"[{i + 1}/{len(combos)}] lowering {combo.id} ...",
              file=sys.stderr, flush=True)
        artifacts.append(lowering.lower_combo(combo))
        print(f"    done in {time.monotonic() - t0:.1f}s",
              file=sys.stderr, flush=True)

    findings = run_passes(artifacts, only=args.passes)
    if args.allowlist:
        findings = apply_allowlist(findings, load_allowlist(args.allowlist))

    pass_names = args.passes or catalog_names
    report = report_dict(findings, [c.id for c in combos], pass_names)
    with open(args.report, "w", encoding="utf-8") as f:
        json.dump(report, f, indent=2)
        f.write("\n")

    counts = report["counts"]
    for sev in (Severity.ERROR, Severity.WARNING):
        for fd in findings:
            if fd.severity is sev:
                where = fd.combo or fd.location or "-"
                print(f"{sev.value.upper():<8} {fd.pass_name:<12} "
                      f"[{fd.code}] {where}: {fd.message}")
    print(f"\n{len(combos)} combos x {len(pass_names)} passes: "
          f"{counts.get('error', 0)} errors, "
          f"{counts.get('warning', 0)} warnings, "
          f"{counts.get('allowlisted', 0)} allowlisted, "
          f"{counts.get('info', 0)} info -> {args.report}")
    return 1 if counts.get("error", 0) else 0


if __name__ == "__main__":
    sys.exit(main())
