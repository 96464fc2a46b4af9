"""The comparison that decides ``correct``: the program's set-up training
steps (``harness.SETUP_STEPS``) against the plain reference's.

Three numbers, each held to a limit from the cell's file:

* ``loss_rel``: the widest relative gap between the program's loss and the
  reference's over the compared steps;
* ``moment_gap``: for every matrix or vector the optimizer treats on its
  own, the gap between the norm of the program's momentum after the first
  step (the clipped first gradient as the optimizer holds it) and the
  reference's, over the larger of the reference's norm of that slice and
  of the median slice; the worst slice;
* ``change_gap``: the same for the norm of each slice's weight change
  over the compared steps.  Slices whose first gradient in the reference
  is under a thousandth of the median slice's are left out: such a slice
  moves by round-off alone.
"""
from __future__ import annotations

import math
import statistics
from typing import Dict, Tuple

NEGLIGIBLE_GRADIENT = 1e-3   # of the median slice's first-gradient norm


def loss_rel(program, reference) -> float:
    if len(program) != len(reference):
        return math.inf
    gaps = [abs(a - b) / abs(b) for a, b in zip(program, reference,
                                                 strict=True)]
    return max(gaps) if all(map(math.isfinite, gaps)) else math.inf


def worst_gap(program: Dict[str, float], reference: Dict[str, float],
              keep=None) -> Tuple[float, str]:
    """``(gap, slice)`` of the worst slice; a slice missing on either side
    or not finite reads infinite."""
    if set(program) != set(reference):
        missing = sorted(set(program) ^ set(reference))
        return math.inf, f"slices differ: {missing[:4]}"
    med = statistics.median(reference.values())
    worst, where = 0.0, ""
    for k, ref in reference.items():
        if keep is not None and k not in keep:
            continue
        gap = abs(program[k] - ref) / max(ref, med)
        if not math.isfinite(gap):
            return math.inf, k
        if gap > worst:
            worst, where = gap, k
    return worst, where


def moving_slices(reference_moment: Dict[str, float]) -> set:
    """The slices whose reference first gradient is not negligible."""
    med = statistics.median(reference_moment.values())
    return {k for k, v in reference_moment.items()
            if v >= NEGLIGIBLE_GRADIENT * med}


def compare(program: Dict, reference: Dict, limits: Dict[str, float]):
    """``(correct, numbers)``: every number with its limit and the slice
    it was read on.  A number the cell's ``limits`` do not name is read and
    reported with the limit ``None`` but decides nothing: a cell leaves one
    out where neither the control nor a fault separates it from sound
    runs."""
    moment, where_m = worst_gap(program["moment"], reference["moment"])
    change, where_c = worst_gap(program["change"], reference["change"],
                                keep=moving_slices(reference["moment"]))
    numbers = {
        "loss_rel": {"value": loss_rel(program["loss"], reference["loss"])},
        "moment_gap": {"value": moment, "at": where_m},
        "change_gap": {"value": change, "at": where_c},
    }
    for k, n in numbers.items():
        n["limit"] = limits.get(k)
    ok = all(n["value"] <= n["limit"] for n in numbers.values()
             if n["limit"] is not None)
    return ok, numbers
