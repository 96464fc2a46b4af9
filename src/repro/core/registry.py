"""Constructor registry: one entry point for every optimizer family member.

``make_optimizer(name, config)`` builds every optimizer the launchers,
benchmarks and examples run: the name is any registered matrix update rule
(core/rules.py — rmnp, muon, normuon, muown, nora) or ``adamw``, and the
config is a plain dict of ``mixed_optimizer`` keyword arguments plus the
two learning rates (floats are wrapped in a constant schedule; callables
pass through as schedules).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

from repro.core.mixed import mixed_optimizer
from repro.core.rules import rule_names
from repro.core.schedule import constant
from repro.core.types import Optimizer


def optimizer_names() -> Tuple[str, ...]:
    """Every name ``make_optimizer`` accepts: the matrix update rules plus
    the everything-through-AdamW baseline."""
    return rule_names() + ("adamw",)


def _as_schedule(lr):
    return lr if callable(lr) else constant(float(lr))


def make_optimizer(name: str, config: Optional[Dict[str, Any]] = None,
                   **overrides) -> Optimizer:
    """Build a mixed optimizer by registry name.

    ``config`` (optionally updated by keyword ``overrides``) holds
    ``lr_matrix`` (required; float or schedule), ``lr_adamw`` (defaults to
    ``lr_matrix``), and any further ``mixed_optimizer`` keyword argument
    (``fused``, ``shard_axis``, ``shard_size``, ``use_kernel``,
    ``momentum_dtype``, ``beta``, ``weight_decay``, ...).
    Unknown names raise the rule registry's ValueError listing what is
    registered."""
    if name not in optimizer_names():
        raise ValueError(
            f"unknown optimizer {name!r}; registered: "
            f"{', '.join(optimizer_names())}")
    cfg = dict(config or {})
    cfg.update(overrides)
    if "lr_matrix" not in cfg:
        raise ValueError("make_optimizer config needs 'lr_matrix' "
                         "(float or schedule)")
    lr_matrix = _as_schedule(cfg.pop("lr_matrix"))
    lr_adamw = _as_schedule(cfg.pop("lr_adamw", lr_matrix))
    return mixed_optimizer(name, lr_matrix, lr_adamw, **cfg)
