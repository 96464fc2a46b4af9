"""Production mesh construction.

Defined as a FUNCTION so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax initialization).

Every mesh here has ``Auto`` axes: the logical-sharding rules
(``distributed/sharding.py``) and the ``shard_map`` steps rely on XLA
propagating shardings, which ``jax.make_mesh``'s default ``Explicit`` axes
turn into sharding-in-types errors.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes, devices=None):
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes),
                         devices=devices)


def make_data_mesh(n: int):
    """1-D ``("data",)`` mesh over the first ``n`` visible devices — the
    mesh of the data-parallel / ZeRO steps."""
    return _auto_mesh((n,), ("data",), devices=jax.devices()[:n])


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1,
                    devices: Optional[Sequence] = None):
    """Small mesh over ``devices`` (default: whatever devices exist)."""
    devices = list(devices) if devices is not None else jax.devices()
    n = len(devices)
    data = min(data, n)
    model = min(model, max(1, n // data))
    return _auto_mesh((data, model), ("data", "model"),
                      devices=devices[:data * model])


# TPU v5e hardware constants used by the roofline analysis
PEAK_FLOPS_BF16 = 197e12     # per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link
