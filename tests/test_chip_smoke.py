"""The checks of ``chip_smoke.py`` at a tiny size on the CPU.

The kernel check runs the RMNP kernel in interpret mode here.  A sound
kernel passes it, and each planted fault in the single-pass kernel's
weight update or direction fails it.
"""
import importlib.util
from pathlib import Path

import jax.numpy as jnp
import pytest

from repro.kernels import ops

_spec = importlib.util.spec_from_file_location(
    "chip_smoke", Path(__file__).resolve().parents[1] / "chip_smoke.py")
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)

SHAPES = [(2, 256, 128)]
_apply = ops.rmnp_bucket_update_apply


def test_platform_check_names_the_platform():
    with pytest.raises(chip_smoke.SmokeFailure, match="found platform 'cpu'"):
        chip_smoke.tpu_devices(1)


def test_kernel_check_passes_sound_kernels():
    chip_smoke.phase_kernel_check(SHAPES, seed=0)


def _dropped(g, v, w, scale, wd, *, beta):
    return _apply(g, v, w, scale, wd, beta=beta)[0], w


def _halved(g, v, w, scale, wd, *, beta):
    return _apply(g, v, w, 0.5 * scale, wd, beta=beta)


def _sign_flipped(g, v, w, scale, wd, *, beta):
    return _apply(g, v, w, -scale, wd, beta=beta)


def _wd_ignored(g, v, w, scale, wd, *, beta):
    return _apply(g, v, w, scale, 0.0, beta=beta)


def _scale_ignored(g, v, w, scale, wd, *, beta):
    return _apply(g, v, w, 1.0, wd, beta=beta)


APPLY_FAULTS = {"dropped": _dropped, "halved": _halved,
                "sign_flipped": _sign_flipped, "wd_ignored": _wd_ignored,
                "scale_ignored": _scale_ignored}


@pytest.mark.parametrize("fault", list(APPLY_FAULTS))
def test_kernel_check_catches_planted_update_fault(monkeypatch, fault):
    monkeypatch.setattr(ops, "rmnp_bucket_update_apply", APPLY_FAULTS[fault])
    with pytest.raises(chip_smoke.SmokeFailure, match="kernel update"):
        chip_smoke.phase_kernel_check(SHAPES, seed=0)


def test_kernel_check_catches_unnormalized_direction(monkeypatch):
    def unnormalized(g, v, w, scale, wd, *, beta):
        v_new, _ = _apply(g, v, w, scale, wd, beta=beta)
        w32 = w.astype(jnp.float32)
        w_new = w32 + (-scale) * (v_new + wd * w32)
        return v_new, w_new.astype(w.dtype)
    monkeypatch.setattr(ops, "rmnp_bucket_update_apply", unnormalized)
    with pytest.raises(chip_smoke.SmokeFailure, match="kernel update"):
        chip_smoke.phase_kernel_check(SHAPES, seed=0)
