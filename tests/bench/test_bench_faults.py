"""The check that decides ``correct``, driven through a whole run at a CPU
size: the look for a chip is skipped, the program's timed path runs, and
a fault planted under it must read as not correct.  The float8 control
(the reference computed a precision step below the configuration's
bfloat16, in the program's place) must fail the same limits."""
import time
from pathlib import Path

import jax
import pytest

import repro.launch.train as launch
from bench import check, harness

CELLS = Path(__file__).resolve().parent / "cells"
CELL = "tiny.rmnp.b4s32"
SEED = 2147483701


@pytest.fixture
def tiny(monkeypatch):
    monkeypatch.setattr(harness, "BENCH", CELLS)
    return monkeypatch


def _run(seed=SEED):
    return harness.run(CELL, seed, 2.0, False, t_start=time.perf_counter(),
                       devices=jax.devices()[:1], log=lambda m: None)


def _wrap_step(monkeypatch, change):
    make = launch.make_train_step

    def wrapped(*a, **k):
        step = make(*a, **k)
        return lambda params, opt_state, batch, i: change(
            step, params, opt_state, batch, i)

    monkeypatch.setattr(launch, "make_train_step", wrapped)


def test_sound_run_is_correct(tiny):
    r = _run()
    assert r["correct"], r["checks"]
    assert r["attempted"] == 2 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {"tokens_per_s", "setup_s"}


def _state_unchanged(step, params, opt_state, batch, i):
    return (params, opt_state) + (step(params, opt_state, batch, i)[2],)


def _half_batch(step, params, opt_state, batch, i):
    return step(params, opt_state,
                {k: v[: v.shape[0] // 2] for k, v in batch.items()}, i)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_planted_fault_is_not_correct(tiny, fault):
    _wrap_step(tiny, fault)
    r = _run()
    assert not r["correct"], r["checks"]


def test_state_unchanged_reads_one(tiny):
    _wrap_step(tiny, _state_unchanged)
    checks = _run()["checks"]
    assert checks["moment_gap"]["value"] == pytest.approx(1.0)
    assert checks["change_gap"]["value"] == pytest.approx(1.0)


def test_float8_control_is_not_correct(tiny):
    cell = harness.load_cell(CELL)
    devs = jax.devices()[:1]
    ref = harness.reference_readings(cell, SEED, 5, devs)
    control = harness.reference_readings(cell, SEED, 5, devs,
                                         precision="fp8")
    ok, numbers = check.compare(control, ref, cell["limits"])
    assert not ok, numbers
