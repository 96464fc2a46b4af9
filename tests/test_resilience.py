"""Numerical-resilience layer: fault parsing, the anomaly escalation
ladder, last-known-good checkpoint semantics, clip-disable, and the
single-device in-graph guard.

The distributed half of the proof — NaN/Inf/bit-flip faults injected into
the real pipelined ZeRO-2 step on the 4-way mesh, held bitwise equal to a
clean run on every surviving step, plus the launch-driver rewind ladder —
lives in ``tests/_zero_shard_worker.py guard``; a quick slice runs here
behind a subprocess (CI runs the full matrix in its own step).
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointManager
from repro.core.mixed import clip_by_global_norm
from repro.distributed.monitor import AnomalyMonitor
from repro.train import faults


class TestFaultSpec:
    def test_parse_forms(self):
        s = faults.parse_fault("nan:embed/tokens:3")
        assert (s.kind, s.leaf, s.step) == ("nan", "embed/tokens", 3)
        assert s.microbatch == -1 and not s.sticky

        s = faults.parse_fault("inf:*:7:2")
        assert (s.kind, s.leaf, s.step, s.microbatch) == ("inf", "*", 7, 2)

        s = faults.parse_fault("nan:*:6+")
        assert s.sticky and s.step == 6

        s = faults.parse_fault("bitflip:768x768:2")
        assert s.kind == "bitflip" and s.leaf == "768x768"
        assert "768x768" in s.describe()

    def test_parse_rejects_garbage(self):
        for bad in ("nan", "nan:*", "frob:*:3", "nan:*:x",
                    "bitflip:k:2:1"):
            with pytest.raises(ValueError):
                faults.parse_fault(bad)

    def test_unknown_leaf_names_available_paths(self):
        spec = faults.parse_fault("nan:no/such/leaf:0")
        grads = {"a": {"w": jnp.ones((2, 2))}}
        with pytest.raises(ValueError, match="a/w"):
            faults.apply_grad_fault(spec, grads, jnp.int32(0))

    def test_grad_fault_fires_only_at_step(self):
        spec = faults.parse_fault("nan:a/w:2")
        grads = {"a": {"w": jnp.ones((2, 2))}}
        clean = faults.apply_grad_fault(spec, grads, jnp.int32(1))
        np.testing.assert_array_equal(np.asarray(clean["a"]["w"]),
                                      np.ones((2, 2)))
        hit = faults.apply_grad_fault(spec, grads, jnp.int32(2))
        assert np.isnan(np.asarray(hit["a"]["w"])[0, 0])
        late = faults.apply_grad_fault(spec, grads, jnp.int32(3))
        assert not np.isnan(np.asarray(late["a"]["w"])).any()

    def test_sticky_fault_keeps_firing(self):
        spec = faults.parse_fault("inf:a/w:2+")
        grads = {"a": {"w": jnp.ones((2, 2))}}
        for t in (2, 5, 9):
            hit = faults.apply_grad_fault(spec, grads, jnp.int32(t))
            assert np.isinf(np.asarray(hit["a"]["w"])[0, 0]), t

    def test_none_fault_is_identity(self):
        grads = {"a": {"w": jnp.ones((2, 2))}}
        assert faults.apply_grad_fault(None, grads, jnp.int32(0)) is grads
        assert faults.wire_fault_for(None, "k", jnp.int32(0), "data") is None


class TestAnomalyMonitor:
    def test_skip_budget_escalates_to_rewind(self):
        mon = AnomalyMonitor(skip_budget=2, rewind_budget=2,
                             leaf_names=["embed/w", "blk/w"])
        assert mon.record(0, 2.0) == "ok"
        assert mon.record(1, float("nan"), skipped=True,
                          flags=[0.0, 1.0]) == "skip"
        assert mon.record(2, 2.0, skipped=True) == "skip"
        assert mon.record(3, 2.0, skipped=True,
                          flags=[1.0, 0.0]) == "rewind"
        assert mon.rewinds == 1
        assert mon.skips[0]["leaves"] == ["embed/w"]
        # the abort message names the last offending step and its leaves
        assert "step 3" in mon.post_mortem()
        assert "blk/w" in mon.post_mortem()

    def test_healthy_step_resets_skip_budget(self):
        mon = AnomalyMonitor(skip_budget=2)
        mon.record(0, 2.0)
        assert mon.record(1, 2.0, skipped=True) == "skip"
        assert mon.record(2, 2.0, skipped=True) == "skip"
        assert mon.record(3, 2.0) == "ok"
        assert mon.record(4, 2.0, skipped=True) == "skip"
        assert mon.consecutive_skips == 1

    def test_nonfinite_loss_counts_as_skip(self):
        mon = AnomalyMonitor(skip_budget=1)
        mon.record(0, 2.0)
        assert mon.record(1, float("inf")) == "skip"
        assert mon.record(2, float("nan")) == "rewind"

    def test_finite_spike_escalates_directly(self):
        mon = AnomalyMonitor(warmup_steps=4, abs_factor=3.0)
        for t in range(8):
            assert mon.record(t, 2.0 + 0.01 * t) == "ok"
        # a 10x finite spike: the poison is already applied, skip can't help
        assert mon.record(8, 20.0) == "rewind"
        assert mon.spikes and mon.spikes[-1]["step"] == 8

    def test_loss_drop_is_never_an_anomaly(self):
        mon = AnomalyMonitor(warmup_steps=2)
        for t in range(6):
            assert mon.record(t, 5.0) == "ok"
        assert mon.record(6, 0.01) == "ok"

    def test_rewind_budget_exhausted_aborts(self):
        mon = AnomalyMonitor(skip_budget=0, rewind_budget=1)
        mon.record(0, 2.0)
        assert mon.record(1, 2.0, skipped=True) == "rewind"
        assert mon.record(2, 2.0, skipped=True) == "abort"
        assert "2 rewinds" in mon.post_mortem()


class TestLastKnownGood:
    def test_mark_good_and_latest(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), async_save=False)
        state = {"w": jnp.ones((2,))}
        mgr.save(1, state)
        mgr.save(2, state)
        assert mgr.latest_good_step() is None
        mgr.mark_good(1)
        assert mgr.good_steps() == [1]
        assert mgr.latest_good_step() == 1
        mgr.mark_good(2)
        assert mgr.latest_good_step() == 2

    def test_mark_good_uncommitted_raises(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), async_save=False)
        mgr.save(1, {"w": jnp.ones((2,))})
        with pytest.raises(ValueError, match="committed"):
            mgr.mark_good(9)

    def test_prune_never_drops_newest_good(self, tmp_path):
        """Three newer-but-unpromoted checkpoints must not push the rewind
        ladder's restore target out of the retention window."""
        mgr = CheckpointManager(str(tmp_path), keep=2, async_save=False)
        state = {"w": jnp.ones((2,))}
        mgr.save(2, state)
        mgr.mark_good(2)
        for s in (4, 6, 8):
            mgr.save(s, state)
        assert mgr._committed_steps() == [2, 6, 8]
        assert mgr.latest_good_step() == 2
        restored, step, _ = mgr.restore_latest(state)
        assert step == 8
        out, data_step = mgr.restore(2, state)
        np.testing.assert_array_equal(np.asarray(out["w"]), np.ones((2,)))


class TestClipDisable:
    def test_zero_clip_norm_is_bitwise_passthrough(self):
        g = {"w": jnp.asarray([[3.0, -4.0]]), "b": jnp.asarray([12.0])}
        out, stats = clip_by_global_norm(g, 0.0)
        # grads untouched — identical objects, not just equal values
        assert out["w"] is g["w"] and out["b"] is g["b"]
        # the norm is still measured (metrics keep reporting), clip is off
        np.testing.assert_allclose(float(stats.global_norm), 13.0)
        assert float(stats.clipped) == 0.0

    def test_negative_clip_norm_also_disables(self):
        g = {"w": jnp.full((4,), 100.0)}
        out, stats = clip_by_global_norm(g, -1.0)
        assert out["w"] is g["w"]
        assert float(stats.clipped) == 0.0
        assert float(stats.global_norm) == 200.0


class TestSingleDeviceGuard:
    def test_guarded_step_skips_bitwise(self):
        """The replicated-path guard: a NaN gradient leaf at step 1 leaves
        params AND optimizer state bitwise frozen, flags name the leaf in
        tree order, and the next healthy step proceeds from the preserved
        state exactly as if the bad step never ran."""
        from repro.configs import get_config
        from repro.core import mixed_optimizer, constant
        from repro.core.types import tree_paths
        from repro.models import init_params
        from repro.train.step import make_train_step

        cfg = get_config("gpt2-60m").reduced()
        params = init_params(cfg, jax.random.PRNGKey(0))
        opt = mixed_optimizer("rmnp", constant(1e-2), constant(1e-2),
                              fused=True)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                  cfg.vocab)
        batch = {"tokens": toks, "labels": toks}
        fault = faults.parse_fault("nan:*:1")
        guarded = jax.jit(make_train_step(cfg, opt, remat="none",
                                          guard=True, fault=fault))
        clean = jax.jit(make_train_step(cfg, opt, remat="none"))

        p_g, s_g = params, opt.init(params)
        p_c, s_c = params, opt.init(params)
        for t in range(3):
            p_g, s_g, m = guarded(p_g, s_g, batch, jnp.int32(t))
            assert float(m["skipped"]) == (1.0 if t == 1 else 0.0), t
            if t != 1:  # the clean run never sees the poisoned step
                p_c, s_c, _ = clean(p_c, s_c, batch, jnp.int32(t))
        target = [p for p, _ in tree_paths(params)][0]
        flags = np.asarray(m["guard_flags"])  # from the last (healthy) step
        assert flags.min() == 1.0
        for (k, a), (_, b) in zip(tree_paths(p_g), tree_paths(p_c),
                                  strict=True):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=f"params {k}")
        for (k, a), (_, b) in zip(tree_paths(s_g), tree_paths(s_c),
                                  strict=True):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=f"opt state {k}")

    def test_guard_flags_name_the_leaf(self):
        from repro.core.types import tree_paths
        from repro.train import pipeline

        grads = {"a": {"w": jnp.ones((2, 2))},
                 "b": {"w": jnp.asarray([[jnp.nan, 1.0]])}}
        info = pipeline.finite_guard(grads)
        assert not bool(info.ok)
        assert np.asarray(info.flags).tolist() == [True, False]
        assert [p for p, _ in tree_paths(grads)] == ["a/w", "b/w"]


# ---------------------------------------------------------------------------
# quick distributed slice (full fault-injection matrix runs in CI's step)
# ---------------------------------------------------------------------------

@pytest.mark.skipif(os.environ.get("CI") == "true",
                    reason="CI runs the full guard scenario in its own step")
def test_guard_fault_injection_quick():
    root = Path(__file__).resolve().parents[1]
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [str(root / "src"), os.environ.get("PYTHONPATH", "")]
               ).rstrip(os.pathsep))
    r = subprocess.run(
        [sys.executable, str(root / "tests" / "_zero_shard_worker.py"),
         "guard", "--quick"],
        env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert r.stdout.rstrip().endswith("GUARD_OK"), r.stdout


@pytest.mark.skipif(os.environ.get("CI") == "true",
                    reason="CI runs the full ckpt corruption sweep in its "
                           "own step")
def test_ckpt_fault_injection_quick():
    root = Path(__file__).resolve().parents[1]
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [str(root / "src"), os.environ.get("PYTHONPATH", "")]
               ).rstrip(os.pathsep))
    r = subprocess.run(
        [sys.executable, str(root / "tests" / "_zero_shard_worker.py"),
         "ckpt", "--quick"],
        env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert r.stdout.rstrip().endswith("CKPT_OK"), r.stdout
