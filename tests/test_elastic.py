"""Elastic ZeRO-2 restart: the reshard transform, the layout manifest,
manager-side validation, and the hang/straggler -> checkpoint ladder.

The mesh-size dependence of a bucketed optimizer state lives entirely in
the padded bucket size (``ceil(L / N) * N``), so unpad-under-the-old-plan
/ repad-under-the-new-plan is an *exact* relayout — these tests hold it
bitwise for every registered rule, through a checkpoint-manager round
trip, and through a continued optimizer step.  Cross-mesh kill-and-resume
fault injection (real SIGKILL, subprocess meshes of 4 and 8 devices) lives
in ``tests/_zero_shard_worker.py elastic``; a quick slice runs here behind
the same subprocess guard as the other worker tests.
"""
import os
import subprocess
import sys
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointManager
from repro.core import bucketing, constant, make_optimizer, mixed_optimizer
from repro.core.rules import rule_names
from repro.distributed import compression, elastic
from repro.distributed.compression import init_compression_state
from repro.distributed.monitor import HangGuard

SHAPES = {**{f"l{i}/w": (2, 8, 16) for i in range(4)},
          "odd/w": (3, 8, 24),   # L=3: uneven and < 4 and < 8
          "six/w": (6, 16, 8)}   # L=6: uneven for both 4 and 8


def make(seed, shapes=None):
    shapes = shapes or SHAPES
    return {k: jax.random.normal(
        jax.random.fold_in(jax.random.PRNGKey(seed), i), s, jnp.float32)
        for i, (k, s) in enumerate(sorted(shapes.items()))}


def build_opt(rule, n):
    return make_optimizer(rule, lr_matrix=0.05, beta=0.9, ns_steps=2,
                          shard_axis="data", shard_size=n)


def warm_state(opt, params, steps=2):
    """A few real update_apply steps so momentum and slots are non-trivial
    (the replicated path works at any shard_size on one device)."""
    state = opt.init(params)
    step = jax.jit(opt.update_apply)
    for t in range(steps):
        params, state = step(make(10 + t), state, params, t)
    return params, state


def assert_tree_equal(a, b, msg=""):
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y), err_msg=msg), a, b)


# ---------------------------------------------------------------------------
# the reshard transform
# ---------------------------------------------------------------------------

class TestReshardTransform:
    @pytest.mark.parametrize("rule", rule_names())
    def test_every_rule_across_meshes(self, rule, tmp_path):
        """Checkpoint round-trip across mesh sizes for every registered
        rule: warm a shard_size=8 state, save it, restore-reshard to 4 via
        the manager, and hold (a) unpadded content bitwise, (b) pad slices
        zero, (c) a continued step bitwise equal under both layouts."""
        opt8, opt4 = build_opt(rule, 8), build_opt(rule, 4)
        params0 = make(0)
        params, state8 = warm_state(opt8, params0)
        plan8, plan4 = opt8.bucket_plan(params), opt4.bucket_plan(params)
        # device-axis EF residual, nonzero: rank r holds the constant r, so
        # the 8 -> 4 reshard must fold the outstanding mass
        # sum(0..7) * (4/8) = 14 onto new rank 0 and zero the rest
        comp = init_compression_state(params, 8)
        comp = comp._replace(error=jax.tree_util.tree_map(
            lambda e: e + jnp.arange(8, dtype=jnp.float32).reshape(
                (8,) + (1,) * (e.ndim - 1)), comp.error))

        state4 = elastic.reshard_bucketed_state(state8, plan8, plan4)
        for b in plan4.buckets:
            assert state4.buckets[b.key].shape[0] == b.padded
            np.testing.assert_array_equal(
                np.asarray(state4.buckets[b.key][b.size:]), 0.0,
                err_msg=f"{rule}: pad slices of {b.key} not zero")
        assert_tree_equal(
            bucketing.unpad_buckets(plan4, state4.buckets),
            bucketing.unpad_buckets(plan8, state8.buckets),
            msg=f"{rule}: momentum content changed in reshard")
        assert set(state4.slots) == set(state8.slots)
        for name in state8.slots:
            assert_tree_equal(
                bucketing.unpad_buckets(plan4, state4.slots[name]),
                bucketing.unpad_buckets(plan8, state8.slots[name]),
                msg=f"{rule}: slot {name} content changed in reshard")

        # roundtrip 8 -> 4 -> 8 is the identity
        back = elastic.reshard_bucketed_state(state4, plan4, plan8)
        assert_tree_equal(back, state8, msg=f"{rule}: roundtrip not exact")

        # manager round trip with the layout manifest + restore_resharded
        mgr = CheckpointManager(str(tmp_path / rule), keep=2)
        layout8 = elastic.state_layout(opt8, params, mesh_size=8, rule=rule,
                                       opt_state=state8)
        mgr.save(7, (params, state8, comp), block=True, layout=layout8)
        assert mgr.read_layout(7)["shard_size"] == 8
        (p_r, s_r, c_r), data_step = elastic.restore_resharded(
            mgr, 7, params0, comp, opt_new=opt4, opt_old=opt8)
        assert data_step == 7
        assert_tree_equal(p_r, params)
        assert_tree_equal(s_r, state4, msg=f"{rule}: managed reshard")
        expected_err = jax.tree_util.tree_map(
            lambda e: np.pad(np.full((1,) + e.shape[1:], 14.0, np.float32),
                             [(0, 3)] + [(0, 0)] * (e.ndim - 1)),
            comp.error)
        assert_tree_equal(c_r.error, expected_err,
                          msg=f"{rule}: EF residual reshard lost mass")

        # a continued step agrees bitwise under either layout
        g = make(99)
        p8, _ = jax.jit(opt8.update_apply)(g, state8, params, 2)
        p4, _ = jax.jit(opt4.update_apply)(g, s_r, p_r, 2)
        assert_tree_equal(p4, p8, msg=f"{rule}: continued step diverged")

    def test_mixed_state_reshards(self):
        """FusedMixedState: stacked matrix buckets reshard, the per-leaf
        AdamW momenta pass through untouched."""
        opt8 = mixed_optimizer("normuon", constant(0.05), constant(0.01),
                               ns_steps=2, fused=True,
                               shard_axis="data", shard_size=8)
        opt2 = mixed_optimizer("normuon", constant(0.05), constant(0.01),
                               ns_steps=2, fused=True,
                               shard_axis="data", shard_size=2)
        params = {**make(0), "head/b": jnp.ones((16,), jnp.float32)}
        state8 = opt8.init(params)
        plan8, plan2 = opt8.bucket_plan(params), opt2.bucket_plan(params)
        state2 = elastic.reshard_bucketed_state(state8, plan8, plan2)
        assert_tree_equal(state2.momentum, state8.momentum)
        assert_tree_equal(state2.nu, state8.nu)
        assert_tree_equal(
            bucketing.unpad_buckets(plan2, state2.buckets),
            bucketing.unpad_buckets(plan8, state8.buckets))
        back = elastic.reshard_bucketed_state(state2, plan2, plan8)
        assert_tree_equal(back, state8)

    def test_stateless_passthrough(self):
        """Per-leaf states (no .buckets) pass through unchanged."""
        state = {"m": jnp.ones((3, 4))}
        out = elastic.reshard_bucketed_state(state, None, None)
        assert out is state

    def test_rejects_different_param_tree(self):
        opt = build_opt("rmnp", 4)
        plan_a = opt.bucket_plan(make(0))
        shapes = dict(SHAPES)
        shapes.pop("odd/w")
        plan_b = opt.bucket_plan(make(0, shapes))
        state = opt.init(make(0))
        with pytest.raises(elastic.LayoutMismatchError,
                           match="different param tree"):
            elastic.reshard_bucketed_state(state, plan_a, plan_b)


# ---------------------------------------------------------------------------
# layout manifest validation
# ---------------------------------------------------------------------------

class TestLayoutValidation:
    def _layout(self, rule, n, params=None):
        opt = build_opt(rule, n)
        params = params if params is not None else make(0)
        return elastic.state_layout(opt, params, mesh_size=n, rule=rule,
                                    opt_state=opt.init(params))

    def test_shard_size_only_difference_is_ok(self):
        elastic.validate_relayout(self._layout("rmnp", 8),
                                  self._layout("rmnp", 4))

    def test_compress_difference_is_ok(self):
        """The EF residual is per-leaf and carried either way — wire choice
        is not a layout incompatibility."""
        a = self._layout("rmnp", 8)
        b = dict(self._layout("rmnp", 8), compress=True)
        elastic.validate_relayout(a, b)

    def test_rule_mismatch_names_both(self):
        with pytest.raises(elastic.LayoutMismatchError) as e:
            elastic.validate_relayout(self._layout("rmnp", 8),
                                      self._layout("normuon", 8))
        msg = str(e.value)
        assert "rmnp" in msg and "normuon" in msg
        assert "checkpoint layout" in msg and "this run's layout" in msg

    def test_tree_mismatch_fails(self):
        shapes = dict(SHAPES)
        shapes.pop("odd/w")
        with pytest.raises(elastic.LayoutMismatchError, match="plan"):
            elastic.validate_relayout(
                self._layout("rmnp", 8),
                self._layout("rmnp", 8, params=make(0, shapes)))

    def test_missing_layout_fails(self):
        with pytest.raises(elastic.LayoutMismatchError,
                           match="no layout manifest"):
            elastic.validate_relayout(None, self._layout("rmnp", 4))


# ---------------------------------------------------------------------------
# manager-side template validation (shape / dtype / tree mismatches)
# ---------------------------------------------------------------------------

class TestManagerValidation:
    def _save(self, tmp_path, state):
        mgr = CheckpointManager(str(tmp_path), keep=2)
        mgr.save(3, state, block=True)
        return mgr

    def test_shape_mismatch_names_leaf_and_both_shapes(self, tmp_path):
        mgr = self._save(tmp_path, {"a/w": np.zeros((8, 4), np.float32)})
        with pytest.raises(ValueError) as e:
            mgr.restore(3, {"a/w": np.zeros((12, 4), np.float32)})
        msg = str(e.value)
        assert "a/w" in msg and "(8, 4)" in msg and "(12, 4)" in msg
        assert "mesh size" in msg  # points at the elastic fix

    def test_dtype_mismatch_refuses_cast(self, tmp_path):
        mgr = self._save(tmp_path, {"a/w": np.zeros((4,), np.float32)})
        with pytest.raises(ValueError, match="float32.*bfloat16|bfloat16"):
            mgr.restore(3, {"a/w": jnp.zeros((4,), jnp.bfloat16)})

    def test_tree_mismatch_names_both_paths(self, tmp_path):
        mgr = self._save(tmp_path, {"a/w": np.zeros((4,), np.float32)})
        with pytest.raises(ValueError, match="'a/w'.*'b/w'|'b/w'.*'a/w'"):
            mgr.restore(3, {"b/w": np.zeros((4,), np.float32)})

    def test_leaf_count_mismatch(self, tmp_path):
        mgr = self._save(tmp_path, {"a/w": np.zeros((4,), np.float32)})
        with pytest.raises(ValueError, match="leaves"):
            mgr.restore(3, {"a/w": np.zeros((4,), np.float32),
                            "b/w": np.zeros((4,), np.float32)})

    def test_eval_shape_template_restores(self, tmp_path):
        """ShapeDtypeStruct templates (the restore_resharded path) pass
        validation and restore to real arrays."""
        opt = build_opt("rmnp", 8)
        params = make(0)
        state = opt.init(params)
        mgr = self._save(tmp_path, state)
        template = jax.eval_shape(opt.init, params)
        restored, _ = mgr.restore(3, template)
        assert_tree_equal(restored, state)


# ---------------------------------------------------------------------------
# hang/straggler detection -> emergency checkpoint (the ladder's first rung)
# ---------------------------------------------------------------------------

class TestHangGuard:
    def test_deadline_fires_and_saves(self):
        saved = []
        guard = HangGuard(0.05, lambda: saved.append(True))
        guard.arm()
        time.sleep(0.3)
        guard.stop()
        assert guard.fired and saved

    def test_pet_prevents_firing(self):
        saved = []
        guard = HangGuard(0.25, lambda: saved.append(True))
        for _ in range(4):
            guard.arm()
            time.sleep(0.05)
        guard.stop()
        time.sleep(0.3)
        assert not guard.fired and not saved

    def test_straggler_triggers_emergency_save(self):
        saved = []
        guard = HangGuard(0.0, lambda: saved.append(True))  # no watchdog
        assert guard.watchdog is None
        for t in range(8):
            assert not guard.record(t, 0.1)
        assert guard.record(8, 10.0)  # >> abs_factor * mean
        assert guard.flagged == 1 and saved

    def test_emergency_save_serialized(self):
        """Timer thread and main loop both reaching the save must not
        interleave (the manager join/replace is not reentrant)."""
        active, overlaps = [], []

        def save():
            active.append(1)
            if len(active) > 1:
                overlaps.append(True)
            time.sleep(0.05)
            active.pop()

        guard = HangGuard(0.02, save)
        guard.arm()
        threads = [threading.Thread(
            target=lambda: guard.record(9, 50.0)) for _ in range(3)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        time.sleep(0.2)
        guard.stop()
        assert not overlaps

    def test_train_wiring_smoke(self, tmp_path):
        """train(..., watchdog_deadline=...) with a generous deadline runs
        clean — guard armed each step, no spurious emergency saves."""
        from repro.launch.train import train
        train("gpt2-60m", steps=2, batch=2, seq=16, log_every=1, seed=0,
              ckpt_dir=str(tmp_path), ckpt_every=0, watchdog_deadline=600.0)
        # only the normal final checkpoint: deadline never hit, nothing
        # flagged, so no emergency saves of earlier steps
        assert CheckpointManager(str(tmp_path))._committed_steps() == [2]


# ---------------------------------------------------------------------------
# end-to-end: train.py restores a checkpoint written at another mesh size
# ---------------------------------------------------------------------------

class TestTrainElasticRestore:
    def test_cross_mesh_restore_bitwise(self, tmp_path):
        """A ZeRO-2 checkpoint re-laid out for shard_size=4 resumes on this
        1-device run through train.py's elastic path, bitwise equal to
        resuming the native 1-way checkpoint.  (True multi-device
        kill/resume runs in the subprocess worker — this exercises the
        train.py wiring itself under tier-1's single device.)"""
        from repro.launch.train import train

        arch, steps, seed = "gpt2-60m", 4, 0
        d_native = tmp_path / "native"
        d_resh = tmp_path / "resharded"

        # natural 1-way zero2 checkpoint at step 2
        train(arch, steps=steps, stop_at=2, batch=2, seq=16, log_every=1,
              seed=seed, ckpt_dir=str(d_native), ckpt_every=2,
              zero2=True, compress=False)
        mgr = CheckpointManager(str(d_native))
        assert mgr.latest_step() == 2
        layout1 = mgr.read_layout(2)
        assert layout1["shard_size"] == 1 and layout1["rule"] == "rmnp"

        # re-lay the state out for a 4-way mesh and save it to a second dir
        from repro.configs import get_config
        from repro.core import cosine_with_warmup
        from repro.models import init_params

        def opt_for(n):
            return make_optimizer("rmnp", dict(
                lr_matrix=cosine_with_warmup(2e-3, steps),
                lr_adamw=cosine_with_warmup(1e-3, steps),
                shard_axis="data", shard_size=n))

        opt1, opt4 = opt_for(1), opt_for(4)
        cfg = get_config(arch).reduced()
        params0 = init_params(cfg, jax.random.PRNGKey(seed))
        comp0 = init_compression_state(params0, 1)
        (p, s1, c), data_step = mgr.restore(
            2, (params0, jax.eval_shape(opt1.init, params0), comp0))
        s4 = elastic.reshard_bucketed_state(
            s1, opt1.bucket_plan(p), opt4.bucket_plan(p))
        c4 = compression.reshard_error(c, 1, 4)
        layout4 = elastic.state_layout(opt4, p, mesh_size=4, rule="rmnp",
                                       opt_state=s4)
        mgr4 = CheckpointManager(str(d_resh))
        mgr4.save(2, (p, s4, c4), data_step=data_step, block=True,
                  layout=layout4)

        # both dirs resume; the resharded one goes through the elastic path
        p_nat, _, _ = train(arch, steps=steps, batch=2, seq=16, log_every=1,
                            seed=seed, ckpt_dir=str(d_native), ckpt_every=2,
                            zero2=True, compress=False)
        p_ela, _, _ = train(arch, steps=steps, batch=2, seq=16, log_every=1,
                            seed=seed, ckpt_dir=str(d_resh), ckpt_every=2,
                            zero2=True, compress=False)
        assert_tree_equal(p_ela, p_nat,
                          msg="elastic resume != native resume")


# ---------------------------------------------------------------------------
# quick kill-and-resume slice (full matrix runs in CI's dedicated step)
# ---------------------------------------------------------------------------

@pytest.mark.skipif(os.environ.get("CI") == "true",
                    reason="CI runs the full elastic scenario in its own step")
def test_elastic_fault_injection_quick():
    root = Path(__file__).resolve().parents[1]
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [str(root / "src"), os.environ.get("PYTHONPATH", "")]
               ).rstrip(os.pathsep))
    r = subprocess.run(
        [sys.executable, str(root / "tests" / "_zero_shard_worker.py"),
         "elastic", "--quick"],
        env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert r.stdout.rstrip().endswith("ELASTIC_OK"), r.stdout
