"""Data pipeline, checkpointing, sharding rules, MoE invariants."""
import json
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_support import given, settings, st
from jax.sharding import PartitionSpec as P

from repro.checkpoint.manager import CheckpointManager
from repro.configs import get_config
from repro.data.pipeline import make_stream
from repro.distributed.sharding import DEFAULT_RULES, spec_for


class TestDataPipeline:
    def test_deterministic(self):
        cfg = get_config("gpt2-small").reduced()
        s1 = make_stream(cfg, 32, 4, seed=1)
        s2 = make_stream(cfg, 32, 4, seed=1)
        b1, b2 = next(s1), next(s2)
        np.testing.assert_array_equal(b1["tokens"], b2["tokens"])

    def test_resume_matches(self):
        cfg = get_config("gpt2-small").reduced()
        s1 = make_stream(cfg, 32, 4, seed=1)
        for _ in range(5):
            next(s1)
        b_next = next(s1)
        s2 = make_stream(cfg, 32, 4, seed=1, start_step=5)
        np.testing.assert_array_equal(b_next["tokens"], next(s2)["tokens"])

    def test_host_sharding_partitions_batch(self):
        cfg = get_config("gpt2-small").reduced()
        s = make_stream(cfg, 16, 8, seed=0, host_id=0, num_hosts=4)
        assert next(s)["tokens"].shape == (2, 16)

    def test_labels_are_shifted_tokens(self):
        cfg = get_config("gpt2-small").reduced()
        b = next(make_stream(cfg, 32, 2, seed=3))
        np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])

    def test_learnable_structure(self):
        """Markov stream must beat uniform entropy (it's learnable)."""
        cfg = get_config("gpt2-small").reduced()
        b = next(make_stream(cfg, 512, 4, seed=0))
        # deterministic continuation appears >50% of the time
        toks = b["tokens"]
        _, counts = np.unique(toks, return_counts=True)
        assert counts.max() > toks.size / cfg.vocab * 2

    @given(st.integers(0, 1000))
    @settings(max_examples=10, deadline=None)
    def test_property_step_determinism(self, step):
        cfg = get_config("gpt2-small").reduced()
        s = make_stream(cfg, 16, 2, seed=9)
        a = s.sample(step)["tokens"]
        b = s.sample(step)["tokens"]
        np.testing.assert_array_equal(a, b)

    def test_replay_full_batch_bitwise(self):
        """Deterministic batch replay for the rewind ladder: a stream
        resumed at ``start_step`` replays the exact same batches from that
        point on — every key, bitwise — and lands on the same stream
        position."""
        cfg = get_config("gpt2-small").reduced()
        s1 = make_stream(cfg, 32, 4, seed=7)
        batches = [next(s1) for _ in range(9)]
        s2 = make_stream(cfg, 32, 4, seed=7, start_step=4)
        for t in range(4, 9):
            b = next(s2)
            assert set(b) == set(batches[t])
            for k in b:
                np.testing.assert_array_equal(
                    b[k], batches[t][k], err_msg=f"step {t} key {k}")
        assert s2.step == s1.step

    def test_frontend_batches(self):
        vlm = get_config("paligemma-3b").reduced()
        b = next(make_stream(vlm, 16, 2))
        assert b["vision_embeds"].shape == (2, vlm.n_frontend_tokens, vlm.d_model)
        aud = get_config("musicgen-large").reduced()
        b = next(make_stream(aud, 16, 2))
        assert b["frames"].shape == (2, 16, aud.d_model)


class TestCheckpoint:
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_roundtrip(self, tmp_path, dtype):
        # npz stores bfloat16 (a dtype numpy does not know) as raw bytes;
        # restore must give it back as bfloat16, bit for bit
        mgr = CheckpointManager(str(tmp_path), async_save=False)
        state = {"w": (jnp.arange(12.0).reshape(3, 4) / 7).astype(dtype),
                 "n": jnp.ones((2,))}
        mgr.save(7, state, data_step=70)
        out = mgr.restore_latest(state)
        assert out is not None
        restored, step, data_step = out
        assert step == 7 and data_step == 70
        assert restored["w"].dtype == state["w"].dtype
        np.testing.assert_array_equal(np.array(restored["w"]), np.array(state["w"]))

    def test_uncommitted_ignored(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), async_save=False)
        state = {"w": jnp.ones((2, 2))}
        mgr.save(1, state)
        # simulate a crash mid-save at step 2: no COMMITTED marker
        d = tmp_path / "step_000000002"
        d.mkdir()
        (d / "manifest.json").write_text(json.dumps({"step": 2, "data_step": 2,
                                                     "leaves": []}))
        assert mgr.latest_step() == 1

    def test_retention_prunes(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep=2, async_save=False)
        state = {"w": jnp.ones((2,))}
        for s in (1, 2, 3, 4):
            mgr.save(s, state)
        assert mgr._committed_steps() == [3, 4]

    def test_torn_write_is_invisible(self, tmp_path, monkeypatch):
        """A save killed mid-write (before the COMMITTED marker) must be
        invisible: restore_latest returns the previous committed step, the
        torn attempt never shadows it, retention never deletes the last
        committed checkpoint, and a retried save at the same step recovers
        from the leftover tmp dir."""
        import repro.checkpoint.manager as manager_mod

        mgr = CheckpointManager(str(tmp_path), keep=1, async_save=False)
        state = {"w": jnp.arange(4.0)}
        mgr.save(1, state, data_step=10)

        # kill the writer mid-npz: partial file on disk, then "SIGKILL"
        real_savez = manager_mod.np.savez

        def torn_savez(path, **arrays):
            with open(path, "wb") as f:
                f.write(b"PK\x03\x04 torn")
            raise KeyboardInterrupt("killed mid-save")

        monkeypatch.setattr(manager_mod.np, "savez", torn_savez)
        with pytest.raises(KeyboardInterrupt):
            mgr.save(2, {"w": jnp.arange(4.0) * 2}, data_step=20)
        monkeypatch.setattr(manager_mod.np, "savez", real_savez)

        # the torn attempt is a tmp dir — never a visible step
        assert (tmp_path / ".tmp_step_000000002").exists()
        assert not (tmp_path / "step_000000002").exists()
        assert mgr.latest_step() == 1
        out, step, data_step = mgr.restore_latest(state)
        assert (step, data_step) == (1, 10)
        np.testing.assert_array_equal(np.asarray(out["w"]),
                                      np.asarray(state["w"]))

        # keep=1 retention never touches the last committed step, even
        # with torn/uncommitted dirs lying around
        d = tmp_path / "step_000000005"
        d.mkdir()
        (d / "manifest.json").write_text("{}")
        mgr._prune()
        assert mgr.latest_step() == 1

        # a retried save at the torn step wins cleanly over the leftovers
        mgr.save(2, {"w": jnp.arange(4.0) * 2}, data_step=20)
        assert not (tmp_path / ".tmp_step_000000002").exists()
        assert mgr.latest_step() == 2
        _, step, data_step = mgr.restore_latest(state)
        assert (step, data_step) == (2, 20)
        # the retried commit pruned step 1 (keep=1) but kept itself
        assert mgr._committed_steps() == [2]

    def test_torn_manifest_falls_back(self, tmp_path):
        """An unparseable manifest.json under a COMMITTED marker (torn at
        the filesystem level after commit) is treated exactly like a
        missing commit marker: the checkpoint becomes invisible with a
        warning and restore_latest falls back to the previous step."""
        mgr = CheckpointManager(str(tmp_path), async_save=False)
        state = {"w": jnp.arange(4.0)}
        mgr.save(1, state, data_step=10)
        mgr.save(2, state, data_step=20)
        (tmp_path / "step_000000002" / "manifest.json").write_text(
            "{ garbage")
        with pytest.warns(RuntimeWarning, match="manifest.json"):
            assert mgr.latest_step() == 1
        with pytest.warns(RuntimeWarning, match="manifest.json"):
            out, step, data_step = mgr.restore_latest(state)
        assert (step, data_step) == (1, 10)
        np.testing.assert_array_equal(np.asarray(out["w"]),
                                      np.asarray(state["w"]))

    def test_async_save(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), async_save=True)
        mgr.save(5, {"w": jnp.ones((64, 64))})
        mgr.wait()
        assert mgr.latest_step() == 5

    def test_prune_pins_newest_good_step(self, tmp_path):
        """Retention never drops the newest last-known-good step: it is
        the rewind ladder's restore target, and ``keep`` newer (possibly
        poisoned) checkpoints must not push it out of the window."""
        mgr = CheckpointManager(str(tmp_path), keep=2, async_save=False)
        state = {"w": jnp.arange(8.0)}
        mgr.save(1, state, data_step=10)
        mgr.mark_good(1)
        for s in (2, 3, 4):
            mgr.save(s, state)
        assert mgr._committed_steps() == [1, 3, 4]
        assert mgr.latest_good_step() == 1
        # a newer good step releases the old pin on the next prune
        mgr.mark_good(4)
        mgr._prune()
        assert mgr._committed_steps() == [3, 4]

    def test_prune_never_deletes_mid_restore(self, tmp_path, monkeypatch):
        """A checkpoint being restored is pinned: retention triggered by
        newer commits must not delete it under the reader (the race fixed
        alongside the async writer — prune used to free-run against
        readers)."""
        import threading

        mgr = CheckpointManager(str(tmp_path), keep=1, async_save=False)
        state = {"w": jnp.arange(256.0)}
        mgr.save(1, state, data_step=10)

        real = CheckpointManager._load_arrays
        entered, release = threading.Event(), threading.Event()

        def slow(self, d, manifest):
            entered.set()
            assert release.wait(10)
            return real(self, d, manifest)

        monkeypatch.setattr(CheckpointManager, "_load_arrays", slow)
        out = {}
        th = threading.Thread(
            target=lambda: out.update(r=mgr.restore(1, state)))
        th.start()
        assert entered.wait(10)
        monkeypatch.setattr(CheckpointManager, "_load_arrays", real)
        # two newer commits while step 1 is mid-read: keep=1 would drop
        # it, the mid-restore pin must not
        mgr.save(2, state)
        mgr.save(3, state)
        assert (tmp_path / "step_000000001" / "COMMITTED").exists()
        release.set()
        th.join(10)
        restored, data_step = out["r"]
        assert data_step == 10
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.asarray(state["w"]))
        # read finished: the pin is gone, the next prune reclaims it
        mgr._prune()
        assert mgr._committed_steps() == [3]

    def test_manifest_parse_cached(self, tmp_path, monkeypatch):
        """restore_latest / latest_step / good_steps stop re-parsing every
        manifest per call: parses are cached keyed on file stat and the
        directory listing on its mtime, invalidated by save/prune."""
        import repro.checkpoint.manager as manager_mod

        mgr = CheckpointManager(str(tmp_path), async_save=False)
        state = {"w": jnp.arange(4.0)}
        mgr.save(1, state, data_step=10)
        mgr.save(2, state, data_step=20)

        calls = []
        real_loads = manager_mod.json.loads

        def counting_loads(s, *a, **k):
            calls.append(1)
            return real_loads(s, *a, **k)

        monkeypatch.setattr(manager_mod.json, "loads", counting_loads)
        for _ in range(5):
            assert mgr.latest_step() == 2
            assert mgr.good_steps() == []
            assert mgr.restore_latest(state) is not None
        assert not calls, f"{len(calls)} manifest re-parses despite cache"
        # a new commit invalidates; afterwards reads are cached again
        mgr.save(3, state, data_step=30)
        assert calls, "save must invalidate the manifest cache"
        calls.clear()
        assert mgr.latest_step() == 3
        assert mgr.restore_latest(state) is not None
        assert not calls, "cache not repopulated after invalidation"

    def test_train_restart_resumes_stream(self, tmp_path):
        """End-to-end fault-tolerance: kill + restart reproduces the batch."""
        from repro.launch.train import train
        p1, _, h1 = train("gpt2-60m", "rmnp", steps=6, batch=2, seq=32,
                          ckpt_dir=str(tmp_path / "ck"), ckpt_every=3,
                          log_every=1)
        # "crash" after step 3: new process restores from step-3 checkpoint
        shutil.rmtree(tmp_path / "ck" / "step_000000006", ignore_errors=True)
        p2, _, h2 = train("gpt2-60m", "rmnp", steps=6, batch=2, seq=32,
                          ckpt_dir=str(tmp_path / "ck"), ckpt_every=3,
                          log_every=1)
        l1 = [h["loss"] for h in h1 if h["step"] == 5]
        l2 = [h["loss"] for h in h2 if h["step"] == 5]
        assert l1 and l2
        np.testing.assert_allclose(l1[0], l2[0], rtol=1e-4)


class TestShardingRules:
    def _mesh(self):
        return jax.make_mesh((1, 1), ("data", "model"))

    def test_divisibility_fallback(self):
        mesh = jax.make_mesh((1,), ("model",))
        # vocab 73448 not divisible by any >1 axis: trivially P(None) on 1-dev
        spec = spec_for((73448, 2560), ("vocab", "embed"), mesh)
        assert spec == P(None, None) or spec == P()

    def test_axis_assignment_unique(self):
        mesh = self._mesh()
        spec = spec_for((16, 16), ("d_in", "mlp"), mesh)
        used = [s for s in spec if s is not None]
        assert len(used) == len(set(used))

    def test_rules_table_covers_model_axes(self):
        for name in ("batch", "vocab", "heads", "mlp", "expert", "d_in",
                     "kv_seq", "long_seq", "d_inner"):
            assert name in DEFAULT_RULES

    def test_logical_noop_outside_mesh(self):
        from repro.distributed.sharding import logical
        x = jnp.ones((4, 4))
        np.testing.assert_array_equal(np.array(logical(x, ("batch", None))),
                                      np.array(x))


class TestMoE:
    def _setup(self, top_k=2, E=4, N=32):
        from repro.configs.base import MoEConfig, ModelConfig
        cfg = ModelConfig(name="t", family="moe", num_layers=1, d_model=16,
                          n_heads=2, n_kv_heads=2, d_ff=32, vocab=64,
                          default_ffn="moe",
                          moe=MoEConfig(num_experts=E, top_k=top_k,
                                        d_ff_expert=32, capacity_factor=4.0),
                          dtype="float32")
        from repro.models.moe import moe_apply, moe_specs
        from repro.models.model import _tree_materialize
        p = _tree_materialize(moe_specs(cfg), jax.random.PRNGKey(0), jnp.float32)
        return cfg, p, moe_apply

    def test_output_finite_and_shaped(self):
        cfg, p, apply = self._setup()
        x = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16))
        y, aux = apply(cfg, p, x)
        assert y.shape == x.shape
        assert np.all(np.isfinite(np.array(y))) and float(aux) > 0

    def test_single_expert_equals_dense(self):
        """E=1, top_k=1 routes everything: output must be the expert FFN."""
        cfg, p, apply = self._setup(top_k=1, E=1)
        x = 0.1 * jax.random.normal(jax.random.PRNGKey(2), (1, 8, 16))
        y, _ = apply(cfg, p, x)
        from repro.models.layers import rms_norm
        h = rms_norm(x, p["norm"], cfg.rms_eps)
        gu = h.reshape(8, 16) @ p["w_in"][0]
        g, u = jnp.split(gu, 2, axis=-1)
        expect = (jax.nn.silu(g) * u) @ p["w_out"][0]
        np.testing.assert_allclose(np.array(y).reshape(8, 16),
                                   np.array(expect), atol=1e-4)

    def test_gate_normalization(self):
        """Top-k gates renormalize to 1 => scaling x scales y (linearity in
        the combine)."""
        cfg, p, apply = self._setup()
        x = 0.1 * jax.random.normal(jax.random.PRNGKey(3), (1, 8, 16))
        y1, _ = apply(cfg, p, x)
        assert np.all(np.isfinite(np.array(y1)))


def test_crash_restart_bitwise_exact(tmp_path):
    """Kill-at-step-40 + restart == uninterrupted run, bitwise (the
    fault-tolerance contract: atomic checkpoints + deterministic stream +
    full-schedule stop_at)."""
    from repro.launch.train import train
    kw = dict(batch=2, seq=16, steps=24, seed=11, log_every=100)
    p_ref, _, _ = train("gpt2-small", **kw)
    train("gpt2-small", stop_at=12, ckpt_dir=str(tmp_path), ckpt_every=6, **kw)
    p_res, _, _ = train("gpt2-small", ckpt_dir=str(tmp_path), ckpt_every=6, **kw)
    for a, b in zip(jax.tree_util.tree_leaves(p_ref),
                    jax.tree_util.tree_leaves(p_res), strict=False):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
