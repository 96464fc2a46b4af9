"""Tests for the bucket-pipelined ZeRO-2 step machinery (train/pipeline.py)
that run on a single device; the 4-device mesh equivalences (bitwise vs
replicated, overlap report on real compiled HLO) live in
tests/_zero_shard_worker.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.core import constant, make_optimizer, mixed_optimizer
from repro.core.bucketing import (
    accumulate_chunks, build_plan, gather_chunks, init_chunk_acc,
)
from repro.core.types import tree_paths
from repro.launch.mesh import make_data_mesh
from repro.models import init_params
from repro.train.dp_step import init_dp_state, make_dp_train_step


def _tree(shapes, seed=0):
    return {k: jax.random.normal(
        jax.random.fold_in(jax.random.PRNGKey(seed), i), s, jnp.float32)
        for i, (k, s) in enumerate(sorted(shapes.items()))}


class TestChunkAccumulation:
    SHAPES = {"a/w": (2, 8, 16), "b/w": (8, 16), "c/w": (3, 8, 24)}

    def test_accumulate_matches_chunking_the_sum(self):
        """Chunking is linear: accumulating chunked microbatch grads equals
        chunking the per-leaf sum, bitwise (same addition order)."""
        plan = build_plan(_tree(self.SHAPES), pad_multiple=4)
        mbs = [_tree(self.SHAPES, seed=i) for i in range(3)]
        acc = init_chunk_acc(plan, 4)
        for mb in mbs:
            acc = accumulate_chunks(plan, mb, acc, 4)
        leaf_sum = mbs[0]
        for mb in mbs[1:]:
            leaf_sum = jax.tree_util.tree_map(lambda a, g: a + g, leaf_sum, mb)
        ref = gather_chunks(plan, leaf_sum, 4, dtype=jnp.float32)
        for k in ref:
            np.testing.assert_array_equal(np.asarray(acc[k]),
                                          np.asarray(ref[k]), err_msg=k)

    def test_init_chunk_acc_validates_divisibility(self):
        plan = build_plan(_tree(self.SHAPES))  # no padding
        with pytest.raises(ValueError, match="pad_multiple"):
            init_chunk_acc(plan, 4)

    def test_pad_slices_stay_zero(self):
        plan = build_plan(_tree(self.SHAPES), pad_multiple=4)
        acc = accumulate_chunks(plan, _tree(self.SHAPES),
                                init_chunk_acc(plan, 4), 4)
        (b24,) = [b for b in plan.buckets if b.key == "8x24"]
        assert b24.padded == 4 and b24.size == 3
        # slice 3 (the pad) is the last chunk's second... with csize=1 it is
        # chunk 3 entirely
        assert np.all(np.asarray(acc["8x24"][3]) == 0)


class TestMicrobatchGrads:
    def test_chunked_accum_means_match_direct(self):
        """accum=2 chunked accumulation ~= the accum=1 direct backward
        (association of the microbatch sums is the only difference), and
        matrix leaves of the rest tree are inert placeholders."""
        from repro.train.pipeline import microbatch_grads_chunked

        cfg = get_config("gpt2-60m").reduced()
        params = init_params(cfg, jax.random.PRNGKey(0))
        opt = mixed_optimizer("rmnp", constant(1e-2), constant(1e-2),
                              shard_axis="data", shard_size=1)
        plan = opt.bucket_plan(params)
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                  cfg.vocab)
        batch = {"tokens": toks, "labels": toks}
        c1, rest1, m1 = jax.jit(
            lambda b: microbatch_grads_chunked(cfg, plan, params, b, 1, 1))(
                batch)
        c2, rest2, m2 = jax.jit(
            lambda b: microbatch_grads_chunked(cfg, plan, params, b, 2, 1))(
                batch)
        mat = plan.paths
        for k in c1:
            np.testing.assert_allclose(np.asarray(c2[k]), np.asarray(c1[k]),
                                       rtol=2e-4, atol=2e-6, err_msg=k)
        for (k, a), (_, b) in zip(tree_paths(rest2), tree_paths(rest1), strict=False):
            if k in mat:
                assert a.shape == (1,) * np.asarray(b).ndim, (k, a.shape)
            else:
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=2e-4, atol=2e-6, err_msg=k)
        np.testing.assert_allclose(float(m2["loss"]), float(m1["loss"]),
                                   rtol=1e-5)

    def test_accum_must_divide_local_batch(self):
        from repro.train.pipeline import microbatch_grads

        cfg = get_config("gpt2-60m").reduced()
        params = init_params(cfg, jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                  cfg.vocab)
        with pytest.raises(ValueError, match="accum=3"):
            jax.eval_shape(
                lambda b: microbatch_grads(cfg, params, b, 3),
                {"tokens": toks, "labels": toks})


class TestTwoPhaseClip:
    def test_single_device_matches_clip_by_global_norm(self):
        """On a 1-way axis every leaf is rank-contained, so gnorm and scale
        are bit-for-bit clip_by_global_norm's — with the clip active."""
        from repro.core.mixed import clip_by_global_norm
        from repro.distributed.compression import exact_reduce_scatter
        from repro.train.pipeline import two_phase_clip

        mesh = make_data_mesh(1)
        shapes = {"a/w": (2, 8, 16), "b/w": (8, 16), "c/w": (3, 8, 24)}
        grads = _tree(shapes, seed=2)
        grads["norm_1d"] = jax.random.normal(jax.random.PRNGKey(7), (11,))
        opt = make_optimizer("rmnp", lr_matrix=0.1, shard_axis="data",
                             shard_size=1)
        plan = opt.bucket_plan({k: v for k, v in grads.items()
                                if v.ndim >= 2})

        def run(g):
            chunks = gather_chunks(plan, g, 1, dtype=jnp.float32)
            shards = {b.key: exact_reduce_scatter(chunks[b.key], "data")
                      for b in plan.buckets}
            scale, _, stats, ginfo = two_phase_clip(plan, shards, g, 1.0,
                                                    "data", 1)
            return scale, stats.global_norm, ginfo.ok, ginfo.flags

        scale, gnorm, ok, flags = jax.jit(shard_map(
            run, mesh=mesh, in_specs=(P(),), out_specs=(P(), P(), P(), P()),
            check_vma=False))(grads)
        assert bool(ok) and bool(np.all(np.asarray(flags)))
        assert flags.shape == (len(grads),)  # one finite flag per leaf
        _, ref = clip_by_global_norm(grads, 1.0)
        assert float(ref.global_norm) > 1.0  # clip engaged
        np.testing.assert_array_equal(np.asarray(gnorm),
                                      np.asarray(ref.global_norm))
        ref_scale = np.minimum(
            np.float32(1.0),
            np.float32(1.0) / (np.asarray(ref.global_norm) + np.float32(1e-12)))
        np.testing.assert_array_equal(np.asarray(scale), ref_scale)


class TestDpStepPipelined:
    """Single-device dp-step coverage of the new accum / overlap knobs (the
    4-device equivalences run in the shard worker)."""

    def _setup(self):
        cfg = get_config("gpt2-60m").reduced()
        params = init_params(cfg, jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                  cfg.vocab)
        batch = {"tokens": toks, "labels": toks}
        mesh = make_data_mesh(1)
        opt = mixed_optimizer("rmnp", constant(1e-2), constant(1e-2),
                              shard_axis="data", shard_size=1)
        return cfg, params, batch, mesh, opt

    def test_pipelined_matches_serialized_bitwise(self):
        cfg, params, batch, mesh, opt = self._setup()
        st = opt.init(params)
        comp = init_dp_state(params)
        outs = {}
        for overlap in (False, True):
            step = jax.jit(make_dp_train_step(
                cfg, opt, mesh, zero2=True, opt_state=st, compress=False,
                accum=2, overlap=overlap))
            outs[overlap] = step(params, st, comp, batch, jnp.int32(0))
        for (k, a), (_, b) in zip(tree_paths(outs[True][0]),
                                  tree_paths(outs[False][0]), strict=False):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32),
                                          err_msg=k)
        np.testing.assert_array_equal(
            np.asarray(outs[True][3]["grad_norm"]),
            np.asarray(outs[False][3]["grad_norm"]))

    def test_compressed_pipelined_accum_trains(self):
        cfg, params, batch, mesh, opt = self._setup()
        st = opt.init(params)
        comp = init_dp_state(params)
        step = jax.jit(make_dp_train_step(
            cfg, opt, mesh, zero2=True, opt_state=st, compress=True,
            accum=2))
        p, s, c = params, st, comp
        for i in range(3):
            p, s, c, m = step(p, s, c, batch, jnp.int32(i))
            assert np.isfinite(float(np.asarray(m["loss"]))), i

    def test_shard_size_mismatch_rejected_up_front(self):
        cfg, params, batch, mesh, opt = self._setup()
        bad = mixed_optimizer("rmnp", constant(1e-2), constant(1e-2),
                              shard_axis="data", shard_size=2)
        st = jax.eval_shape(bad.init, params)
        with pytest.raises(ValueError, match=r"shard_size=2 .* 1 devices"):
            make_dp_train_step(cfg, bad, mesh, zero2=True, opt_state=st)

    def test_accum_validated(self):
        cfg, params, batch, mesh, opt = self._setup()
        st = jax.eval_shape(opt.init, params)
        with pytest.raises(ValueError, match="accum"):
            make_dp_train_step(cfg, opt, mesh, zero2=True, opt_state=st,
                               accum=0)


class TestUpdateApplyBucketContract:
    def test_per_bucket_entry_matches_update_apply_sharded(self):
        """Driving the public per-bucket entry point (Optimizer.
        update_apply_bucket) and scattering the results manually is bitwise
        update_apply_sharded with the same clip_scale — the loop form and
        the per-bucket form cannot drift apart."""
        from repro.core.bucketing import scatter
        from repro.distributed.compression import exact_reduce_scatter

        mesh = make_data_mesh(1)
        opt = make_optimizer("rmnp", lr_matrix=0.1, beta=0.9,
                             shard_axis="data", shard_size=1)
        shapes = {"a/w": (2, 8, 16), "b/w": (8, 16), "c/w": (3, 8, 24)}
        params = _tree(shapes, seed=0)
        grads = _tree(shapes, seed=1)
        state = opt.init(params)
        plan = opt.bucket_plan(params)
        clip = jnp.float32(0.5)

        def shards_of(g):
            chunks = gather_chunks(plan, g, 1, dtype=jnp.float32)
            return {b.key: exact_reduce_scatter(chunks[b.key], "data")
                    for b in plan.buckets}

        def via_sharded(g, s, p):
            return opt.update_apply_sharded(shards_of(g), g, s, p, 0,
                                            clip_scale=clip)

        def via_bucket(g, s, p):
            shards = shards_of(g)
            w_chunks = gather_chunks(plan, p, 1)
            w_b, v_b = {}, {}
            for b in plan.buckets:
                w_b[b.key], v_b[b.key], _ = opt.update_apply_bucket(
                    b, shards[b.key], s.buckets[b.key], w_chunks[b.key],
                    0, clip)
            return scatter(plan, w_b, p, cast=True), v_b

        def run(fn):
            return jax.jit(shard_map(
                fn, mesh=mesh, in_specs=(P(), P(), P()), out_specs=(P(), P()),
                check_vma=False))(grads, state, params)
        p_ref, s_ref = run(via_sharded)
        p_bkt, v_bkt = run(via_bucket)
        for k in p_ref:
            np.testing.assert_array_equal(np.asarray(p_ref[k]),
                                          np.asarray(p_bkt[k]), err_msg=k)
        for k in s_ref.buckets:
            np.testing.assert_array_equal(np.asarray(s_ref.buckets[k]),
                                          np.asarray(v_bkt[k]), err_msg=k)
