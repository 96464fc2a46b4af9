"""Single-pass apply (the update folded into the RMNP kernel) and
ZeRO-1/2 sharding of the bucketed optimizer state and gradients.

Invariants under test:
  * the bucketed engine's ``Optimizer.update_apply`` is bit-for-bit with
    fp32 storage against the per-leaf references (``per_leaf_reference``
    for matrix leaves, the per-leaf mixed path for the rest), on both the
    XLA and interpret-mode Pallas backends;
  * it materializes no full-bucket fp32 ``d`` buffer, and its
    ``pallas_call`` emits no fp32 bucket-shaped output with bf16 momentum
    and bf16 weights;
  * kernel launches stay one per shape bucket;
  * bf16 momentum storage drifts boundedly from fp32 storage over a ~50
    step fused-apply run;
  * ZeRO-1 and ZeRO-2 sharding over a real multi-device CPU mesh: per-rank
    stacked momentum bytes shrink N x (padded uneven buckets included), the
    sharded steps match the replicated step bit-for-bit, and the ZeRO-2
    step materializes no full-bucket fp32 gradient (subprocess — the
    device-count flag must precede jax init);
  * pad slices are zero-filled, inert, and dropped on scatter; a mis-sized
    momentum buffer raises instead of slicing garbage; the plan cache is a
    bounded LRU;
  * train steps dispatch on ``update_apply`` and the dp step validates its
    sharding preconditions.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (constant, make_optimizer, mixed_optimizer,
                         optimizer_names)
from repro.core.bucketing import build_plan, gather
from repro.core.rules import RmnpRule, per_leaf_reference
from repro.launch.mesh import make_data_mesh
from repro.kernels.ops import count_buffer_eqns
from repro.train.step import (_optimizer_call, optimizer_fp32_buffers,
                              optimizer_launches)

RAGGED_SHAPES = {
    "layer_0/w_in": (8, 16),
    "layer_1/w_in": (8, 16),
    "stack/w_in": (3, 8, 16),     # scan/expert leading axis
    "layer_0/w_out": (16, 8),
    "odd/w": (24, 9),             # 9 % block_n != 0 -> padded stripe
}


def make_tree(shapes, seed=0, with_vectors=False):
    tree = {k: jax.random.normal(
        jax.random.fold_in(jax.random.PRNGKey(seed), i), shape, jnp.float32)
        for i, (k, shape) in enumerate(sorted(shapes.items()))}
    if with_vectors:
        tree["norm"] = jax.random.normal(jax.random.PRNGKey(seed + 900), (8,))
        tree["bias"] = jax.random.normal(jax.random.PRNGKey(seed + 901), (16,))
    return tree


def bucketed(use_kernel=False, **kw):
    """The bucketed RMNP engine through the registry."""
    return make_optimizer("rmnp", dict(lr_matrix=0.1, fused=True,
                                       use_kernel=use_kernel, **kw))


def matrix_reference(use_kernel, beta=0.95):
    """The per-leaf reference for RMNP's matrix leaves on one backend.  On
    XLA it is the per-leaf mixed path, the paper's formulation written
    apart from the rules and the kernel (``row_normalize``, then the
    updates tree).  The interpret-mode kernel rounds its row norm
    differently from that formulation (within 1e-5 of it,
    tests/test_kernels.py), so its bit-for-bit reference launches the
    same kernel once per leaf (``per_leaf_reference``)."""
    if use_kernel:
        return per_leaf_reference(RmnpRule(beta=beta), constant(0.1),
                                  use_kernel=True)
    return mixed_optimizer("rmnp", constant(0.1), constant(0.05), beta=beta)


class TestSinglePassBitwise:
    """The engine against the per-leaf references, op by op.  Eagerly
    every operation is its own program, so stacking slices into a bucket
    changes no value; jitted, XLA fuses the per-leaf and the stacked
    programs differently and may contract a multiply-add differently
    (ROADMAP D1).  The jitted engine, the one that runs in production,
    agrees to FMA-contraction level."""

    @pytest.mark.parametrize("use_kernel", [False, True],
                             ids=["xla", "pallas-interpret"])
    def test_rmnp_matches_two_pass(self, use_kernel):
        params = make_tree(RAGGED_SHAPES)
        ref = matrix_reference(use_kernel, beta=0.9)
        eng = bucketed(use_kernel, beta=0.9)
        plan = eng.bucket_plan(params)
        jitted = jax.jit(eng.update_apply)
        sr, sf, sj = ref.init(params), eng.init(params), eng.init(params)
        pr, pf, pj = params, params, params
        for step in range(3):
            grads = make_tree(RAGGED_SHAPES, seed=100 + step)
            pr, sr = ref.update_apply(grads, sr, pr, jnp.int32(step))
            pf, sf = eng.update_apply(grads, sf, pf, jnp.int32(step))
            pj, sj = jitted(grads, sj, pj, jnp.int32(step))
            for k in pr:
                np.testing.assert_array_equal(
                    np.asarray(pr[k]), np.asarray(pf[k]),
                    err_msg=f"{k} (use_kernel={use_kernel}, step={step})")
                np.testing.assert_allclose(
                    np.asarray(pj[k]), np.asarray(pf[k]), rtol=1e-6,
                    atol=1e-7, err_msg=f"{k} jitted (step={step})")
            ref_buckets = gather(plan, sr.momentum)
            for k in sf.buckets:
                np.testing.assert_array_equal(
                    np.asarray(ref_buckets[k]), np.asarray(sf.buckets[k]))

    @pytest.mark.parametrize("use_kernel", [False, True],
                             ids=["xla", "pallas-interpret"])
    def test_mixed_matches_two_pass(self, use_kernel):
        """Matrix leaves against ``matrix_reference``, AdamW leaves against
        the per-leaf mixed path."""
        params = make_tree(RAGGED_SHAPES, with_vectors=True)
        matrix = {k: v for k, v in params.items() if k in RAGGED_SHAPES}
        leaf = mixed_optimizer("rmnp", constant(0.1), constant(0.05))
        ref = matrix_reference(use_kernel)
        eng = mixed_optimizer("rmnp", constant(0.1), constant(0.05),
                              use_kernel=use_kernel, fused=True)
        sl, sr, sf = leaf.init(params), ref.init(matrix), eng.init(params)
        pl_, pr, pf = params, matrix, params
        for step in range(3):
            grads = make_tree(RAGGED_SHAPES, seed=100 + step,
                              with_vectors=True)
            pl_, sl = leaf.update_apply(grads, sl, pl_, jnp.int32(step))
            pr, sr = ref.update_apply({k: grads[k] for k in matrix}, sr, pr,
                                      jnp.int32(step))
            pf, sf = eng.update_apply(grads, sf, pf, jnp.int32(step))
            for k in pf:
                want = pr[k] if k in matrix else pl_[k]
                np.testing.assert_array_equal(
                    np.asarray(want), np.asarray(pf[k]),
                    err_msg=f"{k} (use_kernel={use_kernel}, step={step})")

    def test_mixed_dtype_bucket_keeps_leaf_dtypes(self):
        """Leaves of different dtypes sharing a shape bucket promote when
        the params gather concatenates; update_apply must cast each slice
        back so param dtypes stay stable across steps (no recompiles)."""
        params = {"a/w": jnp.zeros((8, 16), jnp.bfloat16),
                  "b/w": jnp.zeros((8, 16), jnp.float32)}
        grads = make_tree({"a/w": (8, 16), "b/w": (8, 16)}, seed=3)
        opt = bucketed()
        new_params, _ = jax.jit(opt.update_apply)(
            grads, opt.init(params), params, jnp.int32(0))
        assert new_params["a/w"].dtype == jnp.bfloat16
        assert new_params["b/w"].dtype == jnp.float32

    def test_fused_apply_implies_fused(self):
        """``fused`` selects the bucketed engine, whose one entry point is
        ``update_apply``: no updates tree, stacked state."""
        opt = bucketed()
        assert opt.update_apply is not None and opt.update is None
        state = opt.init(make_tree(RAGGED_SHAPES))
        assert hasattr(state, "buckets")
        # the per-leaf path keeps its updates tree, applied by update_apply
        leaf = mixed_optimizer("rmnp", constant(0.1), constant(0.05))
        assert leaf.update is not None and leaf.update_apply is not None
        assert not hasattr(leaf.init(make_tree(RAGGED_SHAPES)), "buckets")

    def test_shard_axis_implies_fused_apply(self):
        """shard_axis outside the bucketed layout would silently replicate
        the state, so setting it must select the bucketed engine."""
        assert make_optimizer("rmnp", lr_matrix=0.1,
                              shard_axis="data").bucket_plan is not None
        assert mixed_optimizer("rmnp", constant(0.1), constant(0.05),
                               shard_axis="data").bucket_plan is not None


@pytest.mark.parametrize("name", optimizer_names())
def test_use_kernel_needs_the_bucketed_engine(name):
    """The per-leaf path is plain jax.numpy and runs no kernel, so asking it
    for one raises, naming the fix, instead of falling back silently;
    rules that exist only bucketed take the engine anyway."""
    if name in ("rmnp", "muon", "adamw"):
        with pytest.raises(ValueError, match="fused=True"):
            make_optimizer(name, lr_matrix=0.1, use_kernel=True)
    else:
        assert make_optimizer(name, lr_matrix=0.1,
                              use_kernel=True).bucket_plan is not None
    opt = make_optimizer(name, lr_matrix=0.1, use_kernel=True, fused=True)
    assert opt.bucket_plan is not None and opt.update is None


class TestNoFp32Intermediate:
    """The single-pass engine's memory claim, verified by tracing."""

    def test_fewer_full_bucket_fp32_buffers(self):
        """With bf16 weights and bf16 momentum the kernel path's only
        full-bucket fp32 buffer is the gathered gradient: no fp32 ``d``
        bucket, no fp32 update."""
        params = jax.tree_util.tree_map(
            lambda p: p.astype(jnp.bfloat16),
            make_tree({"a/w": (8, 16), "b/w": (8, 16), "c/w": (2, 8, 16)}))
        bucket_shape = (4, 8, 16)
        opt = bucketed(True, momentum_dtype="bfloat16")
        one = optimizer_fp32_buffers(opt, params, bucket_shape)
        # the one: the gradient gather (a concatenate of the fp32 leaves)
        assert one == 1, one
        fn, args = _optimizer_call(opt, params)
        assert count_buffer_eqns(fn, bucket_shape, jnp.float32, *args,
                                 exclude_prims=("concatenate",)) == 0

    def test_kernel_emits_no_fp32_d_bucket(self):
        """With bf16 momentum AND bf16 params, the apply kernel must have no
        fp32 bucket-shaped output at all (a ``d`` bucket would be one)."""
        from repro.kernels.ops import _walk_eqns

        shapes = {"a/w": (8, 16), "b/w": (8, 16), "c/w": (2, 8, 16)}
        params = jax.tree_util.tree_map(
            lambda p: p.astype(jnp.bfloat16), make_tree(shapes))
        L = 4

        def pallas_fp32_outputs(opt, fn_name):
            fn = getattr(opt, fn_name)
            def abstract(t):
                return jax.tree_util.tree_map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t)
            state = jax.eval_shape(opt.init, params)
            closed = jax.make_jaxpr(fn)(abstract(params), state,
                                        abstract(params), jnp.int32(0))

            def visit(eqn):
                if eqn.primitive.name != "pallas_call":
                    return 0
                return sum(1 for v in eqn.outvars
                           if v.aval.dtype == jnp.float32
                           and len(v.aval.shape) == 3
                           and v.aval.shape[0] == L)

            return _walk_eqns(closed.jaxpr, visit)

        one = bucketed(True, momentum_dtype="bfloat16")
        assert pallas_fp32_outputs(one, "update_apply") == 0

    def test_launches_stay_one_per_bucket(self):
        params = make_tree(RAGGED_SHAPES)
        n_buckets = len(build_plan(params).buckets)
        one = bucketed(True)
        assert optimizer_launches(one, params) == n_buckets == 3
        mixed = mixed_optimizer("rmnp", constant(0.1), constant(0.05),
                                use_kernel=True, fused=True)
        assert optimizer_launches(
            mixed, make_tree(RAGGED_SHAPES, with_vectors=True)) == 3


class TestBf16MomentumDrift:
    def test_bounded_drift_over_50_fused_apply_steps(self):
        """bf16 momentum storage (fp32 math) must track the fp32-storage
        trajectory to within bf16 rounding accumulation — bounded, not
        divergent — over a multi-step fused-apply run."""
        shapes = {"a/w": (8, 16), "b/w": (16, 8), "s/w": (2, 8, 16)}
        params = make_tree(shapes)
        o32 = make_optimizer("rmnp", lr_matrix=0.05, beta=0.9, fused=True)
        o16 = make_optimizer("rmnp", lr_matrix=0.05, beta=0.9, fused=True,
                             momentum_dtype="bfloat16")
        s32, s16 = o32.init(params), o16.init(params)
        step32 = jax.jit(o32.update_apply)
        step16 = jax.jit(o16.update_apply)
        p32, p16 = params, params
        for step in range(50):
            grads = make_tree(shapes, seed=1000 + step)
            p32, s32 = step32(grads, s32, p32, jnp.int32(step))
            p16, s16 = step16(grads, s16, p16, jnp.int32(step))
        for k in p32:
            a, b = np.asarray(p32[k]), np.asarray(p16[k])
            drift = np.max(np.abs(a - b))
            # row-normalized updates are O(lr) per step; 50 steps of bf16
            # momentum rounding must stay well under one update's magnitude
            assert drift < 0.05, f"{k}: drift {drift}"
            assert np.all(np.isfinite(b))


class TestZeroSharding:
    @pytest.mark.skipif(os.environ.get("CI") == "true",
                        reason="CI runs tests/_zero_shard_worker.py as a "
                               "dedicated workflow step (visible output); "
                               "running it here too would double the "
                               "slowest job in the suite")
    def test_sharded_step_matches_replicated_subprocess(self):
        """4-device CPU mesh: per-rank momentum = padded L/N slices (bytes
        shrink N x), uneven buckets pad + shard under shard_size, ZeRO-1
        and ZeRO-2 both match the replicated step bitwise, the ZeRO-2 step
        traces with zero full-bucket fp32 gradient intermediates, and the
        full dp train step agrees end-to-end on a 2-way mesh."""
        worker = Path(__file__).parent / "_zero_shard_worker.py"
        env = dict(os.environ,
                   XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") +
                              " --xla_force_host_platform_device_count=4").strip(),
                   JAX_PLATFORMS="cpu",
                   PYTHONPATH=os.pathsep.join(
                       [str(Path(__file__).resolve().parents[1] / "src"),
                        os.environ.get("PYTHONPATH", "")]).rstrip(os.pathsep))
        out = subprocess.run([sys.executable, str(worker)], env=env,
                             capture_output=True, text=True, timeout=600)
        assert out.returncode == 0, f"worker failed:\n{out.stdout}\n{out.stderr}"
        assert "ZERO_SHARD_OK" in out.stdout

    def test_shard_state_requires_fused_apply(self):
        from repro.configs import get_config
        from repro.train.dp_step import make_dp_train_step

        mesh = make_data_mesh(1)
        cfg = get_config("gpt2-60m").reduced()
        per_leaf = mixed_optimizer("rmnp", constant(0.1), constant(0.05))
        with pytest.raises(ValueError, match="bucketed engine"):
            make_dp_train_step(cfg, per_leaf, mesh, shard_state=True)

    def test_shard_state_requires_state_example(self):
        from repro.configs import get_config
        from repro.train.dp_step import make_dp_train_step

        mesh = make_data_mesh(1)
        cfg = get_config("gpt2-60m").reduced()
        opt = mixed_optimizer("rmnp", constant(0.1), constant(0.05),
                              shard_axis="data")
        with pytest.raises(ValueError, match="opt_state"):
            make_dp_train_step(cfg, opt, mesh, shard_state=True)

    def test_zero2_requires_sharded_optimizer(self):
        """zero2 needs update_apply_sharded (shard_axis + shard_size at
        optimizer construction); a plain fused-apply optimizer must be
        rejected up front, not fail mid-trace."""
        from repro.configs import get_config
        from repro.train.dp_step import make_dp_train_step

        mesh = make_data_mesh(1)
        cfg = get_config("gpt2-60m").reduced()
        opt = mixed_optimizer("rmnp", constant(0.1), constant(0.05),
                              fused=True)
        state = jax.eval_shape(
            opt.init, {"a/w": jnp.zeros((8, 16), jnp.float32)})
        with pytest.raises(ValueError, match="update_apply_sharded"):
            make_dp_train_step(cfg, opt, mesh, zero2=True, opt_state=state)

    def test_bucket_specs_ignores_param_paths_named_buckets(self):
        """Only the state's top-level `buckets` field is stacked momentum:
        a 3-D AdamW state leaf whose *parameter* path contains 'buckets'
        (under momentum/nu) must stay replicated, not get a ZeRO spec."""
        from repro.distributed.sharding import bucket_specs

        mesh = make_data_mesh(1)
        shapes = dict(RAGGED_SHAPES)
        params = make_tree(shapes)
        # 'conv' token routes this 3-D leaf to AdamW (full-shape mu/nu)
        params["rel_pos_buckets/conv"] = jnp.zeros((4, 3, 64))
        opt = mixed_optimizer("rmnp", constant(0.1), constant(0.05),
                              fused=True)
        state = opt.init(params)
        assert state.momentum["rel_pos_buckets/conv"].shape == (4, 3, 64)
        specs = bucket_specs(state, mesh)
        # bucket leaves go through spec_for (rank-3 spec, possibly all-None
        # on a tiny mesh); everything else must take the bare-P() branch
        assert all(len(s) == 3 for s in specs.buckets.values())
        assert len(specs.momentum["rel_pos_buckets/conv"]) == 0
        assert len(specs.nu["rel_pos_buckets/conv"]) == 0

    def test_bucket_specs_uneven_replicates(self):
        from repro.distributed.sharding import bucket_specs

        mesh = make_data_mesh(1)
        opt = bucketed()
        state = opt.init(make_tree(RAGGED_SHAPES))
        specs = bucket_specs(state, mesh)
        # size-1 mesh axis: every bucket falls back to replication
        assert all(all(ax is None for ax in s)
                   for s in specs.buckets.values())


class TestPaddedBuckets:
    """Uneven-bucket padding (shard_size): pad slices are zero-filled,
    mathematically inert, and dropped on scatter — so the padded optimizer
    is bit-identical to the unpadded one wherever both run."""

    def test_padded_replicated_matches_unpadded(self):
        params = make_tree(RAGGED_SHAPES)
        pad = make_optimizer("rmnp", lr_matrix=0.1, beta=0.9,
                             shard_axis="data", shard_size=4)
        ref = bucketed(beta=0.9)
        sizes = {b.key: b.size for b in ref.bucket_plan(params).buckets}
        sp, sr = pad.init(params), ref.init(params)
        pp, pr = params, params
        for step in range(3):
            grads = make_tree(RAGGED_SHAPES, seed=50 + step)
            pp, sp = jax.jit(pad.update_apply)(grads, sp, pp, jnp.int32(step))
            pr, sr = jax.jit(ref.update_apply)(grads, sr, pr, jnp.int32(step))
            for k in pp:
                np.testing.assert_array_equal(np.asarray(pp[k]),
                                              np.asarray(pr[k]), err_msg=k)
            for k, v in sp.buckets.items():
                assert v.shape[0] % 4 == 0, (k, v.shape)
                np.testing.assert_array_equal(
                    np.asarray(v[:sizes[k]]), np.asarray(sr.buckets[k]))
                # pad-slice invariant: zero grad -> zero momentum, forever
                assert np.all(np.asarray(v[sizes[k]:]) == 0), (k, step)

    def test_gather_pads_zero_scatter_drops(self):
        from repro.core.bucketing import build_plan, gather, scatter

        tree = make_tree({"a/w": (3, 8, 16)})
        plan = build_plan(tree, pad_multiple=4)
        (b,) = plan.buckets
        assert (b.size, b.padded) == (3, 4)
        g = gather(plan, tree, dtype=jnp.float32)["8x16"]
        assert g.shape == (4, 8, 16)
        assert np.all(np.asarray(g[3:]) == 0)
        out = scatter(plan, {"8x16": g}, tree)
        np.testing.assert_array_equal(np.asarray(out["a/w"]),
                                      np.asarray(tree["a/w"]))

    def test_shard_size_needs_axis(self):
        with pytest.raises(ValueError, match="shard_axis"):
            make_optimizer("rmnp", lr_matrix=0.1, shard_size=4)
        with pytest.raises(ValueError, match="shard_axis"):
            mixed_optimizer("rmnp", constant(0.1), constant(0.05),
                            shard_size=4)


class TestShardInference:
    """BucketedEngine.bucket_apply must validate the momentum slice count
    instead of inferring sharding from any size mismatch — a stale or
    mis-meshed buffer would otherwise produce a garbage dynamic_slice."""

    @staticmethod
    def _engine(shard_axis=None):
        from repro.core.engine import BucketedEngine

        params = make_tree({"a/w": (8, 16), "b/w": (2, 8, 16), "c/w": (8, 16)})
        eng = BucketedEngine(RmnpRule(beta=0.9, weight_decay=0.0),
                             constant(0.1), shard_axis=shard_axis)
        (b,) = eng.plan(params).buckets  # L=4
        return eng, b

    def test_missized_momentum_raises(self):
        eng, b = self._engine(shard_axis="data")
        g = jnp.zeros((4, 8, 16), jnp.float32)
        w = jnp.zeros((4, 8, 16), jnp.float32)
        v_bad = jnp.zeros((3, 8, 16), jnp.float32)  # 4 % 3 != 0
        with pytest.raises(ValueError) as ei:
            eng.bucket_apply(b, g, v_bad, {}, w, 0)
        msg = str(ei.value)
        assert "8x16" in msg and "3" in msg and "4" in msg

    def test_missized_operands_raise(self):
        eng, b = self._engine()
        v = jnp.zeros((4, 8, 16), jnp.float32)
        g_bad = jnp.zeros((3, 8, 16), jnp.float32)
        with pytest.raises(ValueError, match="padded bucket"):
            eng.bucket_apply(b, g_bad, v, {}, g_bad, 0)

    def test_sharded_without_axis_raises(self):
        eng, b = self._engine()
        g = jnp.zeros((4, 8, 16), jnp.float32)
        v_shard = jnp.zeros((2, 8, 16), jnp.float32)
        with pytest.raises(ValueError, match="shard_axis"):
            eng.bucket_apply(b, g, v_shard, {}, g, 0)


class TestPlanCache:
    """The leaf->bucket plan cache must stay bounded when one optimizer
    serves many param signatures (long-lived serving processes)."""

    def test_lru_eviction_and_hit_order(self):
        from repro.core.bucketing import PlanCache

        cache = PlanCache(maxsize=2)
        builds = []
        def get(k):
            return cache.get(k, lambda: builds.append(k) or k)
        assert get("a") == "a" and get("b") == "b"
        assert get("a") == "a"          # hit: refreshes 'a'
        get("c")                        # evicts 'b' (LRU), not 'a'
        assert len(cache) == 2
        get("a")
        assert builds == ["a", "b", "c"]  # 'a' never rebuilt
        get("b")                        # rebuilt after eviction
        assert builds == ["a", "b", "c", "b"]

    def test_default_capacity_eight_eviction_order(self):
        """The default cache holds 8 plans; filling past capacity evicts in
        LRU order, refreshed entries survive."""
        from repro.core.bucketing import PlanCache

        cache = PlanCache()
        assert cache.maxsize == 8
        builds = []
        def get(k):
            return cache.get(k, lambda: builds.append(k) or k)
        for k in "abcdefgh":
            get(k)
        assert len(cache) == 8
        get("a")                          # refresh: 'b' is now LRU
        get("i")                          # evicts 'b'
        assert len(cache) == 8
        assert builds == list("abcdefghi")
        get("a")                          # still cached
        assert builds == list("abcdefghi")
        get("b")                          # rebuilt after eviction
        assert builds == list("abcdefghib")

    def test_hit_on_reused_signature(self):
        """Two param trees with identical (path, shape) signatures share
        the cached plan object — values don't matter, metadata does."""
        opt = bucketed()
        shapes = {"a/w": (8, 16), "b/w": (2, 8, 16)}
        plan1 = opt.bucket_plan(make_tree(shapes, seed=0))
        plan2 = opt.bucket_plan(make_tree(shapes, seed=9))
        assert plan1 is plan2
        # a different signature builds a different plan...
        plan3 = opt.bucket_plan(make_tree({"a/w": (8, 32)}))
        assert plan3 is not plan1
        # ...and the original signature still hits
        assert opt.bucket_plan(make_tree(shapes, seed=4)) is plan1

    def test_eviction_does_not_break_inflight_jitted_step(self):
        """A jitted step whose plan gets evicted keeps working: the plan is
        baked into the existing trace, and a re-trace (new signature churn
        in between) just rebuilds it."""
        opt = bucketed()
        shapes = {"w": (8, 16)}
        params = make_tree(shapes, seed=0)
        grads = make_tree(shapes, seed=1)
        state = opt.init(params)
        step = jax.jit(lambda g, s, p: opt.update_apply(g, s, p, 0))
        p_before, _ = step(grads, state, params)
        # churn > maxsize distinct signatures: the (8, 16) plan is evicted
        for i in range(10):
            churn = make_tree({"w": (8, 24 + 8 * i)}, seed=i)
            opt.update_apply(make_tree({"w": (8, 24 + 8 * i)}, seed=50 + i),
                             opt.init(churn), churn, jnp.int32(0))
        # the in-flight jitted step still runs and agrees with its first
        # result (cache hit in jit -> no retrace; the optimizer state was
        # not donated here so the inputs are unchanged)
        p_after, _ = step(grads, state, params)
        np.testing.assert_array_equal(np.asarray(p_before["w"]),
                                      np.asarray(p_after["w"]))

    def test_optimizer_plan_cache_bounded(self):
        opt = bucketed()
        step = None
        for i in range(12):  # > PlanCache default maxsize
            shapes = {"w": (8, 16 + 8 * i)}
            params = make_tree(shapes, seed=i)
            grads = make_tree(shapes, seed=100 + i)
            p, s = opt.update_apply(grads, opt.init(params), params,
                                    jnp.int32(0))
            assert p["w"].shape == params["w"].shape
        # the internal cache is a closure; its bound is observable through
        # PlanCache itself (above) — here we only require correctness to
        # survive arbitrary signature churn, including re-visiting old ones
        params = make_tree({"w": (8, 16)}, seed=0)
        grads = make_tree({"w": (8, 16)}, seed=200)
        p, _ = opt.update_apply(grads, opt.init(params), params, jnp.int32(0))
        assert p["w"].shape == (8, 16)


class TestTrainStepDispatch:
    def test_end_to_end_fused_apply_train(self):
        from repro.launch.train import train

        _, opt_state, hist = train("gpt2-60m", "rmnp", steps=4, batch=2,
                                   seq=16, fused_apply=True, log_every=2)
        assert hasattr(opt_state, "buckets") and opt_state.buckets
        assert all(np.isfinite(h["loss"]) for h in hist)

    @pytest.mark.parametrize("zero2", [False, True],
                             ids=["replicated", "zero2"])
    def test_kernel_train_through_launcher(self, zero2):
        """The launcher's own mesh and the Pallas kernel together: every
        bucket takes the kernel, and both the replicated and the ZeRO-2
        (shard_map) steps train."""
        from repro.launch.train import StepReport, train

        report = StepReport()
        _, _, hist = train("gpt2-60m", "rmnp", steps=2, batch=2, seq=16,
                           use_kernel=True, fused_apply=True, zero2=zero2,
                           compress=False, log_every=1, report=report)
        assert len(hist) == 2
        assert all(np.isfinite(h["loss"]) for h in hist)
        assert report.routes and all(ln is not None
                                     for ln in report.routes.values())

    def test_pjit_step_uses_update_apply(self):
        """make_train_step routes every optimizer through update_apply: the
        per-leaf path's update_apply is its update + apply_updates, the
        bucketed engine's the single pass, and they share math, so one step
        from the same state is equal bit for bit (fp32 model).  The two
        steps are compared op by op: jitted, XLA fuses the per-leaf and the
        stacked programs differently and may contract a multiply-add
        differently (ROADMAP D1).  The jitted bucketed step, the one that
        runs in production, agrees with them to FMA-contraction level."""
        from repro.configs import get_config
        from repro.core.types import tree_paths
        from repro.models import init_params
        from repro.train.step import make_train_step

        cfg = get_config("gpt2-60m").reduced(dtype="float32")
        params = init_params(cfg, jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                  cfg.vocab)
        batch = {"tokens": toks, "labels": toks}
        outs = {}
        for name, kw in (("leaf", dict()),
                         ("one", dict(fused=True))):
            opt = mixed_optimizer("rmnp", constant(1e-2), constant(1e-2), **kw)
            step = make_train_step(cfg, opt, remat="none")
            outs[name] = step(params, opt.init(params), batch, jnp.int32(0))
            if name == "one":
                outs["jit"] = jax.jit(step)(params, opt.init(params), batch,
                                            jnp.int32(0))
        for (k, a), (_, b), (_, c) in zip(tree_paths(outs["leaf"][0]),
                                          tree_paths(outs["one"][0]),
                                          tree_paths(outs["jit"][0]),
                                          strict=True):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=k)
            np.testing.assert_allclose(np.asarray(c), np.asarray(b),
                                       rtol=1e-6, atol=1e-7, err_msg=k)
