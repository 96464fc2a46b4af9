"""Shape-bucketed fused update engine (core/bucketing.py).

Invariants under test:
  * the leaf->bucket plan groups by trailing (d_in, d_out) with leading
    scan/expert axes flattened, and gather/scatter round-trip exactly;
  * bucketed steps match the per-leaf references bit-for-bit in fp32, on
    both the XLA and the interpret-mode Pallas backends, across ragged
    shape mixes, padding remainders, and leading axes;
  * kernel launches per optimizer step equal the number of shape buckets
    (bucketed) vs the number of matrix leaves (per-leaf reference);
  * plan_stripes' one VMEM accounting picks a lane block of at least 128
    lanes, the launch's VMEM limit, and the kernel-or-XLA routing.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_support import given, settings, st

from repro.core import constant, make_optimizer, mixed_optimizer
from repro.core.bucketing import build_plan, gather, init_buckets, scatter
from repro.core.rules import MuonRule, RmnpRule, per_leaf_reference
from repro.kernels.rmnp_update import (APPLY_TEMPS, LANE, MAX_BLOCK_N,
                                       VMEM_LIMIT_CAP, plan_stripes,
                                       stripe_vmem_bytes)
from repro.train.step import optimizer_launches

# ragged mix: two shared buckets (8x16 with a scan stack, 16x8) + a loner,
# including a d_out that is not a multiple of the kernel block (padding path)
RAGGED_SHAPES = {
    "layer_0/w_in": (8, 16),
    "layer_1/w_in": (8, 16),
    "stack/w_in": (3, 8, 16),     # scan/expert leading axis
    "layer_0/w_out": (16, 8),
    "odd/w": (24, 9),             # 9 % block_n != 0 -> padded stripe
}


def make_tree(shapes, seed=0):
    return {k: jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(seed), i),
                                 shape, jnp.float32)
            for i, (k, shape) in enumerate(sorted(shapes.items()))}


class TestBucketPlan:
    def test_groups_by_trailing_shape(self):
        plan = build_plan(make_tree(RAGGED_SHAPES))
        keys = {b.key: b for b in plan.buckets}
        assert set(keys) == {"8x16", "16x8", "24x9"}
        assert keys["8x16"].size == 1 + 1 + 3     # scan stack contributes 3 slices
        assert keys["16x8"].size == 1
        assert plan.n_leaves == 5

    def test_offsets_partition_the_bucket(self):
        plan = build_plan(make_tree(RAGGED_SHAPES))
        for b in plan.buckets:
            offset = 0
            for e in b.entries:
                assert e.offset == offset
                offset += e.lead
            assert offset == b.size

    def test_gather_scatter_roundtrip(self):
        tree = make_tree(RAGGED_SHAPES)
        plan = build_plan(tree)
        stacked = gather(plan, tree)
        back = scatter(plan, stacked, jax.tree_util.tree_map(jnp.zeros_like, tree))
        for k in tree:
            np.testing.assert_array_equal(np.asarray(back[k]), np.asarray(tree[k]))

    def test_init_buckets_shapes_and_dtype(self):
        plan = build_plan(make_tree(RAGGED_SHAPES))
        bufs = init_buckets(plan, jnp.bfloat16)
        assert bufs["8x16"].shape == (5, 8, 16)
        assert all(b.dtype == jnp.bfloat16 for b in bufs.values())

    def test_plan_leaves_out_vectors(self):
        """The default predicate buckets matrices only; a vector stays out
        of the plan and out of the gathered buckets."""
        tree = {"w": jnp.ones((4, 4)), "b": jnp.ones((4,))}
        plan = build_plan(tree)
        assert [e.path for b in plan.buckets for e in b.entries] == ["w"]
        assert set(gather(plan, tree)) == {"4x4"}

    def test_shape_change_detected(self):
        tree = make_tree(RAGGED_SHAPES)
        plan = build_plan(tree)
        tree["odd/w"] = jnp.ones((9, 24))
        with pytest.raises(ValueError, match="changed shape"):
            gather(plan, tree)

    def test_missing_leaf_names_path_and_bucket(self):
        """A planned path absent from the tree (e.g. after a params
        refactor) must raise a ValueError naming the missing path and the
        plan's bucket key, not a bare KeyError."""
        tree = make_tree(RAGGED_SHAPES)
        plan = build_plan(tree)
        del tree["odd/w"]
        with pytest.raises(ValueError, match=r"odd/w.*24x9"):
            gather(plan, tree)

    def test_expert_axes_roundtrip(self):
        """Leaves with several leading axes — e.g. (experts, layers, d, 4d)
        MoE stacks — flatten into lead = experts * layers bucket slices and
        must scatter back exactly."""
        shapes = {
            "moe/w_in": (2, 3, 4, 16),    # experts x layers x d x 4d
            "dense/w_in": (4, 16),
            "moe/w_out": (2, 3, 16, 4),
        }
        tree = make_tree(shapes)
        plan = build_plan(tree)
        keys = {b.key: b for b in plan.buckets}
        assert keys["4x16"].size == 2 * 3 + 1
        assert keys["16x4"].size == 2 * 3
        stacked = gather(plan, tree)
        assert stacked["4x16"].shape == (7, 4, 16)
        back = scatter(plan, stacked,
                       jax.tree_util.tree_map(jnp.zeros_like, tree))
        for k in tree:
            np.testing.assert_array_equal(np.asarray(back[k]),
                                          np.asarray(tree[k]))


def _run_pair(shapes, use_kernel, steps=3, seed=0):
    """(per-leaf params, bucketed params) trajectories over a few steps,
    op by op (jitted, XLA fuses the per-leaf and the stacked programs
    differently — ROADMAP D1).  On XLA the reference is the per-leaf mixed
    path, the paper's formulation written apart from the rules
    (``row_normalize``, then the updates tree).  The interpret-mode kernel
    rounds its row norm differently from it (within 1e-5, tests/
    test_kernels.py), so its bit-for-bit reference launches the kernel
    once per leaf."""
    params = make_tree(shapes, seed)
    ref = (per_leaf_reference(RmnpRule(beta=0.9), constant(0.1),
                              use_kernel=True) if use_kernel
           else mixed_optimizer("rmnp", constant(0.1), constant(0.1),
                                beta=0.9))
    fus = make_optimizer("rmnp", lr_matrix=0.1, beta=0.9,
                         use_kernel=use_kernel, fused=True)
    sr, sf = ref.init(params), fus.init(params)
    pr, pf = params, params
    outs = []
    for step in range(steps):
        grads = make_tree(shapes, seed=seed + 100 + step)
        pr, sr = ref.update_apply(grads, sr, pr, step)
        pf, sf = fus.update_apply(grads, sf, pf, step)
        outs.append((pr, pf))
    return outs


class TestFusedMatchesPerLeaf:
    @pytest.mark.parametrize("use_kernel", [False, True],
                             ids=["xla", "pallas-interpret"])
    def test_bitwise_fp32_ragged_mix(self, use_kernel):
        for pr, pf in _run_pair(RAGGED_SHAPES, use_kernel):
            for k in pr:
                np.testing.assert_array_equal(
                    np.asarray(pr[k]), np.asarray(pf[k]),
                    err_msg=f"{k} (use_kernel={use_kernel})")

    def test_xla_vs_kernel_allclose(self):
        """Cross-backend agreement stays a loose allclose (reduction order
        differs); the bitwise claim above is within-backend."""
        for (pr, _), (pk, _) in zip(_run_pair(RAGGED_SHAPES, False),
                                    _run_pair(RAGGED_SHAPES, True), strict=False):
            for k in pr:
                np.testing.assert_allclose(np.asarray(pr[k]), np.asarray(pk[k]),
                                           atol=1e-5)

    def test_mixed_optimizer_fused_matches(self):
        """The mixed bucketed optimizer against the per-leaf mixed path
        (AdamW leaves, and matrix leaves on XLA) and against the per-leaf
        kernel reference (matrix leaves on the kernel)."""
        shapes = dict(RAGGED_SHAPES, norm=(8,), bias=(16,))
        params = make_tree(shapes)
        matrix = {k: params[k] for k in RAGGED_SHAPES}
        for use_kernel in (False, True):
            leaf = mixed_optimizer("rmnp", constant(0.1), constant(0.05))
            ref = per_leaf_reference(RmnpRule(), constant(0.1),
                                     use_kernel=use_kernel)
            fus = mixed_optimizer("rmnp", constant(0.1), constant(0.05),
                                  use_kernel=use_kernel, fused=True)
            sl, sr, sf = leaf.init(params), ref.init(matrix), fus.init(params)
            pl_, pr, pf = params, matrix, params
            for step in range(3):
                grads = make_tree(shapes, seed=7 + step)
                pl_, sl = leaf.update_apply(grads, sl, pl_, step)
                pr, sr = ref.update_apply({k: grads[k] for k in matrix}, sr,
                                          pr, step)
                pf, sf = fus.update_apply(grads, sf, pf, step)
                for k in params:
                    want = (pr[k] if use_kernel and k in matrix
                            else pl_[k])
                    np.testing.assert_array_equal(
                        np.asarray(want), np.asarray(pf[k]), err_msg=k)

    def test_bf16_momentum_storage(self):
        params = make_tree(RAGGED_SHAPES)
        opt = make_optimizer("rmnp", lr_matrix=0.1, fused=True,
                             momentum_dtype="bfloat16")
        state = opt.init(params)
        assert all(b.dtype == jnp.bfloat16 for b in state.buckets.values())
        grads = make_tree(RAGGED_SHAPES, seed=5)
        new, state = opt.update_apply(grads, state, params, 0)
        assert all(b.dtype == jnp.bfloat16 for b in state.buckets.values())
        # math is fp32: vs the fp32-state path the only error is bf16 storage
        ref = make_optimizer("rmnp", lr_matrix=0.1, fused=True)
        sref = ref.init(params)
        new_ref, _ = ref.update_apply(grads, sref, params, 0)
        for k in params:
            np.testing.assert_allclose(np.asarray(new[k]),
                                       np.asarray(new_ref[k]), atol=1e-5)

    @given(st.lists(st.tuples(st.integers(2, 24), st.integers(2, 24),
                              st.integers(0, 3)),
                    min_size=1, max_size=6),
           st.booleans())
    @settings(max_examples=15, deadline=None)
    def test_property_ragged_shape_mixes(self, dims, use_kernel):
        shapes = {}
        for i, (d_in, d_out, lead) in enumerate(dims):
            shapes[f"p{i}/w"] = (lead, d_in, d_out) if lead else (d_in, d_out)
        for pr, pf in _run_pair(shapes, use_kernel, steps=2,
                                seed=sum(d_in for d_in, _, _ in dims)):
            for k in pr:
                np.testing.assert_array_equal(np.asarray(pr[k]),
                                              np.asarray(pf[k]), err_msg=k)


class TestLaunchCounts:
    def test_fused_launches_equal_bucket_count(self):
        params = make_tree(RAGGED_SHAPES)
        n_buckets = len(build_plan(params).buckets)
        n_leaves = len(params)
        fused = make_optimizer("rmnp", lr_matrix=0.1, use_kernel=True,
                               fused=True)
        leaf = per_leaf_reference(RmnpRule(), constant(0.1), use_kernel=True)
        assert optimizer_launches(fused, params) == n_buckets == 3
        assert optimizer_launches(leaf, params) == n_leaves == 5

    def test_mixed_fused_launches(self):
        shapes = dict(RAGGED_SHAPES, norm=(8,), bias=(16,))
        params = make_tree(shapes)
        fused = mixed_optimizer("rmnp", constant(0.1), constant(0.05),
                                use_kernel=True, fused=True)
        leaf = mixed_optimizer("rmnp", constant(0.1), constant(0.05))
        assert optimizer_launches(fused, params) == 3   # buckets, not leaves
        assert optimizer_launches(leaf, params) == 0    # plain jnp reference
        assert optimizer_launches(
            mixed_optimizer("rmnp", constant(0.1), constant(0.05), fused=True),
            params) == 0                                # XLA fallback: no pallas
        # the per-leaf path runs no kernel, so asking it for one is an error
        with pytest.raises(ValueError, match="fused=True"):
            mixed_optimizer("rmnp", constant(0.1), constant(0.05),
                            use_kernel=True)

    def test_muon_fused_batches_ns_over_buckets(self):
        """Fused Muon batches Newton-Schulz over each bucket's stacked L
        axis: launches scale with the bucket count (4 launches per NS
        iteration per bucket — Gram, G@G, polynomial, apply), not the leaf
        count."""
        shapes = dict(RAGGED_SHAPES, norm=(8,), bias=(16,))
        params = make_tree(shapes)
        fused = mixed_optimizer("muon", constant(0.1), constant(0.05),
                                use_kernel=True, fused=True, ns_steps=2)
        leaf = per_leaf_reference(MuonRule(ns_steps=2), constant(0.1),
                                  use_kernel=True)
        # RAGGED_SHAPES: 5 matrix leaves in 3 shape buckets
        assert optimizer_launches(fused, params) == 4 * 2 * 3
        assert optimizer_launches(leaf, make_tree(RAGGED_SHAPES)) == 4 * 2 * 5


class TestPickBlockN:
    """One VMEM accounting plans every stripe launch (``plan_stripes``):
    each pipelined block double-buffered at its own dtype plus the body's
    fp32 temporaries.  Blocks never drop below 128 lanes; a stripe that
    cannot fit the scoped-VMEM cap even at 128 lanes is routed to XLA."""

    # a stripe kernel with two fp32 blocks in, two out and one fp32 body
    # temporary (a momentum-and-direction kernel), beside the apply kernel
    PRECOND = ([4, 4, 4, 4], 1)
    APPLY = ([4, 4, 4, 4, 4], APPLY_TEMPS)       # g, v, w in; v_new, w_new

    def _plan(self, d_in, n, kind=PRECOND):
        return plan_stripes(d_in, n, *kind)

    @pytest.mark.parametrize("d_in,n", [(8, 8), (64, 1024), (64, 1600),
                                        (1024, 4096), (8192, 512),
                                        (32768, 128), (300, 257)])
    def test_block_within_budget_and_aligned(self, d_in, n):
        plan = self._plan(d_in, n)
        need128 = stripe_vmem_bytes(d_in, LANE, *self.PRECOND)
        if plan is None:
            # routed to XLA only when even the 128-lane floor overflows
            assert need128 > VMEM_LIMIT_CAP * 3 // 4
            return
        bn = plan.block_n
        assert bn % LANE == 0 and bn <= MAX_BLOCK_N
        assert (bn // LANE) & (bn // LANE - 1) == 0     # 128 * 2^k lanes
        need = stripe_vmem_bytes(d_in, bn, *self.PRECOND)
        assert need < plan.vmem_limit <= VMEM_LIMIT_CAP

    def test_grow_fires_when_budget_allows(self):
        # small fan-in, evenly divisible d_out: the doubled block fits the
        # grow budget, so the grow phase must take it all the way to 512
        assert self._plan(64, 1024).block_n == 512

    def test_grow_respects_divisibility(self):
        # 1600 = 128 * 12.5: growth to 256 would add padding, so stay at 128
        assert self._plan(64, 1600).block_n == 128

    def test_shrink_respects_budget(self):
        """No shrink below the 128-lane floor: the gpt2-large down-projection
        fan-in keeps a 128-lane kernel block, while an embedding fan-in is
        routed to XLA by the same plan (no pallas_call traced)."""
        from repro.kernels import ops
        from repro.kernels.ops import count_pallas_calls

        assert self._plan(5120, 1280, self.APPLY).block_n == LANE
        assert self._plan(50432, 1280, self.APPLY) is None
        for d_in, launches in ((5120, 1), (50432, 0)):
            g = jax.ShapeDtypeStruct((1, d_in, 128), jnp.float32)
            n = count_pallas_calls(
                lambda g, v, w: ops.rmnp_bucket_update_apply(
                    g, v, w, 0.1, 0.0, beta=0.9), g, g, g)
            assert n == launches, (d_in, n)

    @pytest.mark.parametrize("d_in,n", [(64, 1024), (1024, 4096),
                                        (8192, 512), (32768, 4096)])
    def test_stripe_count_parameterizes_budget(self, d_in, n):
        """The apply kernel holds more VMEM per lane (a third input and
        output block, one more fp32 temporary) than a two-in two-out
        stripe, so its block is never wider and it is routed to XLA no
        later."""
        pre = self._plan(d_in, n, self.PRECOND)
        app = self._plan(d_in, n, self.APPLY)
        if pre is None:
            assert app is None
        elif app is not None:
            assert app.block_n <= pre.block_n
            assert app.vmem_limit >= stripe_vmem_bytes(d_in, app.block_n,
                                                       *self.APPLY)

    def test_stripe_budget_shrinks_block(self):
        # at a 14336 fan-in the two-in two-out stripe still fits the cap
        # at 128 lanes but the apply kernel's extra residency does not:
        # the routing rule sends the apply launch to XLA first
        assert self._plan(14336, 4096, self.PRECOND).block_n == LANE
        assert self._plan(14336, 4096, self.APPLY) is None
        # momentum stored in bf16 halves its blocks, so the same fan-in
        # fits the apply kernel again
        assert plan_stripes(14336, 4096, [4, 2, 4, 2, 4],
                            APPLY_TEMPS).block_n == LANE


class TestDominanceParity:
    def test_fused_dominance_matches_per_leaf(self):
        """Dominance logging must average *per parameter* (paper Eq. 14-16)
        for fused and non-fused states alike — bucket-wise averaging would
        re-weight shapes with many stacked leaves."""
        from repro.core import global_dominance
        from repro.core.mixed import momentum_for_diagnostics

        shapes = dict(RAGGED_SHAPES, norm=(8,), bias=(16,))
        params = make_tree(shapes)
        grads = make_tree(shapes, seed=11)
        ref = mixed_optimizer("rmnp", constant(0.1), constant(0.05))
        fus = mixed_optimizer("rmnp", constant(0.1), constant(0.05), fused=True)
        sr, sf = ref.init(params), fus.init(params)
        _, sr = ref.update_apply(grads, sr, params, 0)
        _, sf = fus.update_apply(grads, sf, params, 0)
        dom_r = global_dominance(momentum_for_diagnostics(sr, params))
        dom_f = global_dominance(momentum_for_diagnostics(sf, params))
        for k in dom_r:
            np.testing.assert_allclose(np.asarray(dom_r[k]),
                                       np.asarray(dom_f[k]), rtol=1e-6)


class TestFusedTrainSmoke:
    def test_end_to_end_fused_train(self):
        from repro.launch.train import train

        _, opt_state, hist = train("gpt2-60m", "rmnp", steps=4, batch=2,
                                   seq=16, fused=True, log_every=2)
        assert hasattr(opt_state, "buckets") and opt_state.buckets
        assert all(np.isfinite(h["loss"]) for h in hist)
