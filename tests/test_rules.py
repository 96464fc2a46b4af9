"""The pluggable matrix-update-rule API (core/rules.py) on the generic
bucketed engine (core/engine.py).

Invariants under test:
  * batched Newton-Schulz over a stacked leading ``L`` axis equals the
    per-matrix iteration bit-for-bit in fp32 (allclose in bf16), on both
    the XLA and interpret-mode Pallas backends — the foundation of the
    NS-family rules batching one quintic pipeline per bucket;
  * every registered rule run through the bucketed engine — uneven and
    padded buckets included — matches its per-leaf reference optimizer
    bitwise over multiple steps (slots and bias corrections stepping);
  * every registered rule's engine ``update_apply`` agrees with its
    per-leaf reference's ``update`` + ``apply_updates`` — momentum and slot
    stripes bitwise, params to FMA-contraction level (allclose for Muown's
    multiplicative norm control, whose updates-tree form re-associates the
    final add);
  * the bucketed state layout (momentum buckets + slot stripes)
    round-trips through the checkpoint manager for every rule, AdamW
    leaves included.

The 4-device ZeRO-2 equivalences for the same family run in
tests/_zero_shard_worker.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointManager
from repro.core import apply_updates, constant, make_optimizer
from repro.core.muon import newton_schulz
from repro.core.rules import make_rule, per_leaf_reference, rule_names
from repro.core.types import tree_paths

# uneven bucket mix: 8x16 holds 2+1 slices, 8x24 a lone 3-stack, 16x8 a
# single matrix on the transpose (d_in > d_out) Newton-Schulz path
SHAPES = {"a/w": (2, 8, 16), "b/w": (8, 16), "c/w": (3, 8, 24),
          "d/w": (16, 8)}


def _engine(name, **kw):
    """The bucketed engine for rule ``name`` through the registry."""
    return make_optimizer(name, dict(lr_matrix=0.1, beta=0.9, ns_steps=2,
                                     fused=True, **kw))


def _stacked(plan, tree):
    """The per-leaf reference's leaf-shaped state (momentum or slot
    stripes) stacked in the plan's bucket layout."""
    return {b.key: jnp.concatenate(
        [tree[e.path].reshape((e.lead,) + tree[e.path].shape[-2:])
         for e in b.entries]) for b in plan.buckets}


def _tree(shapes, seed=0, dtype=jnp.float32):
    return {k: jax.random.normal(
        jax.random.fold_in(jax.random.PRNGKey(seed), i), s, dtype)
        for i, (k, s) in enumerate(sorted(shapes.items()))}


class TestBatchedNewtonSchulz:
    """newton_schulz batches over leading dims; each slice must compute
    exactly what it would as a standalone matrix."""

    @pytest.mark.parametrize("use_kernel", [False, True],
                             ids=["xla", "pallas-interpret"])
    @pytest.mark.parametrize("shape", [(5, 8, 16), (3, 8, 24), (4, 16, 8)],
                             ids=["8x16", "8x24", "16x8-transpose"])
    def test_fp32_bitwise_per_slice(self, use_kernel, shape):
        x = jax.random.normal(jax.random.PRNGKey(0), shape, jnp.float32)
        batched = jax.jit(lambda v: newton_schulz(
            v, steps=3, use_kernel=use_kernel))(x)
        one = jax.jit(lambda v: newton_schulz(
            v, steps=3, use_kernel=use_kernel))
        for i in range(shape[0]):
            np.testing.assert_array_equal(
                np.asarray(batched[i]), np.asarray(one(x[i])),
                err_msg=f"slice {i} (use_kernel={use_kernel})")

    def test_zero_slices_stay_zero(self):
        """A zero slice (the engine's shard padding) must come out exactly
        zero — the normalization's eps keeps 0/(0+eps) at 0."""
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 16), jnp.float32)
        x = x.at[2].set(0.0)
        out = newton_schulz(x, steps=5)
        assert np.all(np.asarray(out[2]) == 0)
        # and the live slices are unperturbed by the dead one
        ref = newton_schulz(jnp.stack([x[0], x[1], x[3]]), steps=5)
        np.testing.assert_array_equal(np.asarray(out)[[0, 1, 3]],
                                      np.asarray(ref))

    def test_bf16_allclose_per_slice(self):
        x = jax.random.normal(jax.random.PRNGKey(2), (4, 8, 16), jnp.bfloat16)
        batched = newton_schulz(x, steps=3)
        assert batched.dtype == jnp.bfloat16
        for i in range(4):
            np.testing.assert_allclose(
                np.asarray(batched[i], np.float32),
                np.asarray(newton_schulz(x[i], steps=3), np.float32),
                atol=1e-2)


class TestEngineMatchesPerLeafReference:
    """The bucketed engine vs the per-leaf reference, every rule, two steps
    (slots and bias corrections advance), uneven AND padded buckets."""

    @pytest.mark.parametrize("name", rule_names())
    @pytest.mark.parametrize("pad", [1, 2], ids=["unpadded", "padded"])
    def test_bitwise_two_steps(self, name, pad):
        rule = make_rule(name, beta=0.9, ns_steps=2)
        # shard_size pads the buckets without sharding them (the momentum
        # stays full, so the mesh axis is never consulted): pad slices must
        # be inert
        eng = _engine(name, shard_axis="data", shard_size=pad)
        ref = per_leaf_reference(rule, constant(0.1))
        params = _tree(SHAPES, seed=0)
        pe, se = params, eng.init(params)
        pr, sr = params, ref.init(params)
        for step in range(2):
            grads = _tree(SHAPES, seed=10 + step)
            pe, se = jax.jit(eng.update_apply)(grads, se, pe,
                                               jnp.int32(step))
            pr, sr = jax.jit(ref.update_apply)(grads, sr, pr,
                                               jnp.int32(step))
            for k in params:
                np.testing.assert_array_equal(
                    np.asarray(pe[k]), np.asarray(pr[k]),
                    err_msg=f"{name} step {step} pad={pad}: {k}")
        if pad > 1:
            plan = eng.bucket_plan(params)
            for b in plan.buckets:
                assert np.all(np.asarray(se.buckets[b.key])[b.size:] == 0), \
                    (name, b.key)
                for slot, per_bucket in se.slots.items():
                    assert np.all(
                        np.asarray(per_bucket[b.key])[b.size:] == 0), \
                        (name, slot, b.key)

    def test_muon_kernel_interpret_matches_reference(self):
        """The batched multi-launch NS transform (kernels path) over uneven
        buckets equals the per-leaf kernel reference bitwise."""
        rule = make_rule("muon", beta=0.9, ns_steps=2)
        eng = _engine("muon", use_kernel=True)
        ref = per_leaf_reference(rule, constant(0.1), use_kernel=True)
        params = _tree(SHAPES, seed=3)
        grads = _tree(SHAPES, seed=4)
        pe, _ = eng.update_apply(grads, eng.init(params), params,
                                 jnp.int32(0))
        pr, _ = ref.update_apply(grads, ref.init(params), params,
                                 jnp.int32(0))
        for k in params:
            np.testing.assert_array_equal(np.asarray(pe[k]),
                                          np.asarray(pr[k]), err_msg=k)


class TestUpdateApplyConsistency:
    """Property: for every registered rule the engine's single-pass
    ``update_apply`` agrees with a per-leaf updates tree (``update`` +
    ``apply_updates``).  For RMNP and Muon the reference is the per-leaf
    mixed path, written apart from the rules (``row_normalize``,
    ``newton_schulz``); the bucketed-only rules take
    ``per_leaf_reference``.  Both sides jitted — the jit boundary is where
    they run in production — params agree to FMA-contraction level (atol
    1e-7): the two programs fuse the chain into its consumers differently
    (LLVM FMA contraction), so not bitwise.  Muown's multiplicative norm
    control re-associates the final add in the updates-tree form and gets
    the looser tolerance.  Momentum and slot stripes are identical
    expressions and bitwise op by op; jitted, the per-leaf and the stacked
    programs may contract the momentum EMA's multiply-add differently
    (ROADMAP D1), so they are compared on the op-by-op run."""

    @pytest.mark.parametrize("name", rule_names())
    def test_two_pass_matches_fused(self, name):
        from repro.core.mixed import mixed_optimizer

        opt = _engine(name)
        if name in ("rmnp", "muon"):
            ref = mixed_optimizer(name, constant(0.1), constant(0.1),
                                  beta=0.9, ns_steps=2)
        else:
            ref = per_leaf_reference(make_rule(name, beta=0.9, ns_steps=2),
                                     constant(0.1))

        def two_pass(g, s, p, step):
            u, s2 = ref.update(g, s, p, step)
            return apply_updates(p, u), s2

        params = _tree(SHAPES, seed=5)
        plan = opt.bucket_plan(params)
        tol = (dict(rtol=1e-6, atol=1e-6) if name == "muown"
               else dict(rtol=1e-6, atol=1e-7))
        for jit in (True, False):
            wrap = jax.jit if jit else (lambda f: f)
            one, two = wrap(opt.update_apply), wrap(two_pass)
            p1, s1 = params, opt.init(params)
            p2, s2 = params, ref.init(params)
            for step in range(2):
                grads = _tree(SHAPES, seed=20 + step)
                p1, s1 = one(grads, s1, p1, jnp.int32(step))
                p2, s2 = two(grads, s2, p2, jnp.int32(step))
                for k in params:
                    np.testing.assert_allclose(
                        np.asarray(p1[k]), np.asarray(p2[k]), **tol,
                        err_msg=f"{name} step {step} jit={jit}: {k}")
                if jit:
                    continue
                ref_buckets = _stacked(plan, s2.momentum)
                for bk in s1.buckets:
                    np.testing.assert_array_equal(
                        np.asarray(s1.buckets[bk]),
                        np.asarray(ref_buckets[bk]),
                        err_msg=f"{name} momentum {bk}")
                for slot in s1.slots:
                    ref_slots = _stacked(plan, s2.slots[slot])
                    for bk in s1.slots[slot]:
                        np.testing.assert_array_equal(
                            np.asarray(s1.slots[slot][bk]),
                            np.asarray(ref_slots[bk]),
                            err_msg=f"{name} slot {slot}/{bk}")


class TestStateCheckpointRoundTrip:
    """The uniform stacked-bucket state layout makes the checkpoint manager
    rule-agnostic: one save/restore path for the whole family, slot stripes
    included."""

    @pytest.mark.parametrize("name", rule_names())
    def test_bucketed_state_roundtrip(self, name, tmp_path):
        opt = _engine(name)
        params = _tree(SHAPES, seed=6)
        _, state = opt.update_apply(_tree(SHAPES, seed=7), opt.init(params),
                                    params, jnp.int32(0))
        mgr = CheckpointManager(str(tmp_path / name), async_save=False)
        mgr.save(1, state)
        out = mgr.restore_latest(state)
        assert out is not None
        restored, step, _ = out
        assert step == 1
        for (ka, a), (kb, b) in zip(tree_paths(restored), tree_paths(state), strict=False):
            assert ka == kb
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=f"{name}: {ka}")

    def test_mixed_state_roundtrip(self, tmp_path):
        """The four-field mixed state (momentum, nu, buckets, slots) with a
        slot-carrying rule survives save/restore, matrix and AdamW leaves
        alike."""
        from repro.core import mixed_optimizer

        shapes = dict(SHAPES, norm=(8,), bias=(16,))
        params = _tree(shapes, seed=8)
        opt = mixed_optimizer("normuon", constant(0.1), constant(0.05),
                              fused=True, ns_steps=2)
        _, state = opt.update_apply(_tree(shapes, seed=9), opt.init(params),
                                    params, jnp.int32(0))
        assert state.slots["nu"], "normuon must carry nu stripes"
        mgr = CheckpointManager(str(tmp_path), async_save=False)
        mgr.save(2, state)
        restored, step, _ = mgr.restore_latest(state)
        assert step == 2
        for (ka, a), (_, b) in zip(tree_paths(restored), tree_paths(state), strict=False):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=ka)
