"""Unit + property tests for the core optimizer library."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_support import given, settings, st

from repro.core import (
    adamw, apply_updates, clip_by_global_norm, constant, cosine_with_warmup,
    dominance_ratios, global_dominance, is_matrix_param, mixed_optimizer,
    newton_schulz, rms_lr_scale, row_normalize,
)


class TestRowNormalize:
    def test_unit_columns(self):
        v = jax.random.normal(jax.random.PRNGKey(0), (64, 32))
        d = row_normalize(v)
        np.testing.assert_allclose(np.linalg.norm(np.array(d), axis=0), 1.0, atol=1e-5)

    def test_equals_diag_gram_form(self):
        """RN(V) == (diag(V V^T))^{-1/2} V in the paper's convention."""
        v = jax.random.normal(jax.random.PRNGKey(1), (16, 48))
        d = row_normalize(v)
        vp = np.array(v).T                       # paper stores rows = d_out
        expect = np.diag(1.0 / np.sqrt(np.diag(vp @ vp.T) + 0)) @ vp
        np.testing.assert_allclose(np.array(d).T, expect, atol=1e-4)

    @given(st.integers(2, 64), st.integers(2, 64))
    @settings(max_examples=10, deadline=None)
    def test_property_unit_norm(self, m, n):
        v = jax.random.normal(jax.random.PRNGKey(m * 131 + n), (m, n)) + 0.1
        d = row_normalize(v)
        np.testing.assert_allclose(np.linalg.norm(np.array(d), axis=0), 1.0, atol=1e-4)

    def test_batched(self):
        v = jax.random.normal(jax.random.PRNGKey(2), (3, 8, 16))
        d = row_normalize(v)
        np.testing.assert_allclose(
            np.linalg.norm(np.array(d), axis=1), 1.0, atol=1e-5)


class TestNewtonSchulz:
    def test_orthogonalizes(self):
        v = jax.random.normal(jax.random.PRNGKey(0), (64, 128))
        x = newton_schulz(v, steps=10)
        s = np.linalg.svd(np.array(x), compute_uv=False)
        assert s.min() > 0.3 and s.max() < 1.3   # quintic NS band

    def test_transpose_invariance(self):
        v = jax.random.normal(jax.random.PRNGKey(1), (128, 64))
        x = newton_schulz(v)
        xt = newton_schulz(v.T)
        np.testing.assert_allclose(np.array(x), np.array(xt.T), atol=1e-4)

    def test_preserves_shape_and_dtype(self):
        v = jax.random.normal(jax.random.PRNGKey(2), (32, 48)).astype(jnp.bfloat16)
        x = newton_schulz(v)
        assert x.shape == v.shape and x.dtype == v.dtype


class TestRmsScale:
    def test_tall_matrix_scaled(self):
        assert rms_lr_scale((128, 512)) == pytest.approx(2.0)   # d_out/d_in = 4

    def test_wide_matrix_floor(self):
        assert rms_lr_scale((512, 128)) == 1.0


class TestMixedRouting:
    def test_matrix_vs_adamw_partition(self):
        assert is_matrix_param("stack/layer_0/mixer/wq", jnp.ones((4, 4)))
        assert not is_matrix_param("stack/layer_0/mixer/norm", jnp.ones((4, 4)))
        assert not is_matrix_param("x/bias", jnp.ones((4, 4)))
        assert not is_matrix_param("w", jnp.ones((4,)))
        assert not is_matrix_param("embed/tokens", jnp.ones((8, 4)), matrix_embed=False)
        assert is_matrix_param("mamba/dt_w", jnp.ones((4, 8))) is False  # dt_ -> adamw

    def test_rmnp_step_direction(self):
        """A single RMNP step moves along -RN(momentum) with RMS lr scale."""
        params = {"w": jnp.zeros((4, 8))}
        g = {"w": jnp.ones((4, 8))}
        opt = mixed_optimizer("rmnp", constant(0.1), constant(0.1),
                              beta=0.0, weight_decay=0.0)
        st_ = opt.init(params)
        upd, _ = opt.update(g, st_, params, 0)
        expect = -0.1 * rms_lr_scale((4, 8)) * np.array(row_normalize(g["w"]))
        np.testing.assert_allclose(np.array(upd["w"]), expect, atol=1e-6)

    def test_all_three_kinds_step(self):
        params = {"a": {"w": jnp.ones((8, 8)), "norm": jnp.ones((8,))}}
        g = jax.tree_util.tree_map(lambda p: 0.1 * jnp.ones_like(p), params)
        for kind in ("rmnp", "muon", "adamw"):
            opt = mixed_optimizer(kind, constant(1e-2), constant(1e-2))
            s = opt.init(params)
            upd, s2 = opt.update(g, s, params, 0)
            p2 = apply_updates(params, upd)
            for leaf in jax.tree_util.tree_leaves(p2):
                assert np.all(np.isfinite(np.array(leaf)))

    def test_momentum_accumulates(self):
        params = {"w": jnp.zeros((4, 4))}
        g = {"w": jnp.ones((4, 4))}
        opt = mixed_optimizer("rmnp", constant(0.1), constant(0.1), beta=0.9)
        s = opt.init(params)
        _, s1 = opt.update(g, s, params, 0)
        _, s2 = opt.update(g, s1, params, 1)
        m1, m2 = np.array(s1.momentum["w"]).mean(), np.array(s2.momentum["w"]).mean()
        assert m2 > m1 > 0


class TestSchedule:
    def test_warmup_then_cosine(self):
        sch = cosine_with_warmup(1.0, 100, warmup_frac=0.1)
        assert float(sch(0)) == 0.0
        assert float(sch(5)) == pytest.approx(0.5)
        assert float(sch(10)) == pytest.approx(1.0, abs=1e-3)
        assert float(sch(100)) == pytest.approx(0.0, abs=1e-3)
        assert float(sch(55)) == pytest.approx(0.5, abs=0.02)


class TestClip:
    def test_clip_active(self):
        g = {"w": jnp.full((10, 10), 10.0)}
        c, stats = clip_by_global_norm(g, 1.0)
        assert float(stats.clipped) == 1.0
        total = np.sqrt(sum(np.sum(np.square(np.array(x)))
                            for x in jax.tree_util.tree_leaves(c)))
        assert total == pytest.approx(1.0, rel=1e-4)

    def test_clip_inactive(self):
        g = {"w": jnp.full((2, 2), 1e-3)}
        c, stats = clip_by_global_norm(g, 1.0)
        assert float(stats.clipped) == 0.0
        np.testing.assert_allclose(np.array(c["w"]), np.array(g["w"]))

    @given(st.floats(0.1, 100.0))
    @settings(max_examples=10, deadline=None)
    def test_property_never_exceeds(self, scale):
        g = {"w": scale * jax.random.normal(jax.random.PRNGKey(3), (16, 16))}
        c, _ = clip_by_global_norm(g, 1.0)
        total = np.sqrt(np.sum(np.square(np.array(c["w"]))))
        assert total <= 1.0 + 1e-4


class TestDominance:
    def test_orthogonal_rows_give_large_ratio(self):
        v = jnp.eye(16)  # Gram == I: off-diag 0 => huge ratios
        s = dominance_ratios(v)
        assert float(s.r_min) > 1e6

    def test_identical_rows_give_ratio_one(self):
        v = jnp.ones((16, 8))
        s = dominance_ratios(v)
        assert float(s.r_avg) == pytest.approx(1.0, rel=1e-3)

    def test_global_aggregation(self):
        tree = {"a/w": jnp.eye(8), "norm": jnp.ones((8,))}
        out = global_dominance(tree)
        assert set(out) == {"r_avg", "r_min", "r_max"}


class TestConvergenceSanity:
    """RMNP/Muon/AdamW all minimize a least-squares objective; RMNP should be
    no slower than plain AdamW at matched budget (paper's qualitative claim)."""

    def _run(self, kind, steps=120):
        key = jax.random.PRNGKey(0)
        w_true = jax.random.normal(key, (16, 8)) / 4
        xs = jax.random.normal(jax.random.PRNGKey(1), (256, 16))
        ys = xs @ w_true
        params = {"w": jnp.zeros((16, 8))}
        opt = mixed_optimizer(kind, constant(0.05), constant(0.05),
                              weight_decay=0.0)
        s = opt.init(params)

        def loss(p):
            return jnp.mean(jnp.square(xs @ p["w"] - ys))

        @jax.jit
        def step(p, s, i):
            g = jax.grad(loss)(p)
            u, s = opt.update(g, s, p, i)
            return apply_updates(p, u), s

        for i in range(steps):
            params, s = step(params, s, i)
        return float(loss(params))

    def test_all_optimizers_converge(self):
        for kind in ("rmnp", "muon", "adamw"):
            final = self._run(kind)
            assert final < 0.05, f"{kind} failed to converge: {final}"


class TestStateMemoryParity:
    def test_rmnp_and_muon_state_same_bytes(self):
        """Paper Table 3: identical optimizer memory — both keep one fp32
        momentum per matrix param; the preconditioner itself is stateless."""
        from repro.core import constant, mixed_optimizer
        params = {"w": jnp.zeros((64, 128)), "b": jnp.zeros((128,))}
        sizes = {}
        for kind in ("rmnp", "muon"):
            opt = mixed_optimizer(kind, constant(0.1), constant(0.1))
            st = opt.init(params)
            sizes[kind] = sum(leaf.size * leaf.dtype.itemsize
                              for leaf in jax.tree_util.tree_leaves(st))
        assert sizes["rmnp"] == sizes["muon"]
