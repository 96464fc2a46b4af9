"""Pallas kernel validation: interpret-mode execution vs pure-jnp oracles,
with hypothesis shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_support import given, settings, st

from repro.kernels import ops
from repro.kernels.matmul import matmul as pallas_matmul
from repro.kernels.ref import matmul_ref, ns_step_ref, rmnp_rownorm_apply_ref
from repro.kernels.rmnp_update import rmnp_rownorm_apply_2d

_NS = (3.4445, -4.7750, 2.0315)


def _gvw(shape, seed):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(k1, shape), jax.random.normal(k2, shape),
            jax.random.normal(k3, shape))


class TestRmnpKernel:
    """The single-pass apply kernel against its jnp reference: momentum
    EMA, row normalize and the weight update in one pass.  At scale 1 and
    no weight decay ``w_new = w - d``, so the weights carry the direction
    at its own size and the 1e-5 bound holds ``d`` itself; the cells'
    small scale and weight decay are checked on the same operands."""

    @staticmethod
    def _check(g, v, w, beta, apply=None):
        apply = apply or (lambda g, v, w, scale, wd: ops.rmnp_bucket_update_apply(
            g, v, w, scale, wd, beta=beta))
        for scale, wd in ((1.0, 0.0), (0.01, 0.1)):
            vn, wn = apply(g, v, w, scale, wd)
            vr, wr = rmnp_rownorm_apply_ref(g, v, w, scale, wd, beta=beta)
            np.testing.assert_allclose(np.array(vn), np.array(vr), atol=1e-5)
            np.testing.assert_allclose(np.array(wn), np.array(wr), atol=1e-5,
                                       err_msg=f"scale={scale} wd={wd}")

    @pytest.mark.parametrize("shape", [(8, 8), (64, 128), (128, 64),
                                       (300, 257), (1024, 96), (33, 9)])
    def test_matches_ref(self, shape):
        g, v, w = _gvw(shape, shape[0] * shape[1])

        def apply(g, v, w, scale, wd):
            sc = jnp.array([scale, wd], jnp.float32)
            return rmnp_rownorm_apply_2d(g, v, w, sc, beta=0.95,
                                         interpret=True)
        self._check(g, v, w, 0.95, apply)

    @given(st.integers(2, 200), st.integers(2, 200),
           st.sampled_from([0.0, 0.5, 0.9, 0.95, 0.99]))
    @settings(max_examples=12, deadline=None)
    def test_property_sweep(self, m, n, beta):
        self._check(*_gvw((m, n), m * 211 + n), beta)

    def test_batched_stack(self):
        g = jax.random.normal(jax.random.PRNGKey(0), (4, 32, 48))
        v = jnp.zeros((4, 32, 48))
        w = jax.random.normal(jax.random.PRNGKey(1), (4, 32, 48))
        self._check(g, v, w, 0.9)

    def test_output_columns_unit_norm(self):
        """At scale 1 and no weight decay the step is the direction: each
        column of ``w - w_new`` is a unit-norm row-normalized momentum."""
        g = jax.random.normal(jax.random.PRNGKey(0), (128, 256))
        v = jax.random.normal(jax.random.PRNGKey(1), (128, 256))
        w = jax.random.normal(jax.random.PRNGKey(2), (128, 256))
        _, wn = ops.rmnp_bucket_update_apply(g, v, w, 1.0, 0.0, beta=0.5)
        np.testing.assert_allclose(
            np.linalg.norm(np.array(w - wn), axis=0), 1.0, atol=1e-4)


class TestRmnpKernelAliasing:
    """The apply kernel writes v_new and w_new over its v and w operands.
    The values are those of the jitted reference, bit for bit; a caller
    that does not donate keeps its arrays, one that donates gets the same
    values back.  Weights in bf16 (the cells' mixed precision) and fp32."""

    @staticmethod
    def fn(g, v, w, sc):
        return rmnp_rownorm_apply_2d(g, v, w, sc, beta=0.95, interpret=True)

    @staticmethod
    def ref(g, v, w, sc):
        return rmnp_rownorm_apply_ref(g, v, w, sc[0], sc[1], beta=0.95)

    @pytest.mark.parametrize("wdtype", [jnp.bfloat16, jnp.float32],
                             ids=["bf16w", "fp32w"])
    @pytest.mark.parametrize("d_out", [256, 200], ids=["unpadded", "padded"])
    @pytest.mark.parametrize("mdtype", [jnp.float32, jnp.bfloat16],
                             ids=["fp32", "bf16"])
    def test_aliased_state(self, wdtype, d_out, mdtype):
        fn, ref = self.fn, self.ref
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(d_out), 3)
        g = jax.random.normal(k1, (2, 64, d_out))
        v = jax.random.normal(k2, (2, 64, d_out)).astype(mdtype)
        w = jax.random.normal(k3, (2, 64, d_out)).astype(wdtype)
        sc = jnp.array([0.01, 0.1], jnp.float32)
        before = [np.array(x) for x in (g, v, w)]
        want = [np.array(x) for x in jax.jit(ref)(g, v, w, sc)]
        got = [np.array(x) for x in jax.jit(fn)(g, v, w, sc)]
        for a, b in zip(got, want, strict=True):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(a, b)
        for a, b in zip((g, v, w), before, strict=True):
            np.testing.assert_array_equal(np.array(a), b)
        donated = jax.jit(fn, donate_argnums=(1, 2))(
            g, jnp.array(v, copy=True), jnp.array(w, copy=True), sc)
        for a, b in zip(donated, want, strict=True):
            np.testing.assert_array_equal(np.array(a), b)


class TestMatmulKernel:
    @pytest.mark.parametrize("m,k,n", [(8, 8, 8), (128, 256, 64),
                                       (100, 200, 72), (257, 129, 33),
                                       (512, 512, 512)])
    def test_matches_ref(self, m, k, n):
        a = jax.random.normal(jax.random.PRNGKey(m + k), (m, k))
        b = jax.random.normal(jax.random.PRNGKey(n), (k, n))
        out = pallas_matmul(a, b, interpret=True)
        np.testing.assert_allclose(np.array(out), np.array(matmul_ref(a, b)),
                                   rtol=1e-4, atol=1e-3)

    @given(st.integers(4, 150), st.integers(4, 150), st.integers(4, 150))
    @settings(max_examples=8, deadline=None)
    def test_property_sweep(self, m, k, n):
        a = jax.random.normal(jax.random.PRNGKey(m * 7 + k), (m, k))
        b = jax.random.normal(jax.random.PRNGKey(n * 3), (k, n))
        out = pallas_matmul(a, b, interpret=True)
        np.testing.assert_allclose(np.array(out), np.array(matmul_ref(a, b)),
                                   rtol=1e-4, atol=1e-3)

    def test_bf16_inputs_fp32_accumulate(self):
        a = jax.random.normal(jax.random.PRNGKey(0), (64, 64)).astype(jnp.bfloat16)
        b = jax.random.normal(jax.random.PRNGKey(1), (64, 64)).astype(jnp.bfloat16)
        out = pallas_matmul(a, b, interpret=True)
        assert out.dtype == jnp.float32
        np.testing.assert_allclose(np.array(out), np.array(matmul_ref(a, b)),
                                   rtol=2e-2, atol=2e-2)


class TestNewtonSchulzKernel:
    @pytest.mark.parametrize("shape", [(32, 32), (64, 128), (48, 96)])
    def test_matches_ref(self, shape):
        x = jax.random.normal(jax.random.PRNGKey(0), shape) / 20
        out = ops.ns_step(x, *_NS)
        ref = ns_step_ref(x, *_NS)
        np.testing.assert_allclose(np.array(out), np.array(ref),
                                   rtol=1e-4, atol=1e-4)

    def test_five_steps_orthogonalize(self):
        v = jax.random.normal(jax.random.PRNGKey(1), (48, 64))
        x = v / (jnp.linalg.norm(v) + 1e-7)
        for _ in range(5):
            x = ops.ns_step(x, *_NS)
        s = np.linalg.svd(np.array(x), compute_uv=False)
        assert s.min() > 0.3 and s.max() < 1.3


class TestOptimizerKernelPath:
    def test_mixed_rmnp_kernel_equals_jnp_path(self):
        """The bucketed kernel path against the per-leaf jnp path."""
        from repro.core import constant, mixed_optimizer
        params = {"w": jax.random.normal(jax.random.PRNGKey(0), (32, 64))}
        grads = {"w": jax.random.normal(jax.random.PRNGKey(1), (32, 64))}
        o1 = mixed_optimizer("rmnp", constant(0.1), constant(0.1))
        o2 = mixed_optimizer("rmnp", constant(0.1), constant(0.1),
                             use_kernel=True, fused=True)
        p1, _ = o1.update_apply(grads, o1.init(params), params, 0)
        p2, _ = o2.update_apply(grads, o2.init(params), params, 0)
        np.testing.assert_allclose(np.array(p1["w"]), np.array(p2["w"]),
                                   atol=1e-5)


class TestFlashAttentionKernel:
    def _rand(self, B, S, H, K, hd, seed=0):
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, K, hd), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, K, hd), jnp.float32)
        return q, k, v

    @pytest.mark.parametrize("B,S,H,K,hd,bq,bk", [
        (2, 256, 4, 2, 64, 64, 64),    # GQA 2:1
        (1, 128, 4, 4, 32, 128, 32),   # MHA, single q block
        (2, 128, 8, 1, 64, 32, 64),    # MQA
        (1, 512, 2, 2, 128, 128, 256), # rectangular blocks
    ])
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_dense(self, B, S, H, K, hd, bq, bk, causal):
        from repro.kernels.flash_attention import flash_attention_fwd
        from repro.models.layers import _dense_attention
        q, k, v = self._rand(B, S, H, K, hd, seed=S + H)
        out = flash_attention_fwd(q, k, v, causal=causal, block_q=bq,
                                  block_k=bk, interpret=True)
        ref = _dense_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_bf16_io(self):
        from repro.kernels.flash_attention import flash_attention_fwd
        from repro.models.layers import _dense_attention
        q, k, v = self._rand(1, 128, 4, 2, 64)
        qb, kb, vb = (t.astype(jnp.bfloat16) for t in (q, k, v))
        out = flash_attention_fwd(qb, kb, vb, causal=True, block_q=64,
                                  block_k=64, interpret=True)
        assert out.dtype == jnp.bfloat16
        ref = _dense_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref), atol=3e-2, rtol=3e-2)

    def test_gradients_flow_via_recompute_vjp(self):
        """The Pallas backward recomputes the probabilities from the saved
        logsumexp; its gradients are the dense path's."""
        from repro.kernels.flash_attention import flash_attention
        from repro.models.layers import _dense_attention
        q, k, v = self._rand(1, 128, 4, 2, 32, seed=3)

        def f_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, True, 64, 64, True) ** 2)

        def f_dense(q, k, v):
            return jnp.sum(_dense_attention(q, k, v, causal=True) ** 2)

        g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(f_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2, strict=False):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4)

    @pytest.mark.parametrize("B,S,H,K,hd,hdv,bq,bk", [
        (1, 256, 4, 4, 64, 64, 64, 64),      # MHA, square blocks
        (2, 256, 4, 2, 64, 64, 128, 64),     # GQA 2:1, wide q blocks
        (1, 256, 8, 2, 96, 96, 64, 128),     # GQA 4:1, head dim 96
        (1, 512, 2, 2, 96, 96, 256, 128),    # head dim 96, rectangular
        (1, 256, 4, 1, 96, 64, 128, 128),    # MQA, v head dim below q's
    ])
    @pytest.mark.parametrize("causal", [True, False])
    def test_backward_matches_dense(self, B, S, H, K, hd, hdv, bq, bk,
                                    causal):
        from repro.kernels.flash_attention import flash_attention
        from repro.models.layers import _dense_attention
        ks = jax.random.split(jax.random.PRNGKey(S + H + hd), 4)
        q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, K, hd), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, K, hdv), jnp.float32)
        g = jax.random.normal(ks[3], (B, S, H, hdv), jnp.float32)

        def grads(attn, *xs):
            return jax.grad(lambda q, k, v: jnp.sum(attn(q, k, v) * g),
                            argnums=(0, 1, 2))(*xs)

        def flash(q, k, v):
            return flash_attention(q, k, v, causal, bq, bk, True)

        def dense(q, k, v):
            return _dense_attention(q, k, v, causal)

        for a, b in zip(grads(flash, q, k, v), grads(dense, q, k, v),
                        strict=True):
            assert a.dtype == b.dtype and a.shape == b.shape
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4)
        # bf16 operands: the same products in the input dtype, loosely
        lo = [t.astype(jnp.bfloat16) for t in (q, k, v)]
        for a, b in zip(grads(flash, *lo), grads(dense, q, k, v),
                        strict=True):
            assert a.dtype == jnp.bfloat16
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b), atol=6e-2, rtol=6e-2)

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("hd,bq,bk", [(64, 64, 128), (96, 128, 64)])
    def test_logsumexp_residual(self, causal, hd, bq, bk):
        from repro.kernels.flash_attention import _flash_fwd
        B, S, H, K = 2, 256, 4, 2
        q, k, v = self._rand(B, S, H, K, hd, seed=hd)
        _, lse = _flash_fwd(q, k, v, causal, bq, bk, True)
        assert lse.shape == (B * H, 1, S) and lse.dtype == jnp.float32
        scores = jnp.einsum("bqkgh,bskh->bkgqs",
                            q.reshape(B, S, K, H // K, hd), k) / hd ** 0.5
        if causal:
            scores = jnp.where(jnp.tril(jnp.ones((S, S), bool)), scores,
                               -1e30)
        ref = jax.nn.logsumexp(scores, axis=-1).reshape(B * H, S)
        np.testing.assert_allclose(np.asarray(lse[:, 0]), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_vjp_holds_nothing_of_size_s_by_s(self):
        """Outside the kernels, neither the forward's residuals nor the
        backward build a (S, S) array."""
        from repro.kernels.flash_attention import flash_attention
        S = 256
        q, k, v = self._rand(1, S, 2, 2, 64, seed=5)
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda q, k, v: jnp.sum(flash_attention(q, k, v, True, 128, 128,
                                                    True)),
            argnums=(0, 1, 2)))(q, k, v)
        shapes = [tuple(var.aval.shape) for eqn in jaxpr.eqns
                  for var in eqn.outvars]
        assert shapes and not [s for s in shapes if s[-2:] == (S, S)]
        assert sum(e.primitive.name == "pallas_call"
                   for e in jaxpr.eqns) == 2

    def test_chunked_oracle_matches_dense(self):
        from repro.kernels.ref import chunked_attention_ref
        from repro.models.layers import _dense_attention
        q, k, v = self._rand(2, 256, 4, 2, 64, seed=9)
        out = chunked_attention_ref(q, k, v, causal=True, chunk_q=64,
                                    chunk_k=128)
        ref = _dense_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


class TestFlashRoute:
    """``flash_route``: the one rule that sends ``impl="auto"`` attention
    to the Pallas kernels.  It reads shapes and the backend only."""

    @pytest.mark.parametrize("backend,S,Skv,q_offset,hd,hdv,devices,want", [
        ("tpu", 1024, 1024, 0, 64, 64, 1, True),
        ("tpu", 2048, 2048, 0, 96, 96, 1, True),
        ("tpu", 4096, 4096, 0, 128, 128, 1, True),
        ("tpu", 8192, 8192, 0, 64, 64, 1, True),
        ("tpu", 2048, 2048, 0, 256, 256, 1, True),   # a multiple of 128
        ("cpu", 1024, 1024, 0, 64, 64, 1, "backend cpu"),
        ("tpu", 1024, 2048, 0, 64, 64, 1, "not self-attention"),
        ("tpu", 1024, 1024, 16, 64, 64, 1, "not self-attention"),
        ("tpu", 1024, 1024, 0, 64, 64, 4, "GSPMD over 4 devices"),
        ("tpu", 256, 256, 0, 96, 96, 1, True),
        ("tpu", 128, 128, 0, 64, 64, 1, "S 128 < 256"),
        ("tpu", 2000, 2000, 0, 64, 64, 1, "not a multiple of 256"),
        ("tpu", 1152, 1152, 0, 64, 64, 1, "not a multiple of 256"),
        ("tpu", 1024, 1024, 0, 192, 128, 1, "head dim 192"),  # MLA q/k
        ("tpu", 1024, 1024, 0, 128, 160, 1, "head dim 160"),
        ("tpu", 2 ** 20, 2 ** 20, 0, 128, 128, 1, "MiB VMEM"),
    ])
    def test_route(self, backend, S, Skv, q_offset, hd, hdv, devices, want):
        from repro.kernels.flash_attention import flash_route
        got = flash_route(backend, S, Skv, q_offset, hd, hdv, True, devices)
        if want is True:
            assert got is True
        else:
            assert isinstance(got, str) and want in got, got

    @pytest.mark.parametrize("B,S,H,hd", [(8, 1024, 20, 64),
                                          (2, 2048, 32, 96)],
                             ids=["gpt2-large", "phi3-mini-8l"])
    def test_benchmark_cells_route_to_flash_on_tpu(self, B, S, H, hd):
        from repro.kernels.flash_attention import block_sizes, flash_route
        assert flash_route("tpu", S, S, 0, hd, hd, True) is True
        bq, bk = block_sizes(S, hd)
        assert (bq, bk) == (512, 512) and S % bq == 0

    @pytest.mark.parametrize("S,Skv,q_offset,want", [
        (16, 16, 0, "dense"), (1024, 1024, 0, "dense"),
        (2048, 2048, 0, "dense"), (8192, 8192, 0, "chunked"),
        (16384, 16384, 0, "chunked"), (8192, 16384, 0, "dense"),
        (1, 4096, 100, "dense"),
    ])
    def test_cpu_keeps_the_xla_choice(self, S, Skv, q_offset, want):
        """Off the TPU, ``auto`` picks what it picked before the flash
        route: chunked self-attention from 8192, dense below."""
        from repro.models.layers import attention_route
        q = jax.ShapeDtypeStruct((1, S, 4, 64), jnp.bfloat16)
        k = jax.ShapeDtypeStruct((1, Skv, 4, 64), jnp.bfloat16)
        route, why = attention_route(q, k, k, True, q_offset)
        assert (route, why) == (want, "backend cpu")

    def test_auto_on_cpu_is_dense_bitwise(self):
        from repro.models.layers import _dense_attention, attention
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        q, k, v = (jax.random.normal(kk, (2, 64, 4, 32), jnp.bfloat16)
                   for kk in ks)
        np.testing.assert_array_equal(
            np.asarray(attention(q, k, v), np.float32),
            np.asarray(_dense_attention(q, k, v, True), np.float32))

    def test_routes_recorded_per_call_site(self):
        from repro.models.layers import attention, recording_attention_routes
        q = jnp.zeros((2, 32, 4, 16), jnp.float32)
        with recording_attention_routes() as log:
            attention(q, q, q)
            attention(q, q, q)                       # same site, one key
            attention(q, q, q, impl="dense")         # not auto: no route
        assert log == {"attention (2,32,4,16)": "dense: backend cpu"}
        attention(q, q, q)                           # outside: nothing
        assert len(log) == 1
