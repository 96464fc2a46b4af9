"""Gradient compression: quantizer correctness, error feedback, and the
shard_map'd compressed DP step (degenerate 1-device mesh on CPU; the
512-device lowering is exercised by the dry-run)."""
import jax
import jax.numpy as jnp
import numpy as np
from _hypothesis_support import given, settings, st

from repro.distributed.compression import (
    _BLOCK, CompressionState, compressed_mean, dequantize_blockwise,
    init_compression_state, quantize_blockwise,
)
from repro.launch.mesh import make_data_mesh


# ---------------------------------------------------------------------------
# quantizer properties
# ---------------------------------------------------------------------------

@given(st.integers(1, 4), st.integers(0, 2**31 - 1))
@settings(max_examples=20, deadline=None)
def test_quantize_roundtrip_error_bounded(nblocks, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal(nblocks * _BLOCK), jnp.float32)
    q, s = quantize_blockwise(x)
    y = dequantize_blockwise(q, s)
    # max error per element is half an int8 step = scale/2 per block
    step = np.repeat(np.asarray(s), _BLOCK)
    assert np.all(np.abs(np.asarray(x - y)) <= step / 2 + 1e-7)


def test_quantize_exact_on_zero_and_scale_signs():
    x = jnp.zeros(_BLOCK, jnp.float32)
    q, s = quantize_blockwise(x)
    assert np.all(np.asarray(q) == 0)
    y = dequantize_blockwise(q, s)
    assert np.all(np.asarray(y) == 0)


def test_error_feedback_accumulates_to_truth():
    """With EF, sum over steps of compressed values == sum of true values
    up to the final residual — the unbiasedness argument."""
    rng = np.random.default_rng(0)
    n = 3 * _BLOCK
    err = jnp.zeros(n, jnp.float32)
    total_true = np.zeros(n)
    total_sent = np.zeros(n)
    for _ in range(20):
        g = jnp.asarray(rng.standard_normal(n) * 0.01, jnp.float32)
        v = g + err
        q, s = quantize_blockwise(v)
        sent = dequantize_blockwise(q, s)
        err = v - sent
        total_true += np.asarray(g)
        total_sent += np.asarray(sent)
    resid = np.abs(total_true - total_sent)
    # residual equals the final error buffer — one quantization step, not 20
    assert np.all(resid <= np.abs(np.asarray(err)) + 1e-6)


# ---------------------------------------------------------------------------
# compressed mean under shard_map (1-device mesh: collectives degenerate,
# quantization still applies)
# ---------------------------------------------------------------------------

def test_compressed_mean_long_run_no_drift():
    """Regression for the bf16-gather error-feedback bug: the bf16 rounding
    of the all-gathered chunk sum (stage d) must be fed back into the error
    accumulator alongside the int8 residual (stage a).  Without it the
    accumulated compressed mean drifts from the exact mean by ~one bf16 ulp
    *per step* (linear in T); with it the tracking error stays bounded by
    the final error buffer — a few quantization steps, independent of T."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = make_data_mesh(1)
    rng = np.random.default_rng(7)
    # values with plenty of bf16-invisible mantissa bits
    g = {"w": jnp.asarray(rng.standard_normal(2 * _BLOCK) * 0.37 + 1.1,
                          jnp.float32)}
    state = init_compression_state(g)

    step = jax.jit(shard_map(
        lambda gg, s: compressed_mean(gg, s, "data", 1), mesh=mesh,
        in_specs=(P(), P()), out_specs=(P(), P()), check_vma=False))

    steps = 200
    total_sent = np.zeros(g["w"].shape, np.float64)
    for _ in range(steps):
        mean, state = step(g, state)
        total_sent += np.asarray(mean["w"], np.float64)
    total_true = steps * np.asarray(g["w"], np.float64)
    resid = np.abs(total_true - total_sent)
    # bound: the final error buffer plus one quantization step of slack —
    # NOT growing with `steps` (the unfixed code accumulates ~steps * 4e-3)
    q, s = quantize_blockwise(jnp.asarray(g["w"]))
    qstep = np.repeat(np.asarray(s), _BLOCK)
    bound = np.abs(np.asarray(state.error["w"])) + qstep + 1e-4
    assert np.all(resid <= bound), (
        f"compressed mean drifts from exact over {steps} steps: "
        f"max resid {resid.max():.4f} vs bound {bound.max():.4f}")


def test_compressed_reduce_scatter_matches_mean_shard():
    """ZeRO-2 leaf schedule on a degenerate 1-way axis: the returned shard
    must equal the corresponding chunk of the compressed mean (identical
    quantizer, no bf16 gather stage -> *exactly* the local fp32 sum), and
    the residual must reconstruct v - deq."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.distributed.compression import compressed_reduce_scatter_leaf

    mesh = make_data_mesh(1)
    rng = np.random.default_rng(3)
    v = jnp.asarray(rng.standard_normal((1, 3, 8, 16)), jnp.float32)

    out, resid = jax.jit(shard_map(
        lambda x: compressed_reduce_scatter_leaf(x, "data", 1), mesh=mesh,
        in_specs=(P(),), out_specs=(P(), P()), check_vma=False))(v)
    assert out.shape == v.shape[1:]
    q, s = quantize_blockwise(
        jnp.pad(v.reshape(-1), (0, (-v.size) % _BLOCK)))
    deq = dequantize_blockwise(q, s)[:v.size].reshape(v.shape)
    # n_dev=1: shard == own dequantized chunk (fp32, no bf16 rounding)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(deq[0]))
    np.testing.assert_allclose(np.asarray(resid), np.asarray(v - deq),
                               atol=1e-6)


def test_compressed_mean_skip_leaves_untouched():
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = make_data_mesh(1)
    rng = np.random.default_rng(11)
    grads = {"mat/w": jnp.asarray(rng.standard_normal(_BLOCK), jnp.float32),
             "norm": jnp.asarray(rng.standard_normal(_BLOCK), jnp.float32)}
    state = init_compression_state(grads)
    out, new_state = jax.jit(shard_map(
        lambda g, s: compressed_mean(g, s, "data", 1,
                                     skip=lambda p: p.startswith("mat")),
        mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
        check_vma=False))(grads, state)
    # skipped leaf: passed through bit-identically, error untouched
    np.testing.assert_array_equal(np.asarray(out["mat/w"]),
                                  np.asarray(grads["mat/w"]))
    np.testing.assert_array_equal(np.asarray(new_state.error["mat/w"]), 0.0)
    # non-skipped leaf: quantized (error buffer engaged)
    assert np.any(np.asarray(new_state.error["norm"]) != 0.0)


def test_compressed_mean_close_to_exact():
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = make_data_mesh(1)
    grads = {"w": jnp.asarray(
        np.random.default_rng(1).standard_normal((64, 48)), jnp.float32)}
    state = init_compression_state(grads)

    def f(g, s):
        return compressed_mean(g, s, "data", 1)

    out, new_state = shard_map(
        f, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
        check_vma=False)(grads, state)
    err = np.asarray(out["w"] - grads["w"])
    # bf16 gather + int8 quantization: relative error small but nonzero
    assert np.abs(err).max() < 0.05 * np.abs(np.asarray(grads["w"])).max()
    assert new_state.error["w"].shape == grads["w"].shape


def test_dp_step_trains(tmp_path):
    """Compressed DP step decreases loss like the exact step does."""
    from repro.configs import get_config
    from repro.core import cosine_with_warmup, mixed_optimizer
    from repro.data.pipeline import make_stream
    from repro.models import init_params
    from repro.train.dp_step import init_dp_state, make_dp_train_step

    cfg = get_config("llama-60m").reduced()
    mesh = make_data_mesh(1)
    opt = mixed_optimizer("rmnp", cosine_with_warmup(1e-2, 60),
                          cosine_with_warmup(3e-3, 60))
    losses = {}
    for compress in (False, True):
        step_fn = jax.jit(make_dp_train_step(
            cfg, opt, mesh, compress=compress))
        params = init_params(cfg, jax.random.PRNGKey(0))
        opt_state = opt.init(params)
        comp = init_dp_state(params)
        stream = make_stream(cfg, 32, 8, seed=0)
        ls = []
        for step in range(40):
            batch = {k: jnp.asarray(v) for k, v in next(stream).items()}
            params, opt_state, comp, m = step_fn(
                params, opt_state, comp, batch, jnp.int32(step))
            ls.append(float(m["loss"]))
        losses[compress] = ls
    for compress, ls in losses.items():
        assert ls[-1] < ls[0], f"compress={compress} did not learn: {ls[:3]}...{ls[-3:]}"
    # compressed and exact trajectories stay close
    assert abs(losses[True][-1] - losses[False][-1]) < 0.35
