"""Compile the optimizer kernels for a described TPU v5e chip.

Nothing runs: each kernel is lowered at a published gpt2-large bucket
shape and compiled by the TPU compiler for one chip of a described
``v5e:2x2`` topology, which raises what the chip's compiler would raise
(a lane block that is not 128-aligned, a launch over its scoped VMEM).
Interpret-mode tests cannot see either.  The topology is described in a
fixture, so only the test worker that runs this file loads the TPU
library, and every test here skips where it cannot be described.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import newton_schulz, ops, rmnp_update
from repro.launch.train import train

# gpt2-large (36 layers, d_model 1280, gated FFN) block buckets:
# attention q/k/v/o, FFN in (gate and up), FFN down
GPT2_LARGE_BUCKETS = [(144, 1280, 1280), (36, 1280, 10240), (36, 5120, 1280)]
# fp32 gradient and momentum, bf16 weights: what the train step feeds them
G_DT, V_DT, W_DT = jnp.float32, jnp.float32, jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to test
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to a persistent cache
    # but never read back; keep any configured cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("shape", GPT2_LARGE_BUCKETS,
                         ids=["x".join(map(str, s)) for s in GPT2_LARGE_BUCKETS])
def test_rownorm_kernel_compiles(one_chip, shape):
    """The apply kernel with bf16 momentum storage (``momentum_dtype=
    "bfloat16"``): half-width momentum blocks, the same lane plan rule."""
    g = _spec(shape, G_DT, one_chip)
    v, w = _spec(shape, jnp.bfloat16, one_chip), _spec(shape, W_DT, one_chip)
    plan = rmnp_update.rownorm_apply_plan(g, v, w)
    assert plan is not None and plan.block_n % rmnp_update.LANE == 0
    scalars = _spec((2,), jnp.float32, one_chip)
    compiled = rmnp_update.rmnp_rownorm_apply_2d.lower(
        g, v, w, scalars, beta=0.95, interpret=False).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("shape", GPT2_LARGE_BUCKETS,
                         ids=["x".join(map(str, s)) for s in GPT2_LARGE_BUCKETS])
def test_rownorm_apply_kernel_compiles(one_chip, shape):
    g, v = _spec(shape, G_DT, one_chip), _spec(shape, V_DT, one_chip)
    w = _spec(shape, W_DT, one_chip)
    plan = rmnp_update.rownorm_apply_plan(g, v, w)
    assert plan is not None and plan.block_n % rmnp_update.LANE == 0
    scalars = _spec((2,), jnp.float32, one_chip)
    compiled = rmnp_update.rmnp_rownorm_apply_2d.lower(
        g, v, w, scalars, beta=0.95, interpret=False).compile()
    _assert_kernel(compiled)


def test_newton_schulz_kernel_compiles(one_chip):
    # the Muon baseline's batched NS step at the gpt2-large attention bucket
    x = _spec((36, 1280, 1280), jnp.float32, one_chip)
    compiled = newton_schulz.ns_step3.lower(
        x, a=3.4445, b=-4.7750, c=2.0315, interpret=False).compile()
    _assert_kernel(compiled)


def test_replicated_kernel_step_cannot_partition(topo, monkeypatch):
    """ROADMAP D12, pinned: the replicated (pjit) train step with the
    kernel does not compile on more than one chip, because XLA cannot
    partition a Mosaic kernel.  The kernel's own compile is steered on
    here (``jax.default_backend()`` is the CPU); ``train`` raises while
    compiling its step, before it places any state."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    with pytest.raises(NotImplementedError,
                       match="Mosaic kernels cannot be automatically "
                             "partitioned"):
        train("gpt2-60m", steps=2, batch=4, seq=16, reduced=True,
              fused=True, use_kernel=True, log_every=0,
              devices=list(topo.devices))


def test_gpt2_large_routing():
    """At gpt2-large every block bucket takes the kernel; only the
    (50432, 1280) embedding is routed to XLA by the VMEM plan."""
    for shape in GPT2_LARGE_BUCKETS:
        g = jax.ShapeDtypeStruct(shape, G_DT)
        v = jax.ShapeDtypeStruct(shape, V_DT)
        w = jax.ShapeDtypeStruct(shape, W_DT)
        assert rmnp_update.rownorm_apply_plan(g, v, w) is not None, shape
    emb = jax.ShapeDtypeStruct((1, 50432, 1280), jnp.float32)
    emb_w = jax.ShapeDtypeStruct((1, 50432, 1280), W_DT)
    assert rmnp_update.rownorm_apply_plan(emb, emb, emb_w) is None


# the benchmark cells' attention: (B, S, H, hd), causal self-attention
ATTENTION_SHAPES = {"gpt2-large": (8, 1024, 20, 64),
                    "phi3-mini-8l": (2, 2048, 32, 96)}


@pytest.mark.parametrize("cell", sorted(ATTENTION_SHAPES))
def test_flash_attention_kernels_compile(one_chip, cell):
    """The flash forward and backward at the cells' shapes and blocks, and
    nothing of size S x S around them."""
    from repro.kernels.flash_attention import block_sizes, flash_attention
    B, S, H, hd = ATTENTION_SHAPES[cell]
    x = _spec((B, S, H, hd), jnp.bfloat16, one_chip)
    bq, bk = block_sizes(S, hd)

    def fwd_bwd(q, k, v, g):
        o, vjp = jax.vjp(
            lambda q, k, v: flash_attention(q, k, v, True, bq, bk, False),
            q, k, v)
        return o, vjp(g)
    text = jax.jit(fwd_bwd).lower(x, x, x, x).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert f"{S},{S}]" not in text and f"{S},{S}," not in text


def test_flash_kernels_land_in_forward_and_backward_scopes(topo,
                                                           monkeypatch):
    """A train step with attention routed to flash on a described chip:
    the forward kernel runs under ``jvp(forward)`` and the backward kernel
    (with any remat recompute of the forward) under
    ``transpose(jvp(forward))``, the scopes the benchmark's forward_ms and
    backward_ms read.  The
    backend is steered to the TPU here; ``train`` raises placing its
    state, after the step has compiled."""
    import re

    from repro.launch.train import StepReport
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    report = StepReport()
    with pytest.raises(Exception):
        train("gpt2-60m", steps=1, batch=1, seq=1024, reduced=True,
              log_every=0, devices=[topo.devices[0]], report=report)
    assert [r for k, r in report.routes.items()
            if k.startswith("attention ")] == ["flash"]
    paths = re.findall(r'%(flash_attention_\w+)[.\d]* = .*?op_name="([^"]*)"',
                       report.hlo_text)
    fwd = sorted("transpose(jvp(forward))" in p
                 for n, p in paths if n == "flash_attention_fwd")
    bwd = ["transpose(jvp(forward))" in p
           for n, p in paths if n == "flash_attention_bwd"]
    # the compiler may keep the forward's outputs in place of the
    # recompute where memory allows (this toy model); the cells recompute
    assert fwd[0] is False and bwd == [True]
    assert all("jvp(forward)" in p for _, p in paths)


def test_train_step_updates_donated_state_in_place(topo, monkeypatch):
    """A fused-apply kernel train step at gpt2-large's bucket widths, two
    layers deep: every RMNP kernel writes its momentum and weights over its
    operands, so no donated momentum bucket and no single-leaf weight
    bucket is copied aside before the kernel.  ``train`` raises placing
    its state, after the step has compiled."""
    import re

    from repro.analysis.donation import copied_params, entry_param_ops
    from repro.configs.base import ModelConfig
    from repro.launch.train import StepReport
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = ModelConfig(name="gpt2-large-2l", family="dense", num_layers=2,
                      d_model=1280, n_heads=20, n_kv_heads=20, d_ff=5120,
                      vocab=50304, tie_embeddings=True)
    report = StepReport()
    with pytest.raises(Exception):
        train(cfg, steps=1, batch=1, seq=128, reduced=False, fused=True,
              use_kernel=True, log_every=0,
              devices=[topo.devices[0]], report=report)
    calls = re.findall(r"%rmnp_rownorm_apply[\w.]* = .* custom-call\(.*",
                       report.hlo_text)
    # the three block buckets; the embedding takes the XLA path
    assert len(calls) == 3
    # operands: scalars, g, v, w -> outputs v_new, w_new
    assert all("output_to_operand_aliasing={{0}: (2, {}), {1}: (3, {})}"
               in c for c in calls)
    names = entry_param_ops(report.hlo_text)
    copied = [names[n] for n in copied_params(report.hlo_text)]
    assert not [n for n in copied
                if re.search(r"opt_state_buckets__|w_in__|w_out__", n)]
    assert report.state_copies == 0
