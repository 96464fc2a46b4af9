"""Time causal self-attention's forward and backward on the TPU.

    python -m benchmarks.attention_sweep [--out artifacts/bench/attention_sweep.jsonl]

Paths timed at each shape: the repo's Pallas flash kernels
(``kernels/flash_attention.py``) at every block size that fits, dense
attention (``models/layers.py:_dense_attention``), the chunked path
(long sequences) and JAX's own Pallas flash kernels
(``jax.experimental.pallas.ops.tpu.flash_attention``, fed ``(B, H, S, hd)``
operands, so without the layout transposes the repo's path pays) as the
bar.  ``fwd_ms`` is the forward alone, ``step_ms`` the forward and its
VJP: medians of host-clock times around ``block_until_ready``.

Sections: the two benchmark cells' shapes; the sequence length at which
flash overtakes dense, at head dims 64 and 96; one 8192-token sequence;
and the bfloat16 gradient error of the flash and dense paths against a
float32 dense reference at the highest matmul precision.  Each result is
one JSON line on standard output and in ``--out``.  Needs the TPU.
"""
from __future__ import annotations

import argparse
import json
import statistics
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import block_sizes, flash_attention
from repro.models.layers import _chunked_attention, _dense_attention

CELLS = [("gpt2-large", 8, 1024, 20, 64), ("phi3-mini-8l", 2, 2048, 32, 96)]
BLOCKS = [(128, 128), (256, 256), (256, 512), (512, 256), (512, 512),
          (1024, 512), (1024, 1024)]


def median_ms(fn, *args, iters: int = 10) -> float:
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return 1e3 * statistics.median(times)


def inputs(B, S, H, hd, seed=0, dtype=jnp.bfloat16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return tuple(jax.random.normal(kk, (B, S, H, hd), jnp.float32).astype(dtype)
                 for kk in ks)


def timings(attn, q, k, v, g):
    """(fwd_ms, step_ms) of ``attn(q, k, v)``."""
    fwd = jax.jit(attn)

    @jax.jit
    def step(q, k, v, g):
        o, vjp = jax.vjp(attn, q, k, v)
        return o, vjp(g)
    return median_ms(fwd, q, k, v), median_ms(step, q, k, v, g)


def jax_reference(block: int):
    from jax.experimental.pallas.ops.tpu import flash_attention as ref
    b = ref.BlockSizes(block_q=block, block_k_major=block, block_k=block,
                       block_b=1, block_q_major_dkv=block,
                       block_k_major_dkv=block, block_k_dkv=block,
                       block_q_dkv=block, block_k_major_dq=block,
                       block_k_dq=block, block_q_dq=block)

    def attn(q, k, v):
        return ref.flash_attention(q, k, v, causal=True,
                                   sm_scale=q.shape[-1] ** -0.5,
                                   block_sizes=b)
    return attn


def emit(out, **row):
    line = json.dumps(row)
    print(line, flush=True)
    out.write(line + "\n")
    out.flush()


def measure(out, section, name, attn, args, **tags):
    try:
        fwd, step = timings(attn, *args)
        emit(out, section=section, shape=name, fwd_ms=round(fwd, 4),
             step_ms=round(step, 4), **tags)
    except Exception as e:  # a shape a path cannot take is a result too
        emit(out, section=section, shape=name, error=str(e)[:300], **tags)


def flash(bq, bk):
    return lambda q, k, v: flash_attention(q, k, v, True, bq, bk, False)


def dense(q, k, v):
    return _dense_attention(q, k, v, True)


def cells(out):
    for name, B, S, H, hd in CELLS:
        args = inputs(B, S, H, hd)
        for bq, bk in BLOCKS:
            if bq <= S and bk <= S:
                measure(out, "cells", name, flash(bq, bk), args,
                        path="flash", blocks=[bq, bk])
        measure(out, "cells", name, dense, args, path="dense")
        folded = tuple(t.transpose(0, 2, 1, 3) for t in args)
        for block in (128, 512):
            measure(out, "cells", name, jax_reference(block), folded,
                    path="jax_pallas", blocks=[block, block])


def min_seq(out):
    for hd, H, tokens in ((64, 20, 8192), (96, 32, 4096)):
        for S in (256, 512, 1024, 2048):
            args = inputs(tokens // S, S, H, hd)
            name = f"B{tokens // S} S{S} H{H} hd{hd}"
            measure(out, "min_seq", name, flash(*block_sizes(S, hd)), args,
                    path="flash", blocks=list(block_sizes(S, hd)))
            measure(out, "min_seq", name, dense, args, path="dense")


def long_seq(out):
    args = inputs(1, 8192, 20, 64)
    name = "B1 S8192 H20 hd64"
    for bq, bk in ((512, 512), (1024, 512), (1024, 1024)):
        measure(out, "long_seq", name, flash(bq, bk), args, path="flash",
                blocks=[bq, bk])
    measure(out, "long_seq", name,
            lambda q, k, v: _chunked_attention(q, k, v, True, 2048, 2048),
            args, path="chunked", blocks=[2048, 2048])


def precision(out):
    """Relative L2 error of out, dq, dk, dv in bfloat16 against float32
    dense attention at the highest matmul precision, same inputs."""
    for name, B, S, H, hd in CELLS:
        q, k, v, g = inputs(B, S, H, hd, seed=1)

        def grads(attn, *xs):
            o, vjp = jax.vjp(attn, *xs[:3])
            return (o,) + vjp(xs[3])
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(lambda *xs: grads(dense, *xs))(
                *(t.astype(jnp.float32) for t in (q, k, v, g)))
        for path, attn in (("flash", flash(*block_sizes(S, hd))),
                           ("dense", dense)):
            got = jax.jit(lambda *xs, a=attn: grads(a, *xs))(q, k, v, g)
            err = [float(jnp.linalg.norm(a.astype(jnp.float32) - r)
                         / jnp.linalg.norm(r)) for a, r in zip(got, ref)]
            emit(out, section="precision", shape=name, path=path,
                 rel_err=dict(zip(("out", "dq", "dk", "dv"), err)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", default="artifacts/bench/attention_sweep.jsonl")
    ap.add_argument("--sections",
                    default="cells,min_seq,long_seq,precision")
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        raise SystemExit("attention_sweep times the TPU; none is attached")
    dev = jax.devices()[0]
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    with open(args.out, "w") as out:
        emit(out, device=dev.device_kind, platform=dev.platform)
        for section in args.sections.split(","):
            globals()[section](out)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
