"""Step-loop stall of the async double-buffered checkpoint writer.

A blocking save stalls the step loop for the whole serialize + checksum +
fsync; the async writer stalls it only for the device->host copy into the
pinned double buffer, then serializes on a background thread.  This bench
times the real pipelined int8-EF ZeRO-2 step (``make_dp_train_step``) on
a 4-device CPU mesh and measures, per writer:

* ``save_stall_s`` — wall time of the ``save()`` call itself, i.e. the
  stall injected into the step loop (the async side is drained OUTSIDE
  the timed region so the writer thread never pollutes another sample);
* ``step_during_write_s`` (async only) — a step timed while the
  background writer is busy, the honest cost of overlapping the write
  with compute on an oversubscribed CPU mesh.

    PYTHONPATH=src python -m benchmarks.checkpoint_stall [--iters 10]

Blocking and async samples are taken **interleaved** (b, a, b, a, ...)
per ``benchmarks/guard_overhead.py`` — back-to-back blocks drift by
10-30% on a shared CPU from scheduler state alone.  Emits
``artifacts/bench/BENCH_ckpt.json``; ``benchmarks/run.py summarize()``
folds it into ``BENCH_summary.json`` keyed by the ``writer`` column.
The acceptance claim is ``async save_stall < blocking save_stall``; the
bench prints a loud warning rather than failing hard if CPU noise
inverts it.
"""
from __future__ import annotations

import os

if "XLA_FLAGS" not in os.environ:  # must precede jax init (direct runs)
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import argparse  # noqa: E402
import shutil  # noqa: E402
import tempfile  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks.common import print_table, write_artifact  # noqa: E402
from repro.checkpoint.manager import CheckpointManager  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import constant, mixed_optimizer  # noqa: E402
from repro.launch.mesh import make_data_mesh  # noqa: E402
from repro.models import init_params  # noqa: E402
from repro.train.dp_step import init_dp_state, make_dp_train_step  # noqa: E402


def bench_ckpt_stall(arch: str, batch: int, seq: int, iters: int):
    n_dev = len(jax.devices())
    mesh = make_data_mesh(n_dev)
    cfg = get_config(arch).reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0,
                              cfg.vocab)
    data = {"tokens": toks, "labels": toks}
    opt = mixed_optimizer("rmnp", constant(1e-2), constant(1e-2),
                          shard_axis="data", shard_size=n_dev)
    st = opt.init(params)
    comp = init_dp_state(params, n_dev)
    compiled = jax.jit(make_dp_train_step(
        cfg, opt, mesh, zero2=True, opt_state=st, compress=True,
        overlap=True)).lower(params, st, comp, data, jnp.int32(0)).compile()

    def run_step(p, s, c, t):
        p, s, c, _ = compiled(p, s, c, data, jnp.int32(t))
        jax.block_until_ready((p, s, c))
        return p, s, c

    # warm the executable and take the state the saves will snapshot
    state3 = (params, st, comp)
    for t in range(3):
        state3 = run_step(*state3, t)

    work = tempfile.mkdtemp(prefix="rmnp_ckpt_stall_")
    try:
        mgrs = {
            "blocking": CheckpointManager(f"{work}/blocking", keep=2,
                                          async_save=False),
            "async": CheckpointManager(f"{work}/async", keep=2),
        }
        # warm both writers: first fills allocate the double buffers, the
        # timed fills below reuse them via np.copyto (steady state)
        for name, mgr in mgrs.items():
            for w in range(2):
                mgr.save(w + 1, state3, data_step=w + 1)
                mgr.wait()

        # pure step time (the scale the stall is read against)
        t_step = []
        for i in range(iters):
            t0 = time.perf_counter()
            run_step(*state3, 100 + i)
            t_step.append(time.perf_counter() - t0)

        # interleaved save-stall samples
        stalls = {"blocking": [], "async": []}
        during = []
        for i in range(iters):
            for name in ("blocking", "async"):
                step_no = 10 + 2 * i + (0 if name == "blocking" else 1)
                t0 = time.perf_counter()
                mgrs[name].save(step_no, state3, data_step=step_no)
                stalls[name].append(time.perf_counter() - t0)
                if name == "async":
                    # the honest overlap cost: a step while the writer
                    # thread is serializing this very save
                    t0 = time.perf_counter()
                    run_step(*state3, 200 + i)
                    during.append(time.perf_counter() - t0)
                    mgrs[name].wait()  # drain OUTSIDE every timed region

        def med(xs):
            return sorted(xs)[len(xs) // 2]

        step_s = med(t_step)
        recs = [{
            "bench": "checkpoint_stall", "arch": cfg.name, "n_dev": n_dev,
            "batch": batch, "seq": seq, "wire": "int8",
            "writer": "blocking",
            "step_s": step_s,
            "save_stall_s": med(stalls["blocking"]),
        }, {
            "bench": "checkpoint_stall", "arch": cfg.name, "n_dev": n_dev,
            "batch": batch, "seq": seq, "wire": "int8",
            "writer": "async",
            "step_s": step_s,
            "save_stall_s": med(stalls["async"]),
            "step_during_write_s": med(during),
            "stall_speedup": (med(stalls["blocking"]) / med(stalls["async"])
                              if med(stalls["async"]) else float("inf")),
        }]
        if recs[1]["save_stall_s"] >= recs[0]["save_stall_s"]:
            print(f"[ckpt] WARNING: async save stalled the loop "
                  f"{1e3 * recs[1]['save_stall_s']:.1f}ms >= blocking "
                  f"{1e3 * recs[0]['save_stall_s']:.1f}ms — the "
                  f"double-buffered writer should be strictly cheaper; "
                  f"rerun on a quiet machine before reading into it")
        return recs
    finally:
        shutil.rmtree(work, ignore_errors=True)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt2-60m")
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--iters", type=int, default=10,
                    help="interleaved sample pairs per writer")
    args = ap.parse_args(argv)

    recs = bench_ckpt_stall(args.arch, args.batch, args.seq, args.iters)
    rows = [[r["writer"], f"{1e3 * r['step_s']:.1f}",
             f"{1e3 * r['save_stall_s']:.1f}",
             f"{1e3 * r['step_during_write_s']:.1f}"
             if "step_during_write_s" in r else "-",
             f"{r['stall_speedup']:.1f}x" if "stall_speedup" in r else "-"]
            for r in recs]
    print("\n== checkpoint save stall: blocking vs async double-buffered ==")
    print_table(["writer", "step ms", "save stall ms", "step+write ms",
                 "stall speedup"], rows)
    write_artifact("BENCH_ckpt", recs)
    return recs


if __name__ == "__main__":
    main()
