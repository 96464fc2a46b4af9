"""Bucket-pipelined vs serialized ZeRO-2 step wall-clock.

Times the full ``make_dp_train_step`` (train/dp_step.py) on a 4-device CPU
mesh across ``accum`` (microbatch accumulation factor), schedule
(``serialized`` = all-bucket reduce-scatter then all-bucket update, with
per-leaf fp32 accumulation and pre-scaled gradient shards; ``pipelined`` =
chunked-in-scan accumulation, independent per-bucket collective/update
chains, two-phase clip) and wire format (fp32 ``psum_scatter`` vs the int8
error-feedback a2a).  Also re-verifies the pipelined structure on the
compiled HLO (``collective_overlap_report``: zero cross-bucket
serialization edges) at the largest ``accum``.

    PYTHONPATH=src python -m benchmarks.overlap [--accum 1 2 4 8]

Emits ``artifacts/bench/BENCH_overlap.json``.  When imported from
``benchmarks.run`` (jax already initialized) the mesh uses however many
devices exist; run directly for the 4-device mesh.
"""
from __future__ import annotations

import os

if "XLA_FLAGS" not in os.environ:  # must precede jax init (direct runs)
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import argparse  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks.common import print_table, time_fn, write_artifact  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import constant, mixed_optimizer  # noqa: E402
from repro.launch.mesh import make_data_mesh  # noqa: E402
from repro.models import init_params  # noqa: E402
from repro.train.dp_step import init_dp_state, make_dp_train_step  # noqa: E402


def bench_overlap(arch: str, batch: int, seq: int, accums, iters: int):
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

    valid = [a for a in accums if batch % (n_dev * a) == 0]
    if not valid:
        raise ValueError(
            f"batch {batch} is not divisible by n_dev*accum for any "
            f"requested accum {sorted(accums)} on the {n_dev}-device mesh "
            f"— pick --batch a multiple of {n_dev * min(accums)}")
    for a in sorted(set(accums) - set(valid)):
        print(f"[overlap] skip accum={a}: batch {batch} not divisible by "
              f"n_dev*accum={n_dev * a}")
    check_accum = max(valid)  # HLO structural check runs at this accum

    from repro.analysis.overlap import collective_overlap_report
    plan = opt.bucket_plan(params)
    recs = []
    for compress in (False, True):
        hlo = None
        for accum in valid:
            times = {}
            for overlap in (False, True):
                # every cell is AOT-compiled and timed through the compiled
                # executable — one compile per cell, a uniform calling
                # convention (no jit-dispatch overhead skewing one side of
                # a row), and the structural check below reuses the text
                compiled = jax.jit(make_dp_train_step(
                    cfg, opt, mesh, zero2=True, opt_state=st,
                    compress=compress, accum=accum, overlap=overlap)).lower(
                        params, st, comp, data, jnp.int32(0)).compile()
                if overlap and accum == check_accum:
                    hlo = compiled.as_text()
                times[overlap] = time_fn(compiled, params, st, comp, data,
                                         jnp.int32(0), iters=iters)
            recs.append({
                "bench": "overlap", "arch": cfg.name, "n_dev": n_dev,
                "batch": batch, "seq": seq, "accum": accum,
                "wire": "int8" if compress else "fp32",
                "serialized_step_s": times[False],
                "pipelined_step_s": times[True],
                "pipelined_speedup": (times[False] / times[True]
                                      if times[True] else float("inf")),
            })

        # structural re-check: the pipelined schedule must show zero
        # cross-bucket serialization edges in the compiled HLO
        rep = collective_overlap_report(
            hlo, [(b.key, b.d_in, b.d_out) for b in plan.buckets])
        recs.append({
            "bench": "overlap_report", "arch": cfg.name, "n_dev": n_dev,
            "accum": check_accum, "wire": "int8" if compress else "fp32",
            "n_collectives": len(rep["collectives"]),
            "n_update_gathers": len(rep["update_gathers"]),
            "n_serialization_edges": rep["n_serialization_edges"],
        })
        if rep["n_serialization_edges"]:
            raise AssertionError(
                f"pipelined ZeRO-2 HLO has cross-bucket serialization "
                f"edges: {rep['serialization_edges']}")
    return recs


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt2-60m")
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--accum", nargs="*", type=int, default=[1, 2, 4, 8])
    ap.add_argument("--iters", type=int, default=3)
    args = ap.parse_args(argv)

    accums = sorted(set(args.accum + [1]))  # accum=1 anchors the comparison
    recs = bench_overlap(args.arch, args.batch, args.seq, accums, args.iters)

    rows = [[r["wire"], r["accum"],
             f"{1e3 * r['serialized_step_s']:.1f}",
             f"{1e3 * r['pipelined_step_s']:.1f}",
             f"{r['pipelined_speedup']:.2f}x"]
            for r in recs if r["bench"] == "overlap"]
    print("\n== ZeRO-2 step wall-clock: serialized vs bucket-pipelined ==")
    print_table(["wire", "accum", "serialized ms", "pipelined ms", "speedup"],
                rows)
    for r in recs:
        if r["bench"] == "overlap_report":
            print(f"[overlap] {r['wire']} accum={r['accum']}: "
                  f"{r['n_collectives']} collectives / "
                  f"{r['n_update_gathers']} update gathers / "
                  f"{r['n_serialization_edges']} serialization edges")
    write_artifact("BENCH_overlap", recs)
    return recs


if __name__ == "__main__":
    main()
