"""Paper Table 2 / Figure 1: preconditioner-operator wall-clock, Muon
(Newton-Schulz-5) vs RMNP (row normalization), across GPT-2 scales.

The paper times 100 optimizer steps of only the preconditioning operator.
We time each *unique* matrix shape in the model once (jitted, median of 5)
and derive the per-100-step total as ``100 * sum(count_shape * t_shape)``
— identical arithmetic, far less CPU wall time.  On TPU the same harness
runs un-derived (``--no-derive``).

Also reports the analytic FLOP ratio O(mn*min(m,n)) / O(mn), the paper's
complexity claim.
"""
from __future__ import annotations

import argparse
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from benchmarks.common import print_table, time_fn, write_artifact
from repro.core.muon import newton_schulz
from repro.core.rmnp import row_normalize
from repro.core.schedule import constant

# GPT-2 scales of paper Table 4: name -> (layers, d_model)
GPT2_SIZES = {
    "gpt2-60m": (6, 640),
    "gpt2-small": (12, 768),
    "gpt2-200m": (16, 896),
    "gpt2-medium": (24, 1024),
    "gpt2-500m": (28, 1152),
    "gpt2-large": (36, 1280),
    "gpt2-1.3b": (44, 1536),
    "gpt2-xl": (48, 1600),
}


def layer_matrix_shapes(d: int) -> List[Tuple[Tuple[int, int], int]]:
    """(shape, count-per-layer) for one transformer block, stored (d_in, d_out)."""
    return [((d, 3 * d), 1),   # fused qkv
            ((d, d), 1),       # attention out
            ((d, 4 * d), 1),   # mlp in
            ((4 * d, d), 1)]   # mlp out


def ns_flops(m: int, n: int, steps: int = 5) -> float:
    s = min(m, n)
    # per NS step: X X^T (2 s s n) + G@G (2 s^3) + (·)@X (2 s s n)
    return steps * (2 * s * s * n * 2 + 2 * s ** 3)


def rn_flops(m: int, n: int) -> float:
    return 3.0 * m * n  # square + reduce + scale


def optimizer_state_bytes(layers: int, d: int) -> Dict[str, float]:
    """Paper Table 3's memory-parity claim, analytically: both optimizers
    keep exactly one fp32 momentum per matrix parameter — RMNP's
    normalization and Muon's NS are stateless transforms of it."""
    n_params = sum(count * layers * shape[0] * shape[1]
                   for shape, count in layer_matrix_shapes(d))
    return {"muon_state_bytes": 4.0 * n_params,
            "rmnp_state_bytes": 4.0 * n_params}


def bench_size(name: str, layers: int, d: int, ns_steps: int, iters: int,
               derive: bool = True) -> Dict:
    key = jax.random.PRNGKey(0)
    muon_t = rmnp_t = 0.0
    muon_fl = rmnp_fl = 0.0
    muon_fn = jax.jit(lambda v: newton_schulz(v, steps=ns_steps))
    rmnp_fn = jax.jit(lambda v: row_normalize(v))
    if not derive:
        # un-derived (TPU) harness: one jitted pass applying the operator to
        # every matrix in the model, timed directly
        mats = []
        for si, (shape, count) in enumerate(layer_matrix_shapes(d)):
            for i in range(count * layers):
                mats.append(jax.random.normal(
                    jax.random.fold_in(key, si * 10007 + i), shape, jnp.float32))
        muon_all = jax.jit(lambda ms: [newton_schulz(m, steps=ns_steps) for m in ms])
        rmnp_all = jax.jit(lambda ms: [row_normalize(m) for m in ms])
        muon_t = time_fn(muon_all, mats, iters=iters)
        rmnp_t = time_fn(rmnp_all, mats, iters=iters)
        for shape, count in layer_matrix_shapes(d):
            muon_fl += count * layers * ns_flops(*shape, steps=ns_steps)
            rmnp_fl += count * layers * rn_flops(*shape)
        return {
            "size": name, "layers": layers, "d_model": d, "derived": False,
            "muon_100steps_s": 100 * muon_t,
            "rmnp_100steps_s": 100 * rmnp_t,
            "speedup": muon_t / rmnp_t if rmnp_t else float("inf"),
            "flop_ratio": muon_fl / rmnp_fl,
            **optimizer_state_bytes(layers, d),
        }
    for shape, count in layer_matrix_shapes(d):
        v = jax.random.normal(key, shape, jnp.float32)
        t_m = time_fn(muon_fn, v, iters=iters)
        t_r = time_fn(rmnp_fn, v, iters=iters)
        muon_t += count * layers * t_m
        rmnp_t += count * layers * t_r
        muon_fl += count * layers * ns_flops(*shape, steps=ns_steps)
        rmnp_fl += count * layers * rn_flops(*shape)
    return {
        "size": name, "layers": layers, "d_model": d, "derived": True,
        "muon_100steps_s": 100 * muon_t,
        "rmnp_100steps_s": 100 * rmnp_t,
        "speedup": muon_t / rmnp_t if rmnp_t else float("inf"),
        "flop_ratio": muon_fl / rmnp_fl,
        **optimizer_state_bytes(layers, d),  # Table 3: identical memory
    }


def bench_fused(name: str, layers: int, d: int, iters: int) -> Dict:
    """Shape-bucketed engine vs the per-leaf RMNP reference: wall-clock per
    optimizer step plus kernel launches per step.

    Launches are counted by tracing the Pallas (``use_kernel=True``) update
    and counting ``pallas_call`` equations — no execution, so it is exact
    and free even on CPU.  Wall-clock is measured on the Pallas path on TPU
    and on the XLA path on CPU (interpret-mode Pallas times the Python
    interpreter, not the math)."""
    from repro.core import make_optimizer
    from repro.core.rules import RmnpRule, per_leaf_reference
    from repro.train.step import optimizer_launches

    params, grads = _bucketed_tree(layers, d, jax.random.PRNGKey(0))
    on_tpu = jax.default_backend() == "tpu"

    def per_leaf(use_kernel):
        return per_leaf_reference(RmnpRule(), constant(1e-3),
                                  use_kernel=use_kernel)

    def fused(use_kernel):
        return make_optimizer("rmnp", lr_matrix=1e-3, fused=True,
                              use_kernel=use_kernel)

    launches_leaf = optimizer_launches(per_leaf(True), params)
    launches_fused = optimizer_launches(fused(True), params)

    def step_of(opt):
        state = opt.init(params)
        fn = jax.jit(lambda g, s, p: opt.update_apply(g, s, p, 0))
        return time_fn(fn, grads, state, params, iters=iters)

    t_leaf = step_of(per_leaf(on_tpu))
    t_fused = step_of(fused(on_tpu))
    n_buckets = len({(s.shape[-2], s.shape[-1]) for s in params.values()})
    return {
        "size": name, "layers": layers, "d_model": d,
        "n_matrix_leaves": len(params),
        "n_buckets": n_buckets,
        "launches_per_leaf_step": launches_leaf,
        "launches_fused_step": launches_fused,
        "per_leaf_step_s": t_leaf,
        "fused_step_s": t_fused,
        "fused_speedup": t_leaf / t_fused if t_fused else float("inf"),
        "timed_backend": "pallas" if on_tpu else "xla",
    }


def _bucketed_tree(layers: int, d: int, key):
    """Synthetic (params, grads) trees with the GPT-2 per-layer matrix mix."""
    params, grads = {}, {}
    for i in range(layers):
        for si, (shape, count) in enumerate(layer_matrix_shapes(d)):
            for c in range(count):
                k = f"layer_{i}/m{si}_{c}"
                params[k] = jnp.zeros(shape, jnp.float32)
                grads[k] = jax.random.normal(
                    jax.random.fold_in(key, i * 1009 + si * 31 + c),
                    shape, jnp.float32)
    return params, grads


def bench_zero2(name: str, layers: int, d: int, n_dev: int) -> Dict:
    """ZeRO-0/1/2 per-rank memory and wire-byte accounting for the bucketed
    matrix partition, analytic (exact — these are byte counts, not timings).

    The model's matrix partition buckets into the 4 per-layer shapes, each
    with ``L = layers`` stacked slices, padded to the axis size under
    ZeRO-1/2 (``core/bucketing.py``).  Per step and per rank:

    * ZeRO-0: full fp32 mean-grad bucket + full momentum; ring all-reduce.
    * ZeRO-1: full mean-grad bucket, momentum sharded ``/N``; all-reduce
      plus the updated-param-slice all-gather.
    * ZeRO-2: grad reduce-scattered straight into the shard — grad bucket
      *and* momentum both ``/N``; reduce-scatter + param all-gather moves
      the same bytes as one all-reduce, so the memory win is free.

    The int8 columns use the error-feedback schedule of
    ``distributed/compression.py``: a2a int8 + fp32 block scales (+ bf16
    gather for the mean variants; the ZeRO-2 reduce-scatter drops that
    stage entirely)."""
    shapes = [(shape, layers) for shape, _ in layer_matrix_shapes(d)]
    n = sum(L * m * k for (m, k), L in shapes)
    n_pad = sum(-(-L // n_dev) * n_dev * m * k for (m, k), L in shapes)
    frac = (n_dev - 1) / n_dev
    scales = 4.0 * n / 512          # one fp32 scale per 512-elem block
    scales_pad = 4.0 * n_pad / 512  # the ZeRO-2 path quantizes padded chunks
    # ZeRO-1 gathers the full mean-grad bucket per rank (padded, since the
    # sharded optimizer pads); ZeRO-0 runs the unpadded replicated plan
    grad = {"zero0": 4.0 * n, "zero1": 4.0 * n_pad,
            "zero2": 4.0 * n_pad / n_dev}
    state = {"zero0": 4.0 * n, "zero1": 4.0 * n_pad / n_dev,
             "zero2": 4.0 * n_pad / n_dev}
    gather_w = 4.0 * n_pad * frac  # updated param slices, fp32
    # the ZeRO-0/1 reduction runs per-leaf (unpadded n on the wire; ZeRO-1
    # pads only at the local gather); ZeRO-2 reduce-scatters padded chunks
    wire = {"zero0": 2 * 4.0 * n * frac,
            "zero1": 2 * 4.0 * n * frac + gather_w,
            "zero2": 4.0 * n_pad * frac + gather_w}
    wire_int8 = {"zero0": (1.0 * n + scales) * frac + 2.0 * n * frac,
                 "zero1": (1.0 * n + scales) * frac + 2.0 * n * frac + gather_w,
                 "zero2": (1.0 * n_pad + scales_pad) * frac + gather_w}
    return {"bench": "zero2", "size": name, "layers": layers, "d_model": d,
            "n_dev": n_dev, "matrix_elems": n, "matrix_elems_padded": n_pad,
            **{f"grad_bucket_bytes_{z}": grad[z] for z in grad},
            **{f"state_bytes_{z}": state[z] for z in state},
            **{f"wire_bytes_{z}": wire[z] for z in wire},
            **{f"wire_bytes_int8_{z}": wire_int8[z] for z in wire_int8}}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", nargs="*", default=None)
    ap.add_argument("--quick", action="store_true",
                    help="only up to gpt2-medium (CPU-friendly)")
    ap.add_argument("--ns-steps", type=int, default=5)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--derive", dest="derive", action="store_true", default=True,
                    help="derive per-100-step totals from unique shapes (default)")
    ap.add_argument("--no-derive", dest="derive", action="store_false",
                    help="time every matrix directly (TPU harness)")
    ap.add_argument("--fused", action="store_true",
                    help="also benchmark the shape-bucketed engine "
                         "(wall-clock + launches per optimizer step)")
    ap.add_argument("--fused-layers", type=int, default=4,
                    help="layer count for the fused section (0 = the size's "
                         "real depth; capped by default to bound memory)")
    ap.add_argument("--zero2", action="store_true",
                    help="emit the ZeRO-0/1/2 per-rank grad-bucket / "
                         "momentum / wire-byte accounting "
                         "(BENCH_zero2.json; analytic, exact)")
    ap.add_argument("--zero2-ranks", type=int, default=8,
                    help="data-axis size N for the --zero2 accounting")
    args = ap.parse_args(argv)

    sizes = args.sizes or list(GPT2_SIZES)
    if args.quick and not args.sizes:
        sizes = ["gpt2-60m", "gpt2-small", "gpt2-200m", "gpt2-medium"]

    rows, recs = [], []
    for name in sizes:
        layers, d = GPT2_SIZES[name]
        r = bench_size(name, layers, d, args.ns_steps, args.iters,
                       derive=args.derive)
        recs.append(r)
        rows.append([name, f"{r['muon_100steps_s']:.3f}",
                     f"{r['rmnp_100steps_s']:.3f}", f"{r['speedup']:.1f}x",
                     f"{r['flop_ratio']:.0f}x"])
    print("\n== Table 2: preconditioning wall-clock per 100 steps ==")
    print_table(["size", "Muon (s)", "RMNP (s)", "speedup", "FLOP ratio"], rows)

    if args.fused:
        frows = []
        for name in sizes:
            layers, d = GPT2_SIZES[name]
            fl = args.fused_layers or layers
            fr = bench_fused(name, min(fl, layers), d, args.iters)
            recs.append({"bench": "fused_engine", **fr})
            frows.append([name, fr["n_matrix_leaves"], fr["n_buckets"],
                          fr["launches_per_leaf_step"], fr["launches_fused_step"],
                          f"{1e3 * fr['per_leaf_step_s']:.2f}",
                          f"{1e3 * fr['fused_step_s']:.2f}",
                          f"{fr['fused_speedup']:.2f}x"])
        print("\n== fused update engine: launches + wall-clock per step ==")
        print_table(["size", "leaves", "buckets", "launch/leaf", "launch/fused",
                     "leaf ms", "fused ms", "speedup"], frows)

    if args.zero2:
        zrows, zrecs = [], []
        mb = 1.0 / 2**20
        for name in sizes:
            layers, d = GPT2_SIZES[name]
            zr = bench_zero2(name, layers, d, args.zero2_ranks)
            recs.append(zr)
            zrecs.append(zr)
            zrows.append([name] +
                         [f"{zr[f'grad_bucket_bytes_{z}'] * mb:.1f}"
                          for z in ("zero0", "zero1", "zero2")] +
                         [f"{zr[f'state_bytes_{z}'] * mb:.1f}"
                          for z in ("zero0", "zero1", "zero2")] +
                         [f"{zr[f'wire_bytes_int8_{z}'] * mb:.1f}"
                          for z in ("zero0", "zero1", "zero2")])
        print(f"\n== ZeRO sharding: per-rank MiB (N={args.zero2_ranks}) ==")
        print_table(["size", "grad z0", "grad z1", "grad z2",
                     "mom z0", "mom z1", "mom z2",
                     "wire8 z0", "wire8 z1", "wire8 z2"], zrows)
        write_artifact("BENCH_zero2", zrecs)

    write_artifact("precond_time", recs)
    return recs


if __name__ == "__main__":
    main()
