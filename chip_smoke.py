"""Chip smoke test: RMNP training on a TPU, end to end, at full width.

    python chip_smoke.py             # one chip (the default)
    python chip_smoke.py --chips 4   # the ZeRO-2 path on four chips

One chip, even where more are visible: gpt2-large at full width (random
weights from ``--seed``, synthetic data from ``make_stream``) trains
``TRAIN_STEPS`` steps at batch 8, sequence 1024 through
``repro.launch.train.train`` with the single-pass Pallas RMNP kernel.
Then the full train state makes one async checkpoint round trip that must
restore bitwise, and each kernel-routed bucket shape runs one RMNP update
on the chip, checked against the jnp reference (``kernels/ref.py``).

Four chips: gpt2-large with ZeRO-2 on the pipelined schedule (global
batch 32), each wire compared step by step with the replicated
``make_train_step`` (on the XLA path of the same update) on the same
mesh, seed and batches; per-device peak memory must be balanced.  The
fp32 wire runs all 36 layers.  The int8 wire and its reference run
``INT8_LAYERS`` layers at full width: its error-feedback residual (one
fp32 copy of the parameters per chip) and the quantizer's temporaries
need 18.67 GB per chip at 36 layers, over a v5e's 15.75 GB, whatever the
batch (ROADMAP D11).

Every phase runs in this one process.  A failed check raises and the
script exits non-zero; only when every phase passed is the last line of
standard output the JSON result ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import statistics
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "gpt2-large"
TRAIN_STEPS = 20    # one-chip training steps
ZERO2_STEPS = 6     # steps of each four-chip run
INT8_LAYERS = 18    # depth of the int8-wire run and its reference (D11)

# kernel-vs-reference bounds, as a fraction of the reference's largest
# magnitude: the momentum EMA is elementwise fp32 (only fusion may
# differ); the update w_new - w is read from bf16 weights,
# where the two w_new may round one ulp (at most 2^-7 of max|w_new|)
# apart, and max|w_new| stays within 2x max|update| (see UPDATE_CHECK)
KERNEL_BOUNDS = {"v_new": 1e-6, "update": 2.0 ** -6}
# the apply check's scale and weight decay; its weights are drawn at
# 1/(wd*sqrt(d_in)).  Every term of the update -scale*(d + wd*w) then
# shows in w_new = w + update: |d| is about 1/sqrt(d_in), wd*|w| is as
# large, and |w| is 1/wd of it.  A dropped, halved or sign-flipped
# update, or an ignored wd or scale, moves the update by about half its
# size or more
UPDATE_CHECK = {"scale": 0.5, "wd": 40.0}
# ZeRO-2 vs replicated loss, relative, per step.  The sound runs read at
# most 2.97e-5 (fp32 wire) and 1.42e-4 (int8 wire) at 18 layers on a v5e
# (CHANGES.md); the bounds sit a few times above.  Planted faults on a
# four-device CPU mesh (gpt2-60m reduced, six steps at 10x the default
# learning rates, where the loss falls 2.7% against gpt2-large's 8% on
# the chip) move the loss by 6.2e-3 (one rank's shard not updated) and
# 4.9e-3 (gradients of half the batch) on both wires
LOSS_RTOL = {"fp32": 2e-4, "int8": 5e-4}
PEAK_SPREAD = 0.10   # per-device peak memory within 10% of each other


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def tpu_devices(n: int):
    """The first ``n`` TPU devices; anything else is a failure, not a
    fallback."""
    import jax

    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise SmokeFailure(f"needs a TPU, found platform {d0.platform!r} "
                           f"({d0.device_kind}, {len(devs)} devices)")
    check(len(devs) >= n, f"needs {n} TPU chips, found {len(devs)}")
    return devs[:n]


def _peak(dev) -> int:
    return int(dev.memory_stats()["peak_bytes_in_use"])


def _compiled_bytes(report) -> str:
    """The compiler's memory analysis of the step program, per device."""
    m = report.memory
    return (f"compiled per device: arguments={m.argument_size_in_bytes} "
            f"outputs={m.output_size_in_bytes} "
            f"temps={m.temp_size_in_bytes} "
            f"aliased={m.alias_size_in_bytes}")


def bucket_routes(report) -> dict:
    """``report.routes`` without the attention call sites: per shape
    bucket, its RMNP kernel launch (``None``: the XLA path)."""
    return {k: ln for k, ln in report.routes.items()
            if not k.startswith("attention ")}


def phase_train(dev, *, seed: int):
    """Train on one device; returns (params, opt_state, report)."""
    from repro.configs import get_config
    from repro.launch.train import StepReport, train

    batch, seq = 8, 1024
    report = StepReport()
    params, opt_state, hist = train(
        ARCH, optimizer="rmnp", steps=TRAIN_STEPS, batch=batch, seq=seq,
        reduced=False, seed=seed, fused=True,
        use_kernel=True, log_every=1, devices=[dev], report=report)
    losses = [h["loss"] for h in hist]
    step_s = [h["step_s"] for h in hist]
    log(f"compile_s={report.compile_s}")
    for h in hist:
        log(f"step={h['step']} loss={h['loss']} step_s={h['step_s']}")
    steady = step_s[1:]
    med = statistics.median(steady)
    log(f"step_s median={med} mean={statistics.fmean(steady)} "
        f"min={min(steady)} max={max(steady)} (steps 1..{len(hist) - 1}, "
        f"host clock, block_until_ready)")
    log(f"tokens_per_s={batch * seq / med} (batch {batch} x seq {seq} "
        f"/ median step)")
    log(f"peak_bytes_in_use={_peak(dev)} {_compiled_bytes(report)}")
    check(len(losses) == TRAIN_STEPS,
          f"{len(losses)} of {TRAIN_STEPS} steps logged")
    check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    tail = statistics.fmean(losses[-5:])
    check(tail < losses[0],
          f"loss did not fall: last-5 mean {tail} >= step-0 {losses[0]}")
    log(f"loss step0={losses[0]} last5_mean={tail}")

    vocab = get_config(ARCH).vocab
    buckets = bucket_routes(report)
    kernel = {k: ln for k, ln in buckets.items() if ln is not None}
    xla = [k for k, ln in buckets.items() if ln is None]
    for key, ln in buckets.items():
        log(f"bucket {key}: " + ("xla" if ln is None else
                                 f"kernel {ln.name} grid {ln.grid}"))
    for key, route in report.routes.items():
        if key not in buckets:
            log(f"{key}: {route}")
    check(bool(buckets), "no bucket routing reported")
    # only the embedding (fan-in = the vocabulary) may take XLA
    bad = [k for k in xla if int(k.split("x")[0]) < vocab]
    check(not bad, f"block buckets routed to XLA: {bad}")
    n_custom = report.hlo_text.count('custom_call_target="tpu_custom_call"')
    log(f"tpu_custom_call={n_custom} kernel_buckets={len(kernel)} "
        f"xla_buckets={len(xla)}")
    check(n_custom >= len(kernel),
          f"{n_custom} tpu_custom_call < {len(kernel)} kernel buckets")
    return params, opt_state, report


def phase_checkpoint(state, step: int) -> None:
    """One async save of ``state``, then a restore that must match it
    bitwise, leaf by leaf."""
    import jax
    import numpy as np

    from repro.checkpoint.manager import CheckpointManager
    from repro.core.types import tree_paths

    with tempfile.TemporaryDirectory(dir=ROOT, prefix=".smoke_ckpt_") as d:
        mgr = CheckpointManager(d)
        t0 = time.perf_counter()
        mgr.save(step, state, data_step=step)
        stall = time.perf_counter() - t0
        mgr.wait()
        commit = time.perf_counter() - t0
        check(mgr.latest_step() == step,
              f"async save of step {step} did not commit")
        like = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
        t0 = time.perf_counter()
        restored, _ = mgr.restore(step, like)
        restore_s = time.perf_counter() - t0
        n_bytes, n_leaves = 0, 0
        for (path, a), b in zip(tree_paths(state),
                                jax.tree_util.tree_leaves(restored),
                                strict=True):
            a = np.asarray(a)
            check(a.dtype == b.dtype and a.shape == b.shape
                  and np.array_equal(a.view(np.uint8), b.view(np.uint8)),
                  f"checkpoint leaf {path} not restored bitwise")
            n_bytes += a.nbytes
            n_leaves += 1
    log(f"checkpoint bytes={n_bytes} leaves={n_leaves} save_stall_s={stall} "
        f"commit_s={commit} restore_s={restore_s} bitwise=True")


def _diffs(out, ref):
    """Max abs difference of ``out`` from ``ref`` and ``ref``'s max
    magnitude, both fp32 scalars (traced: reduced inside the jitted check,
    so no full-size difference buffer is ever held)."""
    import jax.numpy as jnp

    out = out.astype(jnp.float32)
    ref = ref.astype(jnp.float32)
    return jnp.max(jnp.abs(out - ref)), jnp.max(jnp.abs(ref))


def phase_kernel_check(shapes, *, seed: int) -> None:
    """One RMNP update per ``(L, d_in, d_out)`` bucket shape through the
    single-pass kernel (``kernels/ops.py``), against the jnp reference on
    the same device: its momentum and its weight update."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref

    beta = 0.95
    scale, wd = UPDATE_CHECK["scale"], UPDATE_CHECK["wd"]

    @jax.jit
    def apply_check(g, v, w):
        got = ops.rmnp_bucket_update_apply(g, v, w, scale, wd, beta=beta)
        want = ref.rmnp_rownorm_apply_ref(g, v, w, scale, wd, beta=beta)
        w32 = w.astype(jnp.float32)
        return {"v_new": _diffs(got[0], want[0]),
                "update": _diffs(got[1].astype(jnp.float32) - w32,
                                 want[1].astype(jnp.float32) - w32)}

    key = jax.random.PRNGKey(seed)
    for i, shape in enumerate(shapes):
        kg, kv, kw = jax.random.split(jax.random.fold_in(key, i), 3)
        g = jax.random.normal(kg, shape, jnp.float32)
        v = jax.random.normal(kv, shape, jnp.float32)
        w_std = 1.0 / (wd * math.sqrt(shape[1]))
        w = (w_std * jax.random.normal(kw, shape, jnp.float32)).astype(
            jnp.bfloat16)
        diffs = apply_check(g, v, w)
        del g, v, w
        for name, (abs_d, mag) in diffs.items():
            abs_d, mag = float(abs_d), float(mag)
            rel_d = abs_d / mag if mag else abs_d
            log(f"kernel_check shape={shape} {name} max_abs={abs_d} "
                f"max_rel={rel_d} bound_rel={KERNEL_BOUNDS[name]}")
            check(rel_d <= KERNEL_BOUNDS[name],
                  f"kernel {name} at {shape}: max_rel {rel_d} > "
                  f"{KERNEL_BOUNDS[name]}")


def run_one_chip(args) -> list:
    import gc

    devs = tpu_devices(1)
    params, opt_state, report = phase_train(devs[0], seed=args.seed)
    phase_checkpoint((params, opt_state), TRAIN_STEPS)
    del params, opt_state
    gc.collect()
    # a launch's stripe operand is (L, d_in, d_out padded to the lane
    # block); the bucket key is "d_inxd_out"
    shapes = [tuple(ln.in_blocks[-1].array_shape[:2])
              + (int(key.split("x")[1]),)
              for key, ln in sorted(bucket_routes(report).items())
              if ln is not None]
    phase_kernel_check(shapes, seed=args.seed)
    return devs


def compare_losses(name: str, hist, ref_hist, rtol: float) -> None:
    """Every step's loss of ``hist`` within ``rtol`` of ``ref_hist``'s."""
    for a, b in zip(hist, ref_hist, strict=True):
        rel = abs(a["loss"] - b["loss"]) / abs(b["loss"])
        log(f"{name} step={a['step']} loss={a['loss']} "
            f"replicated={b['loss']} rel={rel} rtol={rtol}")
        check(math.isfinite(a["loss"]) and rel <= rtol,
              f"{name} step {a['step']}: loss {a['loss']} vs replicated "
              f"{b['loss']} (rel {rel} > {rtol})")


def phase_zero2(devs, *, seed: int) -> None:
    """ZeRO-2 (pipelined) on both wires, each against the replicated step
    at the same depth, on the same mesh, seed and batches."""
    from repro.configs import get_config
    from repro.launch.train import StepReport, train

    full = get_config(ARCH)
    cut = dataclasses.replace(full, num_layers=INT8_LAYERS,
                              pattern=full.pattern[:INT8_LAYERS])
    common = dict(optimizer="rmnp", steps=ZERO2_STEPS, batch=32, seq=1024,
                  reduced=False, seed=seed, fused=True,
                  log_every=1, devices=devs)

    def run(name, cfg, **kw):
        report = StepReport()
        hist = train(cfg, report=report, **dict(common, **kw))[2]
        steady = [h["step_s"] for h in hist[1:]]
        log(f"{name} ({cfg.num_layers} layers) compile_s={report.compile_s} "
            f"step_s median={statistics.median(steady)} "
            f"losses={[h['loss'] for h in hist]}")
        # peaks are per process and never fall: the int8 run goes first,
        # and the fp32 run, which needs more, after it
        peaks = [_peak(d) for d in devs]
        log(f"{name} peak_bytes_in_use per device={peaks} "
            f"{_compiled_bytes(report)}")
        return hist, peaks

    def zero2(name, cfg, compress):
        hist, peaks = run(name, cfg, zero2=True, compress=compress,
                          overlap=True, use_kernel=True)
        check(max(peaks) <= (1 + PEAK_SPREAD) * min(peaks),
              f"{name}: per-device peak memory unbalanced {peaks}")
        return hist

    def replicated(cfg):
        # XLA cannot partition a Mosaic kernel over a mesh (ROADMAP D12),
        # so the replicated (pjit) step takes the same update's XLA path
        # (kernels/ref.py); the one-chip kernel check bounds the kernel
        # against it
        return run("replicated", cfg, zero2=False, use_kernel=False)[0]

    compare_losses("zero2 int8", zero2("zero2 int8", cut, True),
                   replicated(cut), LOSS_RTOL["int8"])
    compare_losses("zero2 fp32", zero2("zero2 fp32", full, False),
                   replicated(full), LOSS_RTOL["fp32"])


def run_four_chips(args) -> list:
    devs = tpu_devices(4)
    phase_zero2(devs, seed=args.seed)
    return devs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="1: train + checkpoint + kernel check on one "
                         "chip; 4: only the ZeRO-2 path on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    tpu_devices(args.chips)   # fail before compiling anything
    log(f"compile cache: {enable_compile_cache()}")
    devs = run_four_chips(args) if args.chips == 4 else run_one_chip(args)
    d0 = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
