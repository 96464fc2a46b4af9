"""Subprocess worker for the ZeRO optimizer-state / gradient sharding tests.

Runs under ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (set by
the parent test — the flag must be in place before jax initializes, which
is why this cannot run in the main pytest process).  Exercises:

  * a 4-way ``data`` mesh over a synthetic bucketed tree with *uneven*
    buckets (``L % N != 0``, including ``L < N``): with the optimizer built
    with ``shard_size=4`` every bucket pads and shards — per-rank stacked
    momentum holds exactly ``padded L / N`` slices, pad slices stay
    identically zero, and both the ZeRO-1 step (full gradient, sharded
    momentum) and the ZeRO-2 step (reduce-scattered gradient shards via
    ``update_apply_sharded``) are bit-identical to the replicated step;
  * a traced-buffer assertion (``count_buffer_eqns``): with bf16 params
    the ZeRO-2 step materializes *zero* full-``(padded L, d_in, d_out)``
    fp32 buffers per rank — the mean-gradient bucket never exists — while
    the ZeRO-1 step (which gathers the full mean-gradient bucket) does;
  * the full ``make_dp_train_step`` path on a reduced GPT-2 model over a
    2-way mesh, ZeRO-1 and ZeRO-2: params after one update match the
    replicated step exactly and every bucket is halved per rank under
    ``shard_size=2``; the compressed (int8 reduce-scatter) ZeRO-2 step
    trains to a finite loss;
  * the bucket-pipelined ZeRO-2 step (train/pipeline.py) over the 4-way
    mesh: pipelined ``accum=1`` is bitwise the replicated step (grad_norm
    metric included), pipelined ``accum=4`` is bitwise the serialized
    ``accum=4`` baseline and allclose to ``accum=1``, the monolithic fp32
    gradient bucket still never materializes with ``accum=4``, and
    ``collective_overlap_report`` finds zero cross-bucket serialization
    edges in the compiled HLO (fp32 and int8 schedules);
  * every registered matrix update rule (rmnp, muon, normuon, muown, nora)
    through the generic bucketed engine: two consecutive ZeRO-2 steps on
    the 4-way mesh — momentum AND slot stripes sharded — bitwise equal to
    the per-leaf reference optimizer (core/rules.py), pad slices zero in
    momentum and every slot, and each rule's pipelined dp step compiling
    with zero cross-bucket serialization edges;
  * the two-phase clip on a synthetic tree whose leaves are each contained
    in one rank's chunk: with the clip ACTIVE, ``grad_norm`` and the clip
    scale are bit-for-bit the replicated ``clip_by_global_norm``'s.

Prints ``ZERO_SHARD_OK`` as the last line on success; any assertion error
fails the subprocess (and therefore the parent test).

Numerical-resilience fault injection (``guard`` argv mode): NaN/Inf
gradient faults and an int8 wire-scale bit-flip are injected into the
REAL guarded ZeRO-2 step (``repro.train.faults``) on the 4-way mesh, and
the guarded run is held BITWISE equal — params, momentum, slot stripes,
AdamW moments and the int8 error-feedback residual — to a clean run with
the faulted step skipped host-side, at every surviving step, for rmnp and
normuon on both wires; plus guard transparency (guarded clean == unguarded
clean bitwise) and the full ``launch/train.py --inject-fault`` rewind
ladder on llama-60m (skip -> rewind to last-known-good -> bitwise
recovery of the uninterrupted run; exhausted ladder -> loud abort).
Prints ``GUARD_OK`` as its last line on success.

Elastic restart fault injection (``elastic`` / ``elastic-phase`` argv
modes): an 8-way ZeRO-2 training loop over the synthetic tree is SIGKILLed
mid-run and resumed 4-way (and 4->8) from the surviving atomic checkpoint;
the resumed run's final params, momentum, slot stripes and EF residual are
held BITWISE equal to an uninterrupted run at the target mesh size, for
the fp32 psum_scatter wire and the int8 error-feedback wire, for rmnp and
normuon.  Cross-mesh bitwise equality is only meaningful because the
driving gradients are exactness-preserving (see ``_int_grads``); the
orchestrator prints ``ELASTIC_OK`` as its last line on success.

Checkpoint corruption fault injection (``ckpt`` argv mode): a real int8-EF
ZeRO-2 state on the 4-way mesh is saved through the sharded two-phase
commit (four shard files + SHARD_COMMITTED markers + CRC32 manifest +
COMMITTED) and restored bitwise — every rank's EF residual included —
then each corruption kind from ``repro.checkpoint.faults`` (bit-rot,
truncated shard, missing rank shard, torn manifest) is injected into the
newest checkpoint and restore must detect it BY NAME and fall back to the
previous good step bitwise; plus the per-rule checksum property (every
registered rule x every shard rank: one flipped byte names the leaf path
and rank).  Prints ``CKPT_OK`` as its last line on success.
"""
import argparse
import os
import shutil
import signal
import subprocess
import sys
import tempfile
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import shard_map  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.core import (  # noqa: E402
    bucketing, constant, make_optimizer, mixed_optimizer,
)
from repro.core.types import tree_paths  # noqa: E402
from repro.distributed.compression import exact_reduce_scatter  # noqa: E402
from repro.distributed.sharding import bucket_specs  # noqa: E402
from repro.kernels.ops import count_buffer_eqns  # noqa: E402
from repro.launch.mesh import make_data_mesh  # noqa: E402

# synthetic tree: bucket 8x16 has L=8 (divisible by 4), bucket 8x24 has
# L=3 (uneven AND < N), bucket 16x8 has L=6 (uneven, > N) -> padded
# sizes 8 / 4 / 8 under shard_size=4.  Lead dims are chosen so no single
# leaf reshape coincides with a full padded bucket shape (keeps the
# traced-buffer count free of reshape false-positives).
SHAPES = {**{f"l{i}/w": (2, 8, 16) for i in range(4)},
          "odd/w": (3, 8, 24),
          "six/w": (6, 16, 8)}
PADDED = {"8x16": (8, 2), "8x24": (4, 1), "16x8": (8, 2)}  # (padded, per-rank)


def make(seed, shapes=None):
    shapes = shapes or SHAPES
    return {k: jax.random.normal(
        jax.random.fold_in(jax.random.PRNGKey(seed), i), s, jnp.float32)
        for i, (k, s) in enumerate(sorted(shapes.items()))}


def synthetic_four_way():
    assert len(jax.devices()) >= 4, f"need 4 CPU devices, got {jax.devices()}"
    mesh = make_data_mesh(4)
    params, grads = make(0), make(1)
    opt_sh = make_optimizer("rmnp", lr_matrix=0.1, beta=0.9,
                            shard_axis="data", shard_size=4)
    opt_rep = make_optimizer("rmnp", lr_matrix=0.1, beta=0.9, fused=True)
    sizes = {b.key: b.size for b in opt_rep.bucket_plan(params).buckets}

    state = opt_sh.init(params)
    sspec = bucket_specs(state, mesh)
    # shard_size=4 pads every bucket, so every bucket must get a real spec
    assert all(s[0] == "data" for s in sspec.buckets.values()), sspec.buckets
    p_rep, s_rep = jax.jit(opt_rep.update_apply)(
        grads, opt_rep.init(params), params, 0)

    def check(tag, p_sh, s_sh):
        for k in p_sh:
            np.testing.assert_array_equal(
                np.asarray(p_sh[k]), np.asarray(p_rep[k]),
                err_msg=f"{tag}: sharded != replicated: {k}")
        for k, (padded, per_rank) in PADDED.items():
            shard = s_sh.buckets[k].addressable_shards[0].data
            assert shard.shape[0] == per_rank, (tag, k, shard.shape)
            assert s_sh.buckets[k].shape[0] == padded, (tag, k)
            assert shard.nbytes * 4 == s_sh.buckets[k].nbytes
            full = np.asarray(s_sh.buckets[k])
            np.testing.assert_array_equal(
                full[:sizes[k]], np.asarray(s_rep.buckets[k]),
                err_msg=f"{tag}: momentum mismatch: {k}")
            # the pad-slice invariant: zero grad -> zero momentum
            assert np.all(full[sizes[k]:] == 0), (tag, k)

    # ZeRO-1: full gradient operand, sharded (padded) momentum
    step_z1 = jax.jit(shard_map(
        lambda g, s, p: opt_sh.update_apply(g, s, p, 0), mesh=mesh,
        in_specs=(P(), sspec, P()), out_specs=(P(), sspec), check_vma=False))
    check("zero1", *step_z1(grads, state, params))

    # ZeRO-2: reduce-scatter the gradient buckets into the shard
    def z2(g, s, p):
        plan = opt_sh.bucket_plan(p)
        chunks = bucketing.gather_chunks(plan, g, 4, dtype=jnp.float32)
        shards = {b.key: exact_reduce_scatter(chunks[b.key], "data")
                  for b in plan.buckets}
        return opt_sh.update_apply_sharded(shards, g, s, p, 0)

    step_z2 = jax.jit(shard_map(
        z2, mesh=mesh, in_specs=(P(), sspec, P()), out_specs=(P(), sspec),
        check_vma=False))
    check("zero2", *step_z2(grads, state, params))
    print("synthetic 4-way: OK (zero1 + zero2 bitwise, uneven buckets "
          "padded+sharded)")


def synthetic_traced_buffers():
    """With bf16 params, any full-(padded L, d_in, d_out) fp32 equation is a
    gradient-path intermediate.  ZeRO-2 must have none — the mean-gradient
    bucket never exists per rank — while ZeRO-1 gathers it (>= 1)."""
    mesh = make_data_mesh(4)
    opt_sh = make_optimizer("rmnp", lr_matrix=0.1, beta=0.9,
                            shard_axis="data", shard_size=4)
    params = jax.tree_util.tree_map(lambda p: p.astype(jnp.bfloat16), make(0))
    grads = make(1)
    state = jax.eval_shape(opt_sh.init, params)
    sspec = bucket_specs(state, mesh)
    abstract = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), (grads, params))

    def z1(g, s, p):
        return opt_sh.update_apply(g, s, p, 0)

    def z2(g, s, p):
        plan = opt_sh.bucket_plan(p)
        chunks = bucketing.gather_chunks(plan, g, 4, dtype=jnp.float32)
        shards = {b.key: exact_reduce_scatter(chunks[b.key], "data")
                  for b in plan.buckets}
        return opt_sh.update_apply_sharded(shards, g, s, p, 0)

    plan = opt_sh.bucket_plan(params)
    for fn, name, expect_zero in ((z1, "zero1", False), (z2, "zero2", True)):
        step = shard_map(fn, mesh=mesh, in_specs=(P(), sspec, P()),
                         out_specs=(P(), sspec), check_vma=False)
        for b in plan.buckets:
            # the shard_map eqn's own outvars are *global-view* avals of the
            # (physically sharded) outputs, not per-rank buffers — the walk
            # recurses into its inner jaxpr where the real allocations live
            n = count_buffer_eqns(step, (b.padded, b.d_in, b.d_out),
                                  jnp.float32, abstract[0], state,
                                  abstract[1], exclude_prims=("shard_map",))
            if expect_zero:
                assert n == 0, (name, b.key, n)
            elif len(b.entries) > 1:  # single-entry buckets gather by reshape
                assert n >= 1, (name, b.key, n)
    print("traced buffers: OK (zero2 has no full fp32 gradient bucket)")


def dp_step_two_way():
    from repro.configs import get_config
    from repro.models import init_params
    from repro.train.dp_step import init_dp_state, make_dp_train_step

    mesh = make_data_mesh(2)
    cfg = get_config("gpt2-60m").reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab)
    batch = {"tokens": toks, "labels": toks}

    opt_sh = mixed_optimizer("rmnp", constant(1e-2), constant(1e-2),
                             shard_axis="data")
    opt_rep = mixed_optimizer("rmnp", constant(1e-2), constant(1e-2),
                              fused=True)
    st_sh, st_rep = opt_sh.init(params), opt_rep.init(params)
    comp = init_dp_state(params, 2)

    step_sh = jax.jit(make_dp_train_step(
        cfg, opt_sh, mesh, shard_state=True, opt_state=st_sh, compress=False))
    step_rep = jax.jit(make_dp_train_step(cfg, opt_rep, mesh, compress=False))
    p1, s1, _, m1 = step_sh(params, st_sh, comp, batch, jnp.int32(0))
    p2, s2, _, _ = step_rep(params, st_rep, comp, batch, jnp.int32(0))
    for (k, a), (_, b) in zip(tree_paths(p1), tree_paths(p2), strict=False):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32), err_msg=k)
    assert np.isfinite(float(np.asarray(m1["loss"])))
    sharded_bytes = sum(b.addressable_shards[0].data.nbytes
                       for b in s1.buckets.values())
    global_bytes = sum(b.nbytes for b in s1.buckets.values())
    # buckets with even L halve per-rank; the L=1 embed bucket replicates
    assert sharded_bytes < global_bytes, (sharded_bytes, global_bytes)
    per_rank = {k: b.addressable_shards[0].data.shape[0]
                for k, b in s1.buckets.items()}
    glob = {k: b.shape[0] for k, b in s1.buckets.items()}
    for k in glob:
        expect = glob[k] // 2 if glob[k] % 2 == 0 else glob[k]
        assert per_rank[k] == expect, (k, per_rank[k], glob[k])
    print(f"dp 2-way zero1: OK (per-rank bucket bytes {sharded_bytes} "
          f"of {global_bytes} global)")


def dp_step_two_way_zero2():
    """Full dp train step, ZeRO-2 vs replicated, bitwise.  clip_norm is set
    above the step's gradient norm in both paths: the global norm itself is
    summed in a different order across the sharded matrix partition (psum
    over shards vs per-leaf tree order), so the scale factor — exactly 1.0
    when unclipped — is the one quantity that cannot match bitwise when the
    clip is active."""
    from repro.configs import get_config
    from repro.models import init_params
    from repro.train.dp_step import init_dp_state, make_dp_train_step

    mesh = make_data_mesh(2)
    cfg = get_config("gpt2-60m").reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab)
    batch = {"tokens": toks, "labels": toks}

    opt_z2 = mixed_optimizer("rmnp", constant(1e-2), constant(1e-2),
                             shard_axis="data", shard_size=2)
    opt_rep = mixed_optimizer("rmnp", constant(1e-2), constant(1e-2),
                              fused=True)
    st_z2, st_rep = opt_z2.init(params), opt_rep.init(params)
    comp = init_dp_state(params, 2)

    step_z2 = jax.jit(make_dp_train_step(
        cfg, opt_z2, mesh, zero2=True, opt_state=st_z2, compress=False,
        clip_norm=1e6, overlap=True))
    step_rep = jax.jit(make_dp_train_step(cfg, opt_rep, mesh, compress=False,
                                          clip_norm=1e6))
    p1, s1, _, m1 = step_z2(params, st_z2, comp, batch, jnp.int32(0))
    p2, _, _, _ = step_rep(params, st_rep, comp, batch, jnp.int32(0))
    for (k, a), (_, b) in zip(tree_paths(p1), tree_paths(p2), strict=False):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32),
                                      err_msg=f"zero2: {k}")
    assert np.isfinite(float(np.asarray(m1["loss"])))
    # shard_size=2 pads every bucket (the L=1 embed bucket included) so
    # every bucket is exactly halved per rank
    for k, b in s1.buckets.items():
        shard = b.addressable_shards[0].data
        assert b.shape[0] % 2 == 0, (k, b.shape)
        assert shard.shape[0] == b.shape[0] // 2, (k, shard.shape, b.shape)

    # no full-bucket fp32 gradient intermediate per rank (all_gather carries
    # the updated fp32 *weights* by design; reshapes are buffer-free views;
    # the shard_map eqn's outvars are global-view avals of sharded outputs)
    opt_tr = mixed_optimizer("rmnp", constant(1e-2), constant(1e-2),
                             shard_axis="data", shard_size=2)
    st_tr = jax.eval_shape(opt_tr.init, params)
    step_tr = make_dp_train_step(cfg, opt_tr, mesh, zero2=True,
                                 opt_state=st_tr, compress=False,
                                 clip_norm=1e6, overlap=True)
    abstract = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), x.dtype),
        (params, comp, batch))
    for b in opt_tr.bucket_plan(params).buckets:
        if any(e.shape == (b.padded, b.d_in, b.d_out) for e in b.entries):
            # a single-leaf bucket whose shape IS the leaf shape: the
            # *local* gradient leaf out of the backward pass collides with
            # the bucket shape and the count cannot distinguish them
            continue
        n = count_buffer_eqns(step_tr, (b.padded, b.d_in, b.d_out),
                              jnp.float32, abstract[0], st_tr, abstract[1],
                              abstract[2], jnp.int32(0),
                              exclude_prims=("all_gather", "reshape",
                                             "shard_map"))
        assert n == 0, (b.key, n)

    # the compressed (int8 reduce-scatter) ZeRO-2 schedule trains
    step_c = jax.jit(make_dp_train_step(
        cfg, opt_z2, mesh, zero2=True, opt_state=st_z2, compress=True))
    pc, sc, cc = params, opt_z2.init(params), comp
    for i in range(3):
        pc, sc, cc, mc = step_c(pc, sc, cc, batch, jnp.int32(i))
        assert np.isfinite(float(np.asarray(mc["loss"]))), i
    print("dp 2-way zero2: OK (bitwise vs replicated, padded buckets "
          "halved, no fp32 grad bucket, int8 schedule trains)")


def dp_step_pipelined_four_way():
    """The bucket-pipelined ZeRO-2 step on the 4-way mesh: numerical
    equivalence (pipelined accum=1 == replicated bitwise, grad_norm metric
    included; pipelined accum=4 == serialized accum=4 bitwise; accum=4 ~=
    accum=1 allclose), the accum>1 traced-buffer invariant, and the
    compiled-HLO overlap report."""
    from repro.configs import get_config
    from repro.kernels.ops import count_buffer_eqns
    from repro.launch.hlo_cost import collective_overlap_report
    from repro.models import init_params
    from repro.train.dp_step import init_dp_state, make_dp_train_step

    mesh = make_data_mesh(4)
    cfg = get_config("gpt2-60m").reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (16, 16), 0, cfg.vocab)
    batch = {"tokens": toks, "labels": toks}

    opt = mixed_optimizer("rmnp", constant(1e-2), constant(1e-2),
                          shard_axis="data", shard_size=4)
    opt_rep = mixed_optimizer("rmnp", constant(1e-2), constant(1e-2),
                              fused=True)
    st = opt.init(params)
    comp = init_dp_state(params, 4)

    def run(step_fn, state):
        return jax.jit(step_fn)(params, state, comp, batch, jnp.int32(0))

    # pipelined accum=1 == replicated, bitwise — grad_norm included: the
    # two-phase clip replays clip_by_global_norm's per-leaf summation order
    # (per-rank partials over each leaf's slices, one psum)
    p1, _, _, m1 = run(make_dp_train_step(
        cfg, opt, mesh, zero2=True, opt_state=st, compress=False,
        clip_norm=1e6, overlap=True), st)
    p_rep, _, _, m_rep = run(make_dp_train_step(
        cfg, opt_rep, mesh, compress=False, clip_norm=1e6),
        opt_rep.init(params))
    for (k, a), (_, b) in zip(tree_paths(p1), tree_paths(p_rep), strict=False):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32),
                                      err_msg=f"pipelined accum=1: {k}")
    np.testing.assert_array_equal(
        np.asarray(m1["grad_norm"]), np.asarray(m_rep["grad_norm"]),
        err_msg="pipelined grad_norm != replicated grad_norm")

    # pipelined accum=4 == serialized accum=4 bitwise (the restructure —
    # chunked-in-scan accumulation, per-bucket chains, clip folded into the
    # update — is numerically exact); accum=4 ~= accum=1 (fp32 association
    # of the microbatch sums is the only difference)
    p4, _, _, _ = run(make_dp_train_step(
        cfg, opt, mesh, zero2=True, opt_state=st, compress=False,
        clip_norm=1e6, accum=4, overlap=True), st)
    p4s, _, _, _ = run(make_dp_train_step(
        cfg, opt, mesh, zero2=True, opt_state=st, compress=False,
        clip_norm=1e6, accum=4, overlap=False), st)
    for (k, a), (_, b) in zip(tree_paths(p4), tree_paths(p4s), strict=False):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32),
                                      err_msg=f"pipelined vs serialized: {k}")
    for (k, a), (_, b) in zip(tree_paths(p4), tree_paths(p1), strict=False):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-4, atol=2e-6,
                                   err_msg=f"accum=4 vs accum=1: {k}")

    # compressed pipelined accum=4 == compressed serialized accum=4 bitwise
    # (the int8 error-feedback fold in chunked layout is exact), and trains
    pc, sc, cc, mc = run(make_dp_train_step(
        cfg, opt, mesh, zero2=True, opt_state=st, compress=True, accum=4,
        overlap=True), st)
    pcs, _, _, _ = run(make_dp_train_step(
        cfg, opt, mesh, zero2=True, opt_state=st, compress=True, accum=4,
        overlap=False), st)
    for (k, a), (_, b) in zip(tree_paths(pc), tree_paths(pcs), strict=False):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32),
                                      err_msg=f"int8 pipelined: {k}")
    assert np.isfinite(float(np.asarray(mc["loss"])))

    # the monolithic fp32 gradient bucket still never exists with accum=4
    st_tr = jax.eval_shape(opt.init, params)
    abstract = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), x.dtype),
        (params, comp, batch))
    plan = opt.bucket_plan(params)
    step_tr = make_dp_train_step(cfg, opt, mesh, zero2=True, opt_state=st_tr,
                                 compress=False, clip_norm=1e6, accum=4,
                                 overlap=True)
    for b in plan.buckets:
        if any(e.shape == (b.padded, b.d_in, b.d_out) for e in b.entries):
            continue  # leaf shape collides with the bucket shape
        n = count_buffer_eqns(step_tr, (b.padded, b.d_in, b.d_out),
                              jnp.float32, abstract[0], st_tr, abstract[1],
                              abstract[2], jnp.int32(0),
                              exclude_prims=("all_gather", "reshape",
                                             "shard_map"))
        assert n == 0, ("accum=4 full fp32 bucket", b.key, n)

    # compiled-HLO structure: no bucket's collective data-depends on
    # another bucket's update output (fp32 and int8 schedules)
    bks = [(b.key, b.d_in, b.d_out) for b in plan.buckets]
    for compress in (False, True):
        step = make_dp_train_step(cfg, opt, mesh, zero2=True,
                                  opt_state=st_tr, compress=compress,
                                  accum=4, overlap=True)
        hlo = jax.jit(step).lower(abstract[0], st_tr, abstract[1],
                                  abstract[2], jnp.int32(0)).compile().as_text()
        rep = collective_overlap_report(hlo, bks)
        assert rep["collectives"], "no gradient collectives found in HLO"
        assert len(rep["update_gathers"]) == len(plan.buckets), rep
        assert rep["n_serialization_edges"] == 0, rep["serialization_edges"]
    print("dp 4-way pipelined: OK (accum=1 bitwise vs replicated incl "
          "grad_norm, accum=4 bitwise vs serialized, no fp32 grad bucket, "
          "0 serialization edges)")


def rule_family_four_way():
    """Every registered matrix update rule (rmnp, muon, normuon, muown,
    nora) through the generic bucketed engine on the ZeRO-2 4-way mesh:
    two consecutive ``update_apply_sharded`` steps — momentum AND slot
    stripes sharded, reduce-scattered gradient shards, bias corrections
    stepping — are bitwise the per-leaf reference optimizer
    (core/rules.py ``per_leaf_reference``), and pad slices stay
    identically zero in the momentum and in every slot."""
    from repro.core.rules import make_rule, per_leaf_reference, rule_names

    mesh = make_data_mesh(4)
    params, grads0, grads1 = make(0), make(1), make(2)
    sizes = None
    for name in rule_names():
        rule = make_rule(name, beta=0.9, ns_steps=2)
        opt_sh = make_optimizer(name, lr_matrix=0.1, beta=0.9, ns_steps=2,
                                shard_axis="data", shard_size=4)
        ref = per_leaf_reference(rule, constant(0.1))
        state = opt_sh.init(params)
        sizes = sizes or {b.key: b.size
                          for b in opt_sh.bucket_plan(params).buckets}
        sspec = bucket_specs(state, mesh)
        assert all(s[0] == "data" for s in sspec.buckets.values()), (
            name, sspec.buckets)
        for slot, per_bucket in sspec.slots.items():
            # slot stripes shard their leading L exactly like the momentum
            assert all(s[0] == "data" for s in per_bucket.values()), (
                name, slot, per_bucket)

        def z2(g, s, p, step, opt_sh=opt_sh):
            plan = opt_sh.bucket_plan(p)
            chunks = bucketing.gather_chunks(plan, g, 4, dtype=jnp.float32)
            shards = {b.key: exact_reduce_scatter(chunks[b.key], "data")
                      for b in plan.buckets}
            return opt_sh.update_apply_sharded(shards, g, s, p, step)

        step_z2 = jax.jit(shard_map(
            z2, mesh=mesh, in_specs=(P(), sspec, P(), P()),
            out_specs=(P(), sspec), check_vma=False))
        p1, s1 = step_z2(grads0, state, params, jnp.int32(0))
        p2, s2 = step_z2(grads1, s1, p1, jnp.int32(1))

        r1, sr1 = jax.jit(ref.update_apply)(grads0, ref.init(params),
                                            params, jnp.int32(0))
        r2, _ = jax.jit(ref.update_apply)(grads1, sr1, r1, jnp.int32(1))
        for tag, got, want in (("step0", p1, r1), ("step1", p2, r2)):
            for k in want:
                np.testing.assert_array_equal(
                    np.asarray(got[k]), np.asarray(want[k]),
                    err_msg=f"{name} {tag}: sharded != per-leaf ref: {k}")
        for k, (padded, per_rank) in PADDED.items():
            assert s2.buckets[k].shape[0] == padded, (name, k)
            shard = s2.buckets[k].addressable_shards[0].data
            assert shard.shape[0] == per_rank, (name, k, shard.shape)
            assert np.all(np.asarray(s2.buckets[k])[sizes[k]:] == 0), (name, k)
            for slot, per_bucket in s2.slots.items():
                assert per_bucket[k].shape[0] == padded, (name, slot, k)
                assert np.all(np.asarray(per_bucket[k])[sizes[k]:] == 0), (
                    name, slot, k)
    print("rule family 4-way: OK (all rules bitwise vs per-leaf refs over "
          "2 steps, slots sharded, pad slices zero)")


def rule_family_overlap_report():
    """Every rule's pipelined ZeRO-2 dp step compiles with zero
    cross-bucket serialization edges — the NS family's batched multi-launch
    transform and the slot-carrying rules inherit the per-bucket
    independence unchanged (rmnp is covered by dp_step_pipelined_four_way)."""
    from repro.configs import get_config
    from repro.launch.hlo_cost import collective_overlap_report
    from repro.models import init_params
    from repro.train.dp_step import init_dp_state, make_dp_train_step

    mesh = make_data_mesh(4)
    cfg = get_config("gpt2-60m").reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (16, 16), 0, cfg.vocab)
    batch = {"tokens": toks, "labels": toks}
    comp = init_dp_state(params, 4)
    abstract = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), x.dtype),
        (params, comp, batch))

    for name in ("muon", "normuon", "muown", "nora"):
        opt = mixed_optimizer(name, constant(1e-2), constant(1e-2),
                              shard_axis="data", shard_size=4, ns_steps=1)
        st = jax.eval_shape(opt.init, params)
        plan = opt.bucket_plan(params)
        bks = [(b.key, b.d_in, b.d_out) for b in plan.buckets]
        step = make_dp_train_step(cfg, opt, mesh, zero2=True, opt_state=st,
                                  compress=False, overlap=True)
        hlo = jax.jit(step).lower(abstract[0], st, abstract[1], abstract[2],
                                  jnp.int32(0)).compile().as_text()
        rep = collective_overlap_report(hlo, bks)
        assert rep["collectives"], (name, "no gradient collectives in HLO")
        assert rep["n_serialization_edges"] == 0, (
            name, rep["serialization_edges"])
    print("rule family overlap: OK (0 serialization edges for muon, "
          "normuon, muown, nora)")


def dp_step_shard_size_mismatch():
    """A ZeRO-2 optimizer built with the wrong shard_size is rejected up
    front, naming both numbers, instead of dying in a shape error inside
    the bucket apply."""
    from repro.configs import get_config
    from repro.models import init_params
    from repro.train.dp_step import make_dp_train_step

    mesh = make_data_mesh(4)
    cfg = get_config("gpt2-60m").reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = mixed_optimizer("rmnp", constant(1e-2), constant(1e-2),
                          shard_axis="data", shard_size=2)
    st = jax.eval_shape(opt.init, params)
    try:
        make_dp_train_step(cfg, opt, mesh, zero2=True, opt_state=st)
    except ValueError as e:
        assert "shard_size=2" in str(e) and "4 devices" in str(e), e
    else:
        raise AssertionError("shard_size mismatch was not rejected")
    print("shard_size mismatch: OK (rejected up front, both numbers named)")


def two_phase_clip_bitwise():
    """Satellite regression: on a tree whose every matrix leaf is contained
    in a single rank's chunk (lead == padded/N per leaf), the two-phase
    clip's grad_norm and scale are bit-for-bit clip_by_global_norm's on the
    replicated mean gradient — with the clip ACTIVE, not just scale=1."""
    from repro.core.mixed import clip_by_global_norm
    from repro.train.pipeline import two_phase_clip

    mesh = make_data_mesh(4)
    # bucket 8x16: 4 leaves of lead 2 -> padded 8, csize 2: each leaf is
    # exactly one rank's chunk.  Plus a couple of 1-D "rest" leaves.  Each
    # rank carries a *different* gradient tree (stacked along a leading
    # rank axis, P("data")-sharded) like a real per-rank backward.
    shapes = {**{f"l{i}/w": (2, 8, 16) for i in range(4)},
              "norm/scale_1d": (33,), "head/bias_1d": (7,)}
    trees = [make(10 + r, shapes) for r in range(4)]
    stacked = {k: jnp.stack([t[k] for t in trees]) for k in trees[0]}
    opt = make_optimizer("rmnp", lr_matrix=0.1, beta=0.9,
                            shard_axis="data", shard_size=4)
    plan = opt.bucket_plan({k: v for k, v in make(0, shapes).items()
                            if v.ndim >= 2})

    def clipped(gs):
        g = jax.tree_util.tree_map(lambda x: x[0], gs)  # this rank's tree
        chunks = bucketing.gather_chunks(plan, g, 4, dtype=jnp.float32)
        shards = {b.key: exact_reduce_scatter(chunks[b.key], "data")
                  for b in plan.buckets}
        mean = jax.tree_util.tree_map(
            lambda x: jax.lax.pmean(x.astype(jnp.float32), "data"), g)
        scale, _, stats, _ = two_phase_clip(plan, shards, mean, 1.0,
                                            "data", 4)
        return scale, stats.global_norm, mean

    scale, gnorm, mean = jax.jit(shard_map(
        clipped, mesh=mesh, in_specs=(P("data"),),
        out_specs=(P(), P(), P()), check_vma=False))(stacked)
    # replicated reference: clip_by_global_norm on the same mean gradient,
    # with a clip norm BELOW gnorm so the clip is active
    _, ref_stats = clip_by_global_norm(mean, 1.0)
    ref_gnorm = np.asarray(ref_stats.global_norm)
    assert float(ref_gnorm) > 1.0, "clip must be active for this test"
    np.testing.assert_array_equal(np.asarray(gnorm), ref_gnorm,
                                  err_msg="two-phase gnorm != replicated")
    ref_scale = np.minimum(np.float32(1.0),
                           np.float32(1.0) / (ref_gnorm + np.float32(1e-12)))
    np.testing.assert_array_equal(np.asarray(scale), ref_scale,
                                  err_msg="two-phase scale != replicated")
    print(f"two-phase clip: OK (gnorm {float(gnorm):.6f} bitwise == "
          "replicated, clip active)")


# ---------------------------------------------------------------------------
# elastic restart fault injection (kill an 8-way run, resume 4-way, bitwise)
# ---------------------------------------------------------------------------

def _int_grads(step, shapes=None):
    """Deterministic synthetic gradients valued in {0, +-127} — the
    exactness trick that makes cross-mesh BITWISE comparison meaningful.

    A real backward pass is not bitwise reproducible across mesh sizes
    (the gradient-mean association differs with N; ~1 ulp drift per step).
    These gradients are: every rank contributes the same integer-valued
    addend, so the fp32 psum_scatter sum is exact at any association
    (|sum| <= 8 * 127 << 2**24), the /N mean is exact for power-of-two N,
    and the int8 blockwise quantizer maps {0, +-127} to itself exactly
    (block scale is 0 or 1 -> zero residual).  Both wires therefore
    produce bit-identical mean shards at 4 and 8 devices, and the
    optimizer update itself is mesh-invariant (rule_family_four_way), so
    whole training trajectories match bitwise across mesh sizes."""
    shapes = shapes or SHAPES
    out = {}
    for i, (k, s) in enumerate(sorted(shapes.items())):
        rng = np.random.default_rng(np.random.SeedSequence([step, i]))
        out[k] = jnp.asarray(127.0 * rng.integers(-1, 2, size=s), jnp.float32)
    return out


def elastic_phase(args):
    """One training phase at the current process's device count: build the
    ZeRO-2 step (fp32 or int8-EF wire), resume from the checkpoint dir if
    it holds a committed step — resharding via the layout manifest when the
    writer's mesh size differs — then train, checkpoint, and optionally
    SIGKILL itself mid-run or dump the final state."""
    from repro.checkpoint.manager import CheckpointManager
    from repro.distributed import compression, elastic
    from repro.distributed.compression import (
        compressed_reduce_scatter_leaf, init_compression_state)

    n_dev = len(jax.devices())
    assert n_dev == args.devices, (n_dev, args.devices)
    mesh = make_data_mesh(n_dev)

    def build_opt(n):
        return make_optimizer(args.rule, lr_matrix=0.05, beta=0.9,
                              ns_steps=2, shard_axis="data", shard_size=n)

    opt = build_opt(n_dev)
    params = make(0)
    plan = opt.bucket_plan(params)
    state = opt.init(params)
    comp = init_compression_state(params, n_dev)
    layout = elastic.state_layout(opt, params, mesh_size=n_dev,
                                  rule=args.rule, compress=args.compress,
                                  opt_state=state)
    mgr = CheckpointManager(args.ckpt_dir, keep=3)
    start = 0
    latest = mgr.latest_step()
    if latest is not None:
        old_layout = mgr.read_layout(latest)
        elastic.validate_relayout(old_layout, layout)
        if old_layout["shard_size"] != n_dev:
            (params, state, comp), _ = elastic.restore_resharded(
                mgr, latest, params, comp, opt_new=opt,
                opt_old=build_opt(old_layout["shard_size"]))
            print(f"[elastic] resumed step {latest}: resharded "
                  f"{old_layout['shard_size']}-way -> {n_dev}-way")
        else:
            (params, state, comp), _ = mgr.restore(
                latest, (params, state, comp))
            print(f"[elastic] resumed step {latest} (same mesh)")
        start = latest

    sspec = bucket_specs(state, mesh)

    def step_fn(g, s, c, p, t):
        c = compression.local_view(c)  # (1, *shape) rank block -> local
        if args.compress:
            v = jax.tree_util.tree_map(
                lambda x, e: x.astype(jnp.float32) + e, g, c.error)
            chunks = bucketing.gather_chunks(plan, v, n_dev,
                                             dtype=jnp.float32)
            shards, resid = {}, {}
            for b in plan.buckets:
                shards[b.key], resid[b.key] = compressed_reduce_scatter_leaf(
                    chunks[b.key], "data", n_dev)
            c = c._replace(error=bucketing.scatter_chunks(plan, resid,
                                                          c.error))
        else:
            chunks = bucketing.gather_chunks(plan, g, n_dev,
                                             dtype=jnp.float32)
            shards = {b.key: exact_reduce_scatter(chunks[b.key], "data")
                      for b in plan.buckets}
        p_new, s_new = opt.update_apply_sharded(shards, g, s, p, t)
        return p_new, s_new, compression.from_local(c)

    step = jax.jit(shard_map(step_fn, mesh=mesh,
                             in_specs=(P(), sspec, P("data"), P(), P()),
                             out_specs=(P(), sspec, P("data")),
                             check_vma=False))

    for t in range(start, args.steps):
        g = _int_grads(t)
        params, state, comp = step(g, state, comp, params, jnp.int32(t))
        if args.ckpt_every and (t + 1) % args.ckpt_every == 0:
            mgr.save(t + 1, (params, state, comp), data_step=t + 1,
                     layout=layout)
        if args.kill_at and t + 1 == args.kill_at:
            # genuine ungraceful death: the async save just launched for
            # this step may be torn — atomic commit keeps it invisible and
            # resume falls back to the previous committed step, which
            # replays to the same bitwise trajectory
            print(f"[elastic] SIGKILL at step {t + 1}", flush=True)
            os.kill(os.getpid(), signal.SIGKILL)
    mgr.wait()

    if args.dump:
        flat = {}
        for k, v in tree_paths(params):
            flat[f"p/{k}"] = np.asarray(v)
        for k, v in state.buckets.items():
            flat[f"m/{k}"] = np.asarray(v)
        for name, per in state.slots.items():
            for k, v in per.items():
                flat[f"s/{name}/{k}"] = np.asarray(v)
        for k, v in tree_paths(comp.error):
            flat[f"e/{k}"] = np.asarray(v)
        np.savez(args.dump, **flat)
    print(f"[elastic] phase done at step {args.steps} ({n_dev}-way)")


def _phase_args(argv):
    ap = argparse.ArgumentParser()
    ap.add_argument("--rule", default="rmnp")
    ap.add_argument("--devices", type=int, required=True)
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--ckpt-dir", required=True)
    ap.add_argument("--ckpt-every", type=int, default=4)
    ap.add_argument("--kill-at", type=int, default=0)
    ap.add_argument("--compress", action="store_true")
    ap.add_argument("--dump", default="")
    return ap.parse_args(argv)


def _run_phase(phase_argv, n_dev, timeout=600):
    """Spawn an ``elastic-phase`` subprocess with its own device count
    (XLA_FLAGS must be set before jax initializes — hence subprocesses)."""
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={n_dev}",
               JAX_PLATFORMS="cpu",
               # the launcher's persistent compile cache stays off in tests
               JAX_ENABLE_COMPILATION_CACHE="false",
               PYTHONPATH=os.pathsep.join(
                   [str(Path(__file__).resolve().parents[1] / "src"),
                    os.environ.get("PYTHONPATH", "")]).rstrip(os.pathsep))
    cmd = [sys.executable, __file__, "elastic-phase",
           "--devices", str(n_dev)] + phase_argv
    return subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=timeout)


def elastic_scenario(quick=False):
    """Kill-and-resume fault injection across mesh sizes.

    For each (rule, wire) x (8->4, 4->8): phase A trains at the source
    mesh size and SIGKILLs itself mid-run (after at least one committed
    checkpoint), phase B resumes at the *target* mesh size — the layout
    manifest flags the mismatch and the state reshards — and a reference
    phase trains uninterrupted at the target size.  B and the reference
    must agree BITWISE on params, momentum buckets, slot stripes and the
    EF residual.  Plus a negative case: resuming with a different rule
    fails loudly naming both layouts.  ``quick`` runs a single combo (the
    pytest tier-2 hook); CI runs the full matrix."""
    combos = [("rmnp", False), ("rmnp", True),
              ("normuon", False), ("normuon", True)]
    pairs = [(8, 4), (4, 8)]
    if quick:
        combos, pairs = [("rmnp", True)], [(8, 4)]
    steps, every, kill = 12, 4, 10
    for rule, compress in combos:
        for n_from, n_to in pairs:
            wire = "int8" if compress else "fp32"
            tag = f"{rule}/{wire} {n_from}->{n_to}"
            work = tempfile.mkdtemp(prefix="rmnp_elastic_")
            try:
                ckpt, ref_ckpt = f"{work}/ckpt", f"{work}/ref_ckpt"
                dump_b, dump_r = f"{work}/resumed.npz", f"{work}/ref.npz"
                common = ["--rule", rule, "--steps", str(steps),
                          "--ckpt-every", str(every)]
                common += ["--compress"] if compress else []
                ra = _run_phase(common + ["--ckpt-dir", ckpt,
                                          "--kill-at", str(kill)], n_from)
                assert ra.returncode == -signal.SIGKILL, (
                    tag, ra.returncode, ra.stdout, ra.stderr)
                rb = _run_phase(common + ["--ckpt-dir", ckpt,
                                          "--dump", dump_b], n_to)
                assert rb.returncode == 0, (tag, rb.stdout, rb.stderr)
                assert (f"resharded {n_from}-way -> {n_to}-way"
                        in rb.stdout), (tag, rb.stdout)
                rr = _run_phase(common + ["--ckpt-dir", ref_ckpt,
                                          "--dump", dump_r], n_to)
                assert rr.returncode == 0, (tag, rr.stdout, rr.stderr)
                with np.load(dump_b) as a, np.load(dump_r) as b:
                    assert set(a.files) == set(b.files), tag
                    for k in sorted(a.files):
                        np.testing.assert_array_equal(
                            a[k], b[k],
                            err_msg=f"{tag}: {k} resumed != uninterrupted")
                print(f"elastic {tag}: OK (SIGKILLed run resumed bitwise "
                      f"== uninterrupted, params+momentum+slots+EF)")
            finally:
                shutil.rmtree(work, ignore_errors=True)

    # negative: a checkpoint written by one rule must not resume under
    # another — loud LayoutMismatchError naming both layouts
    work = tempfile.mkdtemp(prefix="rmnp_elastic_neg_")
    try:
        ok = _run_phase(["--rule", "rmnp", "--steps", "4",
                         "--ckpt-every", "4", "--ckpt-dir", f"{work}/c"], 4)
        assert ok.returncode == 0, (ok.stdout, ok.stderr)
        bad = _run_phase(["--rule", "normuon", "--steps", "8",
                          "--ckpt-every", "4", "--ckpt-dir", f"{work}/c"], 4)
        assert bad.returncode != 0, bad.stdout
        assert "LayoutMismatch" in bad.stderr, bad.stderr
        assert "rmnp" in bad.stderr and "normuon" in bad.stderr, bad.stderr
        print("elastic negative: OK (rule mismatch fails loudly, both "
              "layouts named)")
    finally:
        shutil.rmtree(work, ignore_errors=True)
    print("ELASTIC_OK")


# ---------------------------------------------------------------------------
# crash-consistent sharded checkpointing (commit protocol, integrity layer)
# ---------------------------------------------------------------------------

def _ckpt_grads(step, n_dev=4, shapes=None):
    """Dense per-device float gradients (leading device axis) —
    deliberately NOT the replicated {0, +-127} exactness grads: each rank
    contributes a different gradient, so the int8 error-feedback residual
    comes out nonzero AND per-rank distinct, which is exactly what the
    sharded-save proof must show surviving a checkpoint (identical or
    zero residuals would pass vacuously)."""
    shapes = shapes or SHAPES
    out = {}
    for i, (k, s) in enumerate(sorted(shapes.items())):
        rng = np.random.default_rng(np.random.SeedSequence([step, 91, i]))
        out[k] = jnp.asarray(rng.standard_normal((n_dev,) + s), jnp.float32)
    return out


def _ckpt_build(rule, n_dev=4):
    """A live int8-EF ZeRO-2 train state on the ``n_dev`` mesh: params
    replicated, momentum buckets + slot stripes sharded on the bucket
    axis, EF residual sharded on its leading device axis.  Returns the
    pristine ``(params, state, comp)`` tuple (also the restore template)
    and an ``advance(state_tuple, t)`` closure running one real step."""
    from repro.distributed import compression
    from repro.distributed.compression import (
        compressed_reduce_scatter_leaf, init_compression_state)

    assert len(jax.devices()) >= n_dev, jax.devices()
    mesh = make_data_mesh(n_dev)
    opt = make_optimizer(rule, lr_matrix=0.05, beta=0.9, ns_steps=2,
                         shard_axis="data", shard_size=n_dev)
    params = make(0)
    plan = opt.bucket_plan(params)
    state = opt.init(params)
    comp = init_compression_state(params, n_dev)
    sspec = bucket_specs(state, mesh)

    def step_fn(g, s, c, p, t):
        g = jax.tree_util.tree_map(lambda x: x[0], g)  # this rank's grad
        c = compression.local_view(c)
        v = jax.tree_util.tree_map(
            lambda x, e: x.astype(jnp.float32) + e, g, c.error)
        chunks = bucketing.gather_chunks(plan, v, n_dev, dtype=jnp.float32)
        shards, resid = {}, {}
        for b in plan.buckets:
            shards[b.key], resid[b.key] = compressed_reduce_scatter_leaf(
                chunks[b.key], "data", n_dev)
        c = c._replace(error=bucketing.scatter_chunks(plan, resid, c.error))
        p_new, s_new = opt.update_apply_sharded(shards, g, s, p, t)
        return p_new, s_new, compression.from_local(c)

    step = jax.jit(shard_map(step_fn, mesh=mesh,
                             in_specs=(P("data"), sspec, P("data"), P(), P()),
                             out_specs=(P(), sspec, P("data")),
                             check_vma=False))

    def advance(st3, t):
        p, s, c = st3
        p, s, c = step(_ckpt_grads(t, n_dev), s, c, p, jnp.int32(t))
        return (p, s, c)

    return (params, state, comp), advance


def _assert_state_equal(a, b, tag):
    fa, fb = tree_paths(a), tree_paths(b)
    assert [k for k, _ in fa] == [k for k, _ in fb], tag
    for (k, va), (_, vb) in zip(fa, fb, strict=True):
        np.testing.assert_array_equal(np.asarray(va), np.asarray(vb),
                                      err_msg=f"{tag}: {k}")


def ckpt_sharded_save_roundtrip():
    """The sharded save layout on the 4-device mesh (int8 EF wire): four
    shard files, four SHARD_COMMITTED markers, a format-2 manifest with a
    CRC32 per leaf piece, the global COMMITTED — and a bitwise restore of
    params, momentum buckets, slot stripes and EVERY rank's EF residual
    (not just rank 0's replica).  Also the watchdog path on real sharded
    state: ``snapshot()`` + ``emergency_save()`` persists the buffered
    step without touching the device, and a second emergency save finds
    nothing newer to write."""
    import json

    from repro.checkpoint.manager import CheckpointManager

    n_dev = 4
    like, advance = _ckpt_build("rmnp")
    st = like
    for t in range(3):
        st = advance(st, t)
    work = tempfile.mkdtemp(prefix="rmnp_ckpt_layout_")
    try:
        mgr = CheckpointManager(f"{work}/ckpt", keep=3)
        mgr.save(3, st, data_step=3, block=True)
        d = Path(work) / "ckpt" / "step_000000003"
        assert sorted(q.name for q in d.glob("shard_*.npz")) == \
            [f"shard_{r:05d}.npz" for r in range(n_dev)], list(d.iterdir())
        assert sorted(q.name for q in d.glob("*.SHARD_COMMITTED")) == \
            [f"shard_{r:05d}.SHARD_COMMITTED" for r in range(n_dev)]
        assert (d / "COMMITTED").exists()
        man = json.loads((d / "manifest.json").read_text())
        assert man["format"] == 2 and man["n_shards"] == n_dev, man
        assert man["data_step"] == 3, man
        for lf in man["leaves"]:
            for sh in lf["shards"]:
                assert isinstance(sh["crc32"], int) and "index" in sh, lf
        # momentum buckets and the EF residual really split 4 ways
        mom = [lf for lf in man["leaves"] if lf["path"].startswith("1/")]
        ef = [lf for lf in man["leaves"] if lf["path"].startswith("2/")]
        assert mom and any(len(lf["shards"]) == n_dev for lf in mom), mom
        assert ef and all(len(lf["shards"]) == n_dev for lf in ef), ef
        for lf in ef:
            assert all(sh["shape"][0] == 1 for sh in lf["shards"]), lf
        # the residual is nonzero and per-rank distinct — the proof is not
        # vacuous, and the restore below really recovers all four ranks
        e0 = np.asarray(jax.tree_util.tree_leaves(st[2].error)[0])
        assert e0.shape[0] == n_dev and np.any(e0), "vacuous EF residual"
        assert any(not np.array_equal(e0[i], e0[0])
                   for i in range(1, n_dev)), "ranks share one residual"
        state_r, data_step = mgr.restore(3, like)
        assert data_step == 3
        _assert_state_equal(state_r, st, "sharded roundtrip")
        print("ckpt layout: OK (4 shards + markers + CRC manifest, "
              "restore bitwise incl. every rank's EF residual)")

        # watchdog path: buffer-only snapshot, then an emergency save that
        # never touches the device
        st4 = advance(st, 3)
        mgr.snapshot(4, st4, data_step=4)
        assert mgr.emergency_save() == 4
        state_r, step_r, data_step = CheckpointManager(
            f"{work}/ckpt", keep=3).restore_latest(like)
        assert (step_r, data_step) == (4, 4)
        _assert_state_equal(state_r, st4, "emergency save")
        assert mgr.emergency_save() is None  # nothing newer than step 4
        print("ckpt emergency: OK (snapshot buffer persisted bitwise, "
              "repeat save correctly a no-op)")
    finally:
        shutil.rmtree(work, ignore_errors=True)


def ckpt_corruption_sweep():
    """Every registered corruption kind injected into the NEWEST committed
    checkpoint of a 4-device sharded run: restore must detect the damage
    BY NAME (leaf path / shard rank / manifest, per kind) and fall back to
    the previous good checkpoint bitwise — never silently restore
    garbage, never die without a fallback."""
    import warnings as _warnings

    from repro.checkpoint import faults
    from repro.checkpoint.manager import CheckpointManager

    like, advance = _ckpt_build("rmnp")
    st1 = advance(like, 0)
    st2 = advance(st1, 1)
    rank = 2  # a non-zero rank proves the rank naming is not a default
    expect = {
        "bit_rot": (f"shard rank {rank}",),
        "truncated": (f"shard rank {rank}", "truncated/unreadable"),
        "missing_shard": (f"shard_{rank:05d}.npz", f"rank {rank}"),
        "torn_manifest": ("manifest.json",),
    }
    for kind, injector in faults.CORRUPTIONS.items():
        work = tempfile.mkdtemp(prefix=f"rmnp_ckpt_{kind}_")
        try:
            mgr = CheckpointManager(f"{work}/c", keep=3)
            mgr.save(1, st1, data_step=1, block=True)
            mgr.save(2, st2, data_step=2, block=True)
            injector(Path(work) / "c" / "step_000000002", rank=rank)
            # a fresh manager: restart-after-fault semantics, cold caches
            m2 = CheckpointManager(f"{work}/c", keep=3)
            with _warnings.catch_warnings(record=True) as caught:
                _warnings.simplefilter("always")
                res = m2.restore_latest(like)
            assert res is not None, f"{kind}: no fallback checkpoint found"
            state_r, step_r, data_step = res
            assert (step_r, data_step) == (1, 1), (kind, step_r, data_step)
            msgs = [str(w.message) for w in caught]
            for frag in expect[kind]:
                assert any(frag in m for m in msgs), (kind, frag, msgs)
            if kind != "torn_manifest":
                assert any("falling back to the previous committed step"
                           in m for m in msgs), (kind, msgs)
            _assert_state_equal(state_r, st1, f"{kind} fallback")
            named = next(m for m in msgs
                         if any(f in m for f in expect[kind]))
            print(f"ckpt corruption {kind}: detected by name "
                  f"[{named.splitlines()[0][:120]}] -> fell back to "
                  f"step 1 bitwise")
        finally:
            shutil.rmtree(work, ignore_errors=True)


def ckpt_checksum_property(quick=False):
    """Per-rule checksum property: for EVERY registered matrix update rule
    (each with its own slot stripes) plus the EF residual, a single
    flipped byte in ANY rank's shard file must surface as
    :class:`CheckpointCorruptionError` naming a real leaf path and the
    damaged shard rank — never restore."""
    import json

    from repro.checkpoint import faults
    from repro.checkpoint.manager import (CheckpointCorruptionError,
                                          CheckpointManager)
    from repro.core.rules import rule_names

    n_dev = 4
    rules = ("rmnp",) if quick else rule_names()
    ranks = (1,) if quick else range(n_dev)
    for rule in rules:
        like, advance = _ckpt_build(rule)
        st = advance(advance(like, 0), 1)
        work = tempfile.mkdtemp(prefix=f"rmnp_ckpt_crc_{rule}_")
        try:
            CheckpointManager(f"{work}/c", keep=3).save(
                2, st, data_step=2, block=True)
            src = Path(work) / "c" / "step_000000002"
            man = json.loads((src / "manifest.json").read_text())
            paths = {lf["path"] for lf in man["leaves"]}
            for r in ranks:
                m2 = CheckpointManager(f"{work}/flip_{r}", keep=3)
                shutil.copytree(src, Path(work) / f"flip_{r}" / src.name)
                faults.flip_byte(
                    Path(work) / f"flip_{r}" / src.name
                    / f"shard_{r:05d}.npz",
                    (src / f"shard_{r:05d}.npz").stat().st_size // 2)
                try:
                    m2.restore(2, like)
                    raise AssertionError(
                        f"{rule}: flipped byte in shard rank {r} restored "
                        f"without a checksum error")
                except CheckpointCorruptionError as e:
                    msg = str(e)
                    assert f"shard rank {r}" in msg, (rule, r, msg)
                    assert "leaf '" in msg, (rule, r, msg)
                    named = msg.split("leaf '", 1)[1].split("'", 1)[0]
                    assert named in paths, (rule, r, named, sorted(paths))
            print(f"ckpt checksum {rule}: OK (flipped byte named leaf + "
                  f"rank on {'rank 1' if quick else 'all 4 ranks'})")
        finally:
            shutil.rmtree(work, ignore_errors=True)


def ckpt_scenario(quick=False):
    """Checkpoint corruption fault-injection matrix on the 4-device mesh.
    ``quick`` (the pytest tier-2 hook) runs the layout roundtrip and the
    single-rule checksum property; full mode (CI) adds the four-kind
    corruption sweep and every registered rule x every shard rank."""
    ckpt_sharded_save_roundtrip()
    ckpt_checksum_property(quick=quick)
    if not quick:
        ckpt_corruption_sweep()
    print("CKPT_OK")


# ---------------------------------------------------------------------------
# numerical-resilience fault injection (guard the real step, skip bitwise)
# ---------------------------------------------------------------------------

def _guard_batch(cfg, t):
    """Deterministic batch keyed by the step number, so a run that skips a
    step consumes exactly the batches of a run that never saw it."""
    toks = jax.random.randint(jax.random.fold_in(jax.random.PRNGKey(7), t),
                              (16, 16), 0, cfg.vocab)
    return {"tokens": toks, "labels": toks}


def _guard_snap(params, state, comp):
    """Every leaf the guard must keep bitwise on a skipped step: params,
    momentum buckets, slot stripes, AdamW moments (the whole optimizer
    state tree) and the int8 error-feedback residual."""
    flat = {f"p/{k}": np.asarray(v) for k, v in tree_paths(params)}
    flat.update({f"o/{k}": np.asarray(v) for k, v in tree_paths(state)})
    flat.update({f"e/{k}": np.asarray(v) for k, v in tree_paths(comp.error)})
    return flat


def _guard_run(rule, compress, *, guard, fault, steps, accum=1,
               host_skip=()):
    """Run ``steps`` real guarded/unguarded pipelined ZeRO-2 steps on the
    reduced gpt2-60m over the 4-way mesh, snapshotting the full state after
    every step.  ``host_skip`` steps are not executed at all — the clean
    reference trajectory for a bitwise-skip proof."""
    from repro.configs import get_config
    from repro.models import init_params
    from repro.train.dp_step import init_dp_state, make_dp_train_step
    from repro.train import pipeline

    mesh = make_data_mesh(4)
    cfg = get_config("gpt2-60m").reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = mixed_optimizer(rule, constant(1e-2), constant(1e-2),
                          shard_axis="data", shard_size=4, ns_steps=1)
    names = pipeline.guard_flag_names(opt.bucket_plan(params), params, 4)
    state = opt.init(params)
    comp = init_dp_state(params, 4)
    step_fn = jax.jit(make_dp_train_step(
        cfg, opt, mesh, zero2=True, opt_state=state, compress=compress,
        accum=accum, overlap=True, guard=guard, fault=fault))
    snaps, mets = [], []
    for t in range(steps):
        if t in host_skip:
            snaps.append(_guard_snap(params, state, comp))
            mets.append(None)
            continue
        params, state, comp, m = step_fn(params, state, comp,
                                         _guard_batch(cfg, t), jnp.int32(t))
        snaps.append(_guard_snap(params, state, comp))
        mets.append({k: np.asarray(v) for k, v in m.items()})
    return snaps, mets, names


def _assert_snaps_equal(a, b, tag):
    for t, (sa, sb) in enumerate(zip(a, b, strict=True)):
        assert set(sa) == set(sb), (tag, t)
        for k in sorted(sa):
            np.testing.assert_array_equal(
                sa[k], sb[k], err_msg=f"{tag} step {t}: {k} guarded-faulty "
                "!= clean-with-host-skip")


def guard_transparency(rule, compress):
    """Guard ON with no fault is bitwise the unguarded step — the selects
    and flag folds cost nothing numerically."""
    wire = "int8" if compress else "fp32"
    g, gm, _ = _guard_run(rule, compress, guard=True, fault=None, steps=3)
    u, _, _ = _guard_run(rule, compress, guard=False, fault=None, steps=3)
    _assert_snaps_equal(g, u, f"transparency {rule}/{wire}")
    assert all(float(m["skipped"]) == 0.0 for m in gm), [
        float(m["skipped"]) for m in gm]
    print(f"guard transparency {rule}/{wire}: OK (guarded clean == "
          "unguarded bitwise, 0 skips)")


def guard_skip_case(rule, compress, *, kind="nan", accum=1,
                    microbatch=None, steps=5, bad_step=2):
    """A {kind} gradient fault at step ``bad_step`` is detected in-graph
    and the WHOLE step is skipped bitwise: the guarded faulty run equals a
    clean unguarded run with the same step skipped host-side, on every
    surviving step, on params + momentum + slots + moments + EF residual."""
    from repro.train import faults

    wire = "int8" if compress else "fp32"
    tag = (f"{rule}/{wire}/accum{accum}/{kind}"
           + (f"@mb{microbatch}" if microbatch is not None else ""))
    spec = f"{kind}:*:{bad_step}" + ("" if microbatch is None
                                     else f":{microbatch}")
    fault = faults.parse_fault(spec)
    faulty, fmets, names = _guard_run(rule, compress, guard=True,
                                      fault=fault, steps=steps, accum=accum)
    clean, _, _ = _guard_run(rule, compress, guard=False, fault=None,
                             steps=steps, accum=accum, host_skip={bad_step})
    _assert_snaps_equal(faulty, clean, f"skip {tag}")
    for t, m in enumerate(fmets):
        want = 1.0 if t == bad_step else 0.0
        assert float(m["skipped"]) == want, (tag, t, m["skipped"])
    # flag attribution: leaf "*" is the first tree leaf; on the exact fp32
    # wire only its flag may drop, on int8 the poisoned quantization block
    # may cascade to neighbouring leaves of the same bucket
    flags = fmets[bad_step]["guard_flags"]
    assert flags.shape == (len(names),), (flags.shape, len(names))
    assert flags[0] == 0.0, (tag, "target leaf", names[0], "not flagged")
    if not compress:
        others = [names[i] for i in range(len(names)) if flags[i] == 0.0]
        assert others == [names[0]], (tag, "fp32 cascade", others)
    healthy = fmets[bad_step - 1]["guard_flags"]
    assert healthy.min() == 1.0, (tag, "healthy step flags", healthy)
    print(f"guard skip {tag}: OK (step {bad_step} skipped bitwise, "
          f"flag -> {names[0]})")


def guard_bitflip_case(steps=5, bad_step=2):
    """A bit-flip on an int8 wire block scale (rank 0's outgoing chunk,
    after the sender's EF residual is computed) blows the dequantized shard
    up past fp32 range; the guard's squared-sum flags catch it and the step
    skips bitwise — including the EF residual rollback."""
    from repro.configs import get_config
    from repro.models import init_params
    from repro.train import faults

    cfg = get_config("gpt2-60m").reduced()
    params = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.random.PRNGKey(0))
    opt = mixed_optimizer("rmnp", constant(1e-2), constant(1e-2),
                          shard_axis="data", shard_size=4, ns_steps=1)
    plan = opt.bucket_plan(params)
    # pick a dense bucket (most stacked slices = the transformer blocks'
    # weight matrices) — the embed bucket's first rows can carry all-zero
    # gradients, whose block scale of 0 bit-flips to a benign 2.0
    bucket = max(plan.buckets, key=lambda b: b.size)
    fault = faults.parse_fault(f"bitflip:{bucket.key}:{bad_step}")
    faulty, fmets, _ = _guard_run("rmnp", True, guard=True, fault=fault,
                                  steps=steps)
    clean, _, _ = _guard_run("rmnp", True, guard=False, fault=None,
                             steps=steps, host_skip={bad_step})
    _assert_snaps_equal(faulty, clean, f"bitflip {bucket.key}")
    for t, m in enumerate(fmets):
        want = 1.0 if t == bad_step else 0.0
        assert float(m["skipped"]) == want, (t, m["skipped"])
    assert fmets[bad_step]["guard_flags"].min() == 0.0, (
        "no flag fired for the corrupted wire block")
    print(f"guard bitflip {bucket.key}: OK (wire-scale flip at step "
          f"{bad_step} skipped bitwise, EF residual rolled back)")


def guard_overlap_report():
    """The guarded pipelined step keeps zero cross-bucket serialization
    edges in the compiled HLO — the post-update selects must not chain the
    per-bucket collective/update pipelines (both wires)."""
    from repro.configs import get_config
    from repro.launch.hlo_cost import collective_overlap_report
    from repro.models import init_params
    from repro.train.dp_step import init_dp_state, make_dp_train_step

    mesh = make_data_mesh(4)
    cfg = get_config("gpt2-60m").reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (16, 16), 0, cfg.vocab)
    comp = init_dp_state(params, 4)
    abstract = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), x.dtype),
        (params, comp, {"tokens": toks, "labels": toks}))
    opt = mixed_optimizer("rmnp", constant(1e-2), constant(1e-2),
                          shard_axis="data", shard_size=4)
    st = jax.eval_shape(opt.init, params)
    plan = opt.bucket_plan(params)
    bks = [(b.key, b.d_in, b.d_out) for b in plan.buckets]
    for compress in (False, True):
        step = make_dp_train_step(cfg, opt, mesh, zero2=True, opt_state=st,
                                  compress=compress, overlap=True,
                                  guard=True)
        hlo = jax.jit(step).lower(abstract[0], st, abstract[1], abstract[2],
                                  jnp.int32(0)).compile().as_text()
        rep = collective_overlap_report(hlo, bks)
        assert rep["collectives"], "no gradient collectives in guarded HLO"
        assert rep["n_serialization_edges"] == 0, (
            compress, rep["serialization_edges"])
    print("guard overlap: OK (guarded pipelined step keeps 0 "
          "serialization edges, both wires)")


def _run_launch(extra, n_dev=4, timeout=900):
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={n_dev}",
               JAX_PLATFORMS="cpu",
               # the launcher's persistent compile cache stays off in tests
               JAX_ENABLE_COMPILATION_CACHE="false",
               PYTHONPATH=os.pathsep.join(
                   [str(Path(__file__).resolve().parents[1] / "src"),
                    os.environ.get("PYTHONPATH", "")]).rstrip(os.pathsep))
    cmd = [sys.executable, "-m", "repro.launch.train",
           "--arch", "llama-60m", "--optimizer", "rmnp", "--zero2",
           "--guard", "--steps", "12", "--batch", "8", "--seq", "32",
           "--log-every", "1", "--ckpt-every", "2"] + extra
    return subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=timeout)


def guard_rewind_ladder():
    """The full launch-driver escalation ladder on llama-60m, on BOTH
    wires: a sticky NaN fault exhausts the skip budget, the driver rewinds
    to the last-known-good checkpoint, replays the data stream
    deterministically with the fault disarmed, and finishes BITWISE equal
    to an uninterrupted clean run — loss curve included.  The int8
    error-feedback residual carries an explicit leading device axis
    through the sharded checkpoint (every rank's residual is saved and
    restored, not just rank 0's replica), so the int8-wire rewind replays
    bitwise too — the old ~1e-5 known limitation is gone.  A run whose
    rewind budget is 0 must abort loudly instead of looping."""
    import json

    for wire_args, wire in ((["--no-compress"], "fp32"), ([], "int8")):
        work = tempfile.mkdtemp(prefix=f"rmnp_guard_ladder_{wire}_")
        try:
            pa, pb = f"{work}/a.npz", f"{work}/b.npz"
            la, lb = f"{work}/a.json", f"{work}/b.json"
            ra = _run_launch(wire_args +
                             ["--ckpt-dir", f"{work}/A", "--log-file", la,
                              "--dump-params", pa])
            assert ra.returncode == 0, (wire, ra.stdout, ra.stderr)
            rb = _run_launch(wire_args +
                             ["--ckpt-dir", f"{work}/B", "--log-file", lb,
                              "--dump-params", pb,
                              "--inject-fault", "nan:*:6+",
                              "--anomaly-skip-budget", "2",
                              "--anomaly-rewind-budget", "2",
                              "--anomaly-lr-backoff", "1.0",
                              "--anomaly-health-window", "2"])
            assert rb.returncode == 0, (wire, rb.stdout, rb.stderr)
            assert "rewind #1" in rb.stdout, (wire, rb.stdout)
            assert "disarming the injected fault" in rb.stdout, (wire,
                                                                 rb.stdout)
            assert "SKIPPED bitwise" in rb.stdout, (wire, rb.stdout)
            with np.load(pa) as a, np.load(pb) as b:
                assert set(a.files) == set(b.files), wire
                for k in sorted(a.files):
                    np.testing.assert_array_equal(
                        a[k], b[k],
                        err_msg=f"{wire}: rewound params {k} != "
                                f"uninterrupted")
            # the replayed tail of B's loss curve (last entry per step
            # wins) must equal A's uninterrupted curve exactly from the
            # rewind point
            curve_a = {m["step"]: m["loss"] for m in json.loads(
                Path(la).read_text())}
            curve_b = {}
            for m in json.loads(Path(lb).read_text()):
                curve_b[m["step"]] = m["loss"]
            for s in range(4, 12):
                assert curve_b[s] == curve_a[s], (
                    wire, s, curve_b[s], curve_a[s],
                    "replayed loss != uninterrupted")
            print(f"guard rewind {wire}: OK (ladder rewound to "
                  f"last-known-good, replayed bitwise to the "
                  f"uninterrupted params + loss curve)")
        finally:
            shutil.rmtree(work, ignore_errors=True)

    work = tempfile.mkdtemp(prefix="rmnp_guard_ladder_abort_")
    try:
        rc = _run_launch(["--no-compress", "--ckpt-dir", f"{work}/C",
                          "--inject-fault", "nan:*:3+",
                          "--anomaly-skip-budget", "1",
                          "--anomaly-rewind-budget", "0"])
        assert rc.returncode != 0, (rc.stdout, rc.stderr)
        assert "escalation ladder exhausted" in rc.stderr, rc.stderr
        print("guard abort: OK (exhausted ladder raises, naming the "
              "post-mortem)")
    finally:
        shutil.rmtree(work, ignore_errors=True)


def guard_scenario(quick=False):
    """The fault-injection proof matrix.  ``quick`` (the pytest tier-2
    hook) runs transparency plus the NaN skip proof on both wires; the
    full mode (CI) adds inf, microbatch-targeted accum faults, the wire
    bit-flip, the guarded overlap report and the launch rewind ladder."""
    guard_transparency("rmnp", False)
    guard_skip_case("rmnp", False)
    guard_skip_case("rmnp", True)
    if not quick:
        guard_transparency("rmnp", True)
        guard_skip_case("normuon", False)
        guard_skip_case("normuon", True)
        guard_skip_case("rmnp", False, kind="inf")
        guard_skip_case("rmnp", False, accum=4, microbatch=2)
        guard_bitflip_case()
        guard_overlap_report()
        guard_rewind_ladder()
    print("GUARD_OK")


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "elastic-phase":
        elastic_phase(_phase_args(sys.argv[2:]))
    elif len(sys.argv) > 1 and sys.argv[1] == "elastic":
        elastic_scenario(quick="--quick" in sys.argv[2:])
    elif len(sys.argv) > 1 and sys.argv[1] == "guard":
        guard_scenario(quick="--quick" in sys.argv[2:])
    elif len(sys.argv) > 1 and sys.argv[1] == "ckpt":
        ckpt_scenario(quick="--quick" in sys.argv[2:])
    else:
        synthetic_four_way()
        synthetic_traced_buffers()
        dp_step_two_way()
        dp_step_two_way_zero2()
        dp_step_pipelined_four_way()
        rule_family_four_way()
        rule_family_overlap_report()
        dp_step_shard_size_mismatch()
        two_phase_clip_bitwise()
        print("ZERO_SHARD_OK")
