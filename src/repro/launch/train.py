"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch gpt2-small --reduced \
        --optimizer rmnp --steps 200 --batch 8 --seq 128

Wires together: config -> mesh (whatever devices exist) -> synthetic data ->
mixed optimizer -> pjit train step -> checkpoint manager (resume on restart)
-> metrics log (loss, grad-norm, clip rate, preconditioner diagonal-dominance
ratios).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import signal
import time
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.donation import state_copies
from repro.checkpoint.manager import CheckpointManager
from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.core import (cosine_with_warmup, global_dominance, make_optimizer,
                        optimizer_names)
from repro.core.types import tree_paths
from repro.data.pipeline import make_stream
from repro.distributed import elastic
from repro.distributed.sharding import axis_rules
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.launch.spans import Spans
from repro.models import init_params
from repro.models.layers import recording_attention_routes
from repro.train import faults
from repro.train.step import (kernel_routes, make_train_step,
                              optimizer_kernel_launches)


@dataclasses.dataclass
class StepReport:
    """What :func:`train` learns about its compiled step, for a caller that
    passes one in: the compile time (the ``setup/compile`` span), the
    compiled HLO text (kernel custom calls can be counted in it, and each
    instruction's ``op_name`` names its device scope), the compiler's
    memory analysis (bytes per device of the step program), per shape
    bucket the RMNP kernel launch it traces to (``None``: the bucket takes
    the XLA path) and per attention call site ``"attention (B,S,H,hd)"``
    its route (``"flash"``, or ``"dense: <why>"`` / ``"chunked: <why>"``,
    ``models/layers.py:attention_route``), the host spans ``(name,
    parent, step, t0_ns, t1_ns)`` and, per loop iteration, ``(step,
    compiles)`` (``launch/spans.py``)."""
    compile_s: float = 0.0
    hlo_text: str = ""
    memory: Any = None
    routes: Dict[str, Any] = dataclasses.field(default_factory=dict)
    state_copies: int = 0
    spans: List[Tuple[str, Optional[str], int, int, int]] = \
        dataclasses.field(default_factory=list)
    compiles: List[Tuple[int, int]] = dataclasses.field(default_factory=list)


def train(arch: Union[str, ModelConfig], optimizer: str = "rmnp",
          steps: int = 100,
          batch: int = 8, seq: int = 128, lr_matrix: float = 2e-3,
          lr_adamw: float = 1e-3, reduced: bool = True, seed: int = 0,
          ckpt_dir: str = "", ckpt_every: int = 0, log_every: int = 10,
          dominance_every: int = 0, matrix_embed: bool = True,
          use_kernel: bool = False, fused: bool = False,
          momentum_dtype: str = "float32", fused_apply: bool = False,
          zero2: bool = False, compress: bool = True, accum: int = 1,
          overlap: Optional[bool] = None, log_file: str = "",
          stop_at: int = 0, kill_at: int = 0,
          watchdog_deadline: float = 0.0, dump_params: str = "",
          clip_norm: float = 1.0, guard: bool = False,
          inject_fault: str = "", anomaly_spike_k: float = 6.0,
          anomaly_skip_budget: int = 3, anomaly_rewind_budget: int = 2,
          anomaly_lr_backoff: float = 0.5, anomaly_health_window: int = 2,
          anomaly_skip_batch: bool = False,
          devices: Optional[Sequence] = None,
          report: Optional[StepReport] = None):
    """``stop_at`` simulates a crash: train to that step (schedules still
    span ``steps``) and exit WITHOUT the final checkpoint.  ``kill_at`` is
    harsher fault injection: SIGKILL the process mid-loop at that step —
    no cleanup, no final save, an in-flight async checkpoint may be torn
    (the atomic-commit protocol makes a torn save invisible, not corrupt).

    ``watchdog_deadline`` (seconds) arms the hang/straggler ladder
    (``distributed/monitor.py``): a step exceeding the hard deadline or
    flagged as a straggler triggers an emergency blocking checkpoint of
    the last completed step, taken from a host snapshot (donated device
    buffers of an in-flight step are unreadable by design).

    Restart is mesh-size-agnostic for ``zero2`` runs: the checkpoint's
    layout manifest records the writer's shard size, and a mismatch with
    this run's device count reshards the bucketed state automatically
    (``distributed/elastic.py``) instead of failing on the padded shapes.

    ``fused`` (or ``fused_apply``, its older name) routes matrix parameters
    through the shape-bucketed engine: one single-pass rule apply per
    distinct matrix shape, the weight update folded into the per-bucket
    kernel with ``use_kernel`` (which needs the engine);
    ``momentum_dtype='bfloat16'`` halves its momentum storage; ``zero2``
    (implies ``fused``) switches to the explicit data-parallel step
    with the matrix momentum *and* gradient buckets sharded over the data
    axis — reduce-scatter straight into the bucket shard, padded uneven
    buckets included (``compress`` picks the int8 error-feedback schedule
    over the exact fp32 collectives).  ``accum`` splits each rank's batch
    into that many microbatches (scan accumulation — on the ZeRO-2 path
    the matrix grads accumulate directly in the chunked per-rank layout);
    ``overlap`` picks the bucket-pipelined ZeRO-2 schedule (independent
    per-bucket reduce-scatter/update chains, two-phase clip) over the
    serialized baseline — ``None`` (default) auto-resolves via
    ``train.dp_step.resolve_overlap``.

    **Numerical resilience.**  ``guard=True`` arms the in-graph non-finite
    guard (a NaN/Inf step is masked bitwise, train/pipeline.py) plus the
    host-side escalation ladder (``distributed/monitor.py
    AnomalyMonitor``): more than ``anomaly_skip_budget`` consecutive
    skipped steps, or a finite loss spike the guard cannot see, rewinds to
    the last-known-good checkpoint with the learning rates backed off by
    ``anomaly_lr_backoff`` and the data stream replayed deterministically
    from the checkpointed position (``anomaly_skip_batch=True``
    additionally drops the batches of skipped steps on replay); more than
    ``anomaly_rewind_budget`` rewinds aborts loudly naming the offending
    step and leaves.  A periodic checkpoint is *promoted* to
    last-known-good only after ``anomaly_health_window`` further anomaly-
    free steps (``CheckpointManager.mark_good``).  ``inject_fault``
    (``kind:leaf:step[:microbatch]``, ``repro.train.faults``) injects a
    NaN/Inf/wire-bit-flip fault for the resilience proofs; injected faults
    are disarmed on rewind (transient-fault model — the abort rung covers
    faults that keep firing).  ``clip_norm <= 0`` disables gradient
    clipping (metrics keep reporting).

    ``arch`` is a registered config name or a ``ModelConfig`` (such as a
    registered one cut in depth).  ``devices`` are the devices the data
    mesh spans (default: every visible device).  The step is compiled
    ahead of time from the state's shapes, and the state is then created
    directly in the shardings the compiled step expects.  ``report`` (a
    :class:`StepReport`) receives the compile time, the compiled HLO, its
    memory analysis, the kernel routing, the host spans and the compiles of
    each loop iteration (``launch/spans.py``; the spans also go to a running
    profiler's trace, report or not).  Every logged history entry carries ``step_s``,
    the host time from dispatching that step to its outputs being ready
    (a step's own time when every step is logged)."""
    cfg = arch if isinstance(arch, ModelConfig) else get_config(arch)
    if reduced:
        cfg = cfg.reduced()

    spans = Spans(report)
    devices = list(devices) if devices is not None else jax.devices()
    mesh = make_local_mesh(data=len(devices), devices=devices)
    n_dev = mesh.shape["data"]
    fault_spec = faults.parse_fault(inject_fault) if inject_fault else None
    if fault_spec is not None:
        print(f"[train] fault injection armed: {fault_spec.describe()}",
              flush=True)

    def build_opt(shard_size: int, lr_scale: float = 1.0):
        return make_optimizer(optimizer, dict(
            lr_matrix=cosine_with_warmup(lr_matrix * lr_scale, steps),
            lr_adamw=cosine_with_warmup(lr_adamw * lr_scale, steps),
            matrix_embed=matrix_embed,
            use_kernel=use_kernel,
            fused=fused or fused_apply,
            momentum_dtype=momentum_dtype,
            shard_axis="data" if zero2 else None,
            shard_size=shard_size,
        ))

    opt = build_opt(n_dev if zero2 else 1)

    from repro.train.dp_step import init_dp_state

    def init_state(shardings=None):
        """Fresh (params, opt_state[, comp_state]), built by one jitted
        init straight into ``shardings`` (the compiled step's input
        shardings) — nothing is first materialized whole on one device."""
        def init(key):
            p = init_params(cfg, key)
            s = opt.init(p)
            return (p, s, init_dp_state(p, n_dev)) if zero2 else (p, s)
        return jax.jit(init, out_shardings=shardings)(
            jax.random.PRNGKey(seed))

    # abstract state: the step is compiled from shapes alone, and the real
    # state is then created directly in the shardings the step expects
    state_abs = jax.eval_shape(init_state)
    params, opt_state = state_abs[0], state_abs[1]
    start_step, data_step = 0, 0
    layout = elastic.state_layout(opt, params, mesh_size=n_dev,
                                  rule=optimizer,
                                  compress=compress and zero2,
                                  opt_state=opt_state)

    def build_step(opt_, fault):
        """The jitted step for this opt / fault arming (rebuilt on rewind:
        LR backoff changes the schedules, and the injected fault is
        disarmed)."""
        if zero2:
            from repro.train.dp_step import make_dp_train_step
            fn = make_dp_train_step(
                cfg, opt_, mesh, shard_state=True, zero2=True,
                compress=compress, accum=accum, overlap=overlap,
                opt_state=state_abs[1], clip_norm=clip_norm, guard=guard,
                fault=fault, remat="none" if reduced else "full")
        else:
            fn = make_train_step(cfg, opt_, num_microbatches=accum,
                                 clip_norm=clip_norm, guard=guard,
                                 fault=fault,
                                 remat="none" if reduced else "full")
        # the zero2 step's error-feedback state is donated as well: kept,
        # it would live twice across every step (4 GB more per chip at
        # gpt2-large), and any stale reference would pin a third copy
        return jax.jit(fn, donate_argnums=(0, 1, 2) if zero2 else (0, 1))

    def compile_step(jit_step_, args):
        with spans.span("setup/compile") as compiling, mesh, \
                axis_rules(mesh), recording_attention_routes() as attention:
            compiled_ = jit_step_.lower(*args).compile()
        print(f"[train] step compiled in {compiling.seconds:.1f} s",
              flush=True)
        hlo_text = compiled_.as_text()
        copies = state_copies(hlo_text)
        print(f"[train] donated state buffers copied per step: {copies}",
              flush=True)
        if report is not None:
            report.compile_s = compiling.seconds
            report.hlo_text = hlo_text
            report.memory = compiled_.memory_analysis()
            report.routes.update(attention)
            report.state_copies = copies
        return compiled_, attention

    # one trace of the optimizer step gives both the launch count and the
    # per-bucket RMNP kernel routes
    if use_kernel and optimizer == "rmnp":
        with spans.span("setup/trace_optimizer"):
            launches = optimizer_kernel_launches(opt, params)
        if log_every:
            print(f"[train] preconditioner kernel launches/step: "
                  f"{len(launches)} ({len(opt_state.buckets)} shape "
                  f"buckets)")
        routes = kernel_routes(opt, params, launches)
        if report is not None:
            report.routes = routes
        for key, launch in routes.items():
            print(f"[train] bucket {key}: "
                  + ("xla" if launch is None else
                     f"kernel {launch.name} grid {launch.grid}"), flush=True)

    jit_step = build_step(opt, fault_spec)
    batch_abs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                 for k, v in next(make_stream(cfg, seq, batch,
                                              seed=seed)).items()}
    compiled, attention = compile_step(jit_step, tuple(state_abs) + (
        batch_abs, jax.ShapeDtypeStruct((), jnp.int32)))
    for key, route in attention.items():
        print(f"[train] {key}: {route}", flush=True)
    state_shardings = compiled.input_shardings[0][:len(state_abs)]
    with spans.span("setup/init_state"):
        state = init_state(state_shardings)
    params, opt_state = state[0], state[1]
    comp_state = state[2] if zero2 else None

    mgr = CheckpointManager(ckpt_dir) if ckpt_dir else None
    latest = mgr.latest_step() if mgr is not None else None
    if latest is not None:
        with spans.span("setup/restore"):
            # zero2 checkpoints include the compression error-feedback state:
            # dropping the accumulated residual on restart would break the
            # schedule's unbiased-accumulation guarantee at every resume
            old_layout = mgr.read_layout(latest)
            old_n = old_layout.get("shard_size") if old_layout else None
            if zero2 and old_layout is not None and old_n != n_dev:
                # mesh-size mismatch: anything else differing is fatal (loud,
                # both layouts named), a pure size change reshards exactly
                elastic.validate_relayout(old_layout, layout)
                (params, opt_state, comp_state), data_step = \
                    elastic.restore_resharded(mgr, latest, params, comp_state,
                                              opt_new=opt,
                                              opt_old=build_opt(old_n))
                start_step = latest
                print(f"[train] resumed from step {latest} "
                      f"(elastic reshard {old_n}-way -> {n_dev}-way)")
            else:
                template = ((params, opt_state, comp_state) if zero2
                            else (params, opt_state))
                restored = mgr.restore_latest(template)
                if zero2:
                    (params, opt_state, comp_state), start_step, data_step = restored
                else:
                    (params, opt_state), start_step, data_step = restored
                print(f"[train] resumed from step {start_step}")
            # restored host arrays go onto the compiled step's shardings
            state = jax.device_put((params, opt_state, comp_state) if zero2
                                   else (params, opt_state), state_shardings)
            params, opt_state = state[0], state[1]
            comp_state = state[2] if zero2 else None

    stream = make_stream(cfg, seq, batch, seed=seed, start_step=data_step)

    hang_guard = None
    if watchdog_deadline:
        from repro.distributed.monitor import HangGuard

        def emergency_save():
            if mgr is None:
                print("[watchdog] no checkpoint dir — nothing to save",
                      flush=True)
                return
            # reuses the manager's pinned double buffer (filled at every
            # step boundary below) — no device access, safe while the
            # step loop is hung on donated buffers
            saved = mgr.emergency_save()
            if saved is None:
                print("[watchdog] no snapshot newer than the last "
                      "committed checkpoint — nothing to save", flush=True)
            else:
                print(f"[watchdog] emergency checkpoint written at step "
                      f"{saved}", flush=True)
        hang_guard = HangGuard(watchdog_deadline, emergency_save)

    monitor = None
    if guard:
        from repro.distributed.monitor import AnomalyMonitor
        from repro.train import pipeline
        leaf_names = (pipeline.guard_flag_names(opt.bucket_plan(params),
                                                params, n_dev)
                      if zero2 else [p for p, _ in tree_paths(params)])
        monitor = AnomalyMonitor(spike_k=anomaly_spike_k,
                                 skip_budget=anomaly_skip_budget,
                                 rewind_budget=anomaly_rewind_budget,
                                 leaf_names=leaf_names)
    # abstract template for rewind restores: by rewind time the live
    # arrays have been donated away, so restore validates against shapes
    state_template = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
        (params, opt_state, comp_state) if zero2 else (params, opt_state))
    lr_scale = 1.0
    pending_good: list = []    # (ckpt_step) awaiting the health window
    bad_data_steps: set = set()  # data positions of skipped steps (replay)

    history = []
    t0 = time.time()
    end_step = min(steps, stop_at) if stop_at else steps
    with mesh, axis_rules(mesh), spans:
        step = start_step
        while step < end_step:
            with spans.iteration(step):
                # next(stream) stays in this frame: a wrapped stream may read
                # the loop's params, opt_state and metrics from its caller
                with spans.span("data"):
                    if anomaly_skip_batch and stream.step in bad_data_steps:
                        bad_data_steps.discard(stream.step)
                        next(stream)  # drop the offending batch on replay
                        print(f"[train] replay: dropped the batch of skipped "
                              f"data step {stream.step - 1}", flush=True)
                    np_batch = next(stream)
                    jbatch = {k: jnp.asarray(v) for k, v in np_batch.items()}
                args = (((params, opt_state, comp_state) if zero2
                         else (params, opt_state)) + (jbatch, jnp.int32(step)))
                if compiled is None:
                    compiled, _ = compile_step(jit_step, args)
                if hang_guard is not None:
                    hang_guard.arm()
                    t_step = time.time()
                t_dispatch = time.perf_counter()
                with spans.span("dispatch"):
                    if zero2:
                        params, opt_state, comp_state, metrics = compiled(*args)
                    else:
                        params, opt_state, metrics = compiled(*args)
                if hang_guard is not None:
                    # host snapshot into the manager's double buffer BEFORE
                    # recording: the emergency save must never read live
                    # device buffers — the next step donates them, and a hung
                    # step already owns its donated inputs
                    if mgr is not None:
                        with spans.span("checkpoint"):
                            mgr.snapshot(step + 1,
                                         (params, opt_state, comp_state) if zero2
                                         else (params, opt_state),
                                         data_step=stream.step, layout=layout)
                    hang_guard.record(step, time.time() - t_step)
                if monitor is not None:
                    with spans.span("guard"):
                        gflags = np.asarray(metrics.pop("guard_flags"))
                        was_skipped = bool(float(metrics.pop("skipped")))
                        action = monitor.record(step, float(metrics["loss"]),
                                                skipped=was_skipped, flags=gflags)
                    if action != "ok":
                        pending_good.clear()  # anomaly: nothing in flight
                        #   gets promoted to last-known-good
                    if action == "skip":
                        leaves = ", ".join(monitor.bad_leaves(gflags)) or \
                            "<loss non-finite>"
                        bad_data_steps.add(stream.step - 1)
                        print(f"[train] guard: step {step} SKIPPED bitwise "
                              f"(non-finite: {leaves}; "
                              f"{monitor.consecutive_skips}/"
                              f"{anomaly_skip_budget} consecutive)", flush=True)
                    elif action == "rewind":
                        lr_scale *= anomaly_lr_backoff
                        opt = build_opt(n_dev if zero2 else 1, lr_scale)
                        good = (mgr.latest_good_step()
                                if mgr is not None else None)
                        if good is not None:
                            with spans.span("checkpoint"):
                                mgr.wait()
                                state, data_step = mgr.restore(good,
                                                               state_template)
                                # a restore yields host arrays: put them back
                                # on the step's own shardings
                                state = jax.device_put(state, state_shardings)
                            if zero2:
                                # every rank's EF residual rides the sharded
                                # checkpoint (device-axis CompressionState), so
                                # the replayed tail is bitwise on both wires
                                params, opt_state, comp_state = state
                            else:
                                params, opt_state = state
                            rewind_to = good
                        else:
                            # no good checkpoint yet: restart from init
                            state = init_state(state_shardings)
                            params, opt_state = state[0], state[1]
                            comp_state = state[2] if zero2 else None
                            rewind_to, data_step = 0, 0
                        if fault_spec is not None:
                            print("[train] rewind: disarming the injected "
                                  "fault (transient-fault model)", flush=True)
                            fault_spec = None
                        jit_step, compiled = build_step(opt, fault_spec), None
                        stream = make_stream(cfg, seq, batch, seed=seed,
                                             start_step=data_step)
                        print(f"[train] anomaly ladder: rewind #"
                              f"{monitor.rewinds} to step {rewind_to} "
                              f"(lr x{lr_scale:g}, data step {data_step}; "
                              f"{monitor.post_mortem()})", flush=True)
                        step = rewind_to
                        continue
                    elif action == "abort":
                        raise RuntimeError(
                            f"[train] numerical-anomaly escalation ladder "
                            f"exhausted at step {step}: "
                            f"{monitor.post_mortem()}")
                if log_every and (step % log_every == 0 or step == steps - 1):
                    with spans.span("block"):
                        jax.block_until_ready((params, metrics))
                        step_s = time.perf_counter() - t_dispatch
                        m = {k: float(v) for k, v in metrics.items()}
                    m["step"] = step
                    m["step_s"] = step_s
                    m["wall_s"] = round(time.time() - t0, 2)
                    if dominance_every and step % dominance_every == 0 and \
                            optimizer != "adamw":
                        from repro.core.mixed import momentum_for_diagnostics
                        dom = global_dominance(momentum_for_diagnostics(
                            opt_state, params, matrix_embed=matrix_embed))
                        m.update({k: float(v) for k, v in dom.items()})
                    history.append(m)
                    print(f"[train] step={step} loss={m['loss']:.4f} "
                          f"gnorm={m['grad_norm']:.3f} clip={m['clip_rate']:.0f} "
                          f"step_s={step_s:.4f}"
                          + (f" r_avg={m['r_avg']:.2f}" if "r_avg" in m else ""),
                          flush=True)
                if mgr is not None and ckpt_every and (step + 1) % ckpt_every == 0:
                    state = ((params, opt_state, comp_state) if zero2
                             else (params, opt_state))
                    with spans.span("checkpoint"):
                        mgr.save(step + 1, state, data_step=stream.step,
                                 layout=layout)
                    if monitor is not None:
                        pending_good.append(step + 1)
                if monitor is not None and pending_good:
                    # promote checkpoints that survived the health window of
                    # anomaly-free steps to last-known-good
                    ripe = [s for s in pending_good
                            if step + 1 - s >= anomaly_health_window]
                    for s in ripe:
                        mgr.mark_good(s)
                        pending_good.remove(s)
                        print(f"[train] checkpoint step {s} promoted to "
                              f"last-known-good", flush=True)
                if kill_at and step + 1 == kill_at:
                    print(f"[train] fault injection: SIGKILL at step {step + 1}",
                          flush=True)
                    os.kill(os.getpid(), signal.SIGKILL)
                step += 1
    if hang_guard is not None:
        hang_guard.stop()
    if mgr is not None and end_step == steps:
        state = ((params, opt_state, comp_state) if zero2
                 else (params, opt_state))
        mgr.save(steps, state, data_step=stream.step, block=True,
                 layout=layout)
        mgr.wait()
    elif mgr is not None:
        mgr.wait()  # crash simulation: last periodic checkpoint survives
    if log_file:
        Path(log_file).parent.mkdir(parents=True, exist_ok=True)
        Path(log_file).write_text(json.dumps(history, indent=1))
    if dump_params:
        Path(dump_params).parent.mkdir(parents=True, exist_ok=True)
        np.savez(dump_params, **{p: np.asarray(v, np.float32)
                                 for p, v in tree_paths(params)})
    return params, opt_state, history


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--optimizer", default="rmnp",
                    choices=list(optimizer_names()),
                    help="matrix update rule (everything else gets AdamW); "
                         "'adamw' is the everything-through-AdamW baseline")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr-matrix", type=float, default=2e-3)
    ap.add_argument("--lr-adamw", type=float, default=1e-3)
    ap.add_argument("--full", action="store_true", help="full-size config")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--dominance-every", type=int, default=0)
    ap.add_argument("--use-kernel", action="store_true")
    ap.add_argument("--engine", default="per-leaf",
                    choices=["per-leaf", "single-pass"],
                    help="matrix-partition engine: 'per-leaf' (the plain "
                         "jax.numpy reference, one pass per parameter), "
                         "'single-pass' (shape-bucketed: one rule apply per "
                         "distinct matrix shape with the weight update "
                         "folded in — no fp32 d buffer, no updates tree; "
                         "--use-kernel needs it)")
    ap.add_argument("--momentum-dtype", default="float32",
                    choices=["float32", "bfloat16"],
                    help="bucketed matrix-momentum storage dtype")
    ap.add_argument("--zero2", action="store_true",
                    help="explicit data-parallel step with ZeRO-2 sharding "
                         "(implies --engine single-pass): matrix momentum AND "
                         "gradient buckets shard over the data axis — "
                         "gradients reduce-scatter straight into the bucket "
                         "shard, uneven buckets padded; only updated param "
                         "slices are all-gathered")
    ap.add_argument("--no-compress", action="store_true",
                    help="with --zero2: exact fp32 collectives instead of "
                         "the int8 error-feedback schedule")
    ap.add_argument("--accum", type=int, default=1,
                    help="microbatch gradient-accumulation factor (lax.scan "
                         "over accum microbatches per rank; with --zero2 "
                         "matrix grads accumulate directly in the chunked "
                         "per-destination-rank layout — the monolithic fp32 "
                         "gradient bucket never exists)")
    ap.add_argument("--overlap", default="auto",
                    choices=["auto", "on", "off"],
                    help="with --zero2: 'on' forces the bucket-pipelined "
                         "step (independent per-bucket collective/update "
                         "chains, two-phase global-norm clip), 'off' the "
                         "serialized all-reduce-then-all-update baseline; "
                         "'auto' (default) pipelines except the measured "
                         "accum=1 fp32-wire regression case")
    ap.add_argument("--no-matrix-embed", action="store_true",
                    help="AdamW on LM-head/embeddings (paper App D.4 ablation)")
    ap.add_argument("--stop-at", type=int, default=0,
                    help="simulate a crash at this step (schedules span --steps)")
    ap.add_argument("--kill-at", type=int, default=0,
                    help="fault injection: SIGKILL the process mid-loop at "
                         "this step — no cleanup, no final checkpoint; an "
                         "in-flight async save may be torn (atomic commit "
                         "makes it invisible, not corrupt)")
    ap.add_argument("--watchdog-deadline", type=float, default=0.0,
                    help="arm the hang/straggler watchdog: a step exceeding "
                         "this many seconds (or flagged by the step-time "
                         "monitor) triggers an emergency blocking checkpoint "
                         "of the last completed step")
    ap.add_argument("--dump-params", default="",
                    help="write the final params to this npz (fp32), for "
                         "cross-run comparison by the fault-injection "
                         "harnesses")
    ap.add_argument("--log-file", default="")
    ap.add_argument("--clip-norm", type=float, default=1.0,
                    help="global gradient-norm clip; <= 0 disables clipping "
                         "while grad_norm/clip_rate metrics keep reporting")
    ap.add_argument("--guard", action="store_true",
                    help="numerical resilience: in-graph non-finite guard "
                         "(a NaN/Inf step is skipped with every buffer "
                         "bitwise-unchanged) + the host-side anomaly "
                         "escalation ladder (skip -> rewind to "
                         "last-known-good with LR backoff and deterministic "
                         "batch replay -> loud abort)")
    ap.add_argument("--inject-fault", default="",
                    help="inject a numerical fault (resilience proofs): "
                         "kind:leaf:step[:microbatch] — kind is nan|inf|"
                         "bitflip, leaf a gradient-leaf path ('*' = first) "
                         "or a bucket key for bitflip, a trailing '+' on "
                         "step makes it sticky (every step >= k); e.g. "
                         "nan:*:6+ or bitflip:8x16:4")
    ap.add_argument("--anomaly-spike-k", type=float, default=6.0,
                    help="loss-spike z-score threshold of the anomaly "
                         "ladder (EWMA sigmas)")
    ap.add_argument("--anomaly-skip-budget", type=int, default=3,
                    help="consecutive guard-skipped steps tolerated before "
                         "escalating to a rewind")
    ap.add_argument("--anomaly-rewind-budget", type=int, default=2,
                    help="rewinds tolerated before aborting loudly")
    ap.add_argument("--anomaly-lr-backoff", type=float, default=0.5,
                    help="multiply both learning rates by this on every "
                         "rewind (1.0 = replay at full LR)")
    ap.add_argument("--anomaly-health-window", type=int, default=2,
                    help="anomaly-free steps a periodic checkpoint must "
                         "survive before promotion to last-known-good")
    ap.add_argument("--anomaly-skip-batch", action="store_true",
                    help="on rewind replay, drop the batches that fed "
                         "guard-skipped steps (suspected data poisoning)")
    args = ap.parse_args()
    enable_compile_cache()
    overlap = {"auto": None, "on": True, "off": False}[args.overlap]
    train(args.arch, args.optimizer, args.steps, args.batch, args.seq,
          args.lr_matrix, args.lr_adamw, reduced=not args.full,
          seed=args.seed, ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
          log_every=args.log_every, dominance_every=args.dominance_every,
          matrix_embed=not args.no_matrix_embed,
          use_kernel=args.use_kernel,
          fused=args.engine == "single-pass",
          momentum_dtype=args.momentum_dtype,
          zero2=args.zero2, compress=not args.no_compress,
          accum=args.accum, overlap=overlap,
          log_file=args.log_file, stop_at=args.stop_at,
          kill_at=args.kill_at, watchdog_deadline=args.watchdog_deadline,
          dump_params=args.dump_params, clip_norm=args.clip_norm,
          guard=args.guard, inject_fault=args.inject_fault,
          anomaly_spike_k=args.anomaly_spike_k,
          anomaly_skip_budget=args.anomaly_skip_budget,
          anomaly_rewind_budget=args.anomaly_rewind_budget,
          anomaly_lr_backoff=args.anomaly_lr_backoff,
          anomaly_health_window=args.anomaly_health_window,
          anomaly_skip_batch=args.anomaly_skip_batch)


if __name__ == "__main__":
    main()
