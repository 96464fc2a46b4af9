"""One run of one benchmark cell: set-up, the measured window, the check of
what the window's own steps produced, and the metrics.

A cell (``bench/workloads/<cell>.json``) names a configuration
(``bench/configs/<config>.json``: the model as run, its source and cuts,
and its plain reference under ``bench/reference/``) and a traffic mix
(``bench/traffic/<traffic>.json``: the job — batch, sequence, optimizer
path — and its hyperparameters), the chips it needs, the nominal step
time that sizes its window and the limits of its check.  Per-layer metrics
are readers of their own, ``bench/metrics/<metric>.py``.  Adding a cell, a
configuration or a metric adds files; nothing here names one.

The run drives ``repro.launch.train.train`` itself: one call builds the
compiled step and its state from the seed, trains the first
``SETUP_STEPS`` steps (set-up: they warm the one step program and feed
the check) and then the window's steps, blocking only where ``train``
logs.  ``train`` takes a step count, so the window's count is the cell's
``seconds / step_s``.  A stream wrapped around the program's own data
loader sees the loop each time it asks for a batch; over the set-up steps
it keeps the loss, the momentum norms after the first step and the
weight-change norms after the last (small device arrays, read after the
window), and then it waits for the chips: that instant ends set-up and
opens the window.
"""
from __future__ import annotations

import gc
import importlib
import importlib.util
import json
import math
import sys
import time
from pathlib import Path
from typing import Any, Callable, Dict, List

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
SETUP_STEPS = 2          # steps trained in set-up, compared with the reference


class BenchError(RuntimeError):
    """A run that cannot be made: no chip, an unknown cell, a bad file."""


def read_json(kind: str, name: str) -> Dict[str, Any]:
    path = BENCH / kind / f"{name}.json"
    if not path.is_file():
        raise BenchError(f"no {kind[:-1]} {name!r} ({path} is missing)")
    return json.loads(path.read_text())


def cell_names() -> List[str]:
    return sorted(p.stem for p in (BENCH / "workloads").glob("*.json"))


def load_cell(name: str) -> Dict[str, Any]:
    """The cell with its configuration and traffic mix read in."""
    cell = dict(read_json("workloads", name), name=name)
    cell["config_spec"] = read_json("configs", cell["config"])
    cell["traffic_spec"] = read_json("traffic", cell["traffic"])
    return cell


def per_layer_metrics(cell: str) -> List[Dict[str, Any]]:
    """The ``per_layer`` entries of ``BENCHMARK.json`` this cell reports."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    return [m for m in spec["per_layer"]
            if cell in m.get("workloads", [cell])]


def load_reader(metric: str) -> Callable:
    """``read(ctx)`` of ``bench/metrics/<metric>.py``."""
    path = BENCH / "metrics" / f"{metric}.py"
    if not path.is_file():
        raise BenchError(f"metric {metric!r} has no reader ({path})")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def tpu_devices(chips: int):
    """The first ``chips`` TPU devices; anything else is an error, never a
    fallback to another platform."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"needs {chips} TPU chip(s); JAX found platform "
                         f"{devs[0].platform!r} ({len(devs)} devices)")
    if len(devs) < chips:
        raise BenchError(f"needs {chips} TPU chips; found {len(devs)}")
    return devs[:chips]


# ---------------------------------------------------------------------------
# seeing the training loop
# ---------------------------------------------------------------------------

class Watch:
    """What the set-up steps leave for the check, and the window's start.

    Called by :class:`WatchedStream` with the data step about to be fed and
    the training loop's variables (``params``, ``opt_state``, ``metrics``
    of the step before)."""

    def __init__(self, moment_norms, change_norms, on_window: Callable):
        self._moment_norms, self._change_norms = moment_norms, change_norms
        self._on_window = on_window
        self.losses: List[Any] = []
        self.moment = self.change = None
        self.t_window = None

    def __call__(self, step: int, loop: Dict[str, Any]) -> None:
        if not 1 <= step <= SETUP_STEPS:
            return
        self.losses.append(loop["metrics"]["loss"])
        if step == 1:
            self.moment = self._moment_norms(loop["opt_state"], loop["params"])
        if step == SETUP_STEPS:
            import jax

            self.change = self._change_norms(loop["params"])
            jax.block_until_ready((loop["params"], self.moment, self.change,
                                   self.losses))
            self._on_window()
            self.t_window = time.perf_counter()


class WatchedStream:
    """The program's own batch stream, unchanged, with ``watch`` called
    before each batch it hands the loop (``train``'s frame is the
    caller of ``__next__``)."""

    def __init__(self, inner, watch: Watch):
        self._inner, self._watch = inner, watch

    def __iter__(self):
        return self

    def __next__(self):
        self._watch(self._inner.step, sys._getframe(1).f_locals)
        return next(self._inner)

    def __getattr__(self, name):
        return getattr(self._inner, name)


def program_readers(cfg, seed: int):
    """Jitted readers of the program's state: per-slice norms of its
    momentum and of its weight change since initialisation."""
    import jax

    from bench.reference.dense_lm import slice_norms
    from repro.core.mixed import momentum_for_diagnostics
    from repro.models import init_params

    @jax.jit
    def moment_norms(opt_state, params):
        return slice_norms(momentum_for_diagnostics(opt_state, params))

    @jax.jit
    def change(params, key):
        w0 = init_params(cfg, key)
        return slice_norms(jax.tree_util.tree_map(
            lambda a, b: a.astype("float32") - b.astype("float32"),
            params, w0))

    key = jax.random.PRNGKey(seed)
    return moment_norms, lambda params: change(params, key)


class GcPauses:
    """The garbage collector's collections, timed (a ``gc.callbacks``
    entry): host pauses that leave the chips without work."""

    def __init__(self):
        self.spans: List[Any] = []
        self._t0 = None

    def __call__(self, phase: str, info: Dict[str, int]) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.spans.append((self._t0, time.perf_counter(),
                               info["generation"]))

    def summary(self, lo: float, hi: float) -> str:
        inside = [(e - s, g) for s, e, g in self.spans if lo <= s < hi]
        if not inside:
            return "none"
        longest = max(inside)
        return (f"{len(inside)}, {sum(d for d, _ in inside)} s in all, "
                f"longest {longest[0]} s (generation {longest[1]})")


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def window_steps(cell: Dict[str, Any], seconds: float) -> int:
    return max(1, math.ceil(seconds / cell["step_s"]))


def train_kwargs(cell: Dict[str, Any]) -> Dict[str, Any]:
    t = cell["traffic_spec"]
    return dict(t["train"], batch=t["batch"], seq=t["seq"],
                log_every=t["log_every"], lr_matrix=t["lr_matrix"],
                lr_adamw=t["lr_adamw"], clip_norm=t["clip_norm"],
                reduced=False)


def run(cell_name: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, devices=None,
        log: Callable[[str], None] = print) -> Dict[str, Any]:
    """One run; returns the result line's object.  ``t_start`` is the
    process's start on ``time.perf_counter``'s clock.  ``devices`` skips
    the look for TPU chips (tests)."""
    cell = load_cell(cell_name)
    if devices is None:
        devices = tpu_devices(cell["chips"])

    import jax

    import repro.launch.train as launch
    from bench import check
    from bench.trace import Tracer
    from repro.configs.base import ModelConfig

    model = cell["config_spec"]["model"]
    cfg = ModelConfig(**model)
    n_window = window_steps(cell, seconds)
    steps = SETUP_STEPS + n_window
    kw = train_kwargs(cell)
    tokens_per_step = kw["batch"] * kw["seq"]
    moment_norms, change_norms = program_readers(cfg, seed)
    tracer = Tracer() if trace else None
    watch = Watch(moment_norms, change_norms,
                  on_window=tracer.start if tracer else (lambda: None))

    make_stream = launch.make_stream

    def watched(*a, **k):
        return WatchedStream(make_stream(*a, **k), watch)

    report = launch.StepReport()
    pauses = GcPauses()
    launch.make_stream = watched
    gc.callbacks.append(pauses)
    try:
        params, opt_state, history = launch.train(
            cfg, steps=steps, seed=seed, devices=devices, report=report,
            **kw)
    finally:
        launch.make_stream = make_stream
        gc.callbacks.remove(pauses)
    t_end = time.perf_counter()
    if tracer:
        tracer.stop()
    if watch.t_window is None:
        raise BenchError("the training loop never reached the window")
    t_window = watch.t_window
    setup_s, window_s = t_window - t_start, t_end - t_window
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    window_losses = [h["loss"] for h in history if h["step"] >= SETUP_STEPS]
    program = {"loss": [float(x) for x in watch.losses],
               "moment": _flat(watch.moment), "change": _flat(watch.change)}
    del params, opt_state, watch
    gc.collect()
    log(f"[bench] live device bytes before the reference: "
        f"{sum(a.nbytes for a in jax.live_arrays())}")

    log(f"[bench] {cell_name} seed={seed} setup_s={setup_s} "
        f"window_s={window_s} steps={n_window} "
        f"compile_s={report.compile_s}")
    log(f"[bench] garbage collections in the window: "
        f"{pauses.summary(t_window, t_end)}")
    t_ref = time.perf_counter()
    ref = reference_readings(cell, seed, steps, devices, log=log)
    log(f"[bench] reference took {time.perf_counter() - t_ref} s")
    correct, numbers = check.compare(program, ref, cell["limits"])

    d0 = devices[0]
    result: Dict[str, Any] = {
        "correct": bool(correct),
        "attempted": n_window,
        "failed": sum(1 for x in window_losses if not math.isfinite(x)),
        "metrics": {},
        "device": {"platform": d0.platform, "kind": d0.device_kind,
                   "count": len(devices), "memory_peak_bytes": peak},
    }
    tokens_per_s = n_window * tokens_per_step / window_s
    if not trace:
        result["metrics"] = {
            "tokens_per_s": {"value": tokens_per_s, "unit": "tokens/s"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
    else:
        ctx = Context(cell=cell, devices=devices, report=report,
                      tokens_per_s=tokens_per_s, trace=tracer.reduce())
        for m in per_layer_metrics(cell_name):
            value = load_reader(m["name"])(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        result["device"].update(busy_s=ctx.trace.busy_s,
                                window_s=ctx.trace.window_s)
        result["breakdown"] = ctx.trace.breakdown()
    result["checks"] = {k: {"value": n["value"], "limit": n["limit"]}
                        for k, n in numbers.items() if n["limit"] is not None}
    for k, n in sorted(numbers.items(), key=lambda kv: kv[1]["limit"]
                       is not None):
        log(f"[check] {k} = {n['value']!r} "
            + (f"limit {n['limit']!r}" if n["limit"] is not None
               else "(not compared)")
            + (f" (worst at {n['at']})" if n.get("at") else ""))
    return result


def _flat(norms) -> Dict[str, float]:
    import jax

    from bench.reference.dense_lm import flatten_norms
    return flatten_norms(jax.device_get(norms))


def reference_readings(cell: Dict[str, Any], seed: int, steps: int,
                       devices, **kw) -> Dict[str, Any]:
    """The cell's plain reference over the compared steps."""
    ref = importlib.import_module(
        f"bench.reference.{cell['config_spec']['reference']}")
    t = cell["traffic_spec"]
    job = dict(batch=t["batch"], seq=t["seq"], lr_matrix=t["lr_matrix"],
               lr_adamw=t["lr_adamw"], clip_norm=t["clip_norm"],
               optimizer=t["optimizer"],
               reference_rows=t.get("reference_rows", 1))
    return ref.readings(cell["config_spec"]["model"], job, seed, steps,
                        devices, n_steps=SETUP_STEPS, **kw)


class Context:
    """What a per-layer metric's reader may read."""

    def __init__(self, *, cell, devices, report, tokens_per_s, trace):
        self.cell, self.devices, self.report = cell, devices, report
        self.tokens_per_s, self.trace = tokens_per_s, trace
        self.model = cell["config_spec"]["model"]
        self.seq = cell["traffic_spec"]["seq"]
        self.chips = len(devices)
        self.device_kind = devices[0].device_kind
