"""Host spans and the compile counter of the training loop.

``Spans(report)`` hands :func:`repro.launch.train.train` one context
manager per named piece of host work.  Each span opens a
``jax.profiler.TraceAnnotation`` (a loop iteration a
``StepTraceAnnotation``), so a profiler trace holds it on the host planes,
on the device trace's clock; without a running profiler that is a no-op of
about a microsecond.  Where the caller passed a ``StepReport``, each span
is also appended to ``report.spans`` as ``(name, parent, step, t0, t1)``
(``time.perf_counter_ns``; ``step`` is -1 before the loop), and every loop
iteration appends ``(step, compiles)`` to ``report.compiles``: the
executables built or loaded from the persistent cache while it ran, from
JAX's own compile events.  Nothing here reads the device.
"""
from __future__ import annotations

import contextlib
import time
from typing import Optional

import jax

# emitted once per executable built or loaded from the persistent cache
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Span:
    """One span while it is open; ``seconds`` once it has closed."""

    def __init__(self, owner: "Spans", name: str, annotation):
        self._owner, self.name, self._annotation = owner, name, annotation
        self.parent: Optional[str] = None
        self.t0 = self.t1 = 0

    def __enter__(self) -> "Span":
        self._annotation.__enter__()
        stack = self._owner.open
        self.parent = stack[-1] if stack else None
        stack.append(self.name)
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = time.perf_counter_ns()
        self._owner.open.pop()
        self._annotation.__exit__(*exc)
        if self._owner.report is not None:
            self._owner.report.spans.append(
                (self.name, self.parent, self._owner.step, self.t0, self.t1))

    @property
    def seconds(self) -> float:
        return (self.t1 - self.t0) / 1e9


class Spans:
    """The spans of one ``train()`` call, and its compile counter while the
    loop runs (``with spans:`` around the loop)."""

    def __init__(self, report=None):
        self.report = report
        self.open: list = []
        self.step = -1
        self._compiles = 0

    def span(self, name: str) -> Span:
        return Span(self, name, jax.profiler.TraceAnnotation(name))

    @contextlib.contextmanager
    def iteration(self, step: int):
        """One loop iteration: the ``train_step`` span, a step of the
        profiler's step view, and its count of compiles."""
        self.step = step
        before = self._compiles
        try:
            with Span(self, "train_step", jax.profiler.StepTraceAnnotation(
                    "train_step", step_num=step)):
                yield
        finally:
            if self.report is not None:
                self.report.compiles.append((step, self._compiles - before))

    def _count(self, event: str, _duration: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            self._compiles += 1

    def __enter__(self) -> "Spans":
        if self.report is not None:
            jax.monitoring.register_event_duration_secs_listener(self._count)
        return self

    def __exit__(self, *exc) -> None:
        if self.report is not None:
            jax.monitoring.unregister_event_duration_listener(self._count)
