"""From a profiler trace of the window to per-layer numbers.

The JAX profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it.  Each TPU chip is a plane ``/device:TPU:<n>``.  Its ``XLA
Modules`` line holds one event per program execution, named
``jit_<fn>(<fingerprint>)``; its ``XLA Ops`` line one event per operation,
named by the operation's HLO text (``%rmnp_rownorm_apply.3 = (f32[...])
custom-call(...)``), where a loop (``while``) is an event that spans the
operations of its body; its ``Async XLA Ops`` line holds asynchronous
copies and collectives while they are in flight.  The host's threads are
planes of their own, on the same clock.

Per chip, the steady window runs from the start of the first execution of
the step program (the module with the most device time) to the end of its
last; anything outside is set-up.  Within it:

* busy time is the union of the intervals of the operations that are not
  loops, so overlapping operations count once; the idle share is one less
  busy over window;
* a kernel's time is the sum of its events' durations;
* a collective's exposed time is the part of the union of collective
  operations (in flight on either ops line) during which no other
  operation runs on that chip.  An operation is a collective by its
  opcode (``reduce-scatter``, ``all-gather-start``...) or by the
  computation its fusion calls (``calls=%all-reduce-scatter``), read from
  the event's HLO text: the names alone (``reduce_scatter.49``,
  ``fusion.12``) do not say.
"""
from __future__ import annotations

import glob
import re
import shutil
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

MODULES, OPS, ASYNC = "XLA Modules", "XLA Ops", "Async XLA Ops"
CONTAINERS = ("while", "conditional", "call")
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
COLLECTIVE = re.compile(r"all[-_]?reduce|reduce[-_]?scatter|all[-_]?gather|"
                        r"all[-_]?to[-_]?all|collective[-_]?permute", re.I)
_OPCODE = re.compile(r"\s([a-z][\w-]*)\(")
_CALLS = re.compile(r"calls=%([\w.-]+)")


class Event(NamedTuple):
    device: int
    line: str
    name: str      # the operation (``fusion.445``) or module
    start: float   # ns, on the trace's clock
    dur: float     # ns
    label: str = ""  # the start of the event's own text, for people
    collective: bool = False


def is_collective(text: str) -> bool:
    """Whether an operation, given by its HLO text (``%x = type
    opcode(operands), ..., calls=%computation``), is a collective: by its
    opcode, or by the computation a fusion calls.  Its operands' names do
    not count.  A bare name (no ``%``) is judged by the name."""
    if not text.startswith("%"):
        return bool(COLLECTIVE.search(text))
    rest = text.partition(" = ")[2]
    opcode = _OPCODE.search(" " + rest)
    calls = _CALLS.search(rest)
    return any(m is not None and COLLECTIVE.search(m.group(1))
               for m in (opcode, calls))


def op_name(text: str) -> str:
    """``fusion.445`` of ``%fusion.445 = bf16[...] fusion(...)``; other
    names as they are."""
    if text.startswith("%"):
        return text[1:].split(" ", 1)[0]
    return text


def load(path: str) -> Tuple[List[Event], List[Event]]:
    """``(device events, host events)`` of an ``.xplane.pb``: device events
    of the modules and both ops lines of every TPU plane, host events of
    every host thread (device -1)."""
    from jax.profiler import ProfileData

    dev, host = [], []
    for plane in ProfileData.from_file(path).planes:
        m = _DEVICE.match(plane.name)
        for line in plane.lines:
            if m and line.name in (MODULES, OPS, ASYNC):
                dev.extend(Event(int(m.group(1)), line.name,
                                 op_name(e.name), e.start_ns, e.duration_ns,
                                 e.name[:100], is_collective(e.name))
                           for e in line.events)
            elif not m and plane.name.startswith("/host:"):
                host.extend(Event(-1, line.name, e.name, e.start_ns,
                                  e.duration_ns) for e in line.events)
    return dev, host


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float,
                                                                  float]]:
    """Sorted, merged ``(start, end)`` intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def intersect(a, b) -> List[Tuple[float, float]]:
    """Intersection of two merged interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def clip(events: Iterable[Event], lo: float, hi: float):
    return [(max(e.start, lo), min(e.start + e.dur, hi)) for e in events
            if e.start < hi and e.start + e.dur > lo]


def base_name(name: str) -> str:
    """An operation's name without XLA's ``.N`` instance suffix."""
    return re.sub(r"\.\d+$", "", name)


def is_container(e: Event) -> bool:
    return base_name(e.name) in CONTAINERS


class Device(NamedTuple):
    """One chip's steady window."""
    lo: float
    hi: float
    steps: int
    ops: List[Event]       # operations that start inside the window

    @property
    def compute(self) -> List[Event]:
        """Operations that run on the core: not loops, not in flight."""
        return [e for e in self.ops if e.line == OPS and not is_container(e)]


def step_module(events: List[Event]) -> str:
    total: Dict[str, float] = defaultdict(float)
    for e in events:
        if e.line == MODULES:
            total[base_name(e.name)] += e.dur
    if not total:
        raise ValueError("the trace holds no XLA module on any TPU")
    return max(total, key=total.get)


def windows(events: List[Event]) -> Dict[int, Device]:
    step = step_module(events)
    by_dev: Dict[int, List[Event]] = defaultdict(list)
    for e in events:
        by_dev[e.device].append(e)
    out = {}
    for dev, evs in by_dev.items():
        runs = sorted((e for e in evs if e.line == MODULES
                       and base_name(e.name) == step), key=lambda e: e.start)
        if not runs:
            continue
        lo, hi = runs[0].start, runs[-1].start + runs[-1].dur
        ops = [e for e in evs if e.line in (OPS, ASYNC)
               and lo <= e.start < hi]
        out[dev] = Device(lo, hi, len(runs), ops)
    return out


class Reduction:
    """The numbers the per-layer readers take from one trace."""

    def __init__(self, events: List[Event], host: Optional[List[Event]] = None):
        self.devices = windows(events)
        self.host = host or []
        if not self.devices:
            raise ValueError("no chip ran the step program in the trace")

    @property
    def steps(self) -> int:
        return min(d.steps for d in self.devices.values())

    def busy(self, dev: int) -> float:
        d = self.devices[dev]
        return length(union(clip(d.compute, d.lo, d.hi)))

    @property
    def window_s(self) -> float:
        return sum(d.hi - d.lo for d in self.devices.values()) / (
            1e9 * len(self.devices))

    @property
    def busy_s(self) -> float:
        return sum(self.busy(k) for k in self.devices) / (
            1e9 * len(self.devices))

    def idle_share(self) -> float:
        """Mean over chips of 1 - busy / window."""
        return sum(1 - self.busy(k) / (d.hi - d.lo)
                   for k, d in self.devices.items()) / len(self.devices)

    def kernel(self, name: str) -> Dict[int, Tuple[float, List[Event]]]:
        """Per chip: total ns of the operations named ``name`` (any
        instance) in the window, and those events."""
        out = {}
        for k, d in self.devices.items():
            evs = [e for e in d.compute if base_name(e.name) == name]
            out[k] = (sum(e.dur for e in evs), evs)
        return out

    def collective_exposed(self, dev: int) -> Tuple[float, float]:
        """``(collective ns, exposed ns)`` on one chip: the union of the
        collective operations, and the part of it no other operation
        overlaps."""
        d = self.devices[dev]
        coll = [e for e in d.ops if e.collective or is_collective(e.name)]
        rest = [e for e in d.compute
                if not (e.collective or is_collective(e.name))]
        cu = union(clip(coll, d.lo, d.hi))
        hidden = intersect(cu, union(clip(rest, d.lo, d.hi)))
        return length(cu), length(cu) - length(hidden)

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        """The operations that took most device time (seconds per chip,
        summed by name) and the longest idle gaps, each labelled with the
        host event that covers most of it."""
        total: Dict[str, float] = defaultdict(float)
        label: Dict[str, str] = {}
        for d in self.devices.values():
            for e in d.compute:
                total[e.name] += e.dur
                label[e.name] = e.label or e.name
        n = len(self.devices)
        ops = sorted(total.items(), key=lambda kv: -kv[1])[:top]
        gaps = []
        for d in self.devices.values():
            busy = union(clip(d.compute, d.lo, d.hi))
            prev = d.lo
            for s, e in busy + [(d.hi, d.hi)]:
                if s > prev:
                    gaps.append((prev, s))
                prev = max(prev, e)
        gaps.sort(key=lambda g: g[0] - g[1])
        return {"device_ops": [[label[name], ns / (1e9 * n)]
                               for name, ns in ops],
                "idle_gaps": [[self._host_label(s, e), (e - s) / 1e9]
                              for s, e in gaps[:top]]}

    def _host_label(self, s: float, e: float) -> str:
        best, cover = "no host event", 0.0
        for h in self.host:
            c = min(e, h.start + h.dur) - max(s, h.start)
            if c > cover and h.dur < 10 * (e - s) + 1e6:
                best, cover = f"{h.line}: {h.name}"[:120], c
        return best


class Tracer:
    """The profiler over the window, writing under ``artifacts/`` of the
    checkout (each traced run replaces the last one's trace)."""

    def __init__(self):
        self.root = Path(__file__).resolve().parents[1] / "artifacts" / \
            "bench_trace"

    def start(self) -> None:
        import jax

        shutil.rmtree(self.root, ignore_errors=True)
        self.root.mkdir(parents=True)
        jax.profiler.start_trace(str(self.root))

    def stop(self) -> None:
        import jax

        jax.profiler.stop_trace()

    def path(self) -> str:
        found = sorted(glob.glob(str(self.root / "**" / "*.xplane.pb"),
                                 recursive=True))
        if not found:
            raise FileNotFoundError(f"no trace written under {self.root}")
        return found[-1]

    def reduce(self) -> Reduction:
        dev, host = load(self.path())
        return Reduction(dev, host)
