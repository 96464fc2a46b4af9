"""Device and host time by the program's own names.

The training step names its parts with ``jax.named_scope``, and the
compiler keeps the path in each instruction's metadata
(``%fusion.445 = ... metadata={op_name="jit(train_step)/optimizer/..."}``
in ``StepReport.hlo_text``).  A device event of the trace is named by its
instruction (``fusion.445``), so joining the two puts the event's time in a
layer, by the first of these scopes on its path:

* ``backward``: ``transpose(jvp(forward))``, the gradient of the loss and,
  under remat, the forward recomputed inside it;
* ``forward``: ``jvp(forward)`` or ``forward``, the loss's forward pass,
  the LM head and the loss chunks included;
* ``optimizer``, ``clip``, ``guard``: the update (its children ``gather``,
  ``scatter``, ``bucket_<key>`` and ``adamw``), the global-norm clip and
  the non-finite guard;
* ``reduce_scatter``: ZeRO-2's per-bucket gradient exchange;
* ``""``: unattributed: no metadata (copies the compiler adds) or none of
  these scopes (a program that names none).

The host spans of ``launch/spans.py`` are events of the trace's host
planes (``Reduction.host``), on the device's clock.
"""
from __future__ import annotations

import functools
import re
import sys
from collections import defaultdict, deque
from typing import Dict, List, Optional

from bench.trace import clip, intersect, length, union

LAYERS = ("forward", "backward", "optimizer", "clip", "guard",
          "reduce_scatter")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([\w.-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(ROOT\s+)?%([\w.-]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_RUNS = re.compile(r"\b(?:calls|body|condition|to_apply)=%([\w.-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_REF = re.compile(r"%([\w.-]+)")
_OPCODE = re.compile(r"\s([a-z][\w-]*)\(")
# what inherits no path, and passes none on: no work of its own
_INERT = ("parameter", "constant")


@functools.lru_cache(maxsize=2)
def op_names(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> the ``op_name`` path its time is put under.

    An instruction's own path where it names a layer, else the first that
    does of what it computes (its fused computation's root, then the rest
    of it).  The rest, such as copies the compiler adds without metadata,
    take the path of the work they feed: from what runs a loop body or a
    call to what is in it, and from an instruction to its operands, as
    far as such links reach; what is still left takes the path of the
    nearest operand that has one, and passes it on the same way.
    Parameters and constants neither take a path nor pass one on.  What
    none reaches keeps its own path, or ""."""
    own: Dict[str, str] = {}
    refs: Dict[str, List[str]] = {}
    users: Dict[str, List[str]] = defaultdict(list)
    runs: Dict[str, List[str]] = {}
    members: Dict[str, List[str]] = defaultdict(list)
    inert = set()
    comp = None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if m is None or comp is None:
            continue
        is_root, name, rest = m.groups()
        on = _OP_NAME.search(rest)
        if on and on.group(1):
            own[name] = on.group(1)
        opcode = _OPCODE.search(" " + rest)
        if opcode and opcode.group(1) in _INERT:
            inert.add(name)
        runs[name] = _RUNS.findall(rest)
        for group in _BRANCHES.findall(rest):
            runs[name] += _REF.findall(group)
        refs[name] = _REF.findall(rest.split(", metadata=")[0])
        for x in refs[name]:
            users[x].append(name)
        if is_root:
            members[comp].insert(0, name)
        else:
            members[comp].append(name)

    path: Dict[str, str] = {}
    for name in refs:
        found = [own.get(name, "")] + [own.get(x, "") for c in runs[name]
                                       for x in members.get(c, ())]
        first = next((f for f in found if layer(f)), None)
        if first is not None and name not in inert:
            path[name] = first

    def spread(start, near) -> List[str]:
        queue, reached = deque(start), []
        while queue:
            name = queue.popleft()
            for x in near(name):
                if x in refs and x not in path and x not in inert:
                    path[x] = path[name]
                    queue.append(x)
                    reached.append(x)
        return reached

    def feeds(name: str) -> List[str]:
        return [x for c in runs[name] for x in members.get(c, ())] + refs[name]

    spread(list(path), feeds)
    while True:
        reached = spread(list(path), users.__getitem__)
        if not reached:
            break
        spread(reached, feeds)
    return {name: path.get(name, own.get(name, "")) for name in refs}


def layer(op_name: str) -> str:
    """The layer of an ``op_name`` path ("" where it names none)."""
    for part in op_name.split("/"):
        if part == "transpose(jvp(forward))":
            return "backward"
        if part in ("jvp(forward)", "forward"):
            return "forward"
        if part in ("optimizer", "clip", "guard"):
            return part
        if part.startswith("reduce_scatter_"):
            return "reduce_scatter"
    return ""


def optimizer_part(op_name: str) -> str:
    """The child scope under ``optimizer`` ("" where there is none)."""
    parts = op_name.split("/")
    if "optimizer" not in parts:
        return ""
    rest = parts[parts.index("optimizer") + 1:]
    return rest[0] if len(rest) > 1 else ""


@functools.lru_cache(maxsize=2)
def by_layer(trace, hlo_text: str) -> Dict[int, Dict[str, float]]:
    """Per chip, device ns of the window's operations by layer ("" holds the
    unattributed).  Logs, once per trace, each layer's ms per step, the
    optimizer's children and the longest unattributed operations."""
    names = op_names(hlo_text)
    out: Dict[int, Dict[str, float]] = {}
    parts: Dict[str, float] = defaultdict(float)
    loose: Dict[str, float] = defaultdict(float)
    for dev, d in trace.devices.items():
        ns: Dict[str, float] = defaultdict(float)
        for e in d.compute:
            path = names.get(e.name, "")
            where = layer(path)
            ns[where] += e.dur
            if where == "optimizer":
                parts[optimizer_part(path) or "other"] += e.dur
            elif not where:
                loose[e.label or e.name] += e.dur
        out[dev] = dict(ns)
    if names:
        _log(trace, out, parts, loose)
    return out


def _log(trace, out, parts, loose) -> None:
    per = 1e6 * trace.steps * len(out)
    total = sum(sum(ns.values()) for ns in out.values()) / per
    busy = sum(trace.busy(k) for k in out) / per
    layers = {k: sum(ns.get(k, 0.0) for ns in out.values()) / per
              for k in LAYERS + ("",)}
    print(f"[scopes] device ms per step (mean over chips): busy {busy}, "
          f"operations {total}; "
          + ", ".join(f"{k or 'unattributed'} {v}" for k, v in
                      layers.items())
          + f"; unattributed share {layers[''] / total if total else 0}",
          file=sys.stderr, flush=True)
    print("[scopes] optimizer ms per step: "
          + ", ".join(f"{k} {v / per}" for k, v in
                      sorted(parts.items(), key=lambda kv: -kv[1])),
          file=sys.stderr, flush=True)
    top = sorted(loose.items(), key=lambda kv: -kv[1])[:8]
    print("[scopes] longest unattributed, ms per step: "
          + "; ".join(f"{k[:80]} {v / per}" for k, v in top),
          file=sys.stderr, flush=True)


def layer_ms(ctx, name: str) -> Optional[float]:
    """Device ms per step of one layer, on the chip where it is longest;
    None where the step names no such scope (a program without them)."""
    per_chip = by_layer(ctx.trace, ctx.report.hlo_text)
    ns = [layers.get(name, 0.0) for layers in per_chip.values()]
    if not any(ns):
        return None
    return max(ns) / (1e6 * ctx.trace.steps)


def idle_under(trace, span: str) -> Dict[int, float]:
    """Per chip, ns of the window in which no operation ran while a host
    span named ``span`` was open; empty where the trace holds no such
    span."""
    spans = union((h.start, h.start + h.dur) for h in trace.host
                  if h.name == span)
    if not spans:
        return {}
    out = {}
    for dev, d in trace.devices.items():
        busy = union(clip(d.compute, d.lo, d.hi))
        idle, prev = [], d.lo
        for s, e in busy + [(d.hi, d.hi)]:
            if s > prev:
                idle.append((prev, s))
            prev = max(prev, e)
        out[dev] = length(intersect(idle, spans))
    return out
