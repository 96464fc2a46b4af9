"""Compile one small benchmark cell's training step through ``train()``
and summarise how its compiled instructions fall into the program's named
scopes.  In a test's process, or on four CPU devices:

    python tests/bench/_scopes_worker.py <cell> <devices>
"""
import os
import re
import sys
from collections import Counter
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
CELLS = ROOT / "tests" / "bench" / "cells"
# instructions that run no operation of their own on the device
TRIVIAL = {"parameter", "constant", "get-tuple-element", "tuple", "bitcast",
           "while", "conditional", "call", "opt-barrier", "after-all"}
_COMPUTATION = re.compile(r"^(ENTRY\s+)?%([\w.-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%([\w.-]+) = .*?\s([a-z][\w-]*)\(")
_LOOP = re.compile(r"\b(?:body|condition)=%([\w.-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")


def device_instructions(hlo_text):
    """``(name, opcode)`` of the instructions a device trace shows as
    operations: those of the entry computation and of the loops and
    branches it runs, fused computations and reducers left out."""
    bodies, entry, comp = {}, None, None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(2)
            bodies[comp] = []
            entry = comp if m.group(1) else entry
        elif comp is not None and line.startswith(" "):
            bodies[comp].append(line)
    seen, todo, out = set(), [entry], []
    while todo:
        comp = todo.pop()
        if comp in seen:
            continue
        seen.add(comp)
        for line in bodies[comp]:
            todo += _LOOP.findall(line)
            for group in _BRANCHES.findall(line):
                todo += [x.strip().lstrip("%") for x in group.split(",")]
            m = _INSTRUCTION.match(line)
            if m and m.group(2) not in TRIVIAL:
                out.append(m.groups())
    return out


def step_hlo(cell_name, n_devices):
    import jax

    import repro.launch.train as launch
    from bench import harness
    from repro.configs.base import ModelConfig

    bench, harness.BENCH = harness.BENCH, CELLS
    try:
        cell = harness.load_cell(cell_name)
    finally:
        harness.BENCH = bench
    rep = launch.StepReport()
    launch.train(ModelConfig(**cell["config_spec"]["model"]), steps=1,
                 devices=jax.devices()[:n_devices], report=rep,
                 **dict(harness.train_kwargs(cell), log_every=0))
    return rep.hlo_text


def summary(hlo_text):
    """Own ``op_name`` scope parts, and the layers the join gives the
    device operations (the unattributed ones by name)."""
    from bench.scopes import layer, op_names

    own = set()
    for path in re.findall(r'op_name="([^"]*)"', hlo_text):
        own.update(path.split("/"))
    names = op_names(hlo_text)
    ops = device_instructions(hlo_text)
    layers = Counter(layer(names.get(n, "")) for n, _ in ops)
    loose = [n for n, _ in ops if not layer(names.get(n, ""))]
    return {"parts": sorted(own), "layers": dict(layers), "loose": loose,
            "ops": len(ops)}


if __name__ == "__main__":
    n = int(sys.argv[2])
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + f" --xla_force_host_platform_device_count={n}")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import json

    print(json.dumps(summary(step_hlo(sys.argv[1], n))))
