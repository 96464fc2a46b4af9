"""Readings that set a cell's limits: the control and the planted faults,
at the cell's own size, on the chips the cell asks for.

    python3 bench/control.py --workload <cell> --seeds <n> <n> <n> ...

For each seed it runs the plain reference over the compared steps, then,
in the program's place and compared with it as the benchmark compares the
program: the control (the reference with float8 matrix products, the
precision step below the configuration's bfloat16) and the faults a
training cell can have: ``half_batch`` (half of the rows left out, the
mean taken over the rest) and, on several chips, ``one_chip`` (the
exchange between chips left out: the gradient of the first chip's rows
alone).  A step that returns its state unchanged reads 1 on both gap
numbers by their definition and needs no run.  Prints one JSON line per
reading.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")

    import jax

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    from bench import check, harness

    cell = harness.load_cell(args.workload)
    devices = harness.tpu_devices(cell["chips"])
    steps = harness.SETUP_STEPS + harness.window_steps(
        cell, json.loads((ROOT / "BENCHMARK.json").read_text())[
            "run_seconds"])
    kinds = {"fp8": {"precision": "fp8"},
             "half_batch": {"fault": "half_batch"}}
    if cell["chips"] > 1:
        kinds["one_chip"] = {"fault": "one_chip"}
    for seed in args.seeds:
        ref = harness.reference_readings(cell, seed, steps, devices)
        for kind, kw in kinds.items():
            got = harness.reference_readings(cell, seed, steps, devices, **kw)
            _, numbers = check.compare(got, ref, cell["limits"])
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "kind": kind,
                              "numbers": {k: n["value"]
                                          for k, n in numbers.items()}}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
