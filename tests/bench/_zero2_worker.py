"""One tiny ZeRO-2 run of the benchmark harness on four CPU devices, with
an optional fault planted in the program's exchange; prints the result.

    python tests/bench/_zero2_worker.py [sound|exchange]
"""
import os
import sys
import time
from pathlib import Path

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import json  # noqa: E402

import jax  # noqa: E402


def _local_chunk(chunks, axis_name):
    """Each rank keeps its own gradient chunk: no reduce-scatter."""
    idx = jax.lax.axis_index(axis_name)
    return jax.lax.dynamic_index_in_dim(chunks.astype("float32"), idx, 0,
                                        keepdims=False)


def main(mode: str) -> None:
    from bench import harness
    from repro.train import pipeline

    harness.BENCH = ROOT / "tests" / "bench" / "cells"
    if mode == "exchange":
        pipeline.exact_reduce_scatter = _local_chunk
        pipeline.exact_mean = lambda grads, axis_name, skip=None: grads
    r = harness.run("tiny.zero2-fp32.b8s32", 2147483702, 2.0, False,
                    t_start=time.perf_counter(), devices=jax.devices()[:4],
                    log=lambda m: None)
    print(json.dumps(r))


if __name__ == "__main__":
    main(sys.argv[1])
