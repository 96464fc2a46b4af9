"""Executables built or loaded from the persistent cache while the
window's steps ran (``StepReport.compiles``, from JAX's compile events):
0 in a steady state.  The window's first step is left out: the benchmark
compiles its own readers of the state in it, before the window opens."""
from bench.harness import SETUP_STEPS


def read(ctx):
    compiles = getattr(ctx.report, "compiles", None)
    if compiles is None:
        return None
    return sum(n for step, n in compiles if step > SETUP_STEPS)
