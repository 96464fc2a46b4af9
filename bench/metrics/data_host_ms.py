"""Host time per window step in the training loop's ``data`` span
(``StepReport.spans``): the loader's next batch and its copy to the
device.  The window's first step is left out: its span also holds the
benchmark's own wait for the set-up steps, which opens the window."""
from bench.harness import SETUP_STEPS


def read(ctx):
    spans = [(t0, t1) for name, _, step, t0, t1
             in getattr(ctx.report, "spans", None) or []
             if name == "data" and step > SETUP_STEPS]
    if not spans:
        return None
    return sum(t1 - t0 for t0, t1 in spans) / (1e6 * len(spans))
