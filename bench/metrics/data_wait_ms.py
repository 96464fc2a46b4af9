"""Device idle time per training step while the host was inside the
training loop's ``data`` span (drawing the next batch and copying it to
the device), on the trace's shared clock.  On the chip where it is
longest; nothing where the trace holds no such span."""
from bench.scopes import idle_under


def read(ctx):
    per_chip = idle_under(ctx.trace, "data")
    if not per_chip:
        return None
    return max(per_chip.values()) / (1e6 * ctx.trace.steps)
