"""Share of the steady window in which no operation ran on the chip,
from the device trace (mean over the cell's chips)."""


def read(ctx):
    return 100.0 * ctx.trace.idle_share()
