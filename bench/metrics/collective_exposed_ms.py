"""Device time per training step in which a collective (all-reduce,
reduce-scatter, all-gather, all-to-all) runs and no other operation does
on the same chip: the exchange compute did not hide.  On the chip where
it is longest; cells on one chip have none to read."""


def read(ctx):
    if ctx.chips < 2:
        return None
    exposed = [ctx.trace.collective_exposed(dev)[1]
               for dev in ctx.trace.devices]
    return max(exposed) / (1e6 * ctx.trace.steps)
