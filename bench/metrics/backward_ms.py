"""Device time per training step of the operations under
``transpose(jvp(forward))``: the gradient of the loss, with the forward
recomputed inside it under remat.  On the chip where it is longest."""
from bench.scopes import layer_ms


def read(ctx):
    return layer_ms(ctx, "backward")
