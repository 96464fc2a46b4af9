"""Device time per training step of the operations under the step's
``forward`` scope and not under its transpose: the loss's forward pass,
LM head and loss chunks included.  On the chip where it is longest."""
from bench.scopes import layer_ms


def read(ctx):
    return layer_ms(ctx, "forward")
