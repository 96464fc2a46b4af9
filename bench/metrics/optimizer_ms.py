"""Device time per training step of the operations under the step's
``optimizer`` scope: the RMNP kernel launches, the bucket gather and
scatter copies, the buckets the kernel's plan sends to XLA and the AdamW
sweep.  On the chip where it is longest."""
from bench.scopes import layer_ms


def read(ctx):
    return layer_ms(ctx, "optimizer")
