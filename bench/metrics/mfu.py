"""Model FLOP utilisation of the whole training step: the window's
trained tokens per second times the model FLOPs of a token, over the
chips' bf16 peak.  Recomputation is not counted, so remat shows as a
lower share."""
from bench.flops import flops_per_token, peaks


def read(ctx):
    achieved = ctx.tokens_per_s * flops_per_token(ctx.model, ctx.seq)
    return 100.0 * achieved / (ctx.chips
                               * peaks(ctx.device_kind)["bf16_flops"])
