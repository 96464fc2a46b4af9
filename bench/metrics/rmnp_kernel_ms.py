"""Device time of the RMNP single-pass kernel (``rmnp_rownorm_apply``)
per training step, on the chip where it is longest."""

KERNEL = "rmnp_rownorm_apply"


def read(ctx):
    per_chip = ctx.trace.kernel(KERNEL)
    if not any(evs for _, evs in per_chip.values()):
        return None
    return max(ns for ns, _ in per_chip.values()) / (1e6 * ctx.trace.steps)
