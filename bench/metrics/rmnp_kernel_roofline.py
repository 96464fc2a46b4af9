"""The RMNP single-pass kernel's share of its roofline: the least time
its launches could take, the bytes each reads and writes (from the
operand and result shapes of the compiled program) over the chip's HBM
bandwidth, divided by their measured device time.  The kernel does a few
operations per 16 bytes moved, so the bandwidth bound is the larger one.
On the chip where the kernel is slowest; nothing where a traced launch
matches no launch of the compiled step, whose bytes are then unknown."""
from bench.flops import kernel_launches, peaks

KERNEL = "rmnp_rownorm_apply"


def read(ctx):
    launches = {ln.name: ln.bytes
                for ln in kernel_launches(ctx.report.hlo_text, KERNEL)}
    bandwidth = peaks(ctx.device_kind)["hbm_bytes_per_s"]
    shares = []
    for ns, events in ctx.trace.kernel(KERNEL).values():
        if not events or any(e.name not in launches for e in events):
            return None
        least = sum(launches[e.name] for e in events) / bandwidth
        shares.append(100.0 * least / (ns / 1e9))
    return min(shares) if shares else None
