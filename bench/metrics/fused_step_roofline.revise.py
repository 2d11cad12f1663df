"""Share of its roofline that the fused_step kernel reached in the traced
window: the least time the chip could take for the floor of the kernel's
operations and bytes in the window's dispatches (counted by the
architecture's module, summed by bench/work.py), over the kernel's device
time (%)."""
from readers import FUSED_KERNEL, kernel_ns


def read(ctx):
    t = kernel_ns(ctx, FUSED_KERNEL)
    if not t or ctx.peaks is None or not ctx.work["kernel_flops"]:
        return None
    least = max(ctx.work["kernel_flops"] / ctx.peaks["flops"],
                ctx.work["kernel_bytes"] / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (t / 1e9)
