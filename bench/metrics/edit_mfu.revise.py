"""Whole edit step's share of the chip's peak: the floor of the useful
edit operations of the traced window's dispatches (counted by the
architecture's module, summed by bench/work.py) over the traced window
times the peak (%)."""


def read(ctx):
    if ctx.trace is None or ctx.peaks is None or not ctx.work["edit_flops"]:
        return None
    window_s = ctx.trace.window_ns / 1e9
    return 100.0 * ctx.work["edit_flops"] / (window_s * ctx.peaks["flops"])
