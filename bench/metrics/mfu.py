"""Whole window: model FLOPs of every token computed (prompt tokens not
served from cache, and output tokens) over the window times the peak."""
from bench import readers


def read(ctx):
    if ctx.peaks is None or not ctx.dispatches:
        return None
    flops = sum(readers.work(ctx, d)[0] for d in ctx.dispatches)
    return 100.0 * flops / (ctx.window_s * ctx.peaks.bf16_flops)
