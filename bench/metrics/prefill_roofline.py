"""Prefill (chunk) program: least time of the traced prefill dispatches
over their device time."""
from bench import readers


def read(ctx):
    return readers.roofline_share(ctx, readers.PREFILL, "prefill")
