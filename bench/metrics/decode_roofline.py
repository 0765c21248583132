"""Decode program: least time of the traced decode ticks (the larger of
FLOPs over peak and bytes over HBM bandwidth, live tokens only) over their
device time."""
from bench import readers


def read(ctx):
    return readers.roofline_share(ctx, readers.DECODE, "decode")
