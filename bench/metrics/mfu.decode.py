"""Whole decode step: model FLOPs of the decode ticks over their host time,
from dispatch until the scheduler has their tokens (work queued ahead of
them on the device, such as a chunk, included), times the chip's peak."""
from bench import readers


def read(ctx):
    return readers.mfu_of(ctx, readers.DECODE)
