"""Whole prefill step: model FLOPs of the prefill dispatches over their
host time, from dispatch until the scheduler has their first token and
issues its next dispatch, times the chip's peak."""
from bench import readers


def read(ctx):
    return readers.mfu_of(ctx, readers.PREFILL)
