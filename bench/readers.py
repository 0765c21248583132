"""Helpers the per-layer metric readers share (``bench/metrics/*.py``).

A reader gets the run's context and returns a number, or None when the run
gave it nothing to read. Programs are told apart on the device by the name
of their jitted function. The prefill family (whole prompts, chunks and
suffixes) is ``fn``. The decode program is ``_decode_multi``, jitted as a
``functools.partial``, which JAX 0.9 names ``_unknown``: on a TPU v5e its
runs are ``jit__unknown(<fingerprint>)``, as many as the decode dispatches
logged (chip traces of both cells). The readers pair runs and dispatches
one to one, so a second program of either name reads nothing, not a
wrong number.
"""
from __future__ import annotations

import sys
from typing import List, Optional, Tuple

from bench import roofline

DECODE = ("decode",)
PREFILL = ("prefill", "prefill_suffix")
PROGRAM = {"decode": r"^jit__unknown\b", "prefill": r"^jit_fn\b"}


def work(ctx, d) -> Tuple[int, int]:
    """(FLOPs, bytes) of one logged dispatch."""
    if d.kind in DECODE:
        return roofline.decode_work(ctx.arch, d.k, d.tokens // d.k, d.ctx)
    return roofline.prefill_work(ctx.arch, d.tokens, d.ctx)


def of_kind(ctx, kinds) -> list:
    return [d for d in ctx.dispatches if d.kind in kinds]


def traced(ctx, kinds) -> list:
    """Dispatches issued inside the traced span. The trace starts and stops
    between steps, and each step ends by waiting for its results, so the
    device ran exactly these there. The span is on the window's clock,
    whose origin is ``t_open``."""
    if ctx.trace_span is None or ctx.trace_span[1] is None:
        return []
    lo, hi = (ctx.t_open + x for x in ctx.trace_span)
    return [d for d in of_kind(ctx, kinds) if lo <= d.t0 <= hi]


def device_runs(ctx, program: str) -> Optional[List[Tuple[float, float]]]:
    if ctx.trace is None:
        return None
    return ctx.trace.module_runs(PROGRAM[program])


def matched(ctx, kinds, program: str):
    """(dispatches, device seconds) of the traced runs of ``program``, or
    None when the trace and the log do not pair one to one."""
    runs = device_runs(ctx, program)
    ds = traced(ctx, kinds)
    if not runs or len(runs) != len(ds):
        if ctx.trace is not None:
            print(f"[trace] {program}: {len(runs or [])} device runs, "
                  f"{len(ds)} dispatches logged", file=sys.stderr)
        return None
    return ds, sum(r[1] for r in runs)


def roofline_share(ctx, kinds, program: str) -> Optional[float]:
    m = matched(ctx, kinds, program)
    if m is None or ctx.peaks is None:
        return None
    ds, dev_s = m
    least = sum(roofline.least_time(*work(ctx, d), ctx.peaks) for d in ds)
    return 100.0 * least / dev_s


def mfu_of(ctx, kinds) -> Optional[float]:
    ds = of_kind(ctx, kinds)
    if not ds or ctx.peaks is None:
        return None
    flops = sum(work(ctx, d)[0] for d in ds)
    wall = sum(d.t1 - d.t0 for d in ds)
    return 100.0 * flops / (wall * ctx.peaks.bf16_flops)
