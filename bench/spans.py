"""The scheduler's step phases in a traced window, for the readers that use
them.

A traced run enables the scheduler's profiling hook, and a profiled
scheduler enters every ``step()`` and its phases on the JAX profiler's host
timeline: ``serving.step``, with ``serving.admit`` (admission's host work),
``serving.dispatch`` (a program call, its argument upload included) and
``serving.wait`` (the host blocked on a device result) inside it. They
share their clock with the device planes of the same trace. A program that
enters none gives no events, and the readers of them read nothing.
"""
from __future__ import annotations

import pathlib
from typing import List, Optional, Tuple

from bench import trace_reduce

# where ``bench/run.py`` has the profiler write the traced window
TRACE_DIR = pathlib.Path(__file__).resolve().parents[1] / ".bench_trace"
SERVING = "serving."
STEP, WAIT = SERVING + "step", SERVING + "wait"

Event = Tuple[str, float, float]               # (name, start, end), s


def serving_events(ctx) -> List[Event]:
    """The ``serving.*`` events of the traced window's profiler trace that
    start inside its span, read once per run and kept on ``ctx``; empty
    where there is no trace or no such event."""
    got = getattr(ctx, "serving_events", None)
    if got is None:
        path = trace_reduce.find_xplane(str(TRACE_DIR))
        got = []
        if path is not None:
            from jax.profiler import ProfileData
            got = events_of(ProfileData.from_file(path).planes)
        ctx.serving_events = got
    return got


def events_of(planes) -> List[Event]:
    """The ``serving.*`` host events of a trace's planes (as
    ``trace_reduce.reduce_planes`` takes them) that start inside the
    traced span, in time order."""
    span, out = None, []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                s = e.start_ns * 1e-9
                ev = (e.name, s, s + e.duration_ns * 1e-9)
                if e.name == trace_reduce.WINDOW:
                    span = ev[1:]
                elif e.name.startswith(SERVING):
                    out.append(ev)
    if span is None:
        return []
    return sorted((ev for ev in out if span[0] <= ev[1] <= span[1]),
                  key=lambda ev: (ev[1], -ev[2]))


def host_ms_per_step(events: List[Event]) -> Optional[float]:
    """(time in ``serving.step`` - time in ``serving.wait``) per step, in
    ms: the scheduler's own host time per step."""
    steps = [e - s for name, s, e in events if name == STEP]
    if not steps:
        return None
    waits = sum(e - s for name, s, e in events if name == WAIT)
    return 1e3 * (sum(steps) - waits) / len(steps)


def host_idle_share(red, events: List[Event]) -> Optional[float]:
    """Share of ``red``'s traced span in which a device ran no operation
    while the innermost open ``serving.*`` event was not ``serving.wait``,
    averaged over devices as ``red.idle_share`` is, and so at most it.
    None without ``serving.*`` events."""
    if not events:
        return None
    lo, hi = red.span
    host = [(max(s, lo), min(e, hi)) for name, s, e in innermost(events)
            if name != WAIT and min(e, hi) > max(s, lo)]
    total = sum(e - s for s, e in host)
    idle = sum(total - overlap(host, d.busy) for d in red.devices)
    return idle / len(red.devices) / red.span_s


def innermost(events: List[Event]) -> List[Event]:
    """The timeline of nested events (one thread's annotations) cut into
    pieces, each named after the innermost event open in it, in time
    order."""
    out, stack, t = [], [], 0.0               # stack: (name, end)
    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            top, end = stack.pop()
            out.append((top, t, end))
            t = end
        if stack:
            out.append((stack[-1][0], t, s))
        stack.append((name, e))
        t = s
    while stack:
        top, end = stack.pop()
        out.append((top, t, end))
        t = end
    return [p for p in out if p[2] > p[1]]


def overlap(a, b) -> float:
    """Seconds two sorted lists of disjoint intervals share."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        tot += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot
