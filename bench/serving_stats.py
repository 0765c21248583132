"""End-to-end serving metrics from per-request wall-clock stamps.

All times are seconds on the host clock from the window's start. A request
is in the window when it fell due before the window closed.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Optional, Sequence


@dataclasses.dataclass
class RequestRecord:
    due_s: float
    max_new: int
    admit_s: Optional[float] = None       # end of the step that admitted it
    token_s: List[float] = dataclasses.field(default_factory=list)
    finished: bool = False
    failed: bool = False


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q``
    percent of the values at or below it."""
    if not values:
        raise ValueError("percentile of no values")
    s = sorted(values)
    return s[max(0, math.ceil(q / 100.0 * len(s)) - 1)]


def ttft_s(r: RequestRecord, window_s: float) -> float:
    """Due time to first token; a request with no first token by the
    window's end counts with the wait it has had."""
    if r.token_s:
        return r.token_s[0] - r.due_s
    return window_s - r.due_s


def tpot_s(r: RequestRecord) -> Optional[float]:
    """(last token - first token) / (tokens - 1) over the window; None for
    fewer than two tokens."""
    if len(r.token_s) < 2:
        return None
    return (r.token_s[-1] - r.token_s[0]) / (len(r.token_s) - 1)


def queue_wait_s(r: RequestRecord, window_s: float) -> float:
    return (r.admit_s if r.admit_s is not None else window_s) - r.due_s


def summarize(records: List[RequestRecord], window_s: float,
              ttft_limit_s: float, tpot_limit_s: float) -> dict:
    """The end-to-end numbers of one window."""
    tt = [ttft_s(r, window_s) for r in records]
    tp = [x for x in (tpot_s(r) for r in records) if x is not None]
    tokens = sum(len(r.token_s) for r in records)
    met = 0
    for r in records:
        if not r.finished or r.failed:
            continue
        t = tpot_s(r)
        if ttft_s(r, window_s) <= ttft_limit_s and (
                t is None or t <= tpot_limit_s):
            met += 1
    return {
        "ttft_p95_ms": 1e3 * percentile(tt, 95) if tt else None,
        "tpot_p95_ms": 1e3 * percentile(tp, 95) if tp else None,
        "output_tok_s": tokens / window_s,
        "slo_share": 100.0 * met / len(records) if records else None,
        "queue_wait_p95_ms": (1e3 * percentile(
            [queue_wait_s(r, window_s) for r in records], 95)
            if records else None),
        "requests": len(records),
        "tpot_samples": len(tp),
        "finished": sum(r.finished for r in records),
        "tokens": tokens,
    }
