"""The one traffic generator: a mix's JSON parameters -> timed requests.

Every seed gets the same multiset of sizes and inter-arrival gaps, in
another order, with other token ids: the sizes are quantiles of each stated
distribution taken on an even grid, and the seed only orders them and
draws the tokens. The order is balanced: every aligned run of ``BLOCK``
requests holds one value from each of ``BLOCK`` rank classes of each size
and of the gaps, so each part of the window, its first requests and its
last, carries the same mix whatever the seed. So two seeds offer the same
work, and run-to-run spread measures the system rather than the draw.

A mix file holds:

* ``rate_per_s``: open-loop Poisson arrivals (exponential gaps);
* ``prompt`` and ``output``: length distributions, each
  ``{"dist": "lognormal", "median", "sigma", "min", "max"}`` or
  ``{"dist": "loguniform", "min", "max"}``;
* ``shared_prefix`` (optional): ``{"count", "length": <distribution>,
  "zipf_s"}``, so each prompt is one of ``count`` system prompts, chosen
  with Zipf popularity, followed by a user turn drawn from ``prompt``.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List, Optional

import numpy as np

# Fixes which system prompt length goes with which popularity rank: a size,
# so it is the same for every seed.
_RANK_ORDER_SEED = 0
BLOCK = 8


@dataclasses.dataclass
class TimedRequest:
    due_s: float                 # seconds after the window opens
    prompt: np.ndarray           # int32 token ids
    max_new: int                 # output tokens (greedy, no stop token)
    prefix_id: Optional[int] = None   # shared system prompt, if any


def grid(n: int) -> np.ndarray:
    """n even quantile levels in (0, 1)."""
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, u: np.ndarray) -> np.ndarray:
    """Quantiles ``u`` of a length distribution, clipped, as ints."""
    lo, hi = spec["min"], spec["max"]
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        v = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "loguniform":
        v = np.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(v), lo, hi).astype(np.int64)


def balanced(rng: np.random.Generator, values: np.ndarray,
             block: int = BLOCK) -> np.ndarray:
    """``values``, sorted, in an order drawn from ``rng`` in which each
    aligned run of ``block`` entries holds at most one value from each of
    ``block`` classes of consecutive ranks."""
    n = len(values)
    klass = np.arange(n) * block // n
    run = np.empty(n, np.int64)
    for k in range(block):
        members = np.flatnonzero(klass == k)
        run[members] = rng.permutation(len(members))
    return np.asarray(values)[np.lexsort((rng.random(n), run))]


def max_prompt(mix: dict) -> int:
    sp = mix.get("shared_prefix")
    return mix["prompt"]["max"] + (sp["length"]["max"] if sp else 0)


def max_sequence(mix: dict) -> int:
    """Longest prompt plus longest output the mix can produce."""
    return max_prompt(mix) + mix["output"]["max"]


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent generators per seed and purpose (any size of seed)."""
    return np.random.default_rng([int(seed) % (1 << 64), int(seed) >> 64,
                                  stream])


def zipf_ranks(n: int, count: int, s: float) -> np.ndarray:
    """Ranks of ``n`` draws on an even grid of a Zipf(s) over ``count``."""
    w = 1.0 / np.arange(1, count + 1) ** s
    cdf = np.cumsum(w) / w.sum()
    return np.minimum(np.searchsorted(cdf, grid(n)), count - 1)


def generate(mix: dict, seed: int, seconds: float, vocab: int,
             stream: int = 0) -> List[TimedRequest]:
    """The requests due in a window of ``seconds``: ``rate x seconds`` of
    them, the last due at the window's end."""
    rate = float(mix["rate_per_s"])
    n = max(1, int(rate * seconds))
    rng = rng_for(seed, stream)
    gaps = -np.log1p(-grid(n)) / rate
    gaps *= (n / rate) / gaps.sum()          # the same span for every seed
    due = np.cumsum(balanced(rng, gaps)) * (seconds / (n / rate))
    plens = balanced(rng, lengths(mix["prompt"], grid(n)))
    outs = balanced(rng, lengths(mix["output"], grid(n)))
    sp = mix.get("shared_prefix")
    prefixes, ranks = [], None
    if sp:
        count = int(sp["count"])
        plen_by_rank = np.random.default_rng(_RANK_ORDER_SEED).permutation(
            lengths(sp["length"], grid(count)))
        prefixes = [rng.integers(0, vocab, size=int(m), dtype=np.int32)
                    for m in plen_by_rank]
        ranks = balanced(rng, zipf_ranks(n, count, float(sp["zipf_s"])))
    out = []
    for i in range(n):
        user = rng.integers(0, vocab, size=int(plens[i]), dtype=np.int32)
        pid = None
        if ranks is not None:
            pid = int(ranks[i])
            user = np.concatenate([prefixes[pid], user])
        out.append(TimedRequest(float(due[i]), user, int(outs[i]), pid))
    return out
