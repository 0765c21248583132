"""One cell of the serving benchmark: set-up, the timed window, the check.

The system under test is ``repro.serving.scheduler.ContinuousBatchingScheduler``
built with the cell's configuration, weights, slots, pool and prefill budget,
driven by ``step()`` as its own ``run()`` drives it. Requests are submitted
when they fall due on the host clock (open loop) and every token is stamped
when the ``step()`` that produced it returns: ``step()`` ends by copying the
tokens to the host, so the stamp follows the device.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
import time
from typing import Any, Dict, List, Optional

import numpy as np

from bench import serving_stats, traffic
from bench.reference import arch_of

ROOT = pathlib.Path(__file__).resolve().parents[1]
PAGE_SIZE = 16


@dataclasses.dataclass
class Dispatch:
    """One program dispatch of the scheduler: when it was issued and until
    the host went on past its result."""
    kind: str            # decode | prefill | prefill_suffix
    t0: float
    t1: Optional[float]
    tokens: int          # decode: k * rows; prefill: live prompt tokens
    ctx: int             # decode: live lengths summed; prefill: start
    k: int


class DispatchLog:
    """The scheduler's profiling hook (``enable_profiling``): logs each
    dispatch's kind and size and when it was issued, and waits for nothing,
    so the traced run keeps the schedule of an untraced one. The scheduler
    itself waits for each prefill's first token and each decode scan's
    tokens, so a dispatch lasts, on the host, until the next is issued or
    its ``step()`` returns (``step_done``)."""

    def __init__(self, clock):
        self.clock = clock
        self.rows: List[Dispatch] = []
        self._open = 0

    def timed(self, kind, fn, *args, tokens=0, ctx_tokens=0, **kw):
        t = self.clock()
        self._close(t)
        self.rows.append(Dispatch(kind, t, None, int(tokens),
                                  int(ctx_tokens), int(kw.get("k", 1))))
        return fn(*args, **kw)

    def step_done(self):
        self._close(self.clock())
        self._open = len(self.rows)

    def _close(self, t):
        if len(self.rows) > self._open and self.rows[-1].t1 is None:
            self.rows[-1].t1 = t


class CompileCount:
    """Backend compiles and traces, from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.compiles = self.traces = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration
        elif event == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1


def load_json(rel: str) -> dict:
    return json.loads((ROOT / rel).read_text())


def program_config(conf: dict):
    """The program's ModelConfig for a configuration file, checked against
    the published widths the reference reads."""
    from repro.configs.registry import get_arch
    prog = dict(conf["program"])
    cfg = dataclasses.replace(get_arch(prog.pop("arch")), **prog)
    a = arch_of(conf)
    got = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim,
           cfg.d_ff, cfg.vocab_size, cfg.n_layers, cfg.qkv_bias, cfg.qk_norm)
    want = (a.d, a.heads, a.kv_heads, a.head_dim, a.ff, a.vocab, a.layers,
            a.qkv_bias, a.qk_norm)
    if got != want:
        raise ValueError(f"program config {got} differs from the published "
                         f"configuration {want}")
    return cfg


def param_shapes(cfg) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    from repro.models.model import schema

    def walk(t):
        if isinstance(t, dict):
            return {k: walk(v) for k, v in t.items()}
        return jax.ShapeDtypeStruct(tuple(t.shape), jnp.bfloat16)
    return walk(schema(cfg))


def use_cache() -> str:
    """The program's fixed compile-cache directory, caching every program
    (a small one missed would be compiled again in every run)."""
    import jax
    from repro.launch.compile_cache import use_compile_cache
    path = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def setup(conf: dict, mix: dict, seed: int):
    """Weights from the seed, the scheduler, and its warm-up:
    ``(params, sched, weights_s, warm_up_s, warm_up_requests)``."""
    import jax
    from bench import weights
    cfg = program_config(conf)
    t = time.perf_counter()
    params = jax.block_until_ready(weights.make(param_shapes(cfg), seed))
    t_weights = time.perf_counter() - t
    t = time.perf_counter()
    sched = build_scheduler(cfg, params, mix)
    warm = warm_up(sched, mix, seed, arch_of(conf).vocab)
    if len(sched.index):
        raise RuntimeError("prefix index not empty after warm-up")
    return params, sched, t_weights, time.perf_counter() - t, warm


def build_scheduler(cfg, params, mix: dict):
    from repro.serving.scheduler import ContinuousBatchingScheduler
    sv = mix["serve"]
    return ContinuousBatchingScheduler(
        cfg, params, max_slots=sv["max_slots"], page_size=PAGE_SIZE,
        num_pages=sv["pool_tokens"] // PAGE_SIZE + 1,
        max_seq_len=traffic.max_sequence(mix),
        prefill_budget=sv.get("prefill_budget"))


def _covering(buckets, lo: int, hi: int) -> List[int]:
    """One length per prefill bucket that lengths in [lo, hi] land in."""
    out, prev = [], 0
    for b in list(buckets) + [None]:
        top = hi if b is None else min(b, hi)
        if top >= max(lo, prev + 1):
            out.append(top)
        if b is None or b >= hi:
            break
        prev = b
    return out


def warm_up(sched, mix: dict, seed: int, vocab: int) -> int:
    """Compile every program shape the mix can reach, one request (or one
    sharing pair) at a time, on tokens of another stream of the seed.
    Returns the requests served."""
    rng = traffic.rng_for(seed, 1)
    sv = mix["serve"]
    fuse = sv["max_fuse"]
    tok = lambda n: rng.integers(0, vocab, size=n, dtype=np.int32)
    sp = mix.get("shared_prefix")
    p_lo = mix["prompt"]["min"] + (sp["length"]["min"] if sp else 0)
    p_hi = traffic.max_prompt(mix)
    budget = sv.get("prefill_budget")
    groups = []
    if budget is None:
        groups += [[(tok(n), 2)] for n in _covering(sched.buckets, p_lo, p_hi)]
    else:
        groups += [[(tok(budget + n), 2)]
                   for n in _covering(sched.buckets, 1, budget)]
    if sp:
        # a prefix hit whose match ends inside a page: copy-on-write fork,
        # then the suffix prefill at every bucket a user turn reaches
        # (the index keeps a prompt's last partial page, so the second
        # prompt extends the first); a hit's suffix is the user turn plus
        # up to a page less one of the system prompt
        shared = tok(sp["length"]["min"] + PAGE_SIZE // 2 + 1)
        for n in _covering(sched.buckets, mix["prompt"]["min"],
                           mix["prompt"]["max"] + PAGE_SIZE - 1):
            groups.append([(shared, 2),
                           (np.concatenate([shared, tok(n)]), 2)])
    # decode: alone, a request of 2 * max_fuse tokens meets every
    # power-of-two fusion depth on its way down
    groups.append([(tok(p_lo), 2 * fuse)])
    served = 0
    for g in groups:
        for prompt, n in g:
            sched.submit(prompt, n, arrival_step=sched.step_idx)
        sched.run(max_fuse=fuse)
        served += len(g)
    return served


@dataclasses.dataclass
class Window:
    records: List[serving_stats.RequestRecord]
    requests: List[Any]            # the scheduler's Request objects
    window_s: float
    lag_s: List[float]             # submit time - due time
    steps: int
    trace_span: Optional[tuple]    # (start, stop) on the window clock
    t0: float                      # the window clock's origin on ``clock``


def serve_window(sched, reqs: List[traffic.TimedRequest], seconds: float,
                 max_fuse: int, clock, trace_dir: Optional[str] = None,
                 trace_at: float = 0.0, trace_s: float = 0.0,
                 dispatches: Optional[DispatchLog] = None,
                 sleep=time.sleep) -> Window:
    """Offer ``reqs`` open loop for ``seconds`` and stamp every token.

    With ``trace_dir`` the JAX profiler records from ``trace_at`` for
    ``trace_s`` seconds of the window, starting and stopping between steps.
    ``dispatches``, the scheduler's profiling hook, is told where each step
    ends. ``sleep`` waits while nothing is due, on ``clock``'s time.
    """
    import jax
    recs = [serving_stats.RequestRecord(r.due_s, r.max_new) for r in reqs]
    live: Dict[int, list] = {}
    handles: List[Any] = [None] * len(reqs)
    lag, nxt, steps = [], 0, 0
    tracing, span = False, None
    ann = None
    # host annotations, but no tracing of every Python call, which would
    # slow the host and so change the schedule the trace reads
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    t0 = clock()
    now = 0.0
    while now < seconds:
        if trace_dir and not tracing and span is None and now >= trace_at:
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            ann = jax.profiler.TraceAnnotation("bench.traced_window")
            ann.__enter__()
            tracing, span = True, (clock() - t0, None)
        if tracing and now >= span[0] + trace_s:
            ann.__exit__(None, None, None)
            span = (span[0], clock() - t0)
            jax.profiler.stop_trace()
            tracing = False
        while nxt < len(reqs) and reqs[nxt].due_s <= now:
            r = reqs[nxt]
            handles[nxt] = sched.submit(r.prompt, r.max_new,
                                        arrival_step=sched.step_idx)
            live[nxt] = [handles[nxt], 0]
            lag.append(now - r.due_s)
            nxt += 1
        if not sched.waiting and sched.num_active == 0:
            due = reqs[nxt].due_s if nxt < len(reqs) else seconds
            with jax.profiler.TraceAnnotation("bench.idle"):
                sleep(max(0.0, min(due, seconds) - now))
            now = clock() - t0
            continue
        with jax.profiler.TraceAnnotation("bench.step"):
            sched.step(max_fuse=max_fuse)
        if dispatches is not None:
            dispatches.step_done()
        steps += 1
        t = clock() - t0
        for i in list(live):
            h, seen = live[i]
            n = len(h.out_tokens)
            if n > seen:
                recs[i].token_s.extend([t] * (n - seen))
                live[i][1] = n
            if recs[i].admit_s is None and h.admit_step is not None:
                recs[i].admit_s = t
            if h.finish_step is not None:
                recs[i].finished = True
                del live[i]
        now = clock() - t0
    if tracing:
        ann.__exit__(None, None, None)
        span = (span[0], clock() - t0)
        jax.profiler.stop_trace()
    # every request due before the close is in the window, submitted or not
    n_due = max(nxt, sum(r.due_s <= now for r in reqs))
    return Window(recs[:n_due], handles[:n_due], now, lag, steps, span, t0)


def pick_sample(requests: List[Any], seed: int, served_tokens: int
                ) -> List[int]:
    """Finished requests to check: the longest (prompt plus output), then
    others in an order drawn from the seed, until ``served_tokens`` served
    tokens are covered."""
    done = [i for i, r in enumerate(requests)
            if r is not None and r.finish_step is not None]
    if not done:
        return []
    longest = max(done, key=lambda i: (requests[i].plen
                                       + len(requests[i].out_tokens), -i))
    order = [longest] + [i for i in traffic.rng_for(seed, 2).permutation(done)
                         if i != longest]
    out, total = [], 0
    for i in order:
        if total >= served_tokens:
            break
        out.append(int(i))
        total += len(requests[i].out_tokens)
    return out
