"""The observability plane end to end: tracing/metrics/profiling must be
read-only (byte-identical tokens with the plane on or off, dense and
hybrid archs), the disaggregated fleet's trace must show the full
prefill-replica -> page-migration -> decode-replica lifecycle (the PR's
acceptance trace), histogram quantiles must agree with the bench's
nearest-rank percentiles on real latencies, and the kernel profiler must
report sane dispatch summaries."""
import dataclasses
import itertools
import json

import jax
import numpy as np
import pytest

from repro.configs.registry import REDUCED
from repro.models import model as M
from repro.obs.metrics import percentile
from repro.obs.trace import LANE, Tracer
from repro.serving.router import ServingRouter
from repro.serving.scheduler import ContinuousBatchingScheduler


def _fp32(arch):
    cfg = dataclasses.replace(REDUCED[arch], dtype="float32")
    if cfg.n_routed_experts:
        cfg = dataclasses.replace(
            cfg, moe_capacity_factor=float(cfg.n_routed_experts)
            / cfg.moe_top_k)
    return cfg


_PARAMS = {}


def _params(arch):
    if arch not in _PARAMS:
        cfg = _fp32(arch)
        _PARAMS[arch] = (cfg, M.init(cfg, jax.random.PRNGKey(0)))
    return _PARAMS[arch]


def _trace(cfg, seed, n=4, p_lo=3, p_hi=26, g_hi=6):
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        plen = int(rng.randint(p_lo, p_hi + 1))
        gen = int(rng.randint(2, g_hi + 1))
        out.append((rng.randint(0, cfg.vocab_size, size=plen
                                ).astype(np.int32), gen))
    return out


def _ticking_clock():
    """A clock that moves one second on every reading."""
    n = itertools.count()
    return lambda: float(next(n))


def _run_sched(cfg, params, workload, *, observe=False, clock=None,
               prefill_budget=4, profile=None):
    """``observe`` attaches a Tracer (on ``clock``, if given) and, unless
    ``profile`` says otherwise, the kernel profiler."""
    sched = ContinuousBatchingScheduler(cfg, params, max_slots=3,
                                        page_size=8, max_seq_len=64,
                                        prefill_budget=prefill_budget)
    tracer = profiler = None
    if observe:
        tracer = Tracer(clock=clock)
        sched.set_tracer(tracer)
    if observe if profile is None else profile:
        profiler = sched.enable_profiling()
    for i, (prompt, gen) in enumerate(workload):
        sched.submit(prompt, gen, arrival_step=i // 2)
    done = sched.run()
    return done, sched, tracer, profiler


# ------------------------------------------------------- byte identity --

@pytest.mark.parametrize("arch", ("qwen3-32b", "jamba-v0.1-52b"))
def test_observed_run_emits_identical_tokens(arch):
    """The hard contract: tracing + profiling observe the scheduler and
    never steer it — chunked-prefill serving with the full plane attached
    emits exactly the tokens an unobserved run emits."""
    cfg, params = _params(arch)
    workload = _trace(cfg, seed=1)
    plain, _, _, _ = _run_sched(cfg, params, workload)
    observed, sched, tracer, profiler = _run_sched(cfg, params, workload,
                                                   observe=True)
    assert [list(r.out_tokens) for r in observed] == \
        [list(r.out_tokens) for r in plain]
    # and the plane actually recorded the run it watched
    assert {s.name for s in tracer.spans} >= {"queued", "decode"}
    assert sched.h_latency.count == len(workload)
    assert profiler.summary()["decode"]["calls"] > 0
    # the same on a wall clock, which also records every step's phases
    walled, _, tracer, _ = _run_sched(cfg, params, workload, observe=True,
                                      clock=_ticking_clock())
    assert [list(r.out_tokens) for r in walled] == \
        [list(r.out_tokens) for r in plain]
    assert {s.name for s in tracer.spans} >= {
        "queued", "prefill", "prefill_chunk", "held", "decode", "step",
        "admit", "dispatch", "wait"}


# ------------------------------------------------- wall-clock spans --

@pytest.mark.parametrize("budget", (None, 4), ids=("monolithic", "chunked"))
def test_wall_clock_spans_tile_submission_to_first_token(budget):
    """Per request, queued -> prefill -> held follow one another with no
    gap, from its submission to the end of the step() that returned its
    first token; every step phase lies inside its step."""
    cfg, params = _params("qwen3-32b")
    workload = _trace(cfg, seed=5, n=6)
    done, sched, tracer, _ = _run_sched(cfg, params, workload, observe=True,
                                        clock=_ticking_clock(),
                                        prefill_budget=budget)
    assert len(done) == len(workload)
    req = {(s.rid, s.name): s for s in tracer.spans if s.rid != LANE}
    steps = [s for s in tracer.spans if s.name == "step"]
    phases = [s for s in tracer.spans if s.rid == LANE and s.name != "step"]
    assert {p.name for p in phases} == {"admit", "dispatch", "wait"}
    for p in phases:
        assert any(st.t0 < p.t0 and p.t1 < st.t1 for st in steps), p
    for r in done:
        q, pre, held = (req[r.rid, n] for n in ("queued", "prefill", "held"))
        assert q.t0 < q.t1 == pre.t0 < pre.t1 == held.t0 < held.t1
        # held ends with the step whose wait returned the first token
        step = next(st for st in steps if st.t0 < pre.t1 < st.t1)
        assert held.t1 == step.t1
        assert pre.attrs["tokens"] == r.plen
        chunks = [s for s in tracer.spans
                  if s.rid == r.rid and s.name == "prefill_chunk"]
        if budget is None:
            assert not chunks
        else:
            # the prompt lands chunk after chunk, all inside the prefill
            assert [c.attrs["pos"] for c in chunks] == list(
                np.cumsum([0] + [c.attrs["tokens"] for c in chunks[:-1]]))
            assert sum(c.attrs["tokens"] for c in chunks) == r.plen
            assert all(pre.t0 < c.t0 < c.t1 < pre.t1 for c in chunks)


def test_a_blocked_admission_names_its_reason_on_the_queued_span():
    cfg, params = _params("qwen3-32b")
    workload = _trace(cfg, seed=6, n=6)        # 6 requests, 3 slots
    _, _, tracer, _ = _run_sched(cfg, params, workload, observe=True,
                                 clock=_ticking_clock())
    queued = [s for s in tracer.spans if s.name == "queued"]
    assert any(s.attrs.get("blocked") == "slots" for s in queued)


def test_wall_clock_tracing_adds_no_synchronisation(monkeypatch):
    """The spans read the host clock only: the scheduler blocks on the
    device and reads results back exactly as often with them as without."""
    cfg, params = _params("qwen3-32b")
    workload = _trace(cfg, seed=7)
    counts = {}
    orig_bur = jax.block_until_ready
    orig_fetch = ContinuousBatchingScheduler._fetch

    def count(key, fn):
        def wrapped(*a, **k):
            counts[key] = counts.get(key, 0) + 1
            return fn(*a, **k)
        return wrapped

    def run(traced):
        counts.clear()
        monkeypatch.setattr(jax, "block_until_ready",
                            count("block", orig_bur))
        monkeypatch.setattr(ContinuousBatchingScheduler, "_fetch",
                            count("fetch", orig_fetch))
        sched = ContinuousBatchingScheduler(cfg, params, max_slots=3,
                                            page_size=8, max_seq_len=64)
        if traced:
            sched.set_tracer(Tracer(clock=_ticking_clock()))
        for i, (prompt, gen) in enumerate(workload):
            sched.submit(prompt, gen, arrival_step=i // 2)
        sched.run()
        return dict(counts)

    bare, traced = run(False), run(True)
    assert traced.get("block", 0) <= bare.get("block", 0)
    assert traced["fetch"] == bare["fetch"] > 0


def test_without_a_tracer_a_step_makes_no_annotation(monkeypatch):
    """Untraced and unprofiled, each site tests one flag and makes
    nothing: no profiler annotation is even constructed, nor with a tracer
    on the tick clock. With a wall-clock tracer the phases stand on the
    profiler's host timeline as ``serving.<phase>``."""
    cfg, params = _params("qwen3-32b")
    workload = _trace(cfg, seed=8)

    def refuse(*a, **k):
        raise AssertionError("TraceAnnotation constructed")
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    plain, _, _, _ = _run_sched(cfg, params, workload)
    ticked, _, _, _ = _run_sched(cfg, params, workload, observe=True,
                                 profile=False)

    names = []

    class Recorder:
        def __init__(self, name, **kw):
            names.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    _run_sched(cfg, params, workload, observe=True, clock=_ticking_clock())
    assert set(names) == {"serving.step", "serving.admit",
                          "serving.dispatch", "serving.wait"}
    assert [list(r.out_tokens) for r in ticked] == \
        [list(r.out_tokens) for r in plain]


def test_a_profiled_step_enters_its_phases_without_a_tracer(monkeypatch):
    """``enable_profiling`` alone puts every step's phases on the
    profiler's host timeline, nested step > admit/dispatch/wait, and the
    tokens stay those of a bare run."""
    cfg, params = _params("qwen3-32b")
    workload = _trace(cfg, seed=9)
    # monolithic prefill, so that admission dispatches and waits too
    plain, _, _, _ = _run_sched(cfg, params, workload, prefill_budget=None)
    stack, pairs = [], []

    class Recorder:
        def __init__(self, name, **kw):
            self.name = name

        def __enter__(self):
            pairs.append((stack[-1] if stack else None, self.name))
            stack.append(self.name)
            return self

        def __exit__(self, *exc):
            assert stack.pop() == self.name
            return False
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    profiled, sched, _, _ = _run_sched(cfg, params, workload, profile=True,
                                       prefill_budget=None)
    assert sched.tracer is None and not stack
    assert set(pairs) == {(None, "serving.step"),
                          ("serving.step", "serving.admit"),
                          ("serving.step", "serving.dispatch"),
                          ("serving.step", "serving.wait"),
                          ("serving.admit", "serving.dispatch"),
                          ("serving.admit", "serving.wait")}
    assert [list(r.out_tokens) for r in profiled] == \
        [list(r.out_tokens) for r in plain]


def test_the_fleet_refuses_a_wall_clock_tracer():
    cfg, params = _params("qwen3-32b")
    router = ServingRouter(cfg, params, replicas=2, max_slots=2,
                           page_size=8, max_seq_len=64)
    with pytest.raises(ValueError, match="tick clock"):
        router.set_tracer(Tracer(clock=_ticking_clock()))


# -------------------------------------------------- disagg acceptance --

def test_disagg_trace_shows_prefill_migration_decode(tmp_path):
    """Acceptance: a --mixed --disagg style run traced to Chrome JSON
    shows, for a long-prompt request, >= 2 prefill chunks on a
    prefill-role replica, a page-migration instant, and a decode span on
    a decode-role replica — with tokens byte-identical to tracing off."""
    cfg, params = _params("qwen3-32b")

    def build():
        return ServingRouter(cfg, params, replicas=3, max_slots=3,
                             page_size=8, max_seq_len=64,
                             prefill_budget=4, disagg=1)

    rng = np.random.RandomState(3)
    chats = [(rng.randint(0, cfg.vocab_size, size=5).astype(np.int32), 3)
             for _ in range(3)]
    long_prompt = rng.randint(0, cfg.vocab_size, size=24).astype(np.int32)

    def run(router, tracer=None):
        if tracer is not None:
            router.set_tracer(tracer)
        reqs = [router.submit(p, g, arrival_step=i // 2)
                for i, (p, g) in enumerate(chats)]
        long_req = router.submit(long_prompt, 4, arrival_step=0)
        done = router.run()
        return done, long_req.rid

    plain_done, _ = run(build())
    tracer = Tracer()
    traced_done, long_rid = run(build(), tracer)
    assert sorted([r.rid] + list(r.out_tokens) for r in traced_done) == \
        sorted([r.rid] + list(r.out_tokens) for r in plain_done)

    router = build()                          # roles are deterministic
    prefill_ids = {r.replica_id for r in router.replicas.values()
                   if r.role == "prefill"}
    decode_ids = {r.replica_id for r in router.replicas.values()
                  if r.role == "decode"}

    path = tmp_path / "trace.json"
    tracer.finish_open()
    tracer.write_chrome(str(path))
    evs = json.loads(path.read_text())["traceEvents"]
    mine = [e for e in evs if e.get("args", {}).get("rid") == long_rid]

    chunks = [e for e in mine if e["name"] == "prefill_chunk"]
    assert len(chunks) >= 2                   # 24 tokens / budget 4
    assert all(e["args"]["replica"] in prefill_ids for e in chunks)
    assert [e["args"]["chunk"] for e in chunks] == list(range(len(chunks)))

    migr = [e for e in mine if e["name"] == "page_migration"]
    assert len(migr) == 1 and migr[0]["ph"] == "i"
    assert migr[0]["args"]["src"] in prefill_ids
    assert migr[0]["args"]["dst"] in decode_ids
    assert migr[0]["args"]["pages"] > 0 and migr[0]["args"]["bytes"] > 0

    dec = [e for e in mine if e["name"] == "decode" and e["ph"] == "X"]
    assert len(dec) == 1
    assert dec[0]["args"]["replica"] in decode_ids
    assert dec[0]["dur"] > 0
    # the parked span sits between the last chunk and the decode span
    parked = next(e for e in mine if e["name"] == "parked")
    assert parked["ts"] >= chunks[-1]["ts"]
    assert dec[0]["ts"] >= parked["ts"]


# ------------------------------------------------ percentile agreement --

def test_histogram_latency_agrees_with_bench_percentile():
    """The scheduler's latency histogram and the bench's retained-sample
    nearest-rank percentile answer the same question within one bucket's
    growth factor — the S1 contract that lets dashboards drop samples."""
    cfg, params = _params("qwen3-32b")
    workload = _trace(cfg, seed=2, n=8)
    done, sched, _, _ = _run_sched(cfg, params, workload)
    lats = [float(r.finish_step - r.arrival_step) for r in done]
    step = 10.0 ** 0.25                       # TICK_BUCKETS growth factor
    for q in (50, 90, 99):
        exact = percentile(lats, q)
        approx = sched.h_latency.quantile(q)
        assert exact <= approx <= exact * step, (q, exact, approx)


# ----------------------------------------------------------- profiler --

def test_profiler_summary_is_sane():
    cfg, params = _params("qwen3-32b")
    workload = _trace(cfg, seed=4)
    _, _, _, profiler = _run_sched(cfg, params, workload, observe=True)
    summary = profiler.summary()
    assert {"prefill", "decode"} <= set(summary)
    for kind, s in summary.items():
        assert s["calls"] > 0, kind
        assert s["wall_s"] > 0.0, kind
        assert s["modeled_flops"] > 0.0, kind
        assert s["modeled_bytes"] > 0.0, kind
        # the CPU backend has no published peaks: no fraction is made up
        assert s["roofline_frac"] is None, (kind, s)
