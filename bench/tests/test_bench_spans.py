"""The scheduler's spans in the benchmark: a tiny cell on the CPU served
with a wall-clock Tracer splits its times to first token into their parts;
a traced tiny cell reads the step annotations off the profiler's host
plane; the readers' arithmetic on hand-made planes; and a trace without
the annotations, as a program that enters none gives, reads nothing."""
import gzip
import json
import os
import types

import pytest

from bench import harness, run, spans
from bench import trace_reduce as tr
from bench import tracing_cost
from bench.tests.test_bench_run import DATA, ROOT, fake_clock
from repro.obs.trace import LANE, Tracer

NEW = ("host_ms_per_step", "device_idle_share.host")
TINY = [("tiny-qwen3.json", "tiny-chat.json"),
        ("tiny-glm.json", "tiny-docs.json")]


def tiny_cell(names):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return {"workloads": [{"name": "tiny", "config": "x", "traffic": "x",
                           "chips": 1}], "end_to_end": [],
            "per_layer": [dict(m, workloads=["tiny"])
                          for m in bench["per_layer"] if m["name"] in names]}


@pytest.mark.parametrize("conf,mix", TINY, ids=("monolithic", "chunked"))
def test_a_traced_tiny_cell_reads_the_spans_and_its_ttft_adds_up(conf, mix):
    win, summ, tracer = tracing_cost.serve(
        tiny_cell(()), "tiny", 2**32 + 11, 0.5, True,
        conf=harness.load_json(DATA + conf), mix=harness.load_json(DATA + mix),
        **fake_clock())
    got = tracing_cost.readings(win, summ, tracer)
    for k in ("queued_p95_ms", "first_token_held_p95_ms",
              "host_ms_per_step"):
        assert got[k] > 0, k
    rows = tracing_cost.ttft_split(win, tracer)
    assert len(rows) >= 5 and got["split_requests"] == len(rows)
    for r in rows:
        # each clock reading moves the fake clock by 1 ms: the parts leave
        # out the harness's one reading after step() returns, no more
        assert r["gap"] == pytest.approx(1.0), r
        assert min(r[k] for k in ("lag", "queued", "prefill", "held")) >= 0
    assert got["split_gap_max_ms"] == pytest.approx(1.0)


@pytest.mark.parametrize("conf,mix", TINY, ids=("monolithic", "chunked"))
def test_a_traced_tiny_cell_reads_its_steps_off_the_profiler(conf, mix):
    out = run.run_cell(
        tiny_cell(NEW), "tiny", 2**32 + 12, 0.5, True, require_chip=False,
        conf=harness.load_json(DATA + conf), mix=harness.load_json(DATA + mix),
        **fake_clock())
    assert out["correct"] is True
    # the CPU trace has no device plane, so the idle share reads nothing
    assert set(out["metrics"]) == {"host_ms_per_step"}
    assert out["metrics"]["host_ms_per_step"]["value"] > 0


def test_the_readers_look_where_the_run_writes_its_trace():
    assert spans.TRACE_DIR == run.TRACE_DIR


def test_the_tracers_readings_arithmetic():
    t = Tracer(clock=lambda: 0.0)
    for rid, (q, h) in enumerate([(0.010, 0.001), (0.030, 0.004),
                                  (0.020, 0.002)]):
        t.span("queued", rid, 1.0, 1.0 + q)
        t.span("held", rid, 2.0, 2.0 + h)
    t.span("step", LANE, 0.0, 0.050)
    t.span("wait", LANE, 0.010, 0.040)
    t.span("step", LANE, 0.050, 0.060)
    t.span("wait", LANE, 0.052, 0.058)
    # nearest rank: the 95th of three is the largest
    assert tracing_cost.p95_ms(t, "queued") == pytest.approx(30.0)
    assert tracing_cost.p95_ms(t, "held") == pytest.approx(4.0)
    # (60 ms in steps - 36 ms waiting) over 2 steps
    assert tracing_cost.host_ms_per_step(t) == pytest.approx(12.0)


def plane(name, line, events):
    ev = lambda n, s, e: types.SimpleNamespace(
        name=n, start_ns=int(round(s * 1e9)),
        duration_ns=int(round((e - s) * 1e9)))
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=line, events=[ev(*e) for e in events])])


def serving_planes():
    # device ops at [1, 2), [3, 6) and [8, 9) of the traced span [0, 10);
    # inside bench.step, the scheduler's step holds an admit (with a
    # dispatch in it) and two waits on the device; a step before the span
    # is no part of it
    device = plane("/device:TPU:0", tr.OPS,
                   [("fusion", s, e) for s, e in [(1, 2), (3, 6), (8, 9)]])
    host = plane("/host:CPU", "python", [
        ("serving.step", -2.0, -1.0), ("serving.wait", -1.8, -1.2),
        (tr.WINDOW, 0.0, 10.0), ("bench.step", 0.5, 9.5),
        ("serving.step", 0.5, 9.5), ("serving.admit", 0.5, 2.5),
        ("serving.dispatch", 1.5, 2.2), ("serving.wait", 2.5, 6.5),
        ("serving.wait", 7.5, 9.0)])
    return [device, host]


def test_innermost_cuts_nested_annotations_into_pieces():
    events = [("a", 0.0, 10.0), ("b", 2.0, 5.0), ("c", 6.0, 8.0),
              ("d", 12.0, 13.0)]
    assert spans.innermost(events) == [
        ("a", 0.0, 2.0), ("b", 2.0, 5.0), ("a", 5.0, 6.0), ("c", 6.0, 8.0),
        ("a", 8.0, 10.0), ("d", 12.0, 13.0)]


def test_host_ms_per_step_takes_the_steps_in_the_span_less_their_waits():
    events = spans.events_of(serving_planes())
    assert [e[0] for e in events] == [
        "serving.step", "serving.admit", "serving.dispatch", "serving.wait",
        "serving.wait"]
    # one step of 9 s, 5.5 s of it waiting
    assert spans.host_ms_per_step(events) == pytest.approx(3500.0)


def test_host_idle_share_counts_the_gaps_outside_waits_only():
    planes = serving_planes()
    red = tr.reduce_planes(planes)
    # the harness's own annotations are read as before
    assert [h[0] for h in red.host] == [tr.WINDOW, "bench.step"]
    assert dict(red.idle_gaps()) == pytest.approx({"bench.step": 5.0})
    # idle [0, 1), [2, 3), [6, 8), [9, 10): 5 s. Outside waits, inside the
    # scheduler: [0.5, 1) in admit, [2, 2.5) in admit after the dispatch,
    # [6.5, 7.5) and [9, 9.5) in the step itself: 2.5 s
    share = spans.host_idle_share(red, spans.events_of(planes))
    assert red.idle_share == pytest.approx(0.5)
    assert share == pytest.approx(0.25)
    assert share <= red.idle_share
    ctx = types.SimpleNamespace(trace=red,
                                serving_events=spans.events_of(planes))
    assert run.reader("device_idle_share.host")(ctx) == pytest.approx(25.0)
    assert run.reader("host_ms_per_step")(ctx) == pytest.approx(3500.0)


def recorded_planes():
    """The recorded chip trace of ``test_bench_trace``, which predates the
    scheduler's annotations."""
    path = os.path.join(os.path.dirname(__file__), "data",
                        "chat_trace.json.gz")
    with gzip.open(path, "rt") as f:
        planes = json.load(f)["planes"]
    ev = lambda e: types.SimpleNamespace(name=e[0], start_ns=e[1],
                                         duration_ns=e[2])
    return [types.SimpleNamespace(name=p["name"], lines=[
        types.SimpleNamespace(name=l["name"], events=[ev(e)
                                                      for e in l["events"]])
        for l in p["lines"]]) for p in planes]


def test_a_trace_without_serving_annotations_reads_nothing(tmp_path,
                                                           monkeypatch):
    planes = recorded_planes()
    red = tr.reduce_planes(planes)
    ctx = types.SimpleNamespace(trace=red,
                                serving_events=spans.events_of(planes))
    assert ctx.serving_events == []
    for name in NEW:
        assert run.reader(name)(ctx) is None
    # nor does a run with no trace on disk raise
    monkeypatch.setattr(spans, "TRACE_DIR", tmp_path / "none")
    ctx = types.SimpleNamespace(trace=None)
    for name in NEW:
        assert run.reader(name)(ctx) is None
    assert ctx.serving_events == []
