"""Lifecycle tracer: span/instant collection semantics, the Chrome
trace-event export shape, the fail-loud JSONL round trip, the EventLog
bridge, and the launcher's non-autoscale ``--events-out`` path (the trace
is the event stream when no autoscale control loop owns one)."""
import json
import sys

import pytest

from repro.core.events import EventLog
from repro.obs.trace import LANE, TICK_US, Tracer


def _sample_tracer():
    tr = Tracer()
    tr.set_process_name(0, "replica-0 (mixed)")
    tr.set_tick(0)
    tr.begin("queued", 7, replica=0)
    tr.set_tick(2)
    tr.end("queued", 7)
    tr.span("prefill", 7, 2, 3, replica=0, tokens=12, pages=2)
    tr.begin("decode", 7, replica=0)
    tr.instant("routed", rid=7, t=0, replica=None, spillover=False)
    tr.set_tick(9)
    tr.end("decode", 7, tokens=6)
    tr.instant("autoscale", t=4, direction="scale_out", resource="slots")
    return tr


# ----------------------------------------------------------- collection --

def test_begin_end_pairing_and_no_op_rules():
    tr = Tracer()
    tr.begin("queued", 1, t=0, replica=0, first=True)
    tr.begin("queued", 1, t=5, replica=2)      # already open: first wins
    tr.end("queued", 1, t=3)
    tr.end("queued", 1, t=9)                   # unmatched: no-op
    tr.end("decode", 42)                       # never opened: no-op
    assert len(tr.spans) == 1
    s = tr.spans[0]
    assert (s.t0, s.t1, s.replica) == (0.0, 3.0, 0)
    assert s.attrs == {"first": True}


def test_next_index_numbers_per_request_and_name():
    tr = Tracer()
    assert [tr.next_index(1, "prefill_chunk") for _ in range(3)] == [0, 1, 2]
    assert tr.next_index(2, "prefill_chunk") == 0
    assert tr.next_index(1, "other") == 0


def test_finish_open_flushes_with_marker():
    tr = Tracer()
    tr.begin("decode", 3, t=5, replica=1)
    tr.set_tick(8)
    assert tr.finish_open() == 1
    assert tr.finish_open() == 0               # idempotent
    s = tr.spans[-1]
    assert s.t1 == 8.0 and s.attrs["open"] is True


def test_annotate_sets_attrs_of_an_open_span_only():
    tr = Tracer()
    tr.begin("queued", 4, t=0)
    tr.annotate("queued", 4, blocked="slots")
    tr.annotate("queued", 4, blocked="pages")     # the last reason stays
    tr.annotate("queued", 5, blocked="slots")     # never opened: no-op
    tr.end("queued", 4, t=2)
    tr.annotate("queued", 4, blocked="quota")     # closed: no-op
    assert [s.attrs for s in tr.spans] == [{"blocked": "pages"}]


def test_wall_clock_stamps_every_hook_from_its_clock():
    reads = iter([10.0, 10.5, 11.0, 12.0, 13.0])
    tr = Tracer(clock=lambda: next(reads))
    tr.set_tick(99)                            # ticks no longer stamp
    tr.begin("queued", 1)                      # 10.0
    tr.end("queued", 1)                        # 10.5
    tr.instant("admitted", rid=1)              # 11.0
    tr.begin("decode", 1, t=11.5)              # an explicit time is kept
    assert tr.now() == 12.0
    assert tr.finish_open() == 1               # 13.0
    assert [(s.name, s.t0, s.t1) for s in tr.spans] == [
        ("queued", 10.0, 10.5), ("decode", 11.5, 13.0)]
    assert tr.instants[0].t == 11.0
    tr.begin("held", 2, t=20.0)
    tr.finish_open(t=25.0)                     # closed where it is told
    assert (tr.spans[-1].t1, tr.spans[-1].attrs) == (25.0, {"open": True})


# -------------------------------------------------------------- chrome --

def test_chrome_export_shape():
    tr = _sample_tracer()
    doc = tr.to_chrome()
    evs = doc["traceEvents"]
    assert doc["displayTimeUnit"] == "ms"
    # pid 0 is the fleet lane; replica 0's lane is pid 1
    metas = {e["pid"]: e["args"]["name"] for e in evs if e["ph"] == "M"}
    assert metas[0] == "fleet" and metas[1] == "replica-0 (mixed)"
    pre = next(e for e in evs if e["ph"] == "X" and e["name"] == "prefill")
    assert pre["pid"] == 1 and pre["tid"] == 7
    assert pre["ts"] == 2 * TICK_US and pre["dur"] == 1 * TICK_US
    assert pre["args"]["tokens"] == 12 and pre["args"]["replica"] == 0
    routed = next(e for e in evs if e["ph"] == "i" and e["name"] == "routed")
    assert routed["pid"] == 0 and routed["s"] == "t"   # rid-scoped instant
    auto = next(e for e in evs if e["name"] == "autoscale")
    assert auto["s"] == "g"                            # global instant
    json.dumps(doc)                                    # serializable


def test_chrome_export_names_its_clock():
    assert _sample_tracer().to_chrome()["otherData"] == {
        "clock": f"sim tick = {TICK_US} us"}


def test_chrome_export_on_a_wall_clock_renders_seconds():
    tr = Tracer(clock=lambda: 3.0)
    tr.span("prefill", 7, 2.0, 2.25, replica=0)
    tr.span("step", LANE, 1.5, 2.5, replica=0, ticks=4)
    tr.instant("admitted", rid=7, t=2.0, replica=0)
    doc = tr.to_chrome()
    assert "wall clock" in doc["otherData"]["clock"]
    evs = {e["name"]: e for e in doc["traceEvents"] if e["ph"] != "M"}
    assert evs["prefill"]["ts"] == 2.0e6 and evs["prefill"]["dur"] == 0.25e6
    assert evs["step"]["tid"] == LANE and evs["step"]["dur"] == 1.0e6
    assert evs["step"]["args"]["ticks"] == 4
    assert evs["admitted"]["ts"] == 2.0e6


def test_write_chrome_counts_events(tmp_path):
    tr = _sample_tracer()
    path = tmp_path / "trace.json"
    n = tr.write_chrome(str(path))
    doc = json.loads(path.read_text())
    assert n == len(doc["traceEvents"])
    assert n == 2 + 3 + 2                     # metas + spans + instants


# ---------------------------------------------------------------- jsonl --

def test_jsonl_round_trip(tmp_path):
    tr = _sample_tracer()
    path = tmp_path / "trace.jsonl"
    n = tr.write_jsonl(str(path))
    assert n == len(tr.spans) + len(tr.instants)
    back = Tracer.from_jsonl(str(path))
    assert back.process_names == tr.process_names
    assert [s.to_dict() for s in back.spans] == \
        [s.to_dict() for s in tr.spans]
    assert [i.to_dict() for i in back.instants] == \
        [i.to_dict() for i in tr.instants]


@pytest.mark.parametrize("line,match", [
    ("{not json", "line 2 is not valid JSON"),
    ("[1, 2]", "line 2 holds a JSON list"),
    ('{"kind": "mystery", "name": "x"}', "unknown trace record kind"),
    ('{"kind": "span", "name": "x", "rid": 1}', "missing field"),
    ('{"kind": "instant", "name": "x", "t": 1, "attrs": 3}',
     "non-object 'attrs'"),
])
def test_from_jsonl_fails_loud_with_line_numbers(tmp_path, line, match):
    path = tmp_path / "bad.jsonl"
    good = '{"kind": "instant", "name": "ok", "t": 0}'
    path.write_text(good + "\n" + line + "\n")
    with pytest.raises(ValueError, match=match):
        Tracer.from_jsonl(str(path))


# ------------------------------------------------------------- EventLog --

def test_to_event_log_orders_and_names_actors(tmp_path):
    tr = _sample_tracer()
    log = tr.to_event_log()
    assert isinstance(log, EventLog)
    ts = [e.t for e in log.events]
    assert ts == sorted(ts)
    # ties at t=0 keep insertion order (spans before instants), so the
    # queued span leads the routed instant on the shared timeline
    log.assert_order("queued", "routed", "prefill", "decode")
    pre = next(e for e in log.events if e.action == "prefill")
    assert pre.actor == "replica-0" and pre.detail["dur"] == 1.0
    routed = next(e for e in log.events if e.action == "routed")
    assert routed.actor == "fleet" and routed.detail["rid"] == 7
    # and the EventLog round trip still holds for the bridged log
    path = tmp_path / "events.jsonl"
    log.write_jsonl(str(path))
    assert len(EventLog.from_jsonl(str(path)).events) == len(log.events)


# ------------------------------------------------- launcher integration --

def test_serve_events_out_without_autoscale(tmp_path, monkeypatch, capsys):
    """Regression (S2): ``--events-out`` used to be an argparse error
    without ``--autoscale``; now the lifecycle trace is the event stream."""
    from repro.launch import serve
    out = tmp_path / "events.jsonl"
    monkeypatch.setattr(sys, "argv", [
        "serve", "--arch", "qwen3-32b", "--engine", "paged",
        "--requests", "3", "--prompt-len", "8", "--gen", "4",
        "--batch", "2", "--events-out", str(out)])
    serve.main()
    report = json.loads(capsys.readouterr().out)
    assert report["events_written"] > 0
    log = EventLog.from_jsonl(str(out))
    assert len(log.events) == report["events_written"]
    log.assert_order("queued", "prefill", "decode", "finish")
