"""Trace reduction arithmetic on a hand-made device timeline, and on a
recorded chip trace."""
import gzip
import json
import os
import types

import pytest

from bench import trace_reduce as tr


def reduction():
    # ops at [1, 2), [1.5, 3) (overlapping), [5, 6) and [9, 12) seconds; the
    # traced span is [0, 10)
    ops = [(1.0, 2.0), (1.5, 3.0), (5.0, 6.0), (9.0, 12.0)]
    dev = tr.DeviceTime(
        "/device:TPU:0", tr._merge(ops),
        modules=[("jit__unknown(7)", 1.0, 2.0),
                 ("jit_fn(3)", 5.0, 1.0), ("jit__unknown(7)", 9.0, 3.0),
                 ("jit__unknown(7)", 11.0, 1.0)],
        ops={"fusion.1": 2.5, "dot.2": 3.5})
    host = [(tr.WINDOW, 0.0, 10.0), ("bench.step", 0.5, 3.5),
            ("bench.idle", 6.0, 9.0)]
    return tr.Reduction((0.0, 10.0), [dev], host)


def test_busy_is_the_merged_union_clipped_to_the_span():
    r = reduction()
    assert r.devices[0].busy == [(1.0, 3.0), (5.0, 6.0), (9.0, 12.0)]
    # 2 + 1 + 1 seconds inside [0, 10)
    assert r.busy_s == pytest.approx(4.0)
    assert r.span_s == pytest.approx(10.0)


def test_idle_share_is_one_minus_busy_over_the_span():
    r = reduction()
    assert r.idle_share == pytest.approx(1 - 4.0 / 10.0)


def test_program_runs_by_jitted_name_inside_the_span():
    r = reduction()
    # the run at 11 s starts past the span's end on the device's clock: the
    # trace brackets the span, so it is still one of the span's dispatches
    assert r.module_runs(r"^jit__unknown\b") == [
        (1.0, 2.0), (9.0, 3.0), (11.0, 1.0)]
    assert r.module_runs(r"^jit_fn\b") == [(5.0, 1.0)]


def test_idle_gaps_go_to_the_host_annotation_open_in_them():
    r = reduction()
    gaps = dict(r.idle_gaps())
    # gaps [0, 1), [3, 5) and [6, 9): middles 0.5 (bench.step), 4 (none
    # but the window's) and 7.5 (bench.idle)
    assert gaps == pytest.approx({"bench.step": 1.0, "host": 2.0,
                                  "bench.idle": 3.0})
    assert sum(gaps.values()) == pytest.approx(r.span_s - r.busy_s)
    assert r.top_ops() == [["dot.2", 3.5], ["fusion.1", 2.5]]


def test_two_devices_are_averaged():
    r = reduction()
    other = tr.DeviceTime("/device:TPU:1", [(0.0, 10.0)], [], {})
    r2 = tr.Reduction(r.span, r.devices + [other], r.host)
    assert r2.busy_s == pytest.approx((4.0 + 10.0) / 2)


def recorded():
    """The first steps of a traced window of ``qwen3-32b.chat`` on a TPU
    v5e (seed 407164959): the device's ``XLA Modules`` and ``XLA Ops``
    lines (op names cut to the part before `` = ``) and the host's
    ``bench.*`` annotations, the traced span cut after the eighth program
    run. Times in nanoseconds, as ``ProfileData`` gives them."""
    path = os.path.join(os.path.dirname(__file__), "data",
                        "chat_trace.json.gz")
    with gzip.open(path, "rt") as f:
        planes = json.load(f)["planes"]
    ev = lambda e: types.SimpleNamespace(name=e[0], start_ns=e[1],
                                         duration_ns=e[2])
    return tr.reduce_planes([types.SimpleNamespace(
        name=p["name"], lines=[types.SimpleNamespace(
            name=l["name"], events=[ev(e) for e in l["events"]])
            for l in p["lines"]]) for p in planes])


def test_a_recorded_chip_trace_reduces_to_its_programs_and_idle_time():
    from bench import readers
    r = recorded()
    assert [d.name for d in r.devices] == ["/device:TPU:0"]
    assert r.span_s == pytest.approx(0.325052705)
    # two monolithic prefills and two decode dispatches of one tick each
    pre = r.module_runs(readers.PROGRAM["prefill"])
    dec = r.module_runs(readers.PROGRAM["decode"])
    assert [d for _, d in pre] == pytest.approx([0.045907591, 0.190078776])
    assert [d for _, d in dec] == pytest.approx([0.037737831, 0.037826147])
    # every operation runs inside a program, and the programs (two 0.6 us
    # scalar conversions besides) fill the span but for the host's gaps
    programs = sum(d for _, _, d in r.devices[0].modules)
    assert r.busy_s == pytest.approx(programs, rel=1e-3)
    assert r.idle_share == pytest.approx(1 - r.busy_s / r.span_s)
    assert 0.02 < r.idle_share < 0.06
    assert sum(v for _, v in r.idle_gaps()) == pytest.approx(
        r.span_s - r.busy_s)
