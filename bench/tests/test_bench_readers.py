"""Per-layer readers on a hand-made log and trace: dispatches pair with the
programs' device runs by name, and each share has its stated base."""
import importlib.util
import pathlib
import types

import pytest

from bench import roofline
from bench import trace_reduce as tr
from bench.harness import Dispatch
from bench.reference import Arch

ROOT = pathlib.Path(__file__).resolve().parents[2]
ARCH = Arch(d=4, heads=2, kv_heads=1, head_dim=2, ff=8, vocab=10, layers=1,
            eps=1e-6, theta=1e4, rot_dim=2, qk_norm=False, qkv_bias=False)
PEAKS = roofline.Peaks(bf16_flops=1e3, hbm_bw=1e3, source="test")


def read(name, ctx):
    spec = importlib.util.spec_from_file_location(
        "m", ROOT / "bench" / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def context(extra_runs=()):
    # a prefill of 5 tokens, then 2 fused ticks of 3 rows over 30 live
    # tokens; host times [2.0, 2.2) and [2.2, 2.7) seconds of the window
    ds = [Dispatch("prefill", 2.0, 2.2, 5, 0, 1),
          Dispatch("decode", 2.2, 2.7, 6, 30, 2)]
    runs = [("jit_fn(4)", 2.05, 0.1), ("jit__unknown(9)", 2.3, 0.25)]
    dev = tr.DeviceTime("/device:TPU:0", [(2.05, 2.15), (2.3, 2.55)],
                        sorted(runs + list(extra_runs), key=lambda r: r[1]),
                        {})
    trace = tr.Reduction((1.0, 5.0), [dev], [(tr.WINDOW, 1.0, 5.0)])
    return types.SimpleNamespace(arch=ARCH, dispatches=ds, t_open=0.0,
                                 trace=trace, trace_span=(1.0, 5.0),
                                 window_s=10.0, peaks=PEAKS)


def test_roofline_shares_pair_each_dispatch_with_its_device_run():
    ctx = context()
    dec = roofline.least_time(*roofline.decode_work(ARCH, 2, 3, 30), PEAKS)
    pre = roofline.least_time(*roofline.prefill_work(ARCH, 5, 0), PEAKS)
    assert read("decode_roofline", ctx) == pytest.approx(100 * dec / 0.25)
    assert read("prefill_roofline", ctx) == pytest.approx(100 * pre / 0.1)
    # 0.1 device seconds for 5 prompt tokens
    assert read("prefill_ms_per_ktok", ctx) == pytest.approx(20000.0)
    assert read("device_idle_share", ctx) == pytest.approx(
        100 * (1 - 0.35 / 4.0))


def test_mfu_divides_by_host_time_and_by_the_window():
    ctx = context()
    f_dec = roofline.decode_work(ARCH, 2, 3, 30)[0]
    f_pre = roofline.prefill_work(ARCH, 5, 0)[0]
    assert read("mfu.decode", ctx) == pytest.approx(100 * f_dec / (0.5 * 1e3))
    assert read("mfu.prefill", ctx) == pytest.approx(
        100 * f_pre / (0.2 * 1e3))
    assert read("mfu", ctx) == pytest.approx(
        100 * (f_dec + f_pre) / (10.0 * 1e3))


def test_a_device_run_without_its_dispatch_reads_nothing():
    ctx = context(extra_runs=[("jit__unknown(9)", 3.0, 0.25)])
    assert read("decode_roofline", ctx) is None
    assert read("prefill_roofline", ctx) is not None


def test_without_peaks_or_trace_the_device_readers_read_nothing():
    ctx = context()
    ctx.peaks, ctx.trace = None, None
    for name in ("decode_roofline", "prefill_roofline", "mfu.decode",
                 "mfu", "device_idle_share", "prefill_ms_per_ktok"):
        assert read(name, ctx) is None, name


def test_the_last_dispatch_pairs_though_the_host_leaves_it_after_the_span():
    # the decode's step is the last in the span: the host goes on past its
    # result at 5.2 s, after the span closed at 5.0 s on the window's clock
    ctx = context()
    ctx.dispatches[1].t1 = 5.2
    ctx.trace_span = (1.0, 5.0)
    assert read("decode_roofline", ctx) is not None


def test_the_window_tails_are_read_from_the_window_summary():
    ctx = context()
    ctx.summary = {"ttft_p95_ms": 1832.5, "queue_wait_p95_ms": 1500.0,
                   "tpot_p95_ms": 110.9}
    assert read("ttft_p95_ms", ctx) == 1832.5
    assert read("tpot_p95_ms.docs", ctx) == 110.9
    assert read("queue_wait_p95_ms", ctx) == 1500.0
