#!/usr/bin/env python3
"""Run one cell of the on-chip serving benchmark.

    python3 bench/run.py --workload qwen3-32b.chat --seed 7 --seconds 30 --trace 0

Everything is found by the names in ``BENCHMARK.json``: the cell's
configuration file, its traffic mix ``bench/traffic/<traffic>.json`` and one
reader ``bench/metrics/<metric>.py`` per per-layer metric. ``--trace 0``
reports the cell's end-to-end metrics; ``--trace 1`` serves the same window
with each dispatch timed and a profiler trace of part of it, and reports
the per-layer metrics. The last line of standard output is one JSON object;
the numbers compared for ``correct`` close standard error and that object.

Without a TPU, or with fewer chips than the cell asks for, it exits non-zero
and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import (families, harness, roofline, serving_stats,  # noqa: E402
                   traffic)
from bench.reference import control_tokens, token_gaps  # noqa: E402

TRACE_DIR = ROOT / ".bench_trace"


class NoChip(RuntimeError):
    pass


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"no {what} named {name!r} in BENCHMARK.json")


def cell_metrics(bench: dict, cell: str):
    pick = lambda ms: [m for m in ms if cell in m.get("workloads", [cell])]
    return pick(bench["end_to_end"]), pick(bench["per_layer"])


def reader(name: str):
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def check_devices(chips: int, require_chip: bool):
    import jax
    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"need {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs[:chips]


def run_cell(bench: dict, workload: str, seed: int, seconds: float,
             trace: bool, require_chip: bool = True, conf: dict = None,
             mix: dict = None, keep_trace: str = None,
             control: bool = False, clock=time.perf_counter,
             sleep=time.sleep) -> dict:
    """Serve one cell's window and check it; returns the result object.
    With ``control`` the fp8 control's tokens take the served tokens' place
    in the check, which then decides ``correct``; the program's own check
    of the same requests is kept under ``program``. Runs of the benchmark
    do not use it. ``clock`` times the window and ``sleep`` waits on it; a
    test gives fake ones, so that what the window serves does not hang on
    the host's speed."""
    cell = by_name(bench["workloads"], workload, "workload")
    if conf is None:
        conf = harness.load_json(
            by_name(bench["configs"], cell["config"], "config")["file"])
    if mix is None:
        mix = harness.load_json(f"bench/traffic/{cell['traffic']}.json")
    e2e, per_layer = cell_metrics(bench, workload)

    cache_dir = harness.use_cache()
    devices = check_devices(cell["chips"], require_chip)
    dev = devices[0]
    count = harness.CompileCount()
    log(f"[setup] {workload} seed {seed} on {len(devices)} x "
        f"{dev.device_kind}; compile cache {cache_dir}")
    family = families.of(conf)
    a = family.arch(conf)
    vocab = a.vocab
    params, sched, t_weights, t_warm, warm = harness.setup(conf, mix, seed)
    reqs = traffic.generate(mix, seed, seconds, vocab)
    log_hook = None
    if trace:
        log_hook = harness.DispatchLog(clock)
        sched.enable_profiling(log_hook)
    before = dict(sched.stats)
    sched.stats["peak_pages"] = 0   # a high-water mark: make it the window's
    c0, tr0 = count.compiles, count.traces
    setup_s = time.perf_counter() - T_START
    log(f"[setup] weights {t_weights:.2f} s, scheduler + warm-up "
        f"{t_warm:.2f} s ({warm} requests), {count.compiles} compiles "
        f"({count.compile_s:.1f} s), setup_s {setup_s:.3f}")

    if trace and TRACE_DIR.exists():
        shutil.rmtree(TRACE_DIR)
    win = harness.serve_window(
        sched, reqs, seconds, mix["serve"]["max_fuse"], clock,
        trace_dir=str(TRACE_DIR) if trace else None,
        trace_at=0.4 * seconds, trace_s=min(3.0, 0.3 * seconds),
        dispatches=log_hook, sleep=sleep)
    in_window = (count.compiles - c0, count.traces - tr0)
    after = dict(sched.stats)
    delta = {k: after[k] - before.get(k, 0) for k in after}
    delta["peak_pages"] = after["peak_pages"]
    stats = dev.memory_stats() or {}
    peak_bytes = int(stats.get("peak_bytes_in_use", 0))
    lim = mix["limits"]
    summ = serving_stats.summarize(win.records, win.window_s,
                                   lim["ttft_ms"] / 1e3, lim["tpot_ms"] / 1e3)
    lag = sorted(win.lag_s) or [0.0]
    log(f"[window] {win.window_s:.3f} s, {summ['requests']} requests due, "
        f"{summ['finished']} finished, {summ['tokens']} tokens, "
        f"{win.steps} steps, {delta['decode_steps']} decode ticks, "
        f"{delta['prefills']} prefills")
    log(f"[window] submit lag behind due time: median "
        f"{lag[len(lag) // 2] * 1e3:.3f} ms, max {lag[-1] * 1e3:.3f} ms")
    log(f"[window] compiles in window: {in_window[0]} (traces "
        f"{in_window[1]}); peak_bytes_in_use {peak_bytes}")

    # ---- the check: served tokens against the plain reference ----------
    chk = mix["check"]
    sample = harness.pick_sample(win.requests, seed, chk["served_tokens"])
    picked = [(win.requests[i].prompt, list(win.requests[i].out_tokens),
               win.requests[i].max_new_tokens) for i in sample]
    cached = sum(win.requests[i].cached_tokens > 0 for i in sample)
    admitted_prompt = sum(r.plen for r in win.requests
                          if r is not None and r.admit_step is not None)
    del sched, win.requests
    gc.collect()
    t = time.perf_counter()
    correct, checks = check(params, conf, picked, chk, vocab, control)
    if control:
        program = dict(zip(("correct", "checks"),
                           check(params, conf, picked, chk, vocab, False)))
    log(f"[check] {len(picked)} requests ({cached} with a prefix hit) "
        f"against the float32 reference"
        f"{' with the fp8 control in the program' if control else ''} in "
        f"{time.perf_counter() - t:.1f} s")

    metrics, device = {}, {"platform": dev.platform, "kind": dev.device_kind,
                           "count": len(devices),
                           "memory_peak_bytes": peak_bytes}
    breakdown = None
    if not trace:
        values = dict(summ, setup_s=setup_s)
        for m in e2e:
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        red = None
        from bench import trace_reduce
        path = trace_reduce.find_xplane(str(TRACE_DIR))
        if path:
            if keep_trace:
                shutil.copy(path, keep_trace)
            try:
                red = trace_reduce.reduce(path)
            except ValueError as e:
                if dev.platform == "tpu":
                    raise
                log(f"[trace] {e}")         # the CPU has no device plane
        if red is not None:
            device.update(busy_s=red.busy_s, window_s=red.span_s)
            breakdown = {"device_ops": red.top_ops(), "idle_gaps":
                         red.idle_gaps()}
        ctx = types.SimpleNamespace(
            arch=a, family=family, counters=delta, summary=summ,
            window_s=win.window_s,
            max_slots=mix["serve"]["max_slots"],
            pool_pages=mix["serve"]["pool_tokens"] // harness.PAGE_SIZE,
            prompt_tokens_admitted=admitted_prompt,
            dispatches=log_hook.rows, t_open=win.t0, trace=red,
            trace_span=win.trace_span,
            peaks=(roofline.peaks(dev.device_kind)
                   if dev.platform == "tpu" else None))
        for m in per_layer:
            v = reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
            else:
                log(f"[trace] {m['name']}: nothing to read")
        if TRACE_DIR.exists():
            shutil.rmtree(TRACE_DIR)
    for k, c in checks.items():
        log(f"check {k} {c['value']} limit {c['limit']}")
    out = {"correct": bool(correct), "attempted": summ["requests"],
           "failed": 0, "metrics": metrics, "device": device}
    if breakdown:
        out["breakdown"] = breakdown
    if control:
        out["program"] = program
    out["checks"] = checks
    return out


def check(params, conf: dict, picked, chk: dict, vocab: int,
          control: bool):
    """``(correct, checks)``: the served tokens of ``picked`` (prompt,
    tokens, budget) against the reference, or with ``control`` the tokens
    the fp8 forward puts first at the same positions."""
    gap, bad, served = 0.0, 0, 0
    for prompt, out, want in picked:
        if len(out) != want or min(out) < 0 or max(out) >= vocab:
            bad += 1
            continue
        ctl = control_tokens(params, conf, prompt, out) if control else None
        gap = max(gap, float(token_gaps(params, conf, prompt, out,
                                        ctl).max()))
        served += len(out)
    checks = {
        "gap_max": {"value": gap, "limit": chk["gap_limit"]},
        "bad_outputs": {"value": bad, "limit": 0},
        "served_tokens_checked": {"value": served,
                                  "limit": chk["served_tokens"]},
    }
    correct = (gap <= chk["gap_limit"] and bad == 0
               and served >= chk["served_tokens"])
    return correct, checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the raw .xplane.pb here (traced runs)")
    args = ap.parse_args(argv)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    try:
        out = run_cell(bench, args.workload, args.seed, args.seconds,
                       bool(args.trace), keep_trace=args.keep_trace)
    except NoChip as e:
        log(f"error: {e}")
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
