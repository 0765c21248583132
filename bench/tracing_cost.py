#!/usr/bin/env python3
"""What the scheduler's wall-clock spans cost, and where a cell's time to
first token goes: the same seeds served with and without a
``repro.obs.trace.Tracer`` attached, no profiler running, in one process.

    python3 bench/tracing_cost.py --workload qwen3-32b.chat --seconds 45 \\
        --seeds 11 12 13

Each seed's window is served twice, as ``bench/run.py --trace 0`` serves
it (weights, warm-up, open-loop window; no check), once with a Tracer on
the window's clock attached after the warm-up and once without, the order
alternating from seed to seed. Prints one JSON line per window: the
end-to-end numbers and, with spans, the scheduler's host milliseconds per
step, the p95 of ``queued`` and ``held``, how far the harness's time to
first token is from submit lag + ``queued`` + ``prefill`` + ``held`` at
most, and that split for the request nearest the window's p95.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time
from typing import Dict, List, Optional

ROOT = pathlib.Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import families, harness, serving_stats, traffic  # noqa: E402
from bench.run import by_name  # noqa: E402

REQUEST_SPANS = ("queued", "prefill", "held")


def serve(bench: dict, workload: str, seed: int, seconds: float,
          spans: bool, conf: dict = None, mix: dict = None,
          clock=time.perf_counter, sleep=time.sleep):
    """One untraced window of the cell: ``(window, summary, tracer)``, the
    tracer None without ``spans``. ``conf``, ``mix``, ``clock`` and
    ``sleep`` as ``bench/run.py``'s ``run_cell`` takes them."""
    from repro.obs.trace import Tracer
    cell = by_name(bench["workloads"], workload, "workload")
    if conf is None:
        conf = harness.load_json(
            by_name(bench["configs"], cell["config"], "config")["file"])
    if mix is None:
        mix = harness.load_json(f"bench/traffic/{cell['traffic']}.json")
    harness.use_cache()
    _, sched, _, _, _ = harness.setup(conf, mix, seed)
    reqs = traffic.generate(mix, seed, seconds, families.arch_of(conf).vocab)
    tracer = None
    if spans:
        tracer = Tracer(clock=clock)
        sched.set_tracer(tracer)
    win = harness.serve_window(sched, reqs, seconds, mix["serve"]["max_fuse"],
                               clock, sleep=sleep)
    if tracer is not None:
        tracer.finish_open(t=win.t0 + win.window_s)
    lim = mix["limits"]
    summ = serving_stats.summarize(win.records, win.window_s,
                                   lim["ttft_ms"] / 1e3, lim["tpot_ms"] / 1e3)
    return win, summ, tracer


def durations_ms(tracer, name: str) -> List[float]:
    return [1e3 * (s.t1 - s.t0) for s in tracer.spans if s.name == name]


def p95_ms(tracer, name: str) -> Optional[float]:
    """Nearest-rank 95th percentile of the ``name`` spans, in ms."""
    ms = durations_ms(tracer, name)
    return serving_stats.percentile(ms, 95) if ms else None


def host_ms_per_step(tracer) -> Optional[float]:
    """(time in ``step()`` - time waiting on the device) per step, in ms."""
    steps = durations_ms(tracer, "step")
    if not steps:
        return None
    return (sum(steps) - sum(durations_ms(tracer, "wait"))) / len(steps)


def ttft_split(win, tracer) -> List[Dict[str, float]]:
    """Per request of the window with a first token: its time to first
    token as the harness stamps it, and the parts the spans give, in ms:
    ``lag`` (due to submitted, where ``queued`` opens), ``queued``,
    ``prefill`` and ``held``. ``gap`` is what the parts leave out: from
    the end of ``held`` to the harness's stamp after ``step()`` returns."""
    spans = {(s.rid, s.name): s for s in tracer.spans
             if s.name in REQUEST_SPANS}
    rows = []
    for rec, req in zip(win.records, win.requests):
        if req is None or not rec.token_s:
            continue
        got = [spans.get((req.rid, n)) for n in REQUEST_SPANS]
        if None in got:
            continue
        row = {"ttft": 1e3 * (rec.token_s[0] - rec.due_s),
               "lag": 1e3 * (got[0].t0 - win.t0 - rec.due_s)}
        row.update({n: 1e3 * (s.t1 - s.t0)
                    for n, s in zip(REQUEST_SPANS, got)})
        row["gap"] = row["ttft"] - sum(row[n] for n in ("lag",)
                                       + REQUEST_SPANS)
        rows.append(row)
    return rows


def readings(win, summ, tracer) -> dict:
    """The end-to-end numbers of a window, and what its spans read."""
    out = {k: summ[k] for k in ("output_tok_s", "tpot_p95_ms", "slo_share",
                                "ttft_p95_ms", "requests")}
    if tracer is None:
        return out
    rows = ttft_split(win, tracer)
    out.update(host_ms_per_step=host_ms_per_step(tracer),
               queued_p95_ms=p95_ms(tracer, "queued"),
               first_token_held_p95_ms=p95_ms(tracer, "held"),
               split_requests=len(rows),
               split_gap_max_ms=max((abs(r["gap"]) for r in rows),
                                    default=None))
    if rows and summ["ttft_p95_ms"] is not None:
        out["p95_split_ms"] = min(
            rows, key=lambda r: abs(r["ttft"] - summ["ttft_p95_ms"]))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for i, seed in enumerate(args.seeds):
        for spans in ((False, True) if i % 2 == 0 else (True, False)):
            got = serve(bench, args.workload, seed, args.seconds, spans)
            print(json.dumps({"seed": seed, "spans": spans,
                              **readings(*got)}), flush=True)
            del got
            gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
