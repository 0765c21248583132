#!/usr/bin/env python3
"""Find a cell's knee: serve its mix at several fixed Poisson rates, one
window each, in one process on the chip.

    python3 bench/sweep.py --workload qwen3-32b.chat --seconds 15 \\
        --rates 2 3 4 5 6

For each rate it prints one JSON line: the end-to-end numbers and the
backlog (requests due but not yet admitted) at the window's middle and end.
The knee is the highest rate whose backlog does not grow. Between windows
the scheduler drains what is left, untimed.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import families, harness, run, serving_stats, traffic  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = run.by_name(bench["workloads"], args.workload, "workload")
    conf = harness.load_json(run.by_name(bench["configs"], cell["config"],
                                         "config")["file"])
    mix = harness.load_json(f"bench/traffic/{cell['traffic']}.json")
    harness.use_cache()
    run.check_devices(cell["chips"], True)
    vocab = families.arch_of(conf).vocab
    _, sched, _, _, _ = harness.setup(conf, mix, args.seed)
    lim = mix["limits"]
    for rate in args.rates:
        m = dict(mix, rate_per_s=rate)
        reqs = traffic.generate(m, args.seed, args.seconds, vocab)
        backlog = {}
        win = harness.serve_window(sched, reqs, args.seconds,
                                   mix["serve"]["max_fuse"],
                                   time.perf_counter)
        due = len(win.records)
        not_admitted = sum(r.admit_s is None for r in win.records)
        half = [r for r in win.records if r.due_s <= args.seconds / 2]
        backlog["mid"] = sum(r.admit_s is None or r.admit_s > args.seconds / 2
                             for r in half)
        backlog["end"] = not_admitted
        s = serving_stats.summarize(win.records, win.window_s,
                                    lim["ttft_ms"] / 1e3, lim["tpot_ms"] / 1e3)
        sched.run(max_fuse=mix["serve"]["max_fuse"])
        print(json.dumps(dict(rate=rate, due=due, backlog=backlog, **s)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
