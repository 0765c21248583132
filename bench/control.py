#!/usr/bin/env python3
"""Readings for the limit of ``correct``: the program's widest gap on many
seeds, and on some of them the fp8 control's, in one process.

    python3 bench/control.py --workload qwen3-32b.chat --seconds 8 \\
        --seeds 11 12 13 ... --control-seeds 3

Each seed makes its own weights and traffic and serves a short window at the
cell's own load, exactly as a run does. On the first ``--control-seeds``
seeds the fp8 control's tokens also go through the same check in the
program's place, and must come out not correct. Prints one JSON line per
seed. Needs the chip.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args()
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for i, seed in enumerate(args.seeds):
        ctl = i < args.control_seeds
        out = run.run_cell(bench, args.workload, seed, args.seconds, False,
                           control=ctl)
        prog = out["program"] if ctl else out
        row = {"seed": seed, "correct": prog["correct"],
               "gap_max": prog["checks"]["gap_max"]["value"],
               "served": prog["checks"]["served_tokens_checked"]["value"]}
        if ctl:
            row.update(control_correct=out["correct"],
                       control_gap_max=out["checks"]["gap_max"]["value"])
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
