"""Roofline-driven block-size sweep for the chunked flash-prefill kernel.

For each (arch, chunk, page_size) point this sweeps the attend kernel's
``block_q`` grid parameter. ``block_q`` sets the KV re-read factor — every
query block streams the whole live context out of the pool pages, so a
chunk split into ``ceil(chunk / block_q)`` query blocks moves that many
times the context bytes. The sweep therefore:

1. models each candidate on the roofline (bytes moved at ``HBM_BW`` vs
   attention FLOPs at ``PEAK_FLOPS``, whichever bounds) and drops
   candidates whose modeled time is > ``--prune`` x the best model — on
   hardware the model alone nearly always picks the winner;
2. times the surviving candidates (best-of-``--repeats`` on a warm
   program) and keeps the fastest measured one.

Best configs land in ``BENCH_prefill_tune.json``; ``repro.kernels.ops``
loads that file lazily (or via ``$REPRO_PREFILL_TUNE`` /
``register_prefill_tuning``) and every ``ops.paged_prefill`` call with a
matching shape signature picks up the tuned ``block_q``. On CPU the
kernels run interpret-mode, so measured walls are dispatch-dominated
proxies; the modeled ranking is the portable signal and both numbers are
recorded per candidate.

Usage:
    PYTHONPATH=src python benchmarks/prefill_autotune.py          # full sweep
    PYTHONPATH=src python benchmarks/prefill_autotune.py --smoke  # CI
"""
from __future__ import annotations

import argparse
import json
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import REDUCED
from repro.kernels import ops
from repro.kernels import paged_prefill as pp
from repro.obs.profile import HBM_BW, PEAK_FLOPS

PREFIX_PAGES = 3          # synthetic live context = chunk + this many pages


def model_candidate(chunk, ctx, n_pg, ps, H, KVH, d, block_q, itemsize=4):
    """Roofline time for one attend candidate: KV streams once per query
    block, q/out move once, FLOPs are the two attention matmuls."""
    n_qb = math.ceil(chunk / block_q)
    kv_bytes = n_pg * ps * KVH * d * itemsize * 2
    qo_bytes = chunk * H * d * itemsize * 2
    bytes_moved = kv_bytes * n_qb + qo_bytes
    flops = 4 * chunk * ctx * H * d
    return {
        "bytes_moved": int(bytes_moved),
        "flops": int(flops),
        "modeled_ms": round(max(bytes_moved / HBM_BW,
                                flops / PEAK_FLOPS) * 1e3, 6),
    }


def time_candidate(q, pool, bt, start, lens, block_q, repeats):
    fn = jax.jit(lambda q_: pp.paged_prefill_attend(
        q_, pool["k_pages"], pool["v_pages"], bt, start, lens,
        block_q=block_q))
    fn(q).block_until_ready()                                     # warm
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn(q).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def sweep_point(cfg, chunk, ps, candidates, repeats, prune):
    """One (chunk, page_size) point: model, prune, measure, pick."""
    H, KVH = cfg.n_heads, cfg.n_kv_heads
    d = cfg.d_model // cfg.n_heads
    start = PREFIX_PAGES * ps                  # chunk lands mid-sequence
    ctx = start + chunk
    n_pg = -(-ctx // ps)
    key = jax.random.PRNGKey(chunk * 1000 + ps)
    ks = jax.random.split(key, 3)
    pool = {
        "k_pages": jax.random.normal(ks[0], (n_pg + 1, ps, KVH, d),
                                     jnp.float32),
        "v_pages": jax.random.normal(ks[1], (n_pg + 1, ps, KVH, d),
                                     jnp.float32),
    }
    bt = jnp.arange(1, n_pg + 1, dtype=jnp.int32)[None]
    q = jax.random.normal(ks[2], (1, chunk, H, d), jnp.float32)
    lens = jnp.asarray([chunk], jnp.int32)
    starts = jnp.asarray([start], jnp.int32)

    cands = {}
    for bq in sorted({min(b, chunk) for b in candidates}):
        cands[bq] = model_candidate(chunk, ctx, n_pg, ps, H, KVH, d, bq)
    floor = min(c["modeled_ms"] for c in cands.values())
    survivors = [bq for bq, c in cands.items()
                 if c["modeled_ms"] <= prune * floor]
    for bq in survivors:
        cands[bq]["measured_ms"] = round(
            time_candidate(q, pool, bt, starts, lens, bq, repeats), 3)
    best = min(survivors, key=lambda bq: (cands[bq]["measured_ms"],
                                          cands[bq]["modeled_ms"]))
    return {
        "block_q": int(best),
        "modeled_ms": cands[best]["modeled_ms"],
        "measured_ms": cands[best]["measured_ms"],
        "candidates": {str(bq): c for bq, c in cands.items()},
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--arch", default="qwen3-32b", choices=sorted(REDUCED))
    ap.add_argument("--wide", type=int, default=4,
                    help="width multiple matching serve_bench's bench_cfg")
    ap.add_argument("--deep", type=int, default=2)
    ap.add_argument("--chunks", type=int, nargs="+",
                    default=[16, 32, 64, 128],
                    help="chunk buckets to tune (scheduler dispatch sizes)")
    ap.add_argument("--page-sizes", type=int, nargs="+", default=[4, 8])
    ap.add_argument("--candidates", type=int, nargs="+",
                    default=[8, 16, 32, 64, 128])
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--prune", type=float, default=4.0,
                    help="drop candidates modeled worse than this x best")
    ap.add_argument("--out", default="BENCH_prefill_tune.json")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sweep + registry round-trip check (CI)")
    args = ap.parse_args()
    if args.smoke:
        args.chunks, args.page_sizes = [8], [4]
        args.candidates, args.repeats = [4, 8], 1

    import serve_bench
    cfg = serve_bench.bench_cfg(args.arch, args.wide, args.deep)
    H, KVH = cfg.n_heads, cfg.n_kv_heads
    d = cfg.d_model // cfg.n_heads

    entries = {}
    for ps in args.page_sizes:
        for chunk in args.chunks:
            key = ops.prefill_tuning_key(H, d, KVH, chunk, ps)
            entries[key] = sweep_point(cfg, chunk, ps, args.candidates,
                                       args.repeats, args.prune)
            print(f"{key}: block_q={entries[key]['block_q']} "
                  f"modeled={entries[key]['modeled_ms']}ms "
                  f"measured={entries[key]['measured_ms']}ms")

    out = {"version": 1, "arch": cfg.name,
           "dims": {"n_heads": H, "n_kv_heads": KVH, "head_dim": d},
           "entries": entries}
    with open(args.out, "w") as fh:
        json.dump(out, fh, indent=2)
        fh.write("\n")
    print(f"wrote {args.out} ({len(entries)} entries)")

    # round-trip: the table must steer ops.paged_prefill's block_q lookup
    prev = ops.register_prefill_tuning(entries)
    try:
        for ps in args.page_sizes:
            for chunk in args.chunks:
                want = entries[ops.prefill_tuning_key(H, d, KVH, chunk,
                                                      ps)]["block_q"]
                got = ops._prefill_tuned_block_q(H, d, KVH, chunk, ps)
                if got != want:
                    raise SystemExit(f"tuning round-trip failed: chunk "
                                     f"{chunk} ps {ps}: {got} != {want}")
    finally:
        ops.register_prefill_tuning(prev)
    print("tuning round-trip ok")


if __name__ == "__main__":
    main()
