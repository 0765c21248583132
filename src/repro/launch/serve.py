"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Two engines, selectable with ``--engine``:

* ``static`` — the original fixed-batch prefill + greedy decode
  (``repro.serving.engine``): one batch, one ring-buffer cache, every
  stream padded to the same capacity and decoded until the longest one
  finishes.
* ``paged`` — the continuous-batching scheduler over the paged KV cache
  (``repro.serving.scheduler``): requests join on arrival, evict on
  finish, and K/V live in a shared page pool sized by the blueprint
  planner (``repro.core.blueprint.serving_page_plan``). ``--requests``
  builds a mixed-length workload with staggered arrivals to show the
  occupancy win; see ``benchmarks/serve_bench.py`` for the head-to-head.
  With ``--autoscale`` the engine starts at one decode slot and the
  elastic control plane (``repro.autoscale``) grows/shrinks slots and
  page pool with load; ``--events-out run.jsonl`` exports the scale
  decisions for replay (``EventLog.from_jsonl``). Without ``--autoscale``
  the same flag exports the request-lifecycle trace as an event log
  instead (``repro.obs.trace.Tracer.to_event_log``).

Observability (paged/fleet only, see docs/observability.md):
``--trace-out trace.json`` records per-request lifecycle spans (queued /
prefill chunks / parked / migration / decode) as Chrome trace-event JSON
for Perfetto; ``--metrics-out metrics.prom`` dumps the typed metric
registries in Prometheus text exposition; ``--profile`` wall-times every
kernel dispatch and reports modeled roofline fractions. All three are
read-only: emitted tokens are byte-identical with them on or off.

``--replicas k`` (paged only) serves through the replicated fabric
instead: a ``ServingRouter`` front-end spreads the workload over k
scheduler replicas (``--router`` picks the routing policy), and
``--autoscale`` then runs the *fleet* control plane
(``repro.autoscale.FleetController``): start at one replica, add/drain
whole replicas with fleet queue depth.

``--chunked-prefill N`` (paged only) lands each prompt in chunks of at
most N tokens per tick, interleaved with decode ticks, instead of one
monolithic prefill call — tokens stay byte-identical at fp32.
``--disagg k`` (fleet only) splits the fabric into k prefill-role
replicas and ``replicas - k`` decode-role replicas with verbatim KV-page
handoff between them; composes with ``--chunked-prefill`` and
``--autoscale`` (the fleet controller then scales the two roles on
separate signals).

``--tp k`` (paged only) serves every scheduler/replica as a k-way
tensor-parallel *shard group*: page pools and attention heads (and MoE
experts) split k ways while tokens stay byte-identical to ``--tp 1``
(docs/sharding.md). Composes with ``--replicas``: a fleet of shard
groups.

``--seed`` drives both parameter init and workload generation, so
run-to-run variation studies are one flag.

By default both engines serve the arch's reduced config. ``--layers N``
serves its published config instead (``get_arch``) cut only in depth, to
its first N layers: every width as published, the whole vocabulary and
head, float32 params as ``M.init`` makes them. N must be a whole number
of ``layer_pattern`` periods. For qwen3-32b, ``--layers 2`` is ~10.1 GB
of params: what one 16 GB TPU v5e chip holds next to its page pool
(``chip_smoke.py`` serves it). The full-depth serve cells (decode_32k /
long_500k) are lowered and analysed by the dry-run.

Run as a program, the launcher keeps JAX's persistent compilation cache
in ``$JAX_COMPILATION_CACHE_DIR`` when set, else in ``.jax_cache/`` at
the checkout root (``repro.launch.compile_cache``).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import ARCHS, get_arch, get_reduced
from repro.models import model as M
from repro.obs.metrics import percentile
from repro.obs.trace import Tracer
from repro.serving import engine as E
from repro.serving import paged_cache as PC
from repro.serving.scheduler import ContinuousBatchingScheduler, supports_paged


def _finish_obs(out: dict, args, tracer, profiler, expose_fn,
                ctl=None) -> None:
    """Common export tail for the paged/fleet runners: flush the tracer,
    write the requested artifacts, and fold counts into the result dict."""
    if tracer is not None:
        tracer.finish_open()
    if args.trace_out:
        out["trace_events"] = tracer.write_chrome(args.trace_out)
    if args.events_out:
        # the autoscale control loop owns the event log when present;
        # otherwise the lifecycle trace is the run's event stream
        if ctl is not None:
            out["events_written"] = ctl.log.write_jsonl(args.events_out)
        else:
            out["events_written"] = tracer.to_event_log().write_jsonl(
                args.events_out)
    if args.metrics_out:
        text = expose_fn()
        with open(args.metrics_out, "w") as fh:
            fh.write(text)
        out["metrics_written"] = text.count("# TYPE")
    if profiler is not None:
        out["profile"] = profiler.summary()


def run_static(cfg, params, args) -> Tuple[dict, list]:
    key = jax.random.PRNGKey(args.seed)
    B, S = args.batch, args.prompt_len
    batch = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size)}
    if cfg.rope_variant == "mrope":
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        batch["positions"] = jnp.broadcast_to(pos[None], (3, B, S))
    if cfg.is_encdec:
        batch["enc_embeds"] = jax.random.normal(
            key, (B, cfg.enc_positions, cfg.d_model), jnp.float32)

    t0 = time.time()
    lg, cache, cur = E.prefill(cfg, params, batch,
                               capacity=S + args.gen + 8)
    lg.block_until_ready()
    t_pre = time.time() - t0
    first = jnp.argmax(lg[:, -1, :cfg.vocab_size], -1).astype(
        jnp.int32)[:, None]
    t0 = time.time()
    toks, cache, cur = E.greedy_decode(cfg, params, cache, first, cur,
                                       args.gen)
    toks.block_until_ready()
    t_dec = time.time() - t0
    return {
        "engine": "static",
        "arch": cfg.name,
        "prefill_tok_per_s": round(B * S / t_pre, 1),
        "decode_tok_per_s": round(B * args.gen / t_dec, 1),
        "generated": [[int(t) for t in row[:8]] for row in toks],
    }, []


def persona_workload(vocab_size, rng, personas, users, persona_len,
                     user_lo, user_hi, gen_lo, gen_hi):
    """Canonical persona trace: ``personas`` shared system prompts of
    ``persona_len`` tokens, each carried by ``users`` requests that differ
    only in a short user suffix — the fleet-chat shape where re-prefilling
    the persona dominates both prefill FLOPs and page-pool footprint.
    Requests are grouped by persona (one persona's users arrive as a
    burst), so a persona's pages stay live across its users' admissions.
    Shared by the launcher's ``--shared-prefix`` mode and
    ``benchmarks/serve_bench.py`` so both tools measure the same traffic.
    """
    out = []
    for _ in range(personas):
        persona = rng.randint(0, vocab_size, size=persona_len)
        for _ in range(users):
            ulen = int(rng.randint(user_lo, user_hi + 1))
            user = rng.randint(0, vocab_size, size=ulen)
            gen = int(rng.randint(gen_lo, gen_hi + 1))
            out.append((np.concatenate([persona, user]).astype(np.int32),
                        gen))
    return out


def make_workload(cfg, rng, args):
    """(prompt, gen) pairs submitted at ``i // 2`` arrivals.

    Default: independent mixed-length prompts. ``--shared-prefix`` builds
    the persona trace instead (``persona_workload``) so concurrent streams
    share their dominant prefix and admission can skip its prefill.
    """
    if args.shared_prefix:
        user_hi = max(args.user_len, 2)
        out = persona_workload(cfg.vocab_size, rng, args.personas,
                               args.users_per_persona, args.persona_len,
                               max(user_hi // 2, 1), user_hi,
                               max(args.gen // 2, 1), args.gen)
        # an explicit --requests caps the trace (personas x users otherwise)
        if args.requests is not None:
            out = out[:args.requests]
        return out
    out = []
    for _ in range(args.requests if args.requests is not None else 8):
        plen = int(rng.randint(max(args.prompt_len // 2, 1),
                               args.prompt_len + 1))
        gen = int(rng.randint(max(args.gen // 2, 1), args.gen + 1))
        out.append((rng.randint(0, cfg.vocab_size, size=plen), gen))
    return out


def _max_seq(args) -> int:
    if args.shared_prefix:
        return args.persona_len + max(args.user_len, 2) + args.gen + 8
    return args.prompt_len + args.gen + 8


def _prefix_stats(stats: dict) -> dict:
    out = {"prefix_hits": stats.get("prefix_hits", 0),
           "cached_tokens": stats.get("cached_tokens", 0),
           "cow_forks": stats.get("cow_forks", 0)}
    if stats.get("prefills"):
        out["prefix_hit_rate"] = round(out["prefix_hits"]
                                       / stats["prefills"], 3)
    return out


def _spec_kw(args) -> dict:
    """Speculative-decoding knobs for the scheduler/router constructors.

    The draft model is a reduced config initialised from its own seed —
    it shares only the tokenizer (vocab) with the target; the scheduler
    validates that at construction.
    """
    if getattr(args, "spec", None) is None:
        return {}
    draft = None
    if args.spec_draft:
        dcfg = get_reduced(args.spec_draft)
        draft = (dcfg, M.init(dcfg, jax.random.PRNGKey(args.seed + 1)))
    return {"spec_k": args.spec, "spec_draft": draft}


def _parse_tenants(spec):
    """``"free=32,pro=128"`` -> ``{"free": 32, "pro": 128}`` page quotas."""
    if not spec:
        return None
    out = {}
    for part in spec.split(","):
        name, _, pages = part.partition("=")
        name, pages = name.strip(), pages.strip()
        if not name or not pages.isdigit():
            raise ValueError(
                f"--tenants expects name=pages[,name=pages...], got {spec!r}")
        out[name] = int(pages)
    return out


def _tier_kw(args) -> dict:
    """Host-RAM KV tier knobs for the scheduler/router constructors."""
    if getattr(args, "host_pages", None) is None:
        return {}
    return {"host_pages": args.host_pages,
            "tenant_quotas": _parse_tenants(getattr(args, "tenants", None)),
            "swap_crossover": getattr(args, "swap_crossover", None)}


def _submit_kw(args, i: int) -> dict:
    """Per-request tenant/priority tags: requests round-robin over the
    declared tenants (first tenant = priority 2, the rest priority 1) so a
    --tenants run exercises both quota classes without a trace format."""
    quotas = _parse_tenants(getattr(args, "tenants", None))
    if not quotas:
        return {}
    names = sorted(quotas)
    name = names[i % len(names)]
    return {"tenant": name, "priority": 2 if name == names[0] else 1}


def _tier_stats(out: dict, args, stats) -> None:
    if getattr(args, "host_pages", None) is None:
        return
    out["host_pages"] = args.host_pages
    for k in ("swap_outs", "swap_ins", "swap_out_pages", "swap_in_pages",
              "swap_reprefills", "host_evictions", "quota_blocked",
              "index_evictions"):
        out[k] = stats[k]


def _spec_stats(out: dict, args, stats) -> None:
    if not getattr(args, "spec", None):
        return
    out["spec_k"] = args.spec
    if args.spec_draft:
        out["spec_draft"] = args.spec_draft
    for k in ("spec_ticks", "spec_drafted", "spec_accepted",
              "spec_accept_rate"):
        out[k] = stats[k]


def run_fleet(cfg, params, args) -> Tuple[dict, list]:
    """Replicated fabric: k scheduler replicas behind one router."""
    from repro.serving.router import ServingRouter
    if not supports_paged(cfg):
        raise SystemExit(f"{cfg.name}: use --engine static (MLA/enc-dec)")
    rng = np.random.RandomState(args.seed)
    max_seq = _max_seq(args)
    # a disaggregated fleet needs one live replica per role, so the
    # autoscale floor is (disagg prefill + 1 decode) instead of 1
    start = args.replicas if not args.autoscale \
        else (args.disagg + 1 if args.disagg else 1)
    router = ServingRouter(cfg, params, replicas=start,
                           max_slots=args.batch, page_size=args.page_size,
                           max_seq_len=max_seq, route_policy=args.router,
                           prefix_cache=args.prefix_cache, tp=args.tp,
                           prefill_budget=args.chunked_prefill,
                           disagg=args.disagg, **_spec_kw(args),
                           **_tier_kw(args))
    tracer = None
    if args.trace_out or (args.events_out and not args.autoscale):
        tracer = Tracer()
        router.set_tracer(tracer)
    profiler = router.enable_profiling() if args.profile else None
    ctl = None
    if args.autoscale:
        from repro.autoscale import FleetController
        ctl = FleetController(router, min_replicas=start,
                              max_replicas=args.replicas, eval_interval=2)
    for i, (prompt, gen) in enumerate(make_workload(cfg, rng, args)):
        router.submit(prompt, gen, arrival_step=i // 2, **_submit_kw(args, i))

    t0 = time.time()
    done = ctl.run() if ctl else router.run()
    wall = time.time() - t0
    fleet = router.fleet_stats()
    lat = [float(r.finish_step - r.arrival_step) for r in done]
    out = {
        "engine": "fleet",
        "arch": cfg.name,
        "replicas": args.replicas,
        "tp": args.tp,
        "router": args.router,
        "disagg": args.disagg,
        "requests": len(done),
        "tokens_out": fleet["tokens_out"],
        "tok_per_s": round(fleet["tokens_out"] / wall, 1),
        "fleet_ticks": fleet["fleet_ticks"],
        "p50_latency_ticks": percentile(lat, 50),
        "p99_latency_ticks": percentile(lat, 99),
        "spillovers": fleet["spillovers"],
        "reroutes": fleet["reroutes"],
        "generated": [r.out_tokens[:8] for r in done[:4]],
        # where each replica's params and page pool live (one device per
        # replica on a multi-device host)
        "replica_devices": {str(i): rep.devices()
                            for i, rep in sorted(router.replicas.items())},
    }
    if args.chunked_prefill:
        out["chunked_prefill"] = args.chunked_prefill
        out["prefill_chunk_tokens"] = fleet.get("prefill_chunk_tokens", 0)
    if args.disagg:
        out["migrations"] = router.stats.get("migrations", 0)
    _spec_stats(out, args, fleet)
    _tier_stats(out, args, fleet)
    out.update(_prefix_stats(fleet))
    if fleet.get("reserved_page_imbalance") is not None:
        out["reserved_page_imbalance"] = fleet["reserved_page_imbalance"]
    if ctl is not None:
        out["autoscale"] = ctl.summary()
    _finish_obs(out, args, tracer, profiler, router.expose, ctl=ctl)
    return out, done


def run_paged(cfg, params, args) -> Tuple[dict, list]:
    if not supports_paged(cfg):
        raise SystemExit(f"{cfg.name}: use --engine static (MLA/enc-dec)")
    rng = np.random.RandomState(args.seed)
    max_seq = _max_seq(args)
    n_pg = PC.pages_for_len(max_seq, args.page_size)
    start_slots = 1 if args.autoscale else args.batch
    sched = ContinuousBatchingScheduler(
        cfg, params, max_slots=start_slots, page_size=args.page_size,
        num_pages=start_slots * n_pg + 1 if args.autoscale else None,
        max_seq_len=max_seq, prefix_cache=args.prefix_cache, tp=args.tp,
        prefill_budget=args.chunked_prefill, **_spec_kw(args),
        **_tier_kw(args))
    tracer = None
    if args.trace_out or (args.events_out and not args.autoscale):
        tracer = Tracer()
        sched.set_tracer(tracer)
    profiler = sched.enable_profiling() if args.profile else None
    ctl = None
    if args.autoscale:
        from repro.autoscale import AutoscaleController, CapacityBands
        bands = CapacityBands(min_slots=1, max_slots=args.batch,
                              min_pages=n_pg + 1,
                              max_pages=args.batch * n_pg + 1)
        ctl = AutoscaleController(sched, bands, eval_interval=2)
    for i, (prompt, gen) in enumerate(make_workload(cfg, rng, args)):
        sched.submit(prompt, gen, arrival_step=i // 2, **_submit_kw(args, i))

    t0 = time.time()
    done = ctl.run() if ctl else sched.run()
    wall = time.time() - t0
    toks = sched.stats["tokens_out"]
    out = {
        "engine": "paged",
        "arch": cfg.name,
        "tp": args.tp,
        "requests": len(done),
        "decode_steps": sched.stats["decode_steps"],
        "tokens_out": toks,
        "tok_per_s": round(toks / wall, 1),
        # under --autoscale the allocated width varies, so occupancy is
        # decode tokens over *paid* slot-ticks, not a fixed --batch width
        "mean_occupancy": round(
            (toks - sched.stats["prefills"])
            / max(ctl.slot_ticks if ctl is not None
                  else sched.stats["decode_steps"] * args.batch, 1), 3),
        "peak_pages": sched.stats["peak_pages"],
        "generated": [r.out_tokens[:8] for r in done[:4]],
    }
    if args.tp > 1:
        out["shards"] = sched.shard_stats()
    if args.chunked_prefill:
        out["chunked_prefill"] = args.chunked_prefill
        out["prefill_chunk_tokens"] = sched.stats["prefill_chunk_tokens"]
    _spec_stats(out, args, sched.stats)
    _tier_stats(out, args, sched.stats)
    out.update(_prefix_stats(sched.stats))
    if ctl is not None:
        out["autoscale"] = ctl.summary()
    _finish_obs(out, args, tracer, profiler, sched.registry.expose, ctl=ctl)
    return out, done


def parse_args(argv: Optional[List[str]] = None) -> argparse.Namespace:
    """Parse and validate launcher arguments (``argv`` None: sys.argv)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(ARCHS))
    ap.add_argument("--layers", type=int, default=None, metavar="N",
                    help="serve the arch's published config cut to its "
                    "first N layers (a whole number of layer_pattern "
                    "periods): every width as published, the whole "
                    "vocabulary, float32 params; the only cut is depth. "
                    "Default: the reduced config")
    ap.add_argument("--engine", default="static",
                    choices=("static", "paged"))
    ap.add_argument("--batch", type=int, default=4,
                    help="static batch / paged decode slots")
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--requests", type=int, default=None,
                    help="paged engine: workload size (default 8; with "
                    "--shared-prefix the default is personas x "
                    "users-per-persona and an explicit value caps it)")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--replicas", type=int, default=1,
                    help="paged engine: serve through the replicated "
                    "fabric with this many scheduler replicas (with "
                    "--autoscale this is the fleet ceiling)")
    ap.add_argument("--tp", type=int, default=1,
                    help="paged engine: tensor-parallel shard group width "
                    "— each scheduler/replica spans this many shards "
                    "(page pools and attention heads split tp ways; "
                    "tokens are byte-identical to --tp 1, see "
                    "docs/sharding.md)")
    ap.add_argument("--router", default="least-pages",
                    choices=("least-pages", "round-robin",
                             "prefix-affinity"),
                    help="fabric routing policy (--replicas > 1); "
                    "prefix-affinity sends a request to the replica whose "
                    "page pool caches its longest prompt prefix")
    ap.add_argument("--shared-prefix", action="store_true",
                    help="paged engine: serve a persona workload "
                    "(--personas system prompts x --users-per-persona "
                    "suffixes) so the copy-on-write prefix cache shares "
                    "each persona's pages and skips its prefill")
    ap.add_argument("--personas", type=int, default=4,
                    help="--shared-prefix: distinct shared system prompts")
    ap.add_argument("--users-per-persona", type=int, default=8,
                    help="--shared-prefix: concurrent users per persona")
    ap.add_argument("--persona-len", type=int, default=64,
                    help="--shared-prefix: tokens per persona prompt")
    ap.add_argument("--user-len", type=int, default=16,
                    help="--shared-prefix: max tokens per user suffix")
    ap.add_argument("--no-prefix-cache", dest="prefix_cache",
                    action="store_false", default=None,
                    help="disable shared-prefix admission (the no-sharing "
                    "baseline; default: on except MoE archs)")
    ap.add_argument("--chunked-prefill", type=int, default=None,
                    metavar="N",
                    help="paged engine: land each prompt in chunks of at "
                    "most N tokens per tick, interleaved with decode "
                    "ticks (tokens stay byte-identical to monolithic "
                    "prefill)")
    ap.add_argument("--disagg", type=int, nargs="?", const=1, default=0,
                    metavar="K",
                    help="fleet only: dedicate K replicas to prefill and "
                    "the rest to decode, with verbatim KV-page handoff "
                    "between the roles (requires --replicas > K)")
    ap.add_argument("--autoscale", action="store_true",
                    help="paged engine: start at 1 slot and let the "
                    "autoscale control plane move capacity inside "
                    "[1, --batch]; with --replicas > 1 the fleet "
                    "controller moves whole replicas instead (see "
                    "docs/autoscaling.md)")
    ap.add_argument("--events-out", default=None,
                    help="write the run's event log as JSON lines for "
                    "replay: scale decisions under --autoscale, the "
                    "request-lifecycle trace otherwise")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="paged engine: write the request-lifecycle trace "
                    "as Chrome trace-event JSON (open in Perfetto / "
                    "chrome://tracing; see docs/observability.md)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="paged engine: dump the typed metric registries "
                    "in Prometheus text exposition at end of run")
    ap.add_argument("--spec", type=int, default=None, metavar="K",
                    help="paged engine: speculative decoding — draft and "
                    "batch-verify up to K tokens per stream per tick "
                    "(greedy accept keeps tokens byte-identical to spec "
                    "off; drafts come from n-gram prompt lookup unless "
                    "--spec-draft names a model)")
    ap.add_argument("--spec-draft", default=None, metavar="ARCH",
                    choices=sorted(ARCHS),
                    help="reduced draft model for --spec (attention-only, "
                    "sharing the target's vocab), decoding through an "
                    "incremental paged cache mirroring the target's page "
                    "geometry; default is model-free n-gram lookup")
    ap.add_argument("--host-pages", type=int, default=None, metavar="N",
                    help="paged engine: host-RAM KV page tier of N pages "
                    "per scheduler — finished sessions' chains are "
                    "retained for resume and preempted to host RAM under "
                    "HBM pressure (recompute-vs-transfer cost model; see "
                    "docs/serving.md)")
    ap.add_argument("--tenants", default=None, metavar="NAME=PAGES,...",
                    help="per-tenant page quotas, e.g. free=32,pro=128; "
                    "workload requests round-robin over the tenants and "
                    "the first (sorted) tenant submits at priority 2 "
                    "(requires --host-pages)")
    ap.add_argument("--swap-crossover", type=int, default=None, metavar="T",
                    help="override the cost model's recompute-vs-transfer "
                    "decision point: chains of >= T tokens swap to host, "
                    "shorter ones re-prefill (default: derived from the "
                    "roofline constants in repro.obs.profile)")
    ap.add_argument("--profile", action="store_true",
                    help="paged engine: wall-time every kernel dispatch "
                    "and report modeled FLOPs/bytes + roofline fractions "
                    "in the result JSON (read-only; tokens unchanged)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.layers is not None:
        full = get_arch(args.arch)
        period = len(full.layer_pattern)
        if not 1 <= args.layers <= full.n_layers or args.layers % period:
            ap.error(f"--layers must be a multiple of {full.name}'s "
                     f"layer_pattern period ({period}) in [1, "
                     f"{full.n_layers}]")
    if args.autoscale and args.engine != "paged":
        ap.error("--autoscale requires --engine paged")
    for flag, val in (("--events-out", args.events_out),
                      ("--trace-out", args.trace_out),
                      ("--metrics-out", args.metrics_out),
                      ("--profile", args.profile)):
        if val and args.engine != "paged":
            ap.error(f"{flag} requires --engine paged (the static engine "
                     "has no scheduler to observe)")
    if args.replicas > 1 and args.engine != "paged":
        ap.error("--replicas requires --engine paged (the fabric routes "
                 "over paged schedulers)")
    if args.shared_prefix and args.engine != "paged":
        ap.error("--shared-prefix requires --engine paged (only the paged "
                 "cache can share prefix pages)")
    if args.replicas < 1:
        ap.error("--replicas must be >= 1")
    if args.tp < 1:
        ap.error("--tp must be >= 1")
    if args.tp > 1 and args.engine != "paged":
        ap.error("--tp requires --engine paged (shard groups split the "
                 "paged KV pools)")
    if args.chunked_prefill is not None:
        if args.engine != "paged":
            ap.error("--chunked-prefill requires --engine paged")
        if args.chunked_prefill < 1:
            ap.error("--chunked-prefill must be >= 1")
    if args.spec is not None:
        if args.engine != "paged":
            ap.error("--spec requires --engine paged (speculation lives in "
                     "the continuous-batching scheduler)")
        if not 1 <= args.spec <= 32:
            ap.error("--spec must be in [1, 32]")
    if args.spec_draft and args.spec is None:
        ap.error("--spec-draft requires --spec")
    if args.disagg:
        if args.engine != "paged" or args.replicas < 2:
            ap.error("--disagg requires --engine paged and --replicas >= 2 "
                     "(one replica per role at minimum)")
        if args.disagg >= args.replicas:
            ap.error("--disagg must leave at least one decode replica "
                     "(--disagg < --replicas)")
    if args.host_pages is not None:
        if args.engine != "paged":
            ap.error("--host-pages requires --engine paged (the host tier "
                     "holds paged KV chains)")
        if args.host_pages < 1:
            ap.error("--host-pages must be >= 1")
        if args.prefix_cache is False:
            ap.error("--host-pages requires the prefix cache (session "
                     "chains are retained through the prefix index; drop "
                     "--no-prefix-cache)")
    for flag, val in (("--tenants", args.tenants),
                      ("--swap-crossover", args.swap_crossover)):
        if val is not None and args.host_pages is None:
            ap.error(f"{flag} requires --host-pages (tier features live "
                     "on the host-RAM page tier)")
    if args.swap_crossover is not None and args.swap_crossover < 1:
        ap.error("--swap-crossover must be >= 1")
    if args.tenants is not None:
        try:
            _parse_tenants(args.tenants)
        except ValueError as e:
            ap.error(str(e))
    return args


def model_config(args: argparse.Namespace):
    """The config ``args`` serve: the reduced one, or with ``--layers N``
    the published one cut to its first N layers."""
    if args.layers is None:
        return get_reduced(args.arch)
    return dataclasses.replace(get_arch(args.arch), n_layers=args.layers)


def serve(args: argparse.Namespace, params=None) -> Tuple[dict, list]:
    """Serve the workload ``args`` describe: (result dict, finished
    requests; empty for the static engine). ``params`` are weights for
    ``model_config(args)`` made by the caller; by default the launcher
    makes them with ``M.init`` from ``--seed``."""
    cfg = model_config(args)
    if params is None:
        params = M.init(cfg, jax.random.PRNGKey(args.seed))
    if args.engine != "paged":
        runner = run_static
    elif args.replicas > 1:
        runner = run_fleet
    else:
        runner = run_paged
    return runner(cfg, params, args)


def main(argv: Optional[List[str]] = None) -> None:
    out, _ = serve(parse_args(argv))
    print(json.dumps(out))


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
