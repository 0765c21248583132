"""Continuous-batching serving scheduler over the paged KV cache.

The dense engine (``repro.serving.engine``) decodes one fixed batch until
its *longest* member finishes — occupancy decays as short requests drain,
and a new request waits for the whole batch. This scheduler keeps a fixed
set of decode *slots* and runs one jit-compiled paged decode step per tick:

* **join-on-arrival** — a waiting request is prefilled and inserted into
  any free slot between decode steps (no reshape, no recompile: the step
  function's shapes are fixed at ``(max_slots, 1)``);
* **evict-on-finish** — a finished request frees its pages and its slot the
  same tick, so the next arrival takes over immediately;
* **prefill/decode interleave** — admission runs between decode ticks;
  prefill is batch-1, bucketed to a small set of padded lengths so mixed
  prompt lengths share compilations (right padding is causally invisible).

Greedy sampling, like the dense engine. Admission uses worst-case page
reservation (``ceil((prompt + max_new) / page_size)`` pages), so a request
that is admitted can never hit a mid-flight pool OOM. Page-pool sizing for
a target arch/shape comes from ``repro.core.blueprint.serving_page_plan``,
and the provisioning layer exposes it as the "serve" service
(``repro.core.services.AmbariServer.provision_serving``).

Admission also consults the **shared-prefix cache** (``prefix_cache=True``
default for non-MoE archs): the longest in-flight prompt prefix already
holding the request's tokens is shared page-for-page (refcounted; a
mid-page match is copy-on-write forked), only the uncached suffix is
prefilled, and the reservation charges only that suffix — fleet chat
traffic with N personas × M users pays one persona prefill instead of M.
See docs/serving.md "Shared prefixes" for the COW state diagram and the
determinism contract.

Works for decoder-only archs without MLA attention; SSM/hybrid and MoE
archs are supported with exact-length prefill (an SSM state folds padding
in; MoE routing lets padding compete for expert capacity). One caveat for
MoE at multi-slot: the decode router groups all slots' tokens under one
capacity bound (exactly like the dense engine's batch), so concurrent
requests can influence each other's routing when capacity binds — the
late-join byte-determinism guarantee is for dense/SSM archs. See
docs/serving.md for the API walk-through and tuning knobs.

**Chunked prefill** (``prefill_budget=N``): instead of one monolithic
prefill call that blocks every decode tick behind a long prompt, admission
only allocates the prompt's pages and the prompt then lands in chunks of
at most ``N`` tokens per tick, interleaved with decode ticks — the request
sits in the PREFILLING state (``req.prefill_pos`` is the chunk cursor)
and joins decode the tick its last chunk lands. The first chunk is a
bucketed batch-1 prefill; later chunks ride the shared-prefix suffix
paths (``_suffix_fn`` for dense archs, ``_seq_suffix_fn`` from the slot's
SSM state for hybrid/MoE), so chunked output is byte-identical to
monolithic at fp32 — the same contract the prefix cache proves. Budget is
spent FCFS over in-flight prefills, so the oldest admitted prefill always
advances (no starvation) and per-tick chunk tokens never exceed ``N``.

**Disaggregation** (``role="prefill" | "decode"``): a prefill-role
scheduler admits and prefills but never decodes — a completed prompt
*parks* (``handoff_ready``) until the fabric router migrates its KV pages
verbatim to a decode-role scheduler (``adopt`` / ``surrender_slot``,
refcount- and prefix-index-correct on both sides). Prefill-role admission
reserves only the prompt's pages (the decode side reserves worst-case on
adopt), so a prefill replica's pool turns over at prompt, not
prompt+generation, granularity.

The request dataclass and its lifecycle live in ``repro.serving.request``
(shared with the static engine and the fabric router); this module is the
single-scheduler core only. One scheduler drives one page pool — a fleet
of them behind ``repro.serving.router.ServingRouter`` is the replicated
serving fabric, with each scheduler wrapped as a
``repro.serving.replica.ServingReplica`` placed on a cluster node.

``tp > 1`` makes the scheduler a *shard group*: one logical scheduler
whose page pools split into per-shard kv-head slices across ``tp`` devices
(placed on ``tp`` cluster nodes by ``provision_serving``), with the block
table, allocator, prefix index, and admission ledger staying a single
control plane. Decoded tokens are byte-identical to ``tp=1`` for dense
archs — see docs/sharding.md for the determinism contract and the
per-shard page-budget math.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
from typing import Any, Deque, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import flags as model_flags
from repro.models import model as M
from repro.models.transformer import lm_forward
from repro.obs.metrics import MetricsRegistry, StatsView, TICK_BUCKETS
from repro.parallel.context import ShardGroup
from repro.serving import paged_cache as PC
from repro.serving.request import Request, make_request

DEFAULT_BUCKETS = (8, 16, 24, 32, 48, 64, 96, 128, 192, 256, 384, 512)

__all__ = ["ContinuousBatchingScheduler", "DEFAULT_BUCKETS", "Request",
           "clear_program_cache", "program_cache_size", "spec_accept",
           "supports_paged"]

_EMPTY_DRAFT = np.zeros((0,), np.int32)


def spec_accept(drafts, targets) -> int:
    """Greedy rejection sampling, argmax edition: the number of leading
    draft tokens equal to the target model's argmax at the same position.

    ``targets[i]`` is the target argmax given the context plus drafts
    ``< i`` — accepted drafts are exactly the tokens spec-off greedy
    decoding would have emitted, so acceptance preserves byte identity by
    construction. The verify tick emits ``accepted + 1`` tokens: the
    accepted prefix plus the target's correction (or bonus) token. Pure
    host-side rule — the hypothesis ledger machine drives it directly.
    """
    j = 0
    for d, t in zip(drafts, targets):
        if int(d) != int(t):
            break
        j += 1
    return j

# Compiled prefill-family programs shared across *every* scheduler instance
# in the process. A fleet of replicas (router / autoscaler / disaggregation
# benches) builds schedulers with identical (cfg, bucket, tp) shapes; before
# this cache each instance held private ``{n: jit fn}`` dicts and re-traced
# the same programs per replica — the direct cause of the chunked-prefill
# throughput gap serve_bench measured (415.8 -> 192.2 tok/s), since a
# benchmark sweep rebuilds its scheduler per scenario. Keyed on everything
# a program closes over: kind, padded length, the (hashable) ModelConfig,
# page size, the shard group's identity, and the baked-in prefill-kernel
# flag. jit itself dedups by argument shape under each entry, so differing
# block-table widths (max_seq_len) share one entry without confusion.
_PROGRAM_CACHE: Dict[Any, Any] = {}


def program_cache_size() -> int:
    return len(_PROGRAM_CACHE)


def clear_program_cache() -> None:
    """Drop every cached prefill program (tests / leak-hunting hook)."""
    _PROGRAM_CACHE.clear()


def supports_paged(cfg: ModelConfig) -> bool:
    return not cfg.is_encdec and cfg.attn_impl != "mla"


@dataclasses.dataclass
class _RetainedChain:
    """A finished stream's page chain kept warm for session resume.

    Device-resident records (``_retained``) hold the allocator refs the
    finished stream held — nothing is freed at finish — so the chain stays
    prefix-shareable at zero cost until HBM pressure preempts it. Host
    records (``_host_chains``) list *host-tier* page ids after a swap-out.
    ``tokens`` is the chain length the cost model prices a resume at;
    ``(priority, step)`` orders eviction (lowest class first, coldest
    first within a class)."""
    pages: List[int]
    tokens: int
    priority: int
    tenant: str
    step: int


class ContinuousBatchingScheduler:
    """Admission + continuous batching loop over ``max_slots`` decode slots.

    Parameters mirror ``serving_page_plan``'s output: ``page_size`` tokens
    per page, ``num_pages`` in the shared pool (page 0 is the sink),
    ``max_seq_len`` bounds prompt+generation and fixes the block-table
    width.
    """

    def __init__(self, cfg: ModelConfig, params: Any, *, max_slots: int = 4,
                 page_size: int = 16, num_pages: Optional[int] = None,
                 max_seq_len: int = 512,
                 prefill_buckets: Sequence[int] = DEFAULT_BUCKETS,
                 prefix_cache: Optional[bool] = None, tp: int = 1,
                 shard_mesh=None, prefill_budget: Optional[int] = None,
                 role: str = "mixed", prefill_fused: Optional[bool] = None,
                 prefill_kernel: bool = False,
                 spec_k: Optional[int] = None, spec_draft=None,
                 host_pages: Optional[int] = None,
                 tenant_quotas: Optional[Dict[str, int]] = None,
                 swap_crossover: Optional[int] = None, device: Any = None):
        if not supports_paged(cfg):
            raise NotImplementedError(
                f"{cfg.name}: paged serving covers decoder-only non-MLA "
                "archs; use repro.serving.engine for this one")
        self.cfg = cfg
        # placement: with ``device`` set (one device per fleet replica, see
        # ServingRouter) the params, draft params and page pools are
        # committed to it, so every program of this scheduler runs there;
        # None keeps JAX's default device
        self.device = device
        if device is not None:
            params = jax.device_put(params, device)
            if spec_draft is not None:
                spec_draft = (spec_draft[0],
                              jax.device_put(spec_draft[1], device))
        self.params = params
        self.page_size = page_size
        self.max_slots = max_slots
        self.max_seq_len = max_seq_len
        # chunked prefill: at most this many prompt tokens land per tick
        # (None = monolithic prefill at admission, the pre-chunking path)
        if prefill_budget is not None and prefill_budget < 1:
            raise ValueError("prefill_budget must be >= 1 token per tick")
        self.prefill_budget = prefill_budget
        # disaggregation role: "mixed" (default) prefills and decodes;
        # "prefill" parks completed prompts for page handoff; "decode"
        # adopts handed-off streams and only decodes
        if role not in ("mixed", "prefill", "decode"):
            raise ValueError(f"unknown scheduler role {role!r}")
        self.role = role
        # tensor-parallel shard group: one logical scheduler/replica whose
        # page pools, attention heads, and MoE experts split tp ways while
        # the block table / allocator / prefix index stay one control plane
        self.tp = tp
        self.shard = ShardGroup(tp, mesh=shard_mesh) if tp > 1 else None
        if self.shard is not None:
            self.shard.validate_model(cfg)
        self.n_pg = PC.pages_for_len(max_seq_len, page_size)
        if num_pages is None:
            num_pages = max_slots * self.n_pg + 1        # + sink
        # SSM state folds every processed token in, and MoE routing makes
        # tokens compete for expert capacity — bucket padding would change
        # real tokens' results for either, so such archs prefill exact-length
        # (one compile per distinct prompt length).
        self._has_ssm = any(cfg.block_kind(i) == "ssm"
                            for i in range(cfg.n_layers))
        self.exact_prefill = cfg.n_routed_experts > 0 or self._has_ssm
        # fused prefill: land prompt tokens directly in their pages with
        # paged_prefill_step — one dispatch per chunk instead of the
        # prefill+insert pair (first chunk) or the batched-rows suffix trick
        # (every row a full-pool gather). Exact-prefill archs keep the
        # sequential paths: an SSM state must fold tokens in order, and MoE
        # capacity grouping differs between the fused chunk and the decode
        # steps the byte-determinism contract compares against.
        if prefill_fused is None:
            prefill_fused = not self.exact_prefill
        self.prefill_fused = bool(prefill_fused) and not self.exact_prefill
        # bake the Pallas write+attend kernel pair into the fused programs
        # (interpret mode only on the CPU backend, see
        # repro.kernels.resolve_interpret; flags.use_prefill_kernel at
        # trace time)
        self.prefill_kernel = bool(prefill_kernel)
        # speculative decoding: each verify tick runs every decoding slot's
        # last token plus up to spec_k draft tokens as parallel rows of one
        # paged decode dispatch, greedy-accepts the longest matching prefix,
        # and rolls the rejected tail back (seq_lens; SSM snapshots for
        # hybrids). Greedy accept keeps emitted tokens byte-identical to
        # spec-off decoding — the serve_bench --spec hard gate.
        if spec_k is not None and not 1 <= spec_k <= 32:
            raise ValueError("spec_k must be in [1, 32] draft tokens per "
                             "tick (bounds the verify row count)")
        if spec_draft is not None and spec_k is None:
            raise ValueError("spec_draft needs spec_k set")
        if spec_k is not None and cfg.n_routed_experts > 0:
            raise ValueError(
                "speculative decoding needs byte-deterministic decode; MoE "
                "capacity grouping couples concurrent tokens (the multi-slot "
                "caveat in docs/serving.md), so spec_k covers dense/SSM "
                "archs only")
        if spec_draft is not None:
            dcfg = spec_draft[0]
            if dcfg.is_encdec:
                raise ValueError("draft model must be decoder-only")
            if dcfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"draft model must share the tokenizer: draft vocab "
                    f"{dcfg.vocab_size} != target {cfg.vocab_size}")
            if dcfg.n_routed_experts > 0 or any(
                    dcfg.block_kind(i) == "ssm"
                    for i in range(dcfg.n_layers)):
                raise ValueError(
                    "draft model must be attention-only: the incremental "
                    "draft cache rolls rejected positions back by length "
                    "masking, which has no SSM-state or MoE analogue")
        self.spec_k = spec_k
        self.spec_draft = spec_draft            # (draft_cfg, draft_params)
        self.buckets = tuple(sorted(b for b in prefill_buckets
                                    if b <= max_seq_len))
        # shared-prefix cache: admission shares the longest cached prefix's
        # pages and prefills only the uncached suffix. Hybrid archs can only
        # resume where an SSM state snapshot exists (exact-entry hits).
        # Default: on, except for MoE archs — a cached suffix steps through
        # the decode router one token at a time, grouping expert capacity
        # differently than the full prefill it replaces, which breaks the
        # byte-determinism contract the fleet's re-prefill path relies on.
        # MoE archs may still opt in (prefix_cache=True) where approximate
        # token identity under capacity pressure is acceptable.
        if prefix_cache is None:
            prefix_cache = cfg.n_routed_experts == 0
        self.prefix_cache = prefix_cache
        self.index = PC.PrefixIndex(page_size)
        # host-RAM page tier: finished streams' chains are *retained* on
        # device (still prefix-shareable) instead of freed, and under HBM
        # pressure admission preempts the coldest retained chains — the
        # recompute-vs-transfer cost model decides per chain whether its
        # bytes move to host RAM (long chains: PCIe transfer beats prefill
        # FLOPs) or are dropped for re-prefill on resume (short chains).
        # Retention requires the prefix cache: a retained chain is only
        # reachable through the index. ``tenant_quotas`` caps the pages a
        # tenant's *live* streams may reserve (retained chains are not
        # charged: they are reclaimable, so they cost the tenant nothing).
        if host_pages is not None and host_pages < 1:
            raise ValueError("host_pages must be >= 1 (or None to disable "
                             "the host tier)")
        self.host_tier = (PC.HostPageTier(host_pages)
                          if host_pages is not None else None)
        # recompute-vs-transfer decision point, in chain tokens: chains at
        # least this long swap to host (PCIe transfer beats re-prefill
        # FLOPs), shorter ones drop and re-prefill on resume. Default:
        # derived from the cfg's roofline cost model — the comparison is
        # monotone in chain length, so the smallest length where transfer
        # wins summarizes it exactly (None: transfer never wins at this
        # model scale, every preemption re-prefills). Benches/operators may
        # override to place the crossover inside their workload.
        if swap_crossover is not None:
            self._swap_crossover: Optional[int] = int(swap_crossover)
        else:
            self._swap_crossover = PC.swap_crossover_tokens(cfg, page_size)
        self.tenant_quotas = dict(tenant_quotas) if tenant_quotas else None
        self._tenant_reserved: Dict[str, int] = {}
        self._retained: Dict[int, _RetainedChain] = {}
        self._host_chains: Dict[int, _RetainedChain] = {}
        self._host_page_chain: Dict[int, int] = {}   # host page -> chain key
        self._retain_seq = 0
        if self.host_tier is not None:
            # freed host pages invalidate index entries by their *tagged*
            # id — same one-control-plane rule as the device allocator
            self.host_tier.alloc.on_free = (
                lambda p: self.index.invalidate_page(PC.as_host_page(p)))

        self.cache = self._place(
            lambda: PC.init_paged_cache(cfg, num_pages, page_size, max_slots,
                                        tp=tp))
        # incremental draft-model cache: a parallel (unsharded) page pool at
        # the DRAFT's dims mirroring the target's page geometry 1:1 — the
        # draft reuses the target's block tables verbatim, so page alloc /
        # free / COW need no second ledger. Per tick the draft advances by
        # one teacher-forced token (the stream's last committed token, the
        # same input verify row 0 gets) plus spec_k greedy steps: O(k) draft
        # work per tick instead of re-prefilling the context. The cache is
        # best-effort state: stale or collided bytes (COW sharing, a
        # migration) only lower the accept rate — every draft token is
        # target-verified, so emitted tokens never depend on it.
        if spec_draft is not None:
            self._draft_cache = self._place(
                lambda: PC.init_paged_cache(spec_draft[0], num_pages,
                                            page_size, max_slots))
            self._draft_ready = [False] * max_slots
        self.alloc = PC.PageAllocator(num_pages)
        self.alloc.on_free = self.index.invalidate_page
        self.block_table = np.full((max_slots, self.n_pg), PC.SINK_PAGE,
                                   np.int32)
        self.seq_lens = np.zeros((max_slots,), np.int32)
        self.last_tokens = np.zeros((max_slots, 1), np.int32)
        self.slot_req: List[Optional[Request]] = [None] * max_slots
        self.slot_pages: List[List[int]] = [[] for _ in range(max_slots)]
        # per-slot admission bookkeeping: pages charged against the pool
        # (net of shared prefix pages) and the shared-page count itself
        self.slot_reserve: List[int] = [0] * max_slots
        self.slot_shared: List[int] = [0] * max_slots
        # chunked-prefill bookkeeping: SSM resume snapshot for a slot's next
        # chunk (set by a prefix hit; None = read the slot's live state),
        # parked flag (prefill role: done, awaiting page handoff), and the
        # FCFS order budget is spent in (slot ids, admit order)
        self.slot_resume_state: List[Any] = [None] * max_slots
        self.slot_parked: List[bool] = [False] * max_slots
        self._prefill_fifo: List[int] = []
        self.waiting: Deque[Request] = collections.deque()
        self.finished: List[Request] = []
        self._admit_done: List[Request] = []
        self.step_idx = 0
        self.reserved_pages = 0
        # live resize (repro.autoscale): slots above target_slots are
        # draining — no new admissions; the arrays shrink once they empty
        self.target_slots = max_slots
        # a controller may promise future pool growth up to this many pages
        # so submit() validates against the band ceiling, not today's pool
        self.capacity_hint: Optional[int] = None
        # observability plane (repro.obs): every legacy ``stats`` key is
        # backed by a typed registry metric — StatsView keeps the dict
        # idioms (``stats["x"] += 1``, ``dict(stats)``) working while the
        # registry gains Prometheus exposition and latency histograms.
        # All hooks are read-only over scheduler state: tracing/metrics/
        # profiling on vs off is byte-identical in emitted tokens.
        self.replica_id: Optional[int] = None   # set by ServingReplica
        self.tracer = None                      # set via set_tracer
        self._trace_own_clock = True            # router flips: fleet clock
        self.profiler = None                    # set via enable_profiling
        self.registry = MetricsRegistry()
        _gauges = ("peak_pages", "spec_accept_rate", "host_pages_used",
                   "retained_pages")
        self.stats = StatsView({
            k: (self.registry.gauge if k in _gauges
                else self.registry.counter)(f"serving_{k}", unit=u)
            for k, u in (("decode_steps", "ticks"), ("tokens_out", "tokens"),
                         ("prefills", "requests"), ("peak_pages", "pages"),
                         ("admit_blocked", "ticks"), ("resizes", ""),
                         ("prefix_hits", "requests"),
                         ("prefix_misses", "requests"),
                         ("cached_tokens", "tokens"), ("cow_forks", "pages"),
                         ("prefill_chunk_tokens", "tokens"),
                         ("migrations_in", "streams"),
                         ("migrations_out", "streams"),
                         ("prefill_compiles", "programs"),
                         ("prefill_dispatches", "dispatches"),
                         ("spec_ticks", "ticks"),
                         ("spec_drafted", "tokens"),
                         ("spec_accepted", "tokens"),
                         ("spec_accept_rate", ""),
                         ("swap_outs", "chains"),
                         ("swap_out_pages", "pages"),
                         ("swap_ins", "chains"),
                         ("swap_in_pages", "pages"),
                         ("swap_reprefills", "chains"),
                         ("host_evictions", "chains"),
                         ("quota_blocked", "requests"),
                         ("index_evictions", "entries"),
                         ("host_pages_used", "pages"),
                         ("retained_pages", "pages"))})
        self.index.on_evict = self._on_index_evict
        self.h_queue_wait = self.registry.histogram(
            "serving_queue_wait_ticks", TICK_BUCKETS, unit="ticks",
            help="ticks from due arrival to admission")
        self.h_ttft = self.registry.histogram(
            "serving_ttft_ticks", TICK_BUCKETS, unit="ticks",
            help="ticks from due arrival to first output token")
        self.h_latency = self.registry.histogram(
            "serving_latency_ticks", TICK_BUCKETS, unit="ticks",
            help="ticks from due arrival to finish")
        # integer unit-width bounds: emitted-per-verify is a small integer,
        # so quantile() is exact (boundary-valued data, cf. log_buckets)
        self.h_spec_accept = self.registry.histogram(
            "serving_spec_accept_tokens",
            tuple(float(b) for b in range(1, 34)), unit="tokens",
            help="tokens emitted per speculative verify (accepted + 1)")
        self.h_resume = self.registry.histogram(
            "serving_resume_ticks", TICK_BUCKETS, unit="ticks",
            help="ticks from due arrival to admission for streams resumed "
                 "via host-tier swap-in")

        # donate the cache: pools are sized to fill HBM, so the step must
        # update them in place rather than double-buffer (cf. trainer.py)
        self._decode_fn = jax.jit(
            functools.partial(self._decode_multi, cfg, self.shard),
            static_argnames=("k",), donate_argnums=(1,))
        # prefill-family programs live in the module-level _PROGRAM_CACHE,
        # shared across instances; this key captures what they close over
        self._shard_key = (None if self.shard is None
                           else (self.shard.tp, self.shard.axis,
                                 self.shard.mesh))
        self._cow_fn = jax.jit(functools.partial(PC.copy_page, tp=tp),
                               donate_argnums=(0,))
        self._rid = 0

    def _place(self, make):
        """``make()``'s arrays, created on and committed to ``self.device``
        (built there directly: a pool sized for one device's HBM must not
        pass through another's)."""
        if self.device is None:
            return make()
        with jax.default_device(self.device):
            return jax.device_put(make(), self.device)

    # ------------------------------------------------------------ jit fns --
    @staticmethod
    def _decode_multi(cfg, shard, params, cache, tokens, seq_lens,
                      block_table, *, k: int):
        """``k`` fused greedy decode ticks in one lax.scan (one dispatch).

        The host loop picks ``k`` so that no request finishes and no arrival
        becomes admissible mid-scan — fusion is a pure dispatch-overhead
        optimisation, token-for-token identical to k=1 stepping.
        Returns (tokens (k, B), new_cache).
        """
        def body(carry, _):
            toks, lens, cc = carry
            lg, cc = M.paged_decode_step(cfg, params, cc, toks, lens,
                                         block_table, shard=shard)
            nxt = jnp.argmax(lg[:, -1, :cfg.vocab_size],
                             axis=-1).astype(jnp.int32)
            return (nxt[:, None], lens + 1, cc), nxt

        (_, _, new_cache), outs = jax.lax.scan(
            body, (tokens, seq_lens, cache), None, length=k)
        return outs, new_cache

    def _get_program(self, kind: str, n: int, build):
        """Fetch (or build and share) the compiled program ``kind``@``n``.

        Misses count as ``prefill_compiles``; a second scheduler with the
        same (cfg, tp, page size, kernel flag) reuses the entry for free.
        """
        key = (kind, n, self.cfg, self.page_size, self._shard_key,
               self.prefill_kernel)
        fn = _PROGRAM_CACHE.get(key)
        if fn is None:
            fn = _PROGRAM_CACHE[key] = build()
            self.stats["prefill_compiles"] += 1
        return fn

    def _prefill_fn(self, n: int):
        """Batch-1 prefill at padded length ``n``; logits taken at the live
        prompt's last position (right padding is causally invisible)."""
        cfg = self.cfg

        def build():
            def fn(params, tokens, plen):
                positions = None
                if cfg.rope_variant == "mrope":
                    pos = jnp.broadcast_to(
                        jnp.arange(n, dtype=jnp.int32)[None], (1, n))
                    positions = jnp.broadcast_to(pos[None], (3, 1, n))
                hidden, _, pre = lm_forward(cfg, params, tokens,
                                            positions=positions,
                                            mode="prefill")
                h_last = jax.lax.dynamic_slice_in_dim(hidden, plen - 1, 1,
                                                      axis=1)
                lg = M.final_logits(cfg, params, h_last)
                tok = jnp.argmax(lg[0, -1, :cfg.vocab_size]).astype(jnp.int32)
                return tok, pre

            return jax.jit(fn)

        return self._get_program("prefill", n, build)

    def _insert_fn(self, n: int):
        cfg, ps, tp = self.cfg, self.page_size, self.tp

        def build():
            def fn(cache, pre, block_row, slot, plen):
                return PC.write_prefill(cfg, cache, pre, block_row, slot,
                                        plen, n, ps, tp=tp)

            return jax.jit(fn, donate_argnums=(0,))

        return self._get_program("insert", n, build)

    def _chunk_fn(self, n: int):
        """Fused chunk program at padded length ``n`` (dense archs).

        One dispatch lands ``s_live`` prompt tokens at position ``start``
        directly in the sequence's pages (``M.paged_prefill_step``: scatter
        or the Pallas write kernel, then prefix+chunk attention over the
        pages — no contiguous KV intermediate, no separate insert call) and
        reads the next-token logits at the chunk's last live row. Serves
        monolithic admission (start=0, s_live=plen), shared-prefix suffixes,
        and every chunked-prefill chunk — replacing the prefill+insert pair
        and the batched-rows suffix trick (whose ``n`` rows each gathered
        the full pool). ``self.prefill_kernel`` is baked in at trace time.
        """
        cfg, shard, kernel = self.cfg, self.shard, self.prefill_kernel

        def build():
            def fn(params, cache, tokens, start, s_live, row):
                with model_flags.use_prefill_kernel(kernel):
                    hidden, cache = M.paged_prefill_step(
                        cfg, params, cache, tokens[None], start[None],
                        s_live[None], row[None], shard=shard)
                h_last = jax.lax.dynamic_slice_in_dim(hidden[0], s_live - 1,
                                                      1, axis=0)
                lg = M.final_logits(cfg, params, h_last[None])
                tok = jnp.argmax(lg[0, -1, :cfg.vocab_size]).astype(jnp.int32)
                return tok, cache

            return jax.jit(fn, donate_argnums=(1,))

        return self._get_program("chunk", n, build)

    def _suffix_fn(self, n: int):
        """Batched suffix prefill at padded length ``n`` (dense archs).

        The uncached suffix's tokens run as ``n`` parallel rows of one
        paged decode step: row ``i`` carries position ``start + i``, every
        row shares the sequence's block-table row, and all rows' K/V are
        scattered into the pages *before* attention — so row ``i`` attends
        the shared prefix pages plus suffix positions ``<= i``, which is
        exactly causal prefill continued from ``start``. Padding rows are
        routed to the sink page (position 0) and discarded; logits are read
        at the live suffix's last row.
        """
        cfg, shard = self.cfg, self.shard

        def build():
            def fn(params, cache, tokens, start, s_live, row):
                i = jnp.arange(n, dtype=jnp.int32)
                live = i < s_live
                lens = jnp.where(live, start + i, 0).astype(jnp.int32)
                bt = jnp.where(live[:, None], row[None, :],
                               PC.SINK_PAGE).astype(jnp.int32)
                lg, cache = M.paged_decode_step(cfg, params, cache,
                                                tokens[:, None], lens, bt,
                                                shard=shard)
                last = jax.lax.dynamic_slice_in_dim(lg[:, -1, :],
                                                    s_live - 1, 1, axis=0)
                tok = jnp.argmax(last[0, :cfg.vocab_size]).astype(jnp.int32)
                return tok, cache

            return jax.jit(fn, donate_argnums=(1,))

        return self._get_program("suffix", n, build)

    def _seq_suffix_fn(self, s: int):
        """Sequential suffix continuation at exact length ``s`` (SSM and
        MoE archs). A lax.scan of batch-1 paged decode steps threads the
        SSM slot state token by token from the cached snapshot (``state``;
        None for pure-MoE archs, whose suffix still must step one token at
        a time so expert capacity groups match decode's) and writes each
        suffix token's K/V into the sequence's pages."""
        cfg, shard = self.cfg, self.shard

        def build():
            def fn(params, cache, state, tokens, start, row, slot):
                view = PC.ssm_slot_view(cache, state)
                bt = row[None, :].astype(jnp.int32)

                def body(carry, tok):
                    cl, vw = carry
                    lg, vw = M.paged_decode_step(cfg, params, vw,
                                                 tok[None, None],
                                                 cl[None], bt, shard=shard)
                    return (cl + 1, vw), lg[0, -1]

                (_, view), lgs = jax.lax.scan(
                    body, (jnp.asarray(start, jnp.int32), view), tokens)
                tok = jnp.argmax(lgs[-1, :cfg.vocab_size]).astype(jnp.int32)
                if state is None:
                    return tok, view
                return tok, PC.merge_ssm_slot(cache, view, slot)

            return jax.jit(fn, donate_argnums=(1,))

        return self._get_program("seq_suffix", s, build)

    # -------------------------------------------------- speculative decode --
    def _verify_fn(self, n: int):
        """Grouped speculative verify, ``n = spec_k + 1`` rows per slot
        (dense archs). tokens (S, n): slot ``s``'s row 0 carries its last
        real token at position ``seq_lens[s]``, rows ``1..cap`` its draft
        tokens at the following positions; ``live`` (S,) is ``cap + 1``
        (0 masks a non-decoding slot onto the sink page). One fused
        paged-prefill dispatch (``M.paged_verify_step``) gathers each
        stream's pages once, lands all rows' K/V, and returns the per-row
        argmax — the target tokens the host's ``spec_accept`` compares
        drafts against. ``self.prefill_kernel`` is baked in at trace time,
        so verify rides the Pallas write+attend kernels exactly like
        chunked prefill.
        """
        cfg, shard, kernel = self.cfg, self.shard, self.prefill_kernel

        def build():
            def fn(params, cache, tokens, lens, bt, live):
                with model_flags.use_prefill_kernel(kernel):
                    lg, cache = M.paged_verify_step(cfg, params, cache,
                                                    tokens, lens, live, bt,
                                                    shard=shard)
                outs = jnp.argmax(lg[..., :cfg.vocab_size],
                                  axis=-1).astype(jnp.int32)
                return outs, cache

            return jax.jit(fn, donate_argnums=(1,))

        return self._get_program("verify", n, build)

    def _verify_seq_fn(self, n: int):
        """Sequential speculative verify for SSM/hybrid archs: a lax.scan
        of ``n`` full-batch paged decode steps teacher-forced with the
        draft matrix, collecting per-step argmax *and* per-step SSM
        snapshots (``PC.ssm_leaves``). Acceptance is computed in-dispatch
        (cumprod of draft==argmax matches) and ``PC.select_ssm_steps``
        rolls every slot's SSM state back to its accepted step — the PR-6
        snapshot rule per verified token, so a partial reject leaves the
        recurrence exactly where spec-off decoding would have.
        """
        cfg, shard = self.cfg, self.shard

        def build():
            def fn(params, cache, tokens, lens0, bt, live):
                # tokens (S, n); lens0/live (S,); bt (S, n_pg)
                xs = jnp.moveaxis(tokens, 1, 0)[:, :, None]    # (n, S, 1)

                def body(carry, tok):
                    lens, cc = carry
                    lg, cc = M.paged_decode_step(cfg, params, cc, tok, lens,
                                                 bt, shard=shard)
                    out = jnp.argmax(lg[:, -1, :cfg.vocab_size],
                                     axis=-1).astype(jnp.int32)
                    return (lens + 1, cc), (out, PC.ssm_leaves(cc))

                (_, cache), (outs, states) = jax.lax.scan(
                    body, (lens0, cache), xs)
                # draft i (row i of the token matrix) is accepted iff it
                # equals the argmax of row i-1 and sits below the live count
                i = jnp.arange(1, n)[:, None]                  # (n-1, 1)
                match = ((outs[:-1] == jnp.moveaxis(tokens, 1, 0)[1:])
                         & (i < live[None, :]))
                j = jnp.cumprod(match.astype(jnp.int32), axis=0).sum(axis=0)
                cache = PC.select_ssm_steps(cache, states, j)
                return outs, j, cache

            return jax.jit(fn, donate_argnums=(1,))

        return self._get_program("spec_seq", n, build)

    def _draft_prefill_fn(self, n: int):
        """Draft-cache catch-up program at padded length ``n``: land one
        stream's committed context (``s_live`` tokens) into its draft-pool
        pages through the stream's own block table, exactly like a target
        prompt chunk. Runs once per stream per residency — at its first
        speculative tick after admission or adoption — after which the
        per-tick advance keeps the cache current at O(spec_k).
        """
        dcfg = self.spec_draft[0]

        def build():
            def fn(dparams, dcache, tokens, s_live, row):
                _, dcache = M.paged_prefill_step(
                    dcfg, dparams, dcache, tokens[None],
                    jnp.zeros((1,), jnp.int32), s_live[None], row[None])
                return dcache

            # key on the draft cfg too: _get_program's key carries the
            # target cfg, and two schedulers may pair different drafts
            return jax.jit(fn, donate_argnums=(1,))

        return self._get_program(("spec_dpre", dcfg), n, build)

    def _draft_advance_fn(self):
        """Batched draft advance-and-propose program, all slots in one
        dispatch. ``spec_k + 1`` scanned paged decode steps on the draft
        cache: step 0 teacher-forces each live stream's last committed
        token at position ``seq_lens`` (the same input verify row 0 gets),
        steps 1..k feed the previous argmax — the first k outputs are the
        draft tokens. Every step's K/V lands in the stream's pages, so an
        accepted draft's K/V is already correct at its position and the
        next tick teacher-forces only the correction token; a rejected
        tail is masked by ``seq_lens`` and overwritten in place, the same
        rollback the target cache uses. The step-k input (draft k-1)
        writes position ``seq_lens + k`` so a full accept leaves no hole.
        Dead rows route to the sink page.
        """
        dcfg, k = self.spec_draft[0], self.spec_k

        def build():
            def fn(dparams, dcache, last, lens, live, bt):
                btm = jnp.where(live[:, None], bt,
                                PC.SINK_PAGE).astype(jnp.int32)

                def body(carry, i):
                    tok, dc = carry
                    pos = jnp.where(live, lens + i, 0).astype(jnp.int32)
                    lg, dc = M.paged_decode_step(dcfg, dparams, dc,
                                                 tok[:, None], pos, btm)
                    nxt = jnp.argmax(lg[:, -1, :dcfg.vocab_size],
                                     axis=-1).astype(jnp.int32)
                    return (nxt, dc), nxt

                (_, dcache), ds = jax.lax.scan(
                    body, (last, dcache),
                    jnp.arange(k + 1, dtype=jnp.int32))
                return ds[:k].T, dcache          # (S, k) draft tokens

            return jax.jit(fn, donate_argnums=(1,))

        return self._get_program(("spec_adv", dcfg), k, build)

    def _model_drafts(self, decoding: List[int],
                      caps: Dict[int, int]) -> Dict[int, np.ndarray]:
        """Draft-model proposals for every eligible decoding slot, two
        dispatches worst case: catch-up prefills for newly resident
        streams, then one batched advance. Slots whose cap is below
        ``spec_k`` are excluded (their draft K/V would overrun the pages
        grown for ``cap``) and fall back to n-gram drafting.
        """
        dcfg, dparams = self.spec_draft
        k, S = self.spec_k, self.max_slots
        elig = [s for s in decoding if caps[s] == k]
        if not elig:
            return {}
        for slot in elig:
            if self._draft_ready[slot]:
                continue
            req = self.slot_req[slot]
            L = int(self.seq_lens[slot])
            ctx = np.concatenate([req.prompt,
                                  np.asarray(req.out_tokens, np.int32)])[:L]
            b = next((x for x in self.buckets if x >= L),
                     -(-L // self.page_size) * self.page_size)
            toks = np.zeros((b,), np.int32)
            toks[:L] = ctx
            self._draft_cache = self._timed(
                "spec_draft", self._draft_prefill_fn(b), dparams,
                self._draft_cache, jnp.asarray(toks),
                jnp.asarray(L, jnp.int32),
                jnp.asarray(self.block_table[slot]), tokens=L, ctx_tokens=L)
            self._draft_ready[slot] = True
        live = np.zeros((S,), bool)
        live[elig] = True
        ds, self._draft_cache = self._timed(
            "spec_draft", self._draft_advance_fn(), dparams,
            self._draft_cache, jnp.asarray(self.last_tokens[:, 0]),
            jnp.asarray(self.seq_lens), jnp.asarray(live),
            jnp.asarray(self.block_table),
            tokens=(k + 1) * len(elig),
            ctx_tokens=int(np.sum(self.seq_lens[elig])))
        ds = np.asarray(ds)
        return {slot: ds[slot].astype(np.int32) for slot in elig}

    # -------------------------------------------------------- draft sources --
    def _draft(self, req: Request, cap: int) -> np.ndarray:
        """Up to ``cap`` n-gram draft tokens for a decoding stream
        (host-side) — the default speculator, and the fallback for slots
        the draft model skips. A deterministic function of the stream's
        context, so a fleet re-route re-drafts identically.
        """
        if cap <= 0:
            return _EMPTY_DRAFT
        return self._ngram_draft(req, cap)

    def _ngram_draft(self, req: Request, cap: int) -> np.ndarray:
        """Prompt-lookup drafting: find the most recent earlier occurrence
        of the context's final m-gram (m = 3, 2, 1) and propose the tokens
        that followed it. Free (no model call) and strong exactly where
        speculation pays: continuations that repeat prompt or generated
        material."""
        ctx = np.concatenate([req.prompt,
                              np.asarray(req.out_tokens, np.int32)])
        T = int(ctx.shape[0])
        for m in (3, 2, 1):
            if T < m + 1:
                continue
            pat = ctx[T - m:]
            win = np.lib.stride_tricks.sliding_window_view(ctx, m)
            hits = np.flatnonzero((win == pat).all(axis=1))
            hits = hits[hits < T - m]
            if hits.size:
                p = int(hits[-1])
                d = ctx[p + m:p + m + cap]
                if d.size:
                    return d.astype(np.int32)
        return _EMPTY_DRAFT

    # ------------------------------------------------------- observability --
    def set_tracer(self, tracer, *, own_clock: bool = True) -> None:
        """Attach a lifecycle tracer (``repro.obs.trace.Tracer``).

        ``own_clock=False`` means somebody else — the fabric router —
        drives ``tracer.t`` on the fleet clock, so hooks stamp that;
        otherwise they stamp this scheduler's own ``step_idx``.
        """
        self.tracer = tracer
        self._trace_own_clock = own_clock

    def _tnow(self) -> float:
        return (float(self.step_idx) if self._trace_own_clock
                else self.tracer.t)

    def enable_profiling(self, profiler=None):
        """Opt-in kernel dispatch timing (``repro.obs.profile``): every
        prefill/suffix/decode dispatch is wall-timed after
        ``block_until_ready`` with its token/context detail. Read-only —
        profiled runs emit byte-identical tokens."""
        if profiler is None:
            from repro.obs.profile import KernelProfiler
            profiler = KernelProfiler(self.cfg, tp=self.tp)
        self.profiler = profiler
        return profiler

    def _timed(self, kind: str, fn, *args, tokens: int = 0,
               ctx_tokens: int = 0, **kw):
        if self.profiler is None:
            return fn(*args, **kw)
        return self.profiler.timed(kind, fn, *args, tokens=tokens,
                                   ctx_tokens=ctx_tokens, **kw)

    # ---------------------------------------------------------- submission --
    def submit(self, prompt, max_new_tokens: int,
               arrival_step: int = 0, priority: int = 1,
               tenant: str = "default") -> Request:
        req = make_request(self._rid, prompt, max_new_tokens, arrival_step,
                           priority=priority, tenant=tenant)
        self._rid += 1
        return self.submit_request(req)

    def submit_request(self, req: Request) -> Request:
        """Enqueue a pre-built request (the fabric router's entry point: the
        router owns rid assignment, so the same object travels through
        whichever replica scheduler ends up decoding it)."""
        total = req.plen + req.max_new_tokens
        if total > self.max_seq_len:
            raise ValueError(f"request needs {total} positions > "
                             f"max_seq_len {self.max_seq_len}")
        # a prefill-role scheduler only ever holds the prompt (+1 for the
        # first output's logits); generation pages are the adopter's burden
        worst = PC.pages_for_len(
            req.plen + 1 if self.role == "prefill" else total,
            self.page_size)
        cap = self.alloc.capacity
        if self.capacity_hint is not None:
            cap = max(cap, self.capacity_hint - 1)
        if worst > cap:
            raise ValueError(
                f"request reserves {worst} pages but the pool only holds "
                f"{cap} — it could never be admitted")
        self.waiting.append(req)
        if self.tracer is not None:
            # no-op when the fabric router already opened this span at its
            # own submit (first opener wins — fleet clock beats replica's)
            self.tracer.begin("queued", req.rid, t=req.arrival_step,
                              replica=self.replica_id)
        return req

    # ----------------------------------------------------------- admission --
    def _free_slots(self) -> List[int]:
        # slots at or above target_slots are draining (pending shrink)
        return [i for i, r in enumerate(self.slot_req[:self.target_slots])
                if r is None]

    def _try_admit(self) -> None:
        progress = True
        while progress:
            progress = False
            # the due window keeps the original FCFS head gate: requests
            # queued behind a not-yet-due one wait, so priority classes
            # reorder only *simultaneously due* requests (all-equal
            # priorities reduce exactly to the old head-of-line behavior)
            due: List[Request] = []
            for r in self.waiting:
                if r.arrival_step > self.step_idx:
                    break
                due.append(r)
            if not due:
                return
            due.sort(key=lambda r: -r.priority)    # stable: FCFS in class
            for req in due:
                free = self._free_slots()   # _admit may have finished slots
                if not free:
                    self.stats["admit_blocked"] += 1
                    return
                hit = self._prefix_lookup(req)
                reserve, demand = self._admission_demand(req, hit)
                if self._quota_blocked(req, reserve):
                    continue                # other tenants may still fit
                headroom = (self.alloc.num_free
                            - (self.reserved_pages - self.pages_in_use))
                if demand > headroom:
                    # HBM pressure: preempt cold retained chains to the
                    # host tier instead of blocking (protect the hit's own
                    # chain from being evicted out from under us)
                    if not self._reclaim(demand - headroom,
                                         protect=self._hit_pages(hit)):
                        self.stats["admit_blocked"] += 1
                        return              # head of the class blocks
                    # eviction may have remapped or invalidated entries
                    hit = self._prefix_lookup(req)
                    reserve, demand = self._admission_demand(req, hit)
                    if demand > (self.alloc.num_free
                                 - (self.reserved_pages
                                    - self.pages_in_use)):
                        self.stats["admit_blocked"] += 1
                        return
                mat = self._materialize_hit(req, hit)
                if mat is None and hit is not None:
                    # defensive miss: the hit chain vanished; recheck the
                    # full (undiscounted) reservation before admitting
                    reserve, demand = self._admission_demand(req, None)
                    if demand > (self.alloc.num_free
                                 - (self.reserved_pages
                                    - self.pages_in_use)):
                        self.stats["admit_blocked"] += 1
                        return
                hit = mat
                self.waiting.remove(req)
                if self.tenant_quotas is not None:
                    self._tenant_reserved[req.tenant] = (
                        self._tenant_reserved.get(req.tenant, 0) + reserve)
                if self.prefill_budget is not None:
                    self._admit_chunked(req, free[0], reserve, hit)
                else:
                    self._admit(req, free[0], reserve, hit)
                progress = True
                break                       # re-scan with fresh due window

    def _admission_demand(self, req: Request, hit):
        """``(reserve, demand)`` pages for admitting ``req`` against ``hit``.

        ``reserve`` is the worst-case reservation charged to the slot: the
        uncached suffix only — shared full pages are already allocated and
        survive via their refcount, so they are never allocated again. A
        prefill-role scheduler reserves prompt pages only; generation pages
        are reserved by whichever decode scheduler adopts the stream.

        ``demand`` is what the admission ledger must cover *now*: the
        reservation plus one fresh device page per host-resident hit page
        (full or tail), since materializing the hit allocates those
        immediately.
        """
        reserve = PC.pages_for_len(
            req.plen + 1 if self.role == "prefill"
            else req.plen + req.max_new_tokens, self.page_size)
        demand = reserve
        if hit is not None:
            reserve -= len(hit.full_pages)
            n_host = sum(1 for p in hit.full_pages if PC.is_host_page(p))
            if hit.tail_len and PC.is_host_page(hit.tail_page):
                n_host += 1
            demand = reserve + n_host
        return reserve, demand

    def _quota_blocked(self, req: Request, reserve: int) -> bool:
        if self.tenant_quotas is None:
            return False
        quota = self.tenant_quotas.get(req.tenant)
        if quota is None:
            return False
        if self._tenant_reserved.get(req.tenant, 0) + reserve <= quota:
            return False
        self.stats["quota_blocked"] += 1
        return True

    @staticmethod
    def _hit_pages(hit) -> List[int]:
        if hit is None:
            return []
        pages = list(hit.full_pages)
        if hit.tail_len:
            pages.append(hit.tail_page)
        return pages

    # ----------------------------------------------------- host page tier --
    def _on_index_evict(self, entry) -> None:
        self.stats["index_evictions"] += 1

    @property
    def retained_page_count(self) -> int:
        """Device pages held by retained (cold) chains, with multiplicity."""
        return sum(len(c.pages) for c in self._retained.values())

    @property
    def hot_pages(self) -> int:
        """Physical pages referenced by live slots — the hot working set
        the autoscaler should size HBM to (cold retained pages are
        reclaimable at a swap or a re-prefill, not a capacity need)."""
        live = set()
        for pages in self.slot_pages:
            live.update(pages)
        live.discard(PC.SINK_PAGE)
        return len(live)

    def _gauge_tiers(self) -> None:
        self.stats["retained_pages"] = self.retained_page_count
        if self.host_tier is not None:
            self.stats["host_pages_used"] = self.host_tier.pages_used

    def _retain_pages(self, pages: List[int], *, tokens: int, priority: int,
                      tenant: str) -> int:
        """Register a device-resident chain with the retention ledger.

        The record inherits the allocator refs its previous owner held —
        the caller must *not* free ``pages`` — so the chain stays alive and
        prefix-shareable until ``_reclaim`` preempts it."""
        key = self._retain_seq
        self._retain_seq += 1
        self._retained[key] = _RetainedChain(list(pages), int(tokens),
                                             int(priority), tenant,
                                             self.step_idx)
        self._gauge_tiers()
        return key

    def _retain_finished(self, slot: int, req: Request) -> None:
        """Keep a finished stream's chain warm instead of freeing it.

        The chain covers the prompt plus all but the last output token —
        the final token was emitted but its K/V never written — so any
        session-style follow-up prompt (previous context + new user turn)
        prefix-hits it. Pages past the chain (speculative growth headroom)
        are freed; hybrid archs snapshot the slot's SSM state into the
        index so the resume point is exact."""
        L = req.plen + max(len(req.out_tokens) - 1, 0)
        keep = PC.pages_for_len(L, self.page_size)
        pages = self.slot_pages[slot]
        extra = pages[keep:]
        kept = pages[:keep]
        if extra:
            self.alloc.free(extra)
        chain = np.concatenate(
            [req.prompt, np.asarray(req.out_tokens[:-1], np.int32)])
        state = PC.extract_ssm_slot(self.cache, slot) if self._has_ssm \
            else None
        self.index.insert(chain, kept, state=state)
        self._retain_pages(kept, tokens=L, priority=req.priority,
                           tenant=req.tenant)

    def _reclaim(self, short: int, protect: Sequence[int] = ()) -> bool:
        """Free >= ``short`` device pages by preempting retained chains,
        lowest priority class first, coldest first within a class. Each
        chain's private pages either move to the host tier or are dropped
        for re-prefill — ``swap_resume_cost`` decides. Returns whether the
        shortfall was covered."""
        if short <= 0:
            return True
        if not self._retained:
            return False
        prot = set(protect)
        order = sorted(self._retained,
                       key=lambda k: (self._retained[k].priority,
                                      self._retained[k].step))
        freed = 0
        for key in order:
            if freed >= short:
                break
            if prot and not prot.isdisjoint(self._retained[key].pages):
                continue                    # the admission's own hit chain
            freed += self._evict_chain(key)
        self._gauge_tiers()
        return freed >= short

    def _evict_chain(self, key: int) -> int:
        """Preempt one retained chain; returns device pages freed.

        Only *dying* pages (refcount 1, held solely by retention) carry
        bytes to host — pages shared with live slots survive on device,
        and the index entries that straddle the freed/survived boundary
        are invalidated through the allocator's on_free hook. ``swap_chain``
        runs before ``free`` so wholly-covered entries move buckets first
        and never observe a half-swapped chain."""
        ch = self._retained.pop(key)
        dying = [p for p in ch.pages if self.alloc.ref(p) == 1]
        if not dying:                       # fully shared: nothing to move
            self.alloc.free(ch.pages)
            return 0
        store = False
        if (self.host_tier is not None and self._swap_crossover is not None
                and ch.tokens >= self._swap_crossover):
            if not self.host_tier.can_hold(len(dying)):
                self._host_reclaim(len(dying))
            store = self.host_tier.can_hold(len(dying))
        if store:
            host = PC.swap_out_pages(self.cache, self.host_tier, dying,
                                     tp=self.tp, owner=key)
            mapping = {p: PC.as_host_page(h) for p, h in zip(dying, host)}
            self.index.swap_chain(mapping)
            self._host_chains[key] = _RetainedChain(
                list(host), ch.tokens, ch.priority, ch.tenant, self.step_idx)
            for h in host:
                self._host_page_chain[h] = key
            self.stats["swap_outs"] += 1
            self.stats["swap_out_pages"] += len(dying)
            if self.tracer is not None:
                self.tracer.instant(
                    "swap_out", t=self._tnow(), replica=self.replica_id,
                    pages=len(dying), chain_tokens=ch.tokens,
                    bytes=PC.migration_bytes(self.cfg, len(dying),
                                             self.page_size))
        else:
            # cost model (or a full host tier) says drop: a resume will
            # re-prefill this chain from tokens instead of moving bytes
            self.stats["swap_reprefills"] += 1
            if self.tracer is not None:
                self.tracer.instant(
                    "swap_out", t=self._tnow(), replica=self.replica_id,
                    pages=0, chain_tokens=ch.tokens, decision="reprefill")
        self.alloc.free(ch.pages)           # dying pages die; shared survive
        self._gauge_tiers()
        return len(dying)

    def _host_reclaim(self, n: int) -> None:
        """Drop the coldest host chains until ``n`` pages fit."""
        order = sorted(self._host_chains,
                       key=lambda k: (self._host_chains[k].priority,
                                      self._host_chains[k].step))
        for key in order:
            if self.host_tier.can_hold(n):
                return
            self._drop_host_chain(key)

    def _drop_host_chain(self, key: int) -> None:
        ch = self._host_chains.pop(key)
        for h in ch.pages:
            self._host_page_chain.pop(h, None)
        self.host_tier.free(ch.pages)       # on_free invalidates entries
        self.stats["host_evictions"] += 1
        self._gauge_tiers()

    def _materialize_hit(self, req: Request, hit):
        """Swap a host-resident hit chain back into device pages.

        The *whole* owning chain is restored (not just the matched prefix)
        so no host page is left orphaned when its index entries remap; the
        restored pages re-enter the device tier as a fresh retained record
        holding refcount 1, and the admission below shares them exactly
        like any device-resident hit — refcount-clean, still preemptible.
        """
        if hit is None or self.host_tier is None:
            return hit
        tagged = [p for p in self._hit_pages(hit) if PC.is_host_page(p)]
        if not tagged:
            return hit
        # the hit may touch one chain (full pages) plus possibly a second
        # (tail page); restore every chain involved
        keys = set()
        for p in tagged:
            key = self._host_page_chain.get(PC.host_page_id(p))
            if key is None:     # orphaned entry (should not happen): miss
                return None
            keys.add(key)
        mapping: Dict[int, int] = {}
        for key in sorted(keys):
            ch = self._host_chains.pop(key)
            for h in ch.pages:
                self._host_page_chain.pop(h, None)
            dst = self.alloc.alloc(len(ch.pages), owner=("swapin", req.rid))
            m = {PC.as_host_page(h): d for h, d in zip(ch.pages, dst)}
            # remap entries to device ids *before* the swap-in frees the
            # host pages — the on_free invalidation then finds nothing
            # under the tagged ids and the chain is never half-swapped
            self.index.swap_chain(m)
            self.cache = PC.swap_in_pages(self.cache, self.host_tier,
                                          ch.pages, dst, tp=self.tp)
            mapping.update(m)
            self._retain_pages(dst, tokens=ch.tokens, priority=ch.priority,
                               tenant=ch.tenant)
            self.stats["swap_ins"] += 1
            self.stats["swap_in_pages"] += len(dst)
            if self.tracer is not None:
                self.tracer.instant(
                    "swap_in", rid=req.rid, t=self._tnow(),
                    replica=self.replica_id, pages=len(dst),
                    chain_tokens=ch.tokens,
                    bytes=PC.migration_bytes(self.cfg, len(dst),
                                             self.page_size))
        hit.full_pages = [mapping.get(p, p) for p in hit.full_pages]
        if hit.tail_len:
            hit.tail_page = mapping.get(hit.tail_page, hit.tail_page)
        req.swap_ins += 1
        self.h_resume.observe(self.step_idx - req.arrival_step)
        self._gauge_tiers()
        return hit

    def drop_tier_state(self) -> None:
        """Forget both tiers' cold state (replica failure: the node's HBM
        and host RAM die together). Retained device chains release their
        refs, host rows are dropped, per-tenant ledgers reset."""
        for key in list(self._retained):
            ch = self._retained.pop(key)
            self.alloc.free(ch.pages)
        self._host_chains.clear()
        self._host_page_chain.clear()
        if self.host_tier is not None:
            self.host_tier.clear()
        self._tenant_reserved.clear()
        self._gauge_tiers()

    def _prefix_lookup(self, req: Request):
        if not self.prefix_cache:
            return None
        return self.index.lookup(req.prompt, limit=req.plen - 1,
                                 need_state=self._has_ssm)

    def prefix_match_len(self, prompt) -> int:
        """Tokens of ``prompt`` this scheduler's prefix cache could serve —
        the router's prefix-affinity signal (read-only)."""
        if not self.prefix_cache:
            return 0
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        return self.index.match_len(prompt, limit=prompt.shape[0] - 1,
                                    need_state=self._has_ssm)

    @property
    def pages_in_use(self) -> int:
        """Pages charged privately to live slots (net of shared prefix
        pages) — the in-use term of the admission ledger. Physical
        occupancy, which sharing makes smaller, is ``pages_allocated``."""
        return sum(len(p) for p in self.slot_pages) - sum(self.slot_shared)

    @property
    def pages_allocated(self) -> int:
        """Physical pages held (each shared page counted once)."""
        return self.alloc.num_allocated

    def shard_stats(self) -> Dict[str, Any]:
        """Per-shard page-pool occupancy for a ``tp``-way group.

        One allocator ledger governs every shard's storage plane, so the
        per-shard numbers are equal by construction — that lockstep (no
        shard can run out of pages before its peers) is the design point
        the sharded rule set in tests/test_allocator_props.py checks.
        """
        one = {
            "pages_allocated": self.alloc.num_allocated,
            "pages_free": self.alloc.num_free,
            "peak_pages": self.stats["peak_pages"],
            "pool_bytes": PC.pool_bytes(self.cfg, self.alloc.num_pages,
                                        self.page_size, self.tp),
        }
        cap = max(self.alloc.capacity, 1)
        one["peak_utilization"] = round(self.stats["peak_pages"] / cap, 3)
        return {"tp": self.tp, "per_shard": [dict(one)
                                             for _ in range(self.tp)]}

    def _bucket(self, plen: int) -> int:
        if self.exact_prefill:
            return plen
        for b in self.buckets:
            if plen <= b:
                return b
        return self.max_seq_len

    def _admit(self, req: Request, slot: int, reserve: int,
               hit=None) -> None:
        plen = req.plen
        if hit is None:
            first, pages, shared, row = self._admit_full(req, slot)
        else:
            first, pages, shared, row = self._admit_shared(req, slot, hit)
        self.reserved_pages += reserve
        self.block_table[slot] = row
        self.seq_lens[slot] = plen
        self.last_tokens[slot, 0] = first
        self.slot_req[slot] = req
        self.slot_pages[slot] = pages
        self.slot_reserve[slot] = reserve
        self.slot_shared[slot] = shared
        if self.spec_draft is not None:
            self._draft_ready[slot] = False
        req.admit_step = self.step_idx
        req.out_tokens.append(first)
        self.stats["prefills"] += 1
        self.stats["tokens_out"] += 1
        self.h_queue_wait.observe(req.admit_step - req.arrival_step)
        self.h_ttft.observe(self.step_idx - req.arrival_step)
        tr = self.tracer
        if tr is not None:
            now = self._tnow()
            tr.end("queued", req.rid, t=now)
            tr.instant("admitted", rid=req.rid, t=now,
                       replica=self.replica_id, slot=slot, pages=len(pages),
                       shared_pages=shared, cached_tokens=req.cached_tokens,
                       prefix_hit=hit is not None)
            tr.span("prefill", req.rid, now, now + 1,
                    replica=self.replica_id, tokens=plen,
                    cached_tokens=req.cached_tokens, pages=len(pages),
                    shared_pages=shared)
        if req.done:                        # max_new_tokens == 1
            self._finish(slot)
            self._admit_done.append(req)
        elif self.role == "prefill":
            self.slot_parked[slot] = True   # awaiting page handoff
            if tr is not None:
                tr.begin("parked", req.rid, t=now, replica=self.replica_id)
        elif tr is not None:
            tr.begin("decode", req.rid, t=now, replica=self.replica_id)

    def _admit_full(self, req: Request, slot: int):
        """Prefix-cache miss (or caching off): full bucketed prefill.

        Fused: pages are allocated *first* and the whole prompt lands in
        them through one ``_chunk_fn`` dispatch (start=0). Legacy: dense
        prefill to a contiguous cache, then the ``write_prefill`` copy.
        """
        plen = req.plen
        n = self._bucket(plen)
        pages = self.alloc.alloc(PC.pages_for_len(plen + 1, self.page_size),
                                 owner=req.rid)
        row = np.full((self.n_pg,), PC.SINK_PAGE, np.int32)
        row[:len(pages)] = pages
        if self.prefill_fused:
            toks = np.zeros((n,), np.int32)
            toks[:plen] = req.prompt
            self.stats["prefill_dispatches"] += 1
            first, self.cache = self._timed(
                "prefill", self._chunk_fn(n), self.params, self.cache,
                jnp.asarray(toks), jnp.asarray(0, jnp.int32),
                jnp.asarray(plen, jnp.int32), jnp.asarray(row), tokens=plen)
            state = None
        else:
            tokens = np.zeros((1, n), np.int32)
            tokens[0, :plen] = req.prompt
            self.stats["prefill_dispatches"] += 2    # prefill + insert
            first, pre = self._timed("prefill", self._prefill_fn(n),
                                     self.params, jnp.asarray(tokens),
                                     jnp.asarray(plen, jnp.int32),
                                     tokens=plen)
            self.cache = self._insert_fn(n)(self.cache, pre,
                                            jnp.asarray(row),
                                            jnp.asarray(slot, jnp.int32),
                                            jnp.asarray(plen, jnp.int32))
            state = PC.extract_ssm_state(pre) if self._has_ssm else None
        if self.prefix_cache:
            self.index.insert(req.prompt, pages, state=state)
            self.stats["prefix_misses"] += 1
        return int(first), pages, 0, row

    def _admit_shared(self, req: Request, slot: int, hit):
        """Prefix-cache hit: share the matched full pages, COW-fork the
        partially-matched page, and prefill only the uncached suffix."""
        plen, L = req.plen, hit.length
        shared = list(hit.full_pages)
        self.alloc.share(shared)
        own = self.alloc.alloc(
            PC.pages_for_len(plen + 1, self.page_size) - len(shared),
            owner=req.rid)
        if hit.tail_len:
            # the sequence diverges (or continues) inside the matched page:
            # fork a private copy before writing its own tokens there
            self.cache = self._cow_fn(self.cache, hit.tail_page, own[0])
            self.stats["cow_forks"] += 1
        pages = shared + own
        row = np.full((self.n_pg,), PC.SINK_PAGE, np.int32)
        row[:len(pages)] = pages
        suffix = np.asarray(req.prompt[L:], np.int32)
        s = suffix.shape[0]
        self.stats["prefill_dispatches"] += 1
        if self.exact_prefill:
            first, self.cache = self._timed(
                "prefill_seq", self._seq_suffix_fn(s),
                self.params, self.cache, hit.state, jnp.asarray(suffix),
                jnp.asarray(L, jnp.int32), jnp.asarray(row),
                jnp.asarray(slot, jnp.int32), tokens=s, ctx_tokens=L)
        else:
            n = self._bucket(s)
            toks = np.zeros((n,), np.int32)
            toks[:s] = suffix
            fn = (self._chunk_fn(n) if self.prefill_fused
                  else self._suffix_fn(n))
            first, self.cache = self._timed(
                "prefill_suffix", fn,
                self.params, self.cache, jnp.asarray(toks),
                jnp.asarray(L, jnp.int32), jnp.asarray(s, jnp.int32),
                jnp.asarray(row), tokens=s, ctx_tokens=L)
        if not self._has_ssm:
            # extend the index with this prompt's own (longer) chain; hybrid
            # entries need a state snapshot, which only full prefills have
            self.index.insert(req.prompt, pages)
        req.cached_tokens = L
        self.stats["prefix_hits"] += 1
        self.stats["cached_tokens"] += L
        return int(first), pages, len(shared), row

    # ------------------------------------------------------ chunked prefill --
    def _admit_chunked(self, req: Request, slot: int, reserve: int,
                       hit=None) -> None:
        """Allocate the prompt's pages and enter PREFILLING — no model call.

        The prompt lands chunk by chunk in ``_advance_prefills``; until the
        last chunk the slot is masked out of decode (seq_lens 0, sink block
        row), indistinguishable from an empty slot. A prefix hit shares /
        COW-forks pages exactly like monolithic admission, and the chunk
        cursor starts at the hit length.
        """
        plen = req.plen
        n_own = PC.pages_for_len(plen + 1, self.page_size)
        if hit is None:
            pages = self.alloc.alloc(n_own, owner=req.rid)
            shared = 0
            start = 0
            self.slot_resume_state[slot] = None
            if self.prefix_cache:
                self.stats["prefix_misses"] += 1
        else:
            shared_pages = list(hit.full_pages)
            self.alloc.share(shared_pages)
            own = self.alloc.alloc(n_own - len(shared_pages), owner=req.rid)
            if hit.tail_len:
                self.cache = self._cow_fn(self.cache, hit.tail_page, own[0])
                self.stats["cow_forks"] += 1
            pages = shared_pages + own
            shared = len(shared_pages)
            start = hit.length
            self.slot_resume_state[slot] = hit.state
            req.cached_tokens = start
            self.stats["prefix_hits"] += 1
            self.stats["cached_tokens"] += start
        row = np.full((self.n_pg,), PC.SINK_PAGE, np.int32)
        row[:len(pages)] = pages
        self.reserved_pages += reserve
        self.block_table[slot] = row
        self.seq_lens[slot] = 0             # masked until prefill completes
        self.last_tokens[slot, 0] = 0
        self.slot_req[slot] = req
        self.slot_pages[slot] = pages
        self.slot_reserve[slot] = reserve
        self.slot_shared[slot] = shared
        if self.spec_draft is not None:
            self._draft_ready[slot] = False
        req.admit_step = self.step_idx
        req.prefill_pos = start
        self._prefill_fifo.append(slot)
        self.h_queue_wait.observe(req.admit_step - req.arrival_step)
        tr = self.tracer
        if tr is not None:
            now = self._tnow()
            tr.end("queued", req.rid, t=now)
            tr.instant("admitted", rid=req.rid, t=now,
                       replica=self.replica_id, slot=slot, chunked=True,
                       pages=len(pages), shared_pages=shared,
                       cached_tokens=start, prefix_hit=hit is not None)

    def _advance_prefills(self) -> None:
        """Spend this tick's chunk budget FCFS over in-flight prefills.

        The fifo head (oldest admitted prefill) is funded first, so it
        always advances by at least one token — no admitted prefill can
        starve — and total chunk tokens per tick never exceed the budget.
        """
        budget = self.prefill_budget
        for slot in list(self._prefill_fifo):
            if budget <= 0:
                break
            req = self.slot_req[slot]
            pos = req.prefill_pos
            c = min(budget, req.plen - pos)
            budget -= c
            self._prefill_chunk(slot, req, pos, c)
            self.stats["prefill_chunk_tokens"] += c

    def _prefill_chunk(self, slot: int, req: Request, pos: int,
                       c: int) -> None:
        """Land ``c`` prompt tokens at cursor ``pos`` into the slot's pages.

        ``pos == 0`` runs a bucketed batch-1 prefill of the first chunk
        (which also writes the SSM slot state at ``c``); later chunks are
        suffix continuations — the dense batched-rows path or, for
        hybrid/MoE archs, the sequential scan resumed from the slot's live
        SSM state (or the prefix hit's snapshot for the first post-hit
        chunk). The last chunk's logits yield the first output token,
        exactly where monolithic prefill reads them.
        """
        row = self.block_table[slot]
        chunk = np.asarray(req.prompt[pos:pos + c], np.int32)
        if self.prefill_fused:
            n = self._bucket(c)
            toks = np.zeros((n,), np.int32)
            toks[:c] = chunk
            self.stats["prefill_dispatches"] += 1
            # first chunks keep the "prefill" profiler/metrics kind the
            # monolithic path established; continuations are suffixes
            tok, self.cache = self._timed(
                "prefill" if pos == 0 else "prefill_suffix",
                self._chunk_fn(n),
                self.params, self.cache, jnp.asarray(toks),
                jnp.asarray(pos, jnp.int32), jnp.asarray(c, jnp.int32),
                jnp.asarray(row), tokens=c, ctx_tokens=pos)
        elif pos == 0:
            n = self._bucket(c)
            tokens = np.zeros((1, n), np.int32)
            tokens[0, :c] = chunk
            self.stats["prefill_dispatches"] += 2    # prefill + insert
            tok, pre = self._timed("prefill", self._prefill_fn(n),
                                   self.params, jnp.asarray(tokens),
                                   jnp.asarray(c, jnp.int32), tokens=c)
            self.cache = self._insert_fn(n)(self.cache, pre,
                                            jnp.asarray(row),
                                            jnp.asarray(slot, jnp.int32),
                                            jnp.asarray(c, jnp.int32))
        elif self.exact_prefill:
            state = self.slot_resume_state[slot]
            if state is None and self._has_ssm:
                state = PC.extract_ssm_slot(self.cache, slot)
            self.stats["prefill_dispatches"] += 1
            tok, self.cache = self._timed(
                "prefill_seq", self._seq_suffix_fn(c),
                self.params, self.cache, state, jnp.asarray(chunk),
                jnp.asarray(pos, jnp.int32), jnp.asarray(row),
                jnp.asarray(slot, jnp.int32), tokens=c, ctx_tokens=pos)
        else:
            n = self._bucket(c)
            toks = np.zeros((n,), np.int32)
            toks[:c] = chunk
            self.stats["prefill_dispatches"] += 1
            tok, self.cache = self._timed(
                "prefill_suffix", self._suffix_fn(n),
                self.params, self.cache, jnp.asarray(toks),
                jnp.asarray(pos, jnp.int32), jnp.asarray(c, jnp.int32),
                jnp.asarray(row), tokens=c, ctx_tokens=pos)
        tr = self.tracer
        if tr is not None:
            now = self._tnow()
            tr.span("prefill_chunk", req.rid, now, now + 1,
                    replica=self.replica_id,
                    chunk=tr.next_index(req.rid, "prefill_chunk"),
                    pos=pos, tokens=c)
        if pos + c < req.plen:
            req.prefill_pos = pos + c
            if self._has_ssm:
                # the live slot state is NOT safe to resume from: decode
                # ticks for other slots step every slot's SSM recurrence —
                # including this masked one (KV writes land on the sink
                # page, but SSM state lives per slot, not per page). Carry
                # the authoritative post-chunk state host-side and resume
                # the next chunk from the snapshot.
                self.slot_resume_state[slot] = PC.extract_ssm_slot(
                    self.cache, slot)
            return
        # ---- last chunk: the request leaves PREFILLING this tick --------
        self._prefill_fifo.remove(slot)
        self.slot_resume_state[slot] = None
        req.prefill_pos = None
        self.seq_lens[slot] = req.plen
        first = int(tok)
        self.last_tokens[slot, 0] = first
        req.out_tokens.append(first)
        self.stats["prefills"] += 1
        self.stats["tokens_out"] += 1
        self.h_ttft.observe(self.step_idx - req.arrival_step)
        if self.prefix_cache:
            state = (PC.extract_ssm_slot(self.cache, slot)
                     if self._has_ssm else None)
            self.index.insert(req.prompt, self.slot_pages[slot], state=state)
        if req.done:                        # max_new_tokens == 1
            self._finish(slot)
            self._admit_done.append(req)
        elif self.role == "prefill":
            self.slot_parked[slot] = True   # awaiting page handoff
            if tr is not None:
                tr.begin("parked", req.rid, t=now, replica=self.replica_id)
        elif tr is not None:
            tr.begin("decode", req.rid, t=now, replica=self.replica_id)

    # ------------------------------------------------- disaggregation hand --
    def handoff_ready(self) -> List[int]:
        """Slots parked after prefill, awaiting page migration (admit
        order — the router drains the oldest first)."""
        return [s for s in range(self.max_slots) if self.slot_parked[s]]

    def can_adopt(self, req: Request) -> bool:
        """Room for a migrated stream: a free slot plus the worst-case
        reservation the stream's remaining generation needs."""
        if not self._free_slots():
            return False
        need = PC.pages_for_len(req.plen + req.max_new_tokens,
                                self.page_size)
        return (self.alloc.num_free
                - (self.reserved_pages - self.pages_in_use) >= need)

    def adopt(self, req: Request, donor: "ContinuousBatchingScheduler",
              donor_slot: int) -> int:
        """Adopt a prefilled stream from ``donor``: copy its KV pages
        verbatim into freshly allocated pages here (``PC.migrate_pages`` —
        every layer, every shard slice, one call), carry the SSM slot state
        across, and seat the request in a free slot with the full
        worst-case reservation. The caller must still
        ``donor.surrender_slot`` to release the source pages. Returns the
        adopting slot."""
        assert self.can_adopt(req)
        slot = self._free_slots()[0]
        src_pages = donor.slot_pages[donor_slot]
        need = PC.pages_for_len(req.plen + req.max_new_tokens,
                                self.page_size)
        pages = self.alloc.alloc(len(src_pages), owner=req.rid)
        self.cache = PC.migrate_pages(donor.cache, self.cache, src_pages,
                                      pages, tp=self.tp)
        state = None
        if self._has_ssm:
            state = PC.extract_ssm_slot(donor.cache, donor_slot)
            if self.device is not None:
                state = jax.device_put(state, self.device)
            self.cache = PC.merge_ssm_slot(
                self.cache, PC.ssm_slot_view(self.cache, state), slot)
        row = np.full((self.n_pg,), PC.SINK_PAGE, np.int32)
        row[:len(pages)] = pages
        self.reserved_pages += need
        if self.tenant_quotas is not None:
            self._tenant_reserved[req.tenant] = (
                self._tenant_reserved.get(req.tenant, 0) + need)
        self.block_table[slot] = row
        self.seq_lens[slot] = req.plen
        self.last_tokens[slot, 0] = int(req.out_tokens[-1])
        self.slot_req[slot] = req
        self.slot_pages[slot] = list(pages)
        self.slot_reserve[slot] = need
        self.slot_shared[slot] = 0
        if self.spec_draft is not None:
            # the draft cache did not travel with the migration; the next
            # speculative tick re-prefills it here. It may draft different
            # tokens than the donor would have — acceptance may dip for a
            # tick, emitted tokens cannot change (every draft is verified)
            self._draft_ready[slot] = False
        if self.prefix_cache:
            self.index.insert(req.prompt, pages, state=state)
        req.migrations += 1
        # ownership transfers at the copy point, not at surrender: if the
        # donor dies inside the adopt→surrender window, its fail() sees the
        # stream already belongs elsewhere and must not requeue it (the
        # adopter owns the only live copy of its pages)
        if self.replica_id is not None:
            req.replica = self.replica_id
        self.stats["migrations_in"] += 1
        tr = self.tracer
        if tr is not None:
            now = self._tnow()
            tr.end("parked", req.rid, t=now, pages=len(pages))
            tr.begin("decode", req.rid, t=now, replica=self.replica_id)
        return slot

    def surrender_slot(self, slot: int) -> Request:
        """Release a handed-off slot on the donor side: free its pages
        (refcount-correct — shared prefix pages survive for their other
        owners, and ``on_free`` drops any index entry whose last page
        owner this was), drop the reservation, clear the slot. The request
        object itself lives on at the adopter; no finish is recorded."""
        req = self.slot_req[slot]
        self.alloc.free(self.slot_pages[slot])
        if self.tenant_quotas is not None:
            t = req.tenant
            self._tenant_reserved[t] = max(
                0, self._tenant_reserved.get(t, 0) - self.slot_reserve[slot])
        self.reserved_pages -= self.slot_reserve[slot]
        self.slot_reserve[slot] = 0
        self.slot_shared[slot] = 0
        self.slot_pages[slot] = []
        self.slot_req[slot] = None
        self.block_table[slot] = PC.SINK_PAGE
        self.seq_lens[slot] = 0
        self.last_tokens[slot, 0] = 0
        self.slot_parked[slot] = False
        self.slot_resume_state[slot] = None
        self.stats["migrations_out"] += 1
        return req

    @property
    def prefill_backlog(self) -> int:
        """Prompt tokens not yet landed: due queued prompts plus in-flight
        chunk remainders — the prefill-role autoscaling signal."""
        t = sum(r.plen for r in self.waiting
                if r.arrival_step <= self.step_idx)
        t += sum(r.plen - r.prefill_pos for r in self.slot_req
                 if r is not None and r.prefill_pos is not None)
        return t

    # -------------------------------------------------------------- finish --
    def _finish(self, slot: int) -> None:
        req = self.slot_req[slot]
        req.finish_step = self.step_idx
        if (self.host_tier is not None and self.prefix_cache
                and not self.slot_parked[slot]
                and req.prefill_pos is None):
            # host tier on: retain the chain for session resume instead of
            # freeing it — HBM pressure reclaims it later via _reclaim
            self._retain_finished(slot, req)
        else:
            self.alloc.free(self.slot_pages[slot])
        if self.tenant_quotas is not None:
            t = req.tenant
            self._tenant_reserved[t] = max(
                0, self._tenant_reserved.get(t, 0) - self.slot_reserve[slot])
        self.reserved_pages -= self.slot_reserve[slot]
        self.slot_reserve[slot] = 0
        self.slot_shared[slot] = 0
        self.slot_pages[slot] = []
        self.slot_req[slot] = None
        self.block_table[slot] = PC.SINK_PAGE
        self.seq_lens[slot] = 0
        self.last_tokens[slot, 0] = 0
        self.slot_parked[slot] = False
        self.slot_resume_state[slot] = None
        if slot in self._prefill_fifo:
            self._prefill_fifo.remove(slot)
        self.finished.append(req)
        self.h_latency.observe(req.finish_step - req.arrival_step)
        tr = self.tracer
        if tr is not None:
            now = self._tnow()
            tr.end("decode", req.rid, t=now, tokens=len(req.out_tokens))
            tr.end("parked", req.rid, t=now)    # safety: finish while parked
            tr.instant("finish", rid=req.rid, t=now,
                       replica=self.replica_id, tokens=len(req.out_tokens))

    def _grow_pages(self, k: int = 1) -> None:
        """Ensure each active slot owns the pages its next ``k`` tokens land
        in (admission reserved them, so allocation cannot fail here)."""
        for slot, req in enumerate(self.slot_req):
            if req is None or req.prefill_pos is not None \
                    or self.slot_parked[slot]:
                continue                    # not decoding this tick
            needed = (int(self.seq_lens[slot]) + k - 1) // self.page_size + 1
            while len(self.slot_pages[slot]) < needed:
                new = self.alloc.alloc(1, owner=req.rid)[0]
                self.block_table[slot, len(self.slot_pages[slot])] = new
                self.slot_pages[slot].append(new)

    def _fuse_k(self, max_fuse: int,
                decoding: Optional[List[int]] = None) -> int:
        """Largest tick count that changes nothing mid-scan: bounded by the
        earliest finish among decoding requests and the next future
        arrival."""
        if decoding is None:
            reqs = [r for r in self.slot_req if r is not None]
        else:
            reqs = [self.slot_req[i] for i in decoding]
        k = min(r.max_new_tokens - len(r.out_tokens) for r in reqs)
        future = [r.arrival_step - self.step_idx for r in self.waiting
                  if r.arrival_step > self.step_idx]
        if future:
            k = min(k, min(future))
        return max(1, min(k, max_fuse))

    # ------------------------------------------------- speculative verify --
    def _spec_step(self, decoding: List[int],
                   done_now: List[Request]) -> List[Request]:
        """One draft-and-verify tick over every decoding slot.

        Each stream proposes up to ``spec_k`` draft tokens (n-gram lookup
        or the draft model), the target verifies last-token + drafts in a
        single paged dispatch, and the longest matching prefix plus the
        target's correction token is emitted — ``accepted + 1`` tokens per
        stream per tick, byte-identical to spec-off decoding. Rollback:
        ``seq_lens`` advances only past accepted positions (rejected K/V
        stays masked and is overwritten in place), per-slot draft caps
        route overshoot rows to the sink page so the admission reservation
        is never exceeded, and hybrid archs restore the SSM state of the
        accepted step in-dispatch (``PC.select_ssm_steps``).
        """
        k, n, S = self.spec_k, self.spec_k + 1, self.max_slots
        caps: Dict[int, int] = {}
        for slot in decoding:
            req = self.slot_req[slot]
            # cap < remaining: emitting cap+1 tokens can never overrun the
            # token budget (nor the worst-case page reservation)
            caps[slot] = min(k, req.remaining_tokens - 1)
        for slot in decoding:                # pages for positions L..L+cap
            req = self.slot_req[slot]
            needed = (int(self.seq_lens[slot]) + caps[slot]) \
                // self.page_size + 1
            while len(self.slot_pages[slot]) < needed:
                new = self.alloc.alloc(1, owner=req.rid)[0]
                self.block_table[slot, len(self.slot_pages[slot])] = new
                self.slot_pages[slot].append(new)
        # pages must exist before drafting: the draft model writes its own
        # K/V at positions L..L+k through the same (just-grown) block table
        model_drafts = (self._model_drafts(decoding, caps)
                        if self.spec_draft is not None else {})
        drafts: Dict[int, np.ndarray] = {}
        for slot in decoding:
            req = self.slot_req[slot]
            d = model_drafts.get(slot)
            if d is None:
                d = self._draft(req, caps[slot])
            caps[slot] = len(d)
            drafts[slot] = d
            req.speculating = bool(len(d))
            req.spec_drafted += len(d)
            self.stats["spec_drafted"] += len(d)
        self.stats["peak_pages"] = max(self.stats["peak_pages"],
                                       self.alloc.num_allocated)
        ctx = int(np.sum(self.seq_lens))
        toks = np.zeros((S, n), np.int32)
        lens0 = np.zeros((S,), np.int32)
        bt = np.full((S, self.n_pg), PC.SINK_PAGE, np.int32)
        live = np.zeros((S,), np.int32)
        for slot in decoding:
            cap = caps[slot]
            toks[slot, 0] = self.last_tokens[slot, 0]
            if cap:
                toks[slot, 1:1 + cap] = drafts[slot]
            lens0[slot] = self.seq_lens[slot]
            bt[slot] = self.block_table[slot]
            live[slot] = cap + 1
        if self._has_ssm:
            outs, js, self.cache = self._timed(
                "verify", self._verify_seq_fn(n), self.params, self.cache,
                jnp.asarray(toks), jnp.asarray(lens0), jnp.asarray(bt),
                jnp.asarray(live), tokens=n * len(decoding), ctx_tokens=ctx)
            outs = np.asarray(outs).T                      # (S, n)
            js = np.asarray(js)
        else:
            outs, self.cache = self._timed(
                "verify", self._verify_fn(n), self.params, self.cache,
                jnp.asarray(toks), jnp.asarray(lens0), jnp.asarray(bt),
                jnp.asarray(live), tokens=n * len(decoding), ctx_tokens=ctx)
            outs = np.asarray(outs)                        # (S, n)
            js = None
        self.stats["decode_steps"] += 1
        self.stats["spec_ticks"] += 1
        self.step_idx += 1                  # before _finish (cf. step())
        tr = self.tracer
        for slot in decoding:
            req = self.slot_req[slot]
            cap = caps[slot]
            row = outs[slot]
            j = (int(js[slot]) if js is not None
                 else spec_accept(drafts[slot], row[:cap]))
            emitted = [int(t) for t in row[:j + 1]]
            req.out_tokens.extend(emitted)
            req.spec_accepted += j
            self.stats["spec_accepted"] += j
            self.stats["tokens_out"] += len(emitted)
            self.h_spec_accept.observe(len(emitted))
            self.seq_lens[slot] += j + 1
            self.last_tokens[slot, 0] = emitted[-1]
            if tr is not None and cap:
                now = self._tnow()
                tr.span("spec_verify", req.rid, now - 1, now,
                        replica=self.replica_id, drafted=cap, accepted=j)
            if req.done:
                req.speculating = False
                done_now.append(req)
                self._finish(slot)
        if self.stats["spec_drafted"]:
            self.stats["spec_accept_rate"] = round(
                self.stats["spec_accepted"] / self.stats["spec_drafted"], 4)
        return done_now

    # -------------------------------------------------------------- resize --
    def resize(self, *, max_slots: Optional[int] = None,
               num_pages: Optional[int] = None) -> None:
        """Live capacity change (the autoscaler's actuation point).

        Growth is immediate: slot-state rows / page pools are zero-padded,
        which leaves every live sequence's pages and tokens untouched.
        Shrink is drain-before-shrink: slots >= the new target stop
        admitting and the arrays slice down once those slots empty; pages
        >= the new pool size are retired from the free list now and the
        pools slice once their last owner finishes. A page shrink is
        clamped so the pool always covers every outstanding admission
        reservation — an admitted request can never hit a mid-flight OOM,
        resize or not. Each distinct (slots, pages) shape costs one jit
        re-trace, so callers should bucket targets (see
        ``repro.autoscale.controller``).
        """
        if max_slots is not None:
            if max_slots < 1:
                raise ValueError("max_slots must be >= 1")
            if max_slots > self.max_slots:
                self._grow_slots(max_slots)
            self.target_slots = max_slots
        if num_pages is not None:
            # cold retained chains are reclaimable — preempt them to the
            # host tier first so they never pin the pool against a shrink
            floor = (self.alloc.num_allocated + self.reserved_pages
                     - self.pages_in_use + 1)
            if num_pages < floor and self._retained:
                self._reclaim(floor - num_pages)
            # reservation-aware floor (+1 for the sink page): the pool must
            # cover every physically held page plus every outstanding
            # admission reservation's future growth
            num_pages = max(num_pages,
                            self.alloc.num_allocated + self.reserved_pages
                            - self.pages_in_use + 1, 2)
            if num_pages > self.alloc.num_pages:
                self.cache = PC.resize_cache_pages(self.cache, num_pages,
                                                   tp=self.tp)
                if self.spec_draft is not None:
                    self._draft_cache = PC.resize_cache_pages(
                        self._draft_cache, num_pages)
                self.alloc.grow(num_pages)
            else:
                self.alloc.request_shrink(num_pages)
        self.stats["resizes"] += 1
        self._settle_resize()

    def _grow_slots(self, new: int) -> None:
        pad = new - self.max_slots
        self.block_table = np.vstack(
            [self.block_table,
             np.full((pad, self.n_pg), PC.SINK_PAGE, np.int32)])
        self.seq_lens = np.concatenate(
            [self.seq_lens, np.zeros((pad,), np.int32)])
        self.last_tokens = np.vstack(
            [self.last_tokens, np.zeros((pad, 1), np.int32)])
        self.slot_req.extend([None] * pad)
        self.slot_pages.extend([] for _ in range(pad))
        self.slot_reserve.extend([0] * pad)
        self.slot_shared.extend([0] * pad)
        self.slot_resume_state.extend([None] * pad)
        self.slot_parked.extend([False] * pad)
        self.cache = PC.resize_cache_slots(self.cache, new)
        if self.spec_draft is not None:
            self._draft_cache = PC.resize_cache_slots(self._draft_cache, new)
            self._draft_ready.extend([False] * pad)
        self.max_slots = new

    def _settle_resize(self) -> None:
        """Complete any drained shrink (called between decode ticks)."""
        n = self.target_slots
        if n < self.max_slots and all(r is None for r in self.slot_req[n:]):
            self.block_table = self.block_table[:n]
            self.seq_lens = self.seq_lens[:n]
            self.last_tokens = self.last_tokens[:n]
            del self.slot_req[n:]
            del self.slot_pages[n:]
            del self.slot_reserve[n:]
            del self.slot_shared[n:]
            del self.slot_resume_state[n:]
            del self.slot_parked[n:]
            self.cache = PC.resize_cache_slots(self.cache, n)
            if self.spec_draft is not None:
                self._draft_cache = PC.resize_cache_slots(
                    self._draft_cache, n)
                del self._draft_ready[n:]
            self.max_slots = n
        if self.alloc.shrink_pending and self._retained:
            # retained chains holding pages above the shrink target would
            # stall the drain forever — they are cold, so preempt them now
            tgt = self.alloc._shrink_target
            for key in [k for k, c in list(self._retained.items())
                        if any(p >= tgt for p in c.pages)]:
                self._evict_chain(key)
            self._gauge_tiers()
        if self.alloc.shrink_ready():
            new_pages = self.alloc.complete_shrink()
            self.cache = PC.resize_cache_pages(self.cache, new_pages,
                                               tp=self.tp)
            if self.spec_draft is not None:
                self._draft_cache = PC.resize_cache_pages(
                    self._draft_cache, new_pages)

    # ---------------------------------------------------------------- step --
    @property
    def num_active(self) -> int:
        return sum(r is not None for r in self.slot_req)

    @property
    def pending(self) -> int:
        return len(self.waiting)

    @property
    def pending_due(self) -> int:
        """Waiting requests whose arrival time has passed — the real queue
        depth (benchmarks submit whole traces upfront with future
        ``arrival_step``s; those must not read as present load)."""
        return sum(r.arrival_step <= self.step_idx for r in self.waiting)

    def step(self, max_fuse: int = 16) -> List[Request]:
        """Admit what fits, run up to ``max_fuse`` fused decode ticks, evict
        finished requests.

        Fusing runs several ticks in one jit dispatch (a lax.scan) but only
        when nothing could change mid-scan — no active request finishes and
        no waiting arrival becomes due — so the schedule (and every token)
        is identical to single-stepping. Returns the requests that finished.
        A tick with no active slots (arrival gap) only advances the clock.
        """
        self._settle_resize()
        self._try_admit()
        if self.prefill_budget is not None and self._prefill_fifo:
            self._advance_prefills()
        done_now: List[Request] = self._admit_done
        self._admit_done = []
        # slots still landing chunks (PREFILLING) or parked for handoff sit
        # out of decode: masked below, they look exactly like empty slots
        decoding = [i for i, r in enumerate(self.slot_req)
                    if r is not None and r.prefill_pos is None
                    and not self.slot_parked[i]]
        if not decoding:
            # the idle fast-forward may only fire when the scheduler is
            # TRULY idle: any resident stream — including a PREFILLING
            # backlog or a parked handoff slot, neither of which decodes —
            # must see the clock advance one tick at a time, or queue-wait
            # and TTFT histograms under-count the wait that backlog caused
            busy = (self.num_active > 0 or bool(self._prefill_fifo)
                    or any(self.slot_parked))
            arrivals = [r.arrival_step for r in self.waiting]
            if not busy and arrivals and min(arrivals) > self.step_idx:
                # idle gap: skip toward the next arrival instead of spinning
                # ticks — capped at max_fuse so a control loop driving this
                # scheduler still samples (and can scale in) inside the gap
                self.step_idx = min(min(arrivals), self.step_idx + max_fuse)
            else:
                self.step_idx += 1
            return done_now
        if self.spec_k is not None:
            return self._spec_step(decoding, done_now)
        k = self._fuse_k(max_fuse, decoding)
        if self._prefill_fifo:
            k = 1                           # chunks land between single ticks
        k = 1 << (k.bit_length() - 1)       # pow2 buckets bound compiles
        self._grow_pages(k)
        self.stats["peak_pages"] = max(self.stats["peak_pages"],
                                       self.alloc.num_allocated)
        toks, lens, bt = self.last_tokens, self.seq_lens, self.block_table
        if len(decoding) < self.num_active:
            dec = set(decoding)
            toks, lens, bt = toks.copy(), lens.copy(), bt.copy()
            for i, r in enumerate(self.slot_req):
                if r is not None and i not in dec:
                    toks[i, 0] = 0          # identical to an empty slot: the
                    lens[i] = 0             # garbage token lands on the sink
                    bt[i] = PC.SINK_PAGE    # page, masked out of attention
        outs, self.cache = self._timed(
            "decode", self._decode_fn, self.params, self.cache,
            jnp.asarray(toks), jnp.asarray(lens), jnp.asarray(bt), k=k,
            tokens=k * len(decoding), ctx_tokens=int(np.sum(lens)))
        outs = np.asarray(outs)             # (k, max_slots)
        self.stats["decode_steps"] += k
        self.step_idx += k                  # before _finish: finish_step must
        for slot in decoding:               # not depend on max_fuse
            req = self.slot_req[slot]
            req.out_tokens.extend(int(t) for t in outs[:, slot])
            self.stats["tokens_out"] += k
            self.last_tokens[slot, 0] = int(outs[-1, slot])
            self.seq_lens[slot] += k
            if req.done:
                done_now.append(req)
                self._finish(slot)
        return done_now

    def run(self, max_steps: int = 100_000,
            max_fuse: int = 32) -> List[Request]:
        """Drive ``step`` until every submitted request has finished."""
        while (self.waiting or self.num_active) and max_steps:
            self.step(max_fuse=max_fuse)
            max_steps -= 1
        if self.waiting or self.num_active:
            raise RuntimeError(
                f"run() exhausted max_steps with {len(self.waiting)} waiting "
                f"and {self.num_active} active requests")
        return self.finished
