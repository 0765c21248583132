"""Fabric router: one front-end over k scheduler replicas on cluster nodes.

PR 1–2 built a continuous-batching scheduler and taught it to resize, but
one scheduler is one implicit node — the cluster the provisioning layer
builds never shows up in serving throughput. The router makes "serve" a
fleet service:

* **arrival queue** — ``submit`` lands requests in one fleet-wide queue
  gated on the fleet clock; each tick the router routes everything due.
* **routing** — least-outstanding-reserved-pages: candidates are live
  replicas ordered by ``(outstanding_pages, replica_id)`` (the id is the
  deterministic tie-break, so a fleet run is replayable); the first
  candidate whose pool could ever hold the request wins — a request too
  big for the least-loaded replica's pool *spills over* to the next.
  ``route_policy="prefix-affinity"`` instead orders by longest cached
  prompt prefix first (then the least-pages key): the replica already
  holding a request's persona pages admits it with a prefix-cache hit —
  skipping the shared prefill and sharing the pages — where any other
  replica would duplicate both. All-miss requests degrade to least-pages,
  so affinity also spreads *new* prefixes across the fleet.
* **drain / fail** — ``drain_replica`` stops new routing while the
  replica's streams finish (graceful scale-in: the fleet autoscaler's
  scale-in path); ``fail_replica`` (heartbeat DEAD, spot preemption)
  surrenders unfinished streams, and the router re-prefills each one's
  ``prompt + emitted tokens`` on a surviving replica. Greedy decoding
  depends only on the prefix, so the re-routed continuation is
  token-identical for dense/SSM archs (MoE shares the scheduler's
  capacity-coupling caveat).
* **clocks** — replicas keep private scheduler clocks (a replica added at
  fleet tick 40 starts at 0); the router stamps ``finish_step`` and
  restores ``arrival_step`` on the fleet clock when it collects a finished
  request, so latency percentiles are comparable fleet-wide.

Placement is by hostname: ``AmbariServer.provision_serving(replicas=k)``
picks k nodes from the ``NodeDirectory`` and the fleet autoscaler
(``repro.autoscale.fleet``) acquires/releases nodes through
``ClusterLifecycle`` as it adds/removes replicas. ``fail_host`` is the
heartbeat hook: wire ``monitor.on_dead(router.fail_host)``.

**Disaggregation** (``disagg=k``): the first ``k`` replicas become
*prefill* specialists and the rest *decode* specialists. Every prompt
routes to a prefill replica (prefix-affinity and spillover unchanged);
when its prefill completes the stream parks and the router's migration
pass hands its KV pages verbatim to the least-loaded decode replica that
can adopt it (worst-case reservation on the decode side, so an adopted
stream can never OOM). No decode-capable target with room means the
stream stays parked — natural backpressure on the prefill side. Migration
keeps the same ``Request`` object, so fleet-clock latency accounting and
the re-route machinery are untouched; a prefill replica dying mid-prompt
falls back to the existing re-prefill path.

With ``tp > 1`` every fabric member is a *shard group*: one logical
scheduler spanning tp nodes (``provision_serving(tp=k)`` hands out
contiguous node sets, the fleet autoscaler acquires/releases tp nodes per
scaling decision), and ``fail_host`` fails the whole group when any
member dies — unless the fleet controller replaces the member from a warm
spare first, in which case the group's streams never notice. Routing is
tp-agnostic: pages are logical, so ``outstanding_pages`` and the prefix
index compare across members of different tp.
"""
from __future__ import annotations

import collections
import time
from typing import Any, Deque, Dict, List, Optional, Sequence

import jax

from repro.configs.base import ModelConfig
from repro.obs.metrics import (Histogram, MetricsRegistry, StatsView,
                               SECONDS_BUCKETS, TICK_BUCKETS)
from repro.serving import paged_cache as PC
from repro.serving.replica import ServingReplica
from repro.serving.request import Request, make_request, worst_case_pages
from repro.serving.scheduler import supports_paged

ROUTE_POLICIES = ("least-pages", "round-robin", "prefix-affinity")


class ServingRouter:
    """Front-end owning the fleet arrival queue and k scheduler replicas.

    Constructor knobs mirror one replica's scheduler (``max_slots``,
    ``page_size``, ``num_pages``, ``max_seq_len`` are *per replica* — use
    ``serving_page_plan(..., replicas=k)`` for a coherent split) plus the
    fleet ones: ``replicas`` initial fleet size, ``placement`` hostnames,
    ``route_policy`` in ``ROUTE_POLICIES``.
    """

    def __init__(self, cfg: ModelConfig, params: Any, *, replicas: int = 1,
                 max_slots: int = 4, page_size: int = 16,
                 num_pages: Optional[int] = None, max_seq_len: int = 512,
                 placement: Optional[Sequence[Any]] = None,
                 route_policy: str = "least-pages",
                 prefix_cache: Optional[bool] = None, tp: int = 1,
                 prefill_budget: Optional[int] = None, disagg: int = 0,
                 spec_k: Optional[int] = None, spec_draft=None,
                 host_pages: Optional[int] = None, tenant_quotas=None,
                 swap_crossover: Optional[int] = None):
        if not supports_paged(cfg):
            raise NotImplementedError(
                f"{cfg.name}: the fabric routes over paged schedulers; "
                "MLA/enc-dec archs stay on repro.serving.engine")
        if replicas < 1:
            raise ValueError("need at least one replica")
        if route_policy not in ROUTE_POLICIES:
            raise ValueError(f"route_policy must be one of {ROUTE_POLICIES}")
        if disagg and not 1 <= disagg < replicas:
            raise ValueError(
                f"disagg={disagg} needs 1 <= prefill replicas < "
                f"replicas ({replicas}) so both roles exist")
        self.cfg = cfg
        self.params = params
        # one device per replica: replica i's params copy and page pool
        # live on devices[i % n]; one copy of the params per device, shared
        # by every replica placed there. On one device nothing moves.
        self._devices = jax.devices()
        self._device_params: Dict[Any, Any] = {}
        # tp > 1: every fabric member is a shard group — tp nodes, one
        # logical scheduler (placement entries become hostname *lists*)
        self.replica_kw = dict(max_slots=max_slots, page_size=page_size,
                               num_pages=num_pages, max_seq_len=max_seq_len,
                               prefix_cache=prefix_cache, tp=tp,
                               prefill_budget=prefill_budget,
                               spec_k=spec_k, spec_draft=spec_draft,
                               host_pages=host_pages,
                               tenant_quotas=tenant_quotas,
                               swap_crossover=swap_crossover)
        # prefill/decode disaggregation: True once the fleet splits roles
        self.disagg = disagg > 0
        self.route_policy = route_policy
        self.replicas: Dict[int, ServingReplica] = {}
        self.waiting: Deque[Request] = collections.deque()
        self.finished: List[Request] = []
        self.step_idx = 0
        self._rid = 0
        self._next_replica = 0
        self._rr_cursor = 0                  # round-robin route state
        self._arrival: Dict[int, int] = {}   # rid -> fleet arrival tick
        # continuation -> original request (re-routes after a replica loss)
        self._parents: Dict[int, Request] = {}
        # fleet-level observability plane (repro.obs): the router's own
        # counters live on a fleet registry (StatsView keeps the dict
        # idioms), plus fleet-clock latency and per-replica step-wall
        # histograms. ``set_tracer`` threads one lifecycle tracer through
        # every replica on the *fleet* clock; ``enable_profiling`` shares
        # one kernel profiler fleet-wide. All of it is read-only.
        self.registry = MetricsRegistry(labels={"plane": "fleet"})
        self.stats = StatsView({
            k: self.registry.counter(f"fleet_{k}")
            for k in ("routed", "spillovers", "reroutes", "replicas_added",
                      "replicas_removed", "migrations")})
        self.h_latency = self.registry.histogram(
            "fleet_latency_ticks", TICK_BUCKETS, unit="ticks",
            help="fleet-clock ticks from arrival to finish")
        self.h_tick_wall = self.registry.histogram(
            "fleet_tick_wall_seconds", SECONDS_BUCKETS, unit="seconds",
            help="wall seconds of one replica step within a fleet tick")
        self.tracer = None
        self._profiler = None
        # per-tick per-replica step wall times (seconds), recorded only when
        # a bench turns it on: [{replica_id: (role, dt)}, ...]
        self.record_timing = False
        self.tick_timings: List[Dict[int, tuple]] = []
        # counters of replicas that already left the fleet, so fleet totals
        # survive drain-remove and failure
        self._retired_stats: Dict[str, int] = {}
        # same, for departed replicas' latency histograms (bucket counts
        # merge exactly, so fleet quantiles survive churn too)
        self._retired_hists: Dict[str, Histogram] = {}
        # (tick, [reserved_pages per live replica]) when >= 2 are live and
        # every one has work — the steady-state balance samples
        self.balance_log: List[tuple] = []
        placement = list(placement or [])
        for i in range(replicas):
            spot = placement[i] if i < len(placement) else None
            kw = {}
            if disagg:
                kw["role"] = "prefill" if i < disagg else "decode"
            if spot is None or isinstance(spot, str):
                self.add_replica(hostname=spot, **kw)
            else:
                self.add_replica(hostnames=spot, **kw)

    # ----------------------------------------------------------- topology --
    def add_replica(self, *, hostname: Optional[str] = None,
                    hostnames: Optional[Sequence[str]] = None,
                    **overrides: Any) -> ServingReplica:
        """Add a fabric member (``overrides`` patch the default replica
        sizing — fleet members become heterogeneous the moment per-replica
        autoscalers resize them, so routing never assumes symmetry). A
        shard-group member (tp > 1) takes ``hostnames`` — its ``tp`` node
        placement — instead of a single ``hostname``. On a host with n > 1
        devices the member lands on device ``replica_id % n``."""
        params, device = self.params, None
        if len(self._devices) > 1:
            device = self._devices[self._next_replica % len(self._devices)]
            if device not in self._device_params:
                self._device_params[device] = jax.device_put(self.params,
                                                             device)
            params = self._device_params[device]
        rep = ServingReplica.build(
            self.cfg, params, self._next_replica, hostname=hostname,
            hostnames=hostnames, device=device,
            **{**self.replica_kw, **overrides})
        self.replicas[rep.replica_id] = rep
        self._next_replica += 1
        self.stats["replicas_added"] += 1
        self._wire_obs(rep)
        return rep

    # ------------------------------------------------------- observability --
    def _wire_obs(self, rep: ServingReplica) -> None:
        """Thread the fleet tracer/profiler into a (new) replica."""
        if self.tracer is not None:
            rep.sched.set_tracer(self.tracer, own_clock=False)
            self.tracer.set_process_name(
                rep.replica_id, f"replica-{rep.replica_id} ({rep.role})")
        if self._profiler is not None:
            rep.sched.profiler = self._profiler

    def set_tracer(self, tracer) -> None:
        """Attach one lifecycle tracer fleet-wide. Every replica's hooks
        stamp the *fleet* clock (replica clocks drift through idle-gap
        skipping), so all spans share a single timeline."""
        self.tracer = tracer
        for rep in self.replicas.values():
            self._wire_obs(rep)

    def enable_profiling(self, profiler=None):
        """One shared kernel profiler across the fleet (fleet-total
        dispatch timings; see ``repro.obs.profile``)."""
        if profiler is None:
            from repro.obs.profile import KernelProfiler
            profiler = KernelProfiler(self.cfg, tp=self.replica_kw["tp"])
        self._profiler = profiler
        for rep in self.replicas.values():
            rep.sched.profiler = profiler
        return profiler

    def expose(self) -> str:
        """Prometheus text exposition: the fleet registry plus every live
        replica's registry (labeled per replica by ``ServingReplica``)."""
        parts = [self.registry.expose()]
        for rep in sorted(self.replicas.values(),
                          key=lambda r: r.replica_id):
            parts.append(rep.sched.registry.expose())
        return "".join(parts)

    def fleet_histogram(self, name: str) -> Optional[Histogram]:
        """Fleet-wide merge of a per-replica histogram (live replicas plus
        retired ones); None if no replica ever registered it."""
        agg: Optional[Histogram] = None
        sources = [rep.sched.registry.get(name)
                   for rep in sorted(self.replicas.values(),
                                     key=lambda r: r.replica_id)]
        sources.append(self._retired_hists.get(name))
        for m in sources:
            if not isinstance(m, Histogram):
                continue
            if agg is None:
                agg = Histogram(name, m.bounds, help=m.help, unit=m.unit)
            agg.merge(m)
        return agg

    def drain_replica(self, replica_id: int) -> ServingReplica:
        rep = self.replicas[replica_id]
        rep.drain()
        return rep

    def undrain_replica(self, replica_id: int) -> ServingReplica:
        rep = self.replicas[replica_id]
        rep.undrain()
        return rep

    def remove_replica(self, replica_id: int) -> Optional[str]:
        """Remove a drained-and-empty (or failed) replica; returns its
        hostname so the caller can release the node."""
        rep = self.replicas[replica_id]
        if not rep.failed and not rep.idle:
            raise RuntimeError(
                f"replica {replica_id} still holds {rep.num_unfinished} "
                "unfinished requests; drain it first")
        self._retire_stats(rep)
        del self.replicas[replica_id]
        self.stats["replicas_removed"] += 1
        return rep.hostname

    def _retire_stats(self, rep: ServingReplica) -> None:
        for k, v in rep.stats().items():
            self._retired_stats[k] = self._retired_stats.get(k, 0) + v
        for m in rep.sched.registry.metrics():
            if isinstance(m, Histogram):
                agg = self._retired_hists.setdefault(
                    m.name, Histogram(m.name, m.bounds, help=m.help,
                                      unit=m.unit))
                agg.merge(m)

    def fail_replica(self, replica_id: int) -> List[Request]:
        """Replica death (heartbeat DEAD / spot preemption): surrender its
        unfinished streams and queue token-identical continuations. A
        replica already failed directly (member death observed ahead of
        the router) is simply retired from the fleet — its hostnames and
        streams were purged by ``ServingReplica.fail()``."""
        rep = self.replicas[replica_id]
        if rep.failed:
            self._retire_stats(rep)
            del self.replicas[replica_id]
            self.stats["replicas_removed"] += 1
            return []
        lost = rep.fail()
        if self.tracer is not None:
            self.tracer.instant("failover", t=self.step_idx,
                                replica=replica_id, lost=len(lost))
        rerouted = []
        for req in lost:
            rerouted.append(self._requeue(req))
        self.stats["reroutes"] += len(rerouted)
        self._retire_stats(rep)
        del self.replicas[replica_id]
        self.stats["replicas_removed"] += 1
        return rerouted

    def fail_host(self, hostname: str) -> List[Request]:
        """Heartbeat hook: fail every replica with a member on
        ``hostname`` — losing one shard of a tp-way group loses the whole
        group's device state (unless the fleet controller intercepts the
        death first and swaps the member from a warm spare)."""
        out = []
        for rid in [r.replica_id for r in self.replicas.values()
                    if hostname in r.hostnames]:
            out.extend(self.fail_replica(rid))
        return out

    def _requeue(self, req: Request) -> Request:
        """Queue the continuation of a lost stream at the *front* (it has
        already waited once; re-prefill as soon as capacity exists)."""
        tr = self.tracer
        if tr is not None:
            # the lost stream's open span (whichever state it died in)
            for name in ("decode", "parked", "queued"):
                tr.end(name, req.rid, t=self.step_idx, lost=True)
        orig = self._parents.pop(req.rid, req)   # chain continuations
        orig.replica = None
        orig.reroutes += 1
        if req is not orig:
            orig.out_tokens.extend(req.out_tokens)
            orig.cached_tokens += req.cached_tokens
        if orig.remaining_tokens == 0:
            # lost after its last token was emitted: it is simply finished
            self._collect(orig)
            return orig
        cont = make_request(self._rid, list(orig.prompt) + orig.out_tokens,
                            orig.remaining_tokens,
                            arrival_step=self.step_idx)
        self._rid += 1
        self._parents[cont.rid] = orig
        self.waiting.appendleft(cont)
        if tr is not None:
            tr.instant("reroute", rid=req.rid, t=self.step_idx,
                       cont=cont.rid,
                       emitted=len(orig.out_tokens))
            tr.begin("queued", cont.rid, t=self.step_idx)
        return cont

    # --------------------------------------------------------- submission --
    def submit(self, prompt, max_new_tokens: int,
               arrival_step: int = 0, priority: int = 1,
               tenant: str = "default") -> Request:
        req = make_request(self._rid, prompt, max_new_tokens, arrival_step,
                           priority=priority, tenant=tenant)
        self._rid += 1
        if not any(rep.fits(req) for rep in self.replicas.values()
                   if rep.role != "decode"):
            raise ValueError(
                f"request needs {req.plen + req.max_new_tokens} positions / "
                f"{worst_case_pages(req, self.replica_kw['page_size'])} "
                f"pages — no replica in the fleet could ever admit it")
        if self.disagg and not any(
                rep.fits(req) for rep in self.replicas.values()
                if rep.role != "prefill"):
            raise ValueError(
                f"request needs {req.plen + req.max_new_tokens} positions "
                "but no decode-role replica could ever adopt it after "
                "prefill")
        self._arrival[req.rid] = arrival_step
        self.waiting.append(req)
        if self.tracer is not None:
            self.tracer.begin("queued", req.rid, t=arrival_step)
        return req

    # ------------------------------------------------------------ routing --
    def _live(self) -> List[ServingReplica]:
        return sorted((r for r in self.replicas.values() if r.live),
                      key=lambda r: r.replica_id)

    def _routable(self) -> List[ServingReplica]:
        """Live replicas new prompts may route to — decode specialists only
        take work through the migration pass."""
        return [r for r in self._live() if r.role != "decode"]

    def _candidates(self, live: List[ServingReplica],
                    req: Request) -> List[ServingReplica]:
        if self.route_policy == "round-robin":
            k = len(live)
            order = [live[(self._rr_cursor + i) % k] for i in range(k)]
            self._rr_cursor = (self._rr_cursor + 1) % max(k, 1)
            return order
        if self.route_policy == "prefix-affinity":
            # longest cached prefix first — the replica already holding the
            # request's prefix pages skips that much prefill and shares the
            # pages instead of duplicating them. Least-outstanding-pages
            # breaks affinity ties (including the all-miss case, where this
            # degrades to the default policy), replica id breaks the rest,
            # so placement stays deterministic and replayable.
            return sorted(live, key=lambda r: (
                -r.prefix_match_len(req.prompt), r.outstanding_pages,
                r.replica_id))
        return sorted(live, key=lambda r: (r.outstanding_pages,
                                           r.replica_id))

    def route_due(self) -> int:
        """Assign every due waiting request to a replica; returns count."""
        routed = 0
        deferred: List[Request] = []
        while self.waiting:
            if self.waiting[0].arrival_step > self.step_idx:
                break
            req = self.waiting.popleft()
            live = self._routable()
            placed = False
            for i, rep in enumerate(self._candidates(live, req)):
                if rep.fits(req):
                    if i > 0:
                        self.stats["spillovers"] += 1
                    if self.tracer is not None:
                        self.tracer.instant("routed", rid=req.rid,
                                            t=self.step_idx,
                                            replica=rep.replica_id,
                                            spillover=i > 0)
                    rep.accept(req)
                    routed += 1
                    placed = True
                    break
            if not placed:
                # no live replica can ever hold it right now (e.g. every
                # fleet member is draining): hold at the front until the
                # fleet changes shape
                deferred.append(req)
        for req in reversed(deferred):
            self.waiting.appendleft(req)
        self.stats["routed"] += routed
        return routed

    # --------------------------------------------------------------- step --
    @property
    def num_unfinished(self) -> int:
        return (len(self.waiting)
                + sum(r.num_unfinished for r in self.replicas.values()))

    @property
    def pending_due(self) -> int:
        return sum(r.arrival_step <= self.step_idx for r in self.waiting)

    def _collect(self, req: Request) -> None:
        req.finish_step = self.step_idx
        req.arrival_step = self._arrival.pop(req.rid, req.arrival_step)
        self.finished.append(req)
        self.h_latency.observe(req.finish_step - req.arrival_step)

    def _migrate_ready(self) -> int:
        """Hand parked prefilled streams to decode-capable replicas.

        Donors drain oldest-parked-first; each stream goes to the live
        non-prefill replica with the fewest outstanding pages that can
        adopt it (free slot + full worst-case reservation). A stream with
        no adoptable target stays parked and retries next tick — the
        backpressure that keeps prefill replicas from outrunning decode
        capacity."""
        moved = 0
        for donor in sorted(self.replicas.values(),
                            key=lambda r: r.replica_id):
            if donor.failed or donor.role != "prefill":
                continue
            for slot in donor.handoff_ready():
                req = donor.sched.slot_req[slot]
                targets = sorted(
                    (r for r in self._live() if r.role != "prefill"),
                    key=lambda r: (r.outstanding_pages, r.replica_id))
                for t in targets:
                    if t.can_adopt(req):
                        n_pages = len(donor.sched.slot_pages[slot])
                        t.adopt(req, donor, slot)
                        moved += 1
                        if self.tracer is not None:
                            self.tracer.instant(
                                "page_migration", rid=req.rid,
                                t=self.step_idx, replica=t.replica_id,
                                src=donor.replica_id, dst=t.replica_id,
                                pages=n_pages,
                                bytes=PC.migration_bytes(
                                    self.cfg, n_pages,
                                    self.replica_kw["page_size"]))
                        break
        self.stats["migrations"] += moved
        return moved

    def step(self, max_fuse: int = 16) -> List[Request]:
        """One fleet tick: route due arrivals, step every replica once,
        migrate parked prefilled streams to decode replicas, collect
        finishes (joining re-routed continuations to their originals),
        advance the fleet clock."""
        if self.tracer is not None:
            self.tracer.set_tick(self.step_idx)
        self.route_due()
        done_now: List[Request] = []
        timing: Dict[int, tuple] = {}
        for rep in sorted(self.replicas.values(),
                          key=lambda r: r.replica_id):
            if rep.failed:
                continue
            t0 = time.perf_counter()
            stepped = rep.step(max_fuse=max_fuse)
            dt = time.perf_counter() - t0
            self.h_tick_wall.observe(dt)
            if self.record_timing:
                timing[rep.replica_id] = (rep.role, dt)
            for req in stepped:
                orig = self._parents.pop(req.rid, None)
                if orig is not None:
                    orig.out_tokens.extend(req.out_tokens)
                    orig.cached_tokens += req.cached_tokens
                    req = orig
                self._collect(req)
                done_now.append(req)
        if self.record_timing:
            self.tick_timings.append(timing)
        self._migrate_ready()
        if len(self.replicas) >= 2:
            live = self._live()
            if len(live) >= 2 and all(r.sched.num_active > 0 for r in live):
                self.balance_log.append(
                    (self.step_idx, [r.reserved_pages for r in live]))
        self.step_idx += 1
        return done_now

    def run(self, max_steps: int = 100_000,
            max_fuse: int = 16) -> List[Request]:
        while self.num_unfinished and max_steps:
            self.step(max_fuse=max_fuse)
            max_steps -= 1
        if self.num_unfinished:
            raise RuntimeError(
                f"router run() exhausted max_steps with "
                f"{self.num_unfinished} unfinished requests")
        return self.finished

    # ------------------------------------------------- role-split signals --
    def live_by_role(self, role: str) -> List[ServingReplica]:
        return [r for r in self._live() if r.role == role]

    def prefill_backlog(self) -> int:
        """Prompt tokens awaiting prefill fleet-wide: due queued prompts
        plus every prefill-capable replica's in-flight chunk remainders —
        the prefill-role autoscaling signal."""
        t = sum(r.plen for r in self.waiting
                if r.arrival_step <= self.step_idx)
        for rep in self.replicas.values():
            if not rep.failed and rep.role != "decode":
                t += rep.sched.prefill_backlog
        return t

    def decode_demand(self) -> int:
        """Streams that need (or are about to need) a decode slot: active
        and queued streams on decode-capable replicas plus prefilled
        streams parked for handoff — the decode-role autoscaling signal."""
        n = 0
        for rep in self.replicas.values():
            if rep.failed:
                continue
            if rep.role == "prefill":
                n += len(rep.handoff_ready())
            else:
                n += rep.num_unfinished
        return n

    # ------------------------------------------------------------ metrics --
    def prefix_hit_rate(self) -> float:
        """Fleet-wide prefix-cache hit rate over all prefills so far,
        retired replicas included — the single definition shared by
        ``fleet_stats`` and the fleet autoscaler's telemetry."""
        hits = self._retired_stats.get("prefix_hits", 0)
        prefills = self._retired_stats.get("prefills", 0)
        for r in self.replicas.values():
            hits += r.sched.stats["prefix_hits"]
            prefills += r.sched.stats["prefills"]
        return hits / prefills if prefills else 0.0

    def imbalance(self) -> Optional[float]:
        """Mean steady-state reserved-page imbalance (max-min over mean)
        across the balance samples; None when the fleet never had two busy
        replicas at once."""
        if not self.balance_log:
            return None
        vals = []
        for _, pages in self.balance_log:
            mean = sum(pages) / len(pages)
            if mean > 0:
                vals.append((max(pages) - min(pages)) / mean)
        return sum(vals) / len(vals) if vals else None

    def fleet_stats(self) -> Dict[str, Any]:
        per_replica = {rid: rep.stats() for rid, rep in
                       sorted(self.replicas.items())}
        out: Dict[str, Any] = dict(self.stats)
        out["fleet_ticks"] = self.step_idx
        out["live_replicas"] = len(self._live())
        for key in ("tokens_out", "decode_steps", "prefills",
                    "prefix_hits", "cached_tokens", "cow_forks",
                    "prefill_chunk_tokens", "migrations_in",
                    "migrations_out", "prefill_dispatches",
                    "prefill_compiles", "spec_ticks", "spec_drafted",
                    "spec_accepted", "swap_outs", "swap_out_pages",
                    "swap_ins", "swap_in_pages", "swap_reprefills",
                    "host_evictions", "quota_blocked", "index_evictions"):
            out[key] = (sum(s.get(key, 0) for s in per_replica.values())
                        + self._retired_stats.get(key, 0))
        # tier gauges: summed over *live* replicas only (retired replicas'
        # tiers died with them, so their last gauge values must not linger)
        for key in ("host_pages_used", "retained_pages"):
            out[key] = sum(s.get(key, 0) for rid, s in per_replica.items()
                           if self.replicas[rid].live)
        # derived, not summed: the fleet accept rate over all drafts so far
        out["spec_accept_rate"] = round(
            out["spec_accepted"] / max(out["spec_drafted"], 1), 4)
        out["prefix_hit_rate"] = round(self.prefix_hit_rate(), 3)
        imb = self.imbalance()
        if imb is not None:
            out["reserved_page_imbalance"] = round(imb, 3)
        out["per_replica"] = per_replica
        return out
