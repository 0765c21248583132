"""A serving replica: one scheduler + one page pool, placed on a *shard
group* of cluster nodes (one node at tp=1).

The fabric router (``repro.serving.router``) spreads requests over a fleet
of these. The wrapper is deliberately thin — all decode/admission logic
stays in ``ContinuousBatchingScheduler`` — and adds only what the fleet
needs to reason about a member:

* **placement** — the cluster hostnames this replica's "serve" service
  spans: ``tp`` shard-group members placed on contiguous nodes by
  ``AmbariServer.provision_serving`` + ``NodeDirectory`` (one hostname at
  tp=1; ``None``/empty for an unplaced, in-process fabric). ``fail()``
  purges the hostnames so a dead member can never read as still occupying
  a node in any hostname-derived stats or routing signal;
* **load** — ``outstanding_pages`` is the routing signal: worst-case pages
  reserved by admitted streams plus the worst-case pages of everything in
  the replica's own queue, so routing sees committed-but-not-yet-admitted
  work too (pages are logical, so the signal is tp-invariant);
* **device** — the one accelerator its params copy and page pool live on
  (``build(device=...)``; the router assigns replica ``i`` device
  ``i % n`` on an n-device host);
* **lifecycle** — ``draining`` stops new routing while admitted/queued
  streams finish (graceful scale-in); ``failed`` marks a dead replica
  (heartbeat DEAD / spot preemption) whose unfinished streams the router
  re-prefills elsewhere. A single preempted *member* of a tp>1 group is
  survivable when a warm spare exists — ``repro.autoscale.fleet`` swaps
  the node without failing the group;
* **role** — under disaggregation a replica is a ``prefill`` or
  ``decode`` specialist (default ``mixed`` does both): prefill replicas
  take every routed prompt, park completed prompts (``handoff_ready``)
  and donate their KV pages verbatim to a decode replica (the router's
  migration pass); ``fits`` on a prefill replica therefore checks prompt
  pages only, while decode replicas answer for the worst case.
"""
from __future__ import annotations

from typing import Any, List, Optional, Sequence

import jax

from repro.serving.request import Request, worst_case_pages
from repro.serving.scheduler import ContinuousBatchingScheduler


class ServingReplica:
    def __init__(self, replica_id: int,
                 sched: ContinuousBatchingScheduler, *,
                 hostname: Optional[str] = None,
                 hostnames: Optional[Sequence[str]] = None):
        if hostname is not None and hostnames is not None:
            raise ValueError("pass hostname or hostnames, not both")
        self.replica_id = replica_id
        self.sched = sched
        # the observability plane's identity for this member: lifecycle
        # spans carry the replica id, and the registry's exposition labels
        # every sample so a fleet's concatenated /metrics stays unambiguous
        sched.replica_id = replica_id
        sched.registry.labels.update({"replica": str(replica_id),
                                      "role": sched.role})
        self.hostnames: List[str] = (list(hostnames) if hostnames
                                     else [hostname] if hostname else [])
        if sched.tp > 1 and self.hostnames \
                and len(self.hostnames) != sched.tp:
            raise ValueError(
                f"shard group of tp={sched.tp} needs {sched.tp} hostnames, "
                f"got {self.hostnames}")
        self.draining = False
        self.failed = False

    @property
    def role(self) -> str:
        """Disaggregation role ("mixed" | "prefill" | "decode")."""
        return self.sched.role

    @property
    def hostname(self) -> Optional[str]:
        """Primary (rank-0) member hostname — the fleet's stable key for
        single-node replicas; None once failed (hostnames are purged)."""
        return self.hostnames[0] if self.hostnames else None

    @property
    def tp(self) -> int:
        return self.sched.tp

    def devices(self) -> List[str]:
        """Every device that holds a leaf of this replica's params or page
        pool, as JAX names it (one, under one-device-per-replica)."""
        leaves = jax.tree_util.tree_leaves((self.sched.params,
                                            self.sched.cache))
        return sorted({str(d) for leaf in leaves for d in leaf.devices()})

    @classmethod
    def build(cls, cfg, params, replica_id: int, *, max_slots: int = 4,
              page_size: int = 16, num_pages: Optional[int] = None,
              max_seq_len: int = 512, prefix_cache: Optional[bool] = None,
              tp: int = 1, hostname: Optional[str] = None,
              hostnames: Optional[Sequence[str]] = None,
              prefill_budget: Optional[int] = None,
              role: str = "mixed", spec_k: Optional[int] = None,
              spec_draft=None, host_pages: Optional[int] = None,
              tenant_quotas=None,
              swap_crossover: Optional[int] = None,
              device: Any = None) -> "ServingReplica":
        """``device``: the one device this replica's params copy and page
        pool live on (None: JAX's default device)."""
        sched = ContinuousBatchingScheduler(
            cfg, params, max_slots=max_slots, page_size=page_size,
            num_pages=num_pages, max_seq_len=max_seq_len,
            prefix_cache=prefix_cache, tp=tp, prefill_budget=prefill_budget,
            role=role, spec_k=spec_k, spec_draft=spec_draft,
            host_pages=host_pages, tenant_quotas=tenant_quotas,
            swap_crossover=swap_crossover, device=device)
        return cls(replica_id, sched, hostname=hostname, hostnames=hostnames)

    # -------------------------------------------------------------- state --
    @property
    def live(self) -> bool:
        """Accepting new routed requests."""
        return not (self.draining or self.failed)

    @property
    def num_unfinished(self) -> int:
        return self.sched.num_active + len(self.sched.waiting)

    @property
    def idle(self) -> bool:
        return self.num_unfinished == 0

    @property
    def reserved_pages(self) -> int:
        return self.sched.reserved_pages

    @property
    def outstanding_pages(self) -> int:
        """Routing load signal: reservations held by admitted streams plus
        the worst-case reservations of this replica's queued streams.

        Tier-aware by construction: retained (cold) chains and host-
        resident pages carry no reservation — they are reclaimable under
        pressure — so a replica dense with idle sessions still reads as
        lightly loaded, while its prefix index keeps advertising those
        sessions through ``prefix_match_len`` (affinity routing sees
        host-resident chains too)."""
        ps = self.sched.page_size
        queued = sum(worst_case_pages(r, ps) for r in self.sched.waiting)
        return self.sched.reserved_pages + queued

    @property
    def hot_pages(self) -> int:
        """Pages backing live streams (the autoscaler's working set)."""
        return self.sched.hot_pages

    def prefix_match_len(self, prompt) -> int:
        """Tokens of ``prompt`` already cached in this replica's page pool —
        the router's prefix-affinity routing signal."""
        return self.sched.prefix_match_len(prompt)

    def fits(self, req: Request) -> bool:
        """Could this replica *ever* admit the request (spill-over check)?
        A prefill-role replica answers for the prompt's pages only — the
        generation worst case is the adopting decode replica's burden."""
        if req.plen + req.max_new_tokens > self.sched.max_seq_len:
            return False
        cap = self.sched.alloc.capacity
        if self.sched.capacity_hint is not None:
            cap = max(cap, self.sched.capacity_hint - 1)
        if self.role == "prefill":
            from repro.serving.paged_cache import pages_for_len
            return pages_for_len(req.plen + 1, self.sched.page_size) <= cap
        return worst_case_pages(req, self.sched.page_size) <= cap

    # ------------------------------------------------------------- handoff --
    def handoff_ready(self) -> List[int]:
        """Slots parked after prefill, awaiting KV-page migration."""
        return self.sched.handoff_ready()

    def can_adopt(self, req: Request) -> bool:
        return self.sched.can_adopt(req)

    def adopt(self, req: Request, donor: "ServingReplica",
              donor_slot: int) -> int:
        """Verbatim page handoff: copy the donor slot's KV pages into this
        replica's pool, then release them on the donor."""
        slot = self.sched.adopt(req, donor.sched, donor_slot)
        # the donor may have died (and freed its copy of the pages) between
        # the copy and this release — surrender only a slot the donor still
        # holds for THIS stream, or a failed donor's already-cleared slot
        # would double-free
        if donor.sched.slot_req[donor_slot] is req:
            donor.sched.surrender_slot(donor_slot)
        req.replica = self.replica_id
        return slot

    # ---------------------------------------------------------- lifecycle --
    def accept(self, req: Request) -> None:
        req.replica = self.replica_id
        # routed requests are already due on the fleet clock; gate them on
        # the replica's own clock so admission may happen this very tick
        req.arrival_step = min(req.arrival_step, self.sched.step_idx)
        self.sched.submit_request(req)

    def step(self, max_fuse: int = 16) -> List[Request]:
        return self.sched.step(max_fuse=max_fuse)

    def drain(self) -> None:
        """Stop routing to this replica; admitted/queued streams finish."""
        self.draining = True

    def undrain(self) -> None:
        if not self.failed:
            self.draining = False

    def fail(self) -> List[Request]:
        """Mark dead and surrender every unfinished stream for re-routing.

        The device state is considered lost: queued streams come back
        untouched, admitted streams come back with the tokens they already
        emitted (the router re-prefills ``prompt + out_tokens`` elsewhere).
        The hostnames are purged too: every hostname-derived signal —
        node-occupancy checks before a release, prefix-affinity stats, a
        later ``fail_host`` sweep — must stop seeing this replica on its
        nodes the moment it dies, or a replacement booting on the same
        hostname races a ghost (the regression in tests/test_fabric.py).
        """
        self.failed = True
        self.draining = True
        self.hostnames = []           # purge placement: the nodes are free
        lost: List[Request] = list(self.sched.waiting)
        self.sched.waiting.clear()
        # host-side bookkeeping is still ours to zero out (the simulated
        # node is gone; the scheduler object just stops being stepped)
        for slot, req in enumerate(self.sched.slot_req):
            if req is not None:
                if req.replica is not None \
                        and req.replica != self.replica_id:
                    # adopted away mid-handoff (scheduler.adopt transfers
                    # ownership at the copy point): the decode side owns the
                    # only live copy of the stream — free our now-orphaned
                    # source pages, but do NOT requeue it or touch its
                    # cursor, or the stream would decode twice
                    self.sched.stats["migrations_out"] += 1
                else:
                    lost.append(req)
                    req.prefill_pos = None  # a mid-prefill stream restarts
                self.sched.alloc.free(self.sched.slot_pages[slot])
                self.sched.slot_pages[slot] = []
                self.sched.slot_req[slot] = None
                self.sched.slot_reserve[slot] = 0
                self.sched.slot_shared[slot] = 0
                self.sched.slot_parked[slot] = False
                self.sched.slot_resume_state[slot] = None
        self.sched._prefill_fifo.clear()
        self.sched.reserved_pages = 0
        # both memory tiers died with the node: retained chains release
        # their refs, host-RAM rows are dropped, tenant ledgers reset
        self.sched.drop_tier_state()
        self.sched.index.clear()      # the device's cached prefixes died too
        return lost

    def stats(self) -> dict:
        return dict(self.sched.stats)

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        host = self.hostname or "unplaced"
        return (f"ServingReplica({self.replica_id}@{host}, "
                f"active={self.sched.num_active}, "
                f"queued={len(self.sched.waiting)}, "
                f"reserved={self.sched.reserved_pages})")
