"""Paged KV cache: shared page pools + block tables for the serving engine.

The dense engine (``repro.serving.engine``) gives every sequence a
capacity-padded ring buffer — memory scales with ``batch * capacity`` even
when most sequences are short. Here K/V live in per-layer *page pools* of
shape ``(num_pages, page_size, KVH, head_dim)``; a sequence owns just the
pages its tokens fill, recorded in a block table row. Allocation and
freeing are O(pages) host-side list operations, so the continuous-batching
scheduler (``repro.serving.scheduler``) can admit and evict sequences
mid-flight without reshaping any device buffer.

Layout invariants
-----------------
* Page 0 is the **sink page**: never allocated, and every unused block-table
  entry points at it. Idle decode slots write their garbage token there and
  the attention mask (``seq_lens``) keeps it out of every real sequence's
  softmax.
* Token ``t`` of a sequence lives at ``(block_table[t // page_size],
  t % page_size)`` — pages are filled densely in order, so a sequence of
  length ``n`` owns exactly ``ceil(n / page_size)`` pages.
* With ``cfg.cache_quant`` the pools hold int8 K/V plus fp32
  per-(position, kv-head) scale pages — the same quantisation contract as
  the dense engine's ring buffers (``repro.models.attention.quantize_kv``).

SSM layers need no paging (their state is O(1) per sequence); they keep a
dense ``(max_slots, ...)`` state row per scheduler slot in the same cache
pytree, so hybrid archs (jamba, mamba2) flow through the same decode step.

Shared prefixes: pages carry refcounts, ``PrefixIndex`` maps token-hash
chains of in-flight prompts to the pages holding their K/V, and
``copy_page`` is the copy-on-write fork for a sequence diverging inside a
shared page — see docs/serving.md "Shared prefixes" for the state diagram
and the admission contract built on top in ``repro.serving.scheduler``.

Shard groups (``tp > 1``): pages are *logical*, storage is *per shard*.
Every attention pool leaf grows a leading shard axis — shard ``s`` stores
the ``KVH/tp`` kv-head slice ``[s*KVH/tp, (s+1)*KVH/tp)`` of every page —
while the page-id space, the allocator's refcounts, the block tables, and
the prefix index stay a single shared control plane: page ``p`` addresses
the same slot in every shard's pool the same way it already addresses the
same slot in every layer's pool. Cache ops that move whole pages
(``write_prefill``, ``copy_page``, ``resize_cache_pages``) take ``tp`` and
touch every shard's slice in one call, so a COW fork or prefill insert can
never leave shards disagreeing about a page's contents — the invariant
the sharded rule set in tests/test_allocator_props.py drives. SSM slot
state is O(1) per sequence and stays replicated (unsharded).

Host-RAM tier: ``HostPageTier`` is a second page plane in host memory
with its own allocator; ``swap_out_pages`` / ``swap_in_pages`` move page
chains between tiers byte-exactly (int8/fp8 pools and scale pages
included), ``HOST_BIT`` tags host-resident ids wherever they sit in the
shared id spaces (saved block-table rows, prefix-index chains), and
``swap_resume_cost`` is the modeled recompute-vs-transfer decision the
scheduler resumes preempted streams with — see docs/serving.md "Memory
tiers & preemption".
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import ssm as ssm_mod
from repro.models.attention import kv_quant_mode, quantize_kv
from repro.models.transformer import depth_plan

SINK_PAGE = 0

# leaves whose first axis is the page-pool axis
PAGE_LEAVES = ("k_pages", "v_pages", "k_scale_pages", "v_scale_pages")

# Residency bit: a page id with HOST_BIT set addresses the HostPageTier's
# id space instead of the device pools. Block-table rows of swapped-out
# streams and prefix-index chains preempted to host carry tagged ids; the
# device allocator, the decode kernels, and every live block table only
# ever see untagged ids — swap-in strips the bit before a page re-enters
# the control plane. 2^30 keeps tagged ids positive in int32 block tables.
HOST_BIT = 1 << 30


def is_host_page(page: int) -> bool:
    """True if ``page`` is a host-tier id (residency bit set)."""
    return bool(int(page) & HOST_BIT)


def host_page_id(page: int) -> int:
    """Strip the residency bit: the HostPageTier-plane id."""
    return int(page) & ~HOST_BIT


def as_host_page(page: int) -> int:
    """Tag a host-plane id for storage in index chains / saved rows."""
    return int(page) | HOST_BIT


def pages_for_len(n_tokens: int, page_size: int) -> int:
    """Pages a sequence of ``n_tokens`` occupies (dense fill from page 0)."""
    return -(-n_tokens // page_size)


class PageAllocator:
    """Host-side refcounted free-list allocator over the shared page-id space.

    One allocator serves every layer: layer pools are shaped identically, so
    page id ``p`` addresses the same slot in each. Page 0 (the sink) is
    never handed out.

    Pages are *refcounted* so the prefix cache can share one physical page
    between sequences: ``alloc`` hands out pages at refcount 1, ``share``
    adds an owner, and ``free`` drops one reference per page — a shared page
    survives until its last owner releases it. ``on_free`` (when set) fires
    once per page as its refcount reaches zero, before the page re-enters
    the free list; the scheduler wires it to prefix-index invalidation.
    """

    def __init__(self, num_pages: int):
        assert num_pages >= 2, "need at least one allocatable page + sink"
        self.num_pages = num_pages
        self._free: List[int] = list(range(num_pages - 1, SINK_PAGE, -1))
        self._owner: Dict[int, Any] = {}      # page -> first owner (debug aid)
        self._ref: Dict[int, int] = {}        # page -> live reference count
        self.on_free = None                   # callback(page_id) at ref == 0

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_allocated(self) -> int:
        return len(self._ref)

    def ref(self, page: int) -> int:
        """Live reference count of ``page`` (0 if free/retired)."""
        return self._ref.get(page, 0)

    def can_alloc(self, n: int) -> bool:
        return n <= len(self._free)

    def alloc(self, n: int, owner: Any = None) -> List[int]:
        if n > len(self._free):
            raise MemoryError(
                f"page pool exhausted: want {n}, free {len(self._free)} "
                f"of {self.num_pages - 1}")
        out = [self._free.pop() for _ in range(n)]
        for p in out:
            self._owner[p] = owner
            self._ref[p] = 1
        return out

    def share(self, pages: List[int]) -> None:
        """Add one reference per page (prefix sharing across sequences)."""
        for p in pages:
            if p == SINK_PAGE:
                raise ValueError("sink page cannot be shared")
            if p not in self._ref:
                raise ValueError(f"cannot share unallocated page {p}")
        for p in pages:
            self._ref[p] += 1

    def free(self, pages: List[int]) -> None:
        """Drop one reference per page; pages reaching refcount 0 return to
        the free list (or retire into a pending shrink).

        Raises on the sink page, on a page with no live reference, and on a
        duplicate page id within one call (one owner releasing the same
        page twice in a single ``free`` is always a caller bug — with
        refcounts it would silently steal another owner's reference).
        Validation runs before any mutation, so a raising call leaves the
        allocator untouched.
        """
        seen = set()
        for p in pages:
            if p == SINK_PAGE:
                raise ValueError("sink page cannot be freed")
            if p not in self._ref:
                raise ValueError(f"double free of page {p}")
            if p in seen:
                raise ValueError(
                    f"page {p} appears twice in one free() call")
            seen.add(p)
        for p in pages:
            self._ref[p] -= 1
            if self._ref[p]:
                continue                      # surviving sharers
            del self._ref[p]
            self._owner.pop(p, None)
            if self.on_free is not None:
                self.on_free(p)
            if p < self._shrink_target:
                self._free.append(p)
            # else: the page is being retired by a pending shrink

    # --------------------------------------------------------- live resize --
    # _shrink_target defaults past any page id, i.e. "no shrink pending";
    # set as a class attribute so allocators pickled/built before this field
    # existed keep working.
    _shrink_target: int = 1 << 62

    @property
    def shrink_pending(self) -> bool:
        return self._shrink_target < self.num_pages

    def grow(self, new_num_pages: int) -> None:
        """Add pages ``[num_pages, new_num_pages)`` to the free list; cancels
        any pending shrink (its retired pages return to the pool). The
        shrink target is cleared unconditionally — a stale target below the
        new size would read as a phantom pending shrink and let a later
        ``complete_shrink`` slice the grown pool out from under the free
        list."""
        assert new_num_pages >= self.num_pages
        old_target = min(self._shrink_target, self.num_pages)
        in_free = set(self._free)
        self._free.extend(p for p in range(old_target, self.num_pages)
                          if p not in self._ref and p not in in_free)
        self._shrink_target = 1 << 62
        self._free.extend(range(self.num_pages, new_num_pages))
        self.num_pages = new_num_pages

    def request_shrink(self, new_num_pages: int) -> None:
        """Retire free pages with id >= ``new_num_pages`` immediately; pages
        still owned keep their owner and block ``complete_shrink`` until
        freed (drain-before-shrink). Raising a pending target un-retires the
        pages between the two targets."""
        assert 2 <= new_num_pages <= self.num_pages
        old = min(self._shrink_target, self.num_pages)
        if new_num_pages > old:
            in_free = set(self._free)
            self._free.extend(p for p in range(old, new_num_pages)
                              if p not in self._ref
                              and p not in in_free)
        # relaxing all the way back to the pool size is a cancellation, not
        # a pending shrink — leave no stale target behind
        self._shrink_target = (new_num_pages if new_num_pages < self.num_pages
                               else 1 << 62)
        self._free = [p for p in self._free if p < new_num_pages]

    def shrink_ready(self) -> bool:
        # a page with live sharers (ref > 0) always blocks the shrink
        return self.shrink_pending and all(p < self._shrink_target
                                           for p in self._ref)

    def complete_shrink(self) -> int:
        """Finish a drained shrink; returns the new pool size."""
        assert self.shrink_ready()
        self.num_pages = self._shrink_target
        self._shrink_target = 1 << 62
        return self.num_pages

    @property
    def effective_pages(self) -> int:
        """Pool size after any pending shrink lands (including sink)."""
        return min(self.num_pages, self._shrink_target)

    @property
    def capacity(self) -> int:
        """Allocatable pages after any pending shrink lands (minus sink)."""
        return self.effective_pages - 1


# ---------------------------------------------------------------------------
# prefix index: token-hash -> page chain (shared-prefix cache)
# ---------------------------------------------------------------------------

def _digest(tokens: np.ndarray) -> bytes:
    return hashlib.blake2b(np.ascontiguousarray(tokens, np.int32).tobytes(),
                           digest_size=16).digest()


def _boundary_digests(prompt: np.ndarray, n_full: int,
                      page_size: int) -> List[bytes]:
    """``_digest(prompt[:k * page_size])`` for k = 1..n_full, computed in
    one O(plen) pass: blake2b over concatenated page chunks equals the
    one-shot hash of the whole prefix, so keys are identical to per-prefix
    digests without re-hashing O(plen^2 / page_size) bytes per admission."""
    arr = np.ascontiguousarray(prompt, np.int32)
    h = hashlib.blake2b(digest_size=16)
    out = []
    for k in range(n_full):
        h.update(arr[k * page_size:(k + 1) * page_size].tobytes())
        out.append(h.copy().digest())
    return out


@dataclasses.dataclass
class PrefixHit:
    """Result of a prefix lookup, pre-capped at ``limit`` tokens.

    ``full_pages`` hold exactly ``len(full_pages) * page_size`` matched
    tokens and are shared as-is (refcount++). ``tail_page`` (if any) holds
    ``tail_len`` further matched tokens mid-page; the admitting sequence
    copy-on-write forks it before writing its own tokens into the same
    page. ``state`` is the SSM slot state at ``length`` for hybrid archs.
    """
    length: int                       # total cached tokens usable
    full_pages: List[int]
    tail_page: Optional[int] = None
    tail_len: int = 0
    state: Any = None


@dataclasses.dataclass(eq=False)          # identity equality: fields hold arrays
class _Entry:
    kind: str                         # "full" | "tail" | "exact"
    key: bytes
    tokens: np.ndarray                # the exact token prefix this entry maps
    pages: List[int]                  # page chain backing those tokens
    state: Any = None                 # SSM slot state at len(tokens) ("exact")
    dead: bool = False


class PrefixIndex:
    """Token-hash → page-chain index over the *in-flight* page pool.

    Entries reference pages owned by live sequences (the index holds no
    refcount of its own): the allocator's ``on_free`` hook invalidates
    every entry touching a page the moment its last owner releases it, so
    a hit can always be shared safely. Three entry kinds:

    * ``full`` — a full-page-aligned prefix (``k * page_size`` tokens →
      ``k`` pages), keyed by the token hash. The workhorse for dense archs.
    * ``tail`` — up to ``page_size - 1`` extra tokens inside the page after
      a ``full`` boundary; matched by longest-common-prefix so sequences
      that diverge *inside* a page still share it (COW on the hit side).
    * ``exact`` — a whole prompt with an SSM state snapshot at its length;
      hybrid archs can only resume from positions where a state exists, so
      their hits are exact-entry matches rather than per-page ones.

    A match must cover at least one full page (``page_size`` tokens):
    shorter overlaps are not worth a fork and keep accidental sharing out
    of unrelated workloads.

    Two tier-related extensions:

    * ``exact`` entries each pin a full SSM state snapshot host-side, and
      with chain retention (host tier) pages live long enough for every
      session turn to add one — unbounded growth on long persona runs.
      ``max_exact`` caps them with LRU eviction (refreshed on hit);
      ``evictions`` counts drops and ``on_evict`` (if set) observes them.
    * chains may be *host-resident*: ``swap_chain`` re-points entries at
      ``HOST_BIT``-tagged ids when a cold chain is preempted to the host
      tier (and back on swap-in). Only entries whose whole chain moves are
      remapped — the index never holds a half-swapped chain.
    """

    def __init__(self, page_size: int, max_exact: Optional[int] = 512):
        self.page_size = page_size
        self.max_exact = max_exact
        self.evictions = 0
        self.on_evict = None                      # callback(entry) on LRU drop
        self._full: Dict[bytes, _Entry] = {}
        self._tails: Dict[bytes, List[_Entry]] = {}
        self._exact: Dict[bytes, _Entry] = {}     # insertion-ordered = LRU
        self._exact_lens: Dict[int, int] = {}     # length -> entry count
        self._by_page: Dict[int, List[_Entry]] = {}

    def __len__(self) -> int:
        return len(self._full) + len(self._exact) + sum(
            len(v) for v in self._tails.values())

    # ----------------------------------------------------------- insert --
    def _track(self, e: _Entry) -> None:
        for p in e.pages:
            self._by_page.setdefault(p, []).append(e)

    def _untrack(self, e: _Entry) -> None:
        for p in e.pages:
            lst = self._by_page.get(p)
            if lst is None:
                continue
            if e in lst:
                lst.remove(e)
            if not lst:
                del self._by_page[p]

    def _drop_exact_len(self, plen: int) -> None:
        n = self._exact_lens[plen] - 1
        if n:
            self._exact_lens[plen] = n
        else:
            del self._exact_lens[plen]

    def _evict_exact(self, e: _Entry) -> None:
        e.dead = True
        del self._exact[e.key]
        self._drop_exact_len(len(e.tokens))
        self._untrack(e)
        self.evictions += 1
        if self.on_evict is not None:
            self.on_evict(e)

    def insert(self, prompt: np.ndarray, pages: List[int],
               state: Any = None) -> None:
        """Index a freshly prefilled prompt's page chain.

        With ``state`` (hybrid archs) one ``exact`` entry is added at the
        full prompt length. Without it, one ``full`` entry per page
        boundary plus a ``tail`` entry for the mid-page remainder; existing
        entries win ties (they are already shared more broadly).
        """
        ps = self.page_size
        plen = int(prompt.shape[0])
        if state is not None:
            key = _digest(prompt)
            if key in self._exact and not self._exact[key].dead:
                self._exact[key] = self._exact.pop(key)   # LRU refresh
                return
            e = _Entry("exact", key, np.array(prompt, np.int32),
                       list(pages[:pages_for_len(plen, ps)]), state=state)
            self._exact[key] = e
            self._exact_lens[plen] = self._exact_lens.get(plen, 0) + 1
            self._track(e)
            if self.max_exact is not None:
                while len(self._exact) > self.max_exact:
                    self._evict_exact(next(iter(self._exact.values())))
            return
        n_full = plen // ps
        keys = _boundary_digests(prompt, n_full, ps)
        for k in range(1, n_full + 1):
            key = keys[k - 1]
            if key in self._full and not self._full[key].dead:
                continue
            e = _Entry("full", key, np.array(prompt[:k * ps], np.int32),
                       list(pages[:k]))
            self._full[key] = e
            self._track(e)
        rem = plen % ps
        if rem and n_full >= 1:
            key = keys[n_full - 1]
            tails = self._tails.setdefault(key, [])
            tail = np.array(prompt[n_full * ps:], np.int32)
            for t in tails:
                if not t.dead and t.tokens.shape == tail.shape \
                        and bool(np.all(t.tokens == tail)):
                    return
            e = _Entry("tail", key, tail, [pages[n_full]])
            tails.append(e)
            self._track(e)

    # ----------------------------------------------------------- lookup --
    def lookup(self, prompt: np.ndarray, *, limit: Optional[int] = None,
               need_state: bool = False) -> Optional[PrefixHit]:
        """Longest cached prefix of ``prompt``, capped at ``limit`` tokens
        (callers cap at ``plen - 1`` so a hit always leaves at least one
        suffix token to produce the first output logits from)."""
        ps = self.page_size
        plen = int(prompt.shape[0])
        limit = plen if limit is None else min(limit, plen)
        if need_state:
            for L in sorted(self._exact_lens, reverse=True):
                if L > limit or L < ps:
                    continue
                e = self._exact.get(_digest(prompt[:L]))
                if e is None or e.dead or not bool(
                        np.all(e.tokens == prompt[:L])):
                    continue
                self._exact[e.key] = self._exact.pop(e.key)   # LRU refresh
                n_full, rem = L // ps, L % ps
                return PrefixHit(
                    length=L, full_pages=list(e.pages[:n_full]),
                    tail_page=e.pages[n_full] if rem else None,
                    tail_len=rem, state=e.state)
            return None
        keys = _boundary_digests(prompt, limit // ps, ps)
        for k in range(limit // ps, 0, -1):
            e = self._full.get(keys[k - 1])
            if e is None or e.dead or not bool(
                    np.all(e.tokens == prompt[:k * ps])):
                continue
            hit = PrefixHit(length=k * ps, full_pages=list(e.pages))
            room = limit - k * ps
            best = 0
            for t in self._tails.get(e.key, []):
                if t.dead:
                    continue
                n = min(len(t.tokens), room)
                lcp = int(np.argmin(np.concatenate(
                    [t.tokens[:n] == prompt[k * ps:k * ps + n], [False]])))
                if lcp > best:
                    best, hit.tail_page = lcp, t.pages[0]
            hit.tail_len = best
            hit.length += best
            return hit
        return None

    def match_len(self, prompt: np.ndarray, *, limit: Optional[int] = None,
                  need_state: bool = False) -> int:
        """Length of the longest cached prefix (0 on miss) — the router's
        prefix-affinity signal; never mutates the index."""
        hit = self.lookup(prompt, limit=limit, need_state=need_state)
        return hit.length if hit else 0

    # ------------------------------------------------------- invalidation --
    def invalidate_page(self, page: int) -> None:
        """Drop every entry whose chain contains ``page`` (wired to
        ``PageAllocator.on_free``: the page's last owner just released it,
        so its contents are about to be recycled)."""
        for e in self._by_page.pop(page, []):
            if e.dead:
                continue
            e.dead = True
            if e.kind == "full":
                if self._full.get(e.key) is e:
                    del self._full[e.key]
            elif e.kind == "exact":
                if self._exact.get(e.key) is e:
                    del self._exact[e.key]
                    self._drop_exact_len(len(e.tokens))
            else:
                tails = self._tails.get(e.key, [])
                if e in tails:
                    tails.remove(e)
                if not tails:
                    self._tails.pop(e.key, None)

    # --------------------------------------------------- tier residency --
    def swap_chain(self, mapping: Dict[int, int]) -> int:
        """Re-point entries across a tier move: every page id in
        ``mapping`` keys is about to change identity (device id →
        ``HOST_BIT``-tagged host id on swap-out, the reverse on swap-in).

        Only entries whose page chain lies *entirely* within ``mapping``
        are remapped — an entry is never left half-swapped. Entries that
        straddle the move (some pages staying put because other owners
        still hold them) are left untouched; on swap-out their dying pages
        hit ``invalidate_page`` via the allocator's ``on_free`` as usual.
        Returns the number of entries remapped.
        """
        cand: List[_Entry] = []
        seen: set = set()
        for p in mapping:
            for e in self._by_page.get(p, []):
                if not e.dead and id(e) not in seen:
                    seen.add(id(e))
                    cand.append(e)
        n = 0
        for e in cand:
            if all(p in mapping for p in e.pages):
                self._untrack(e)
                e.pages = [mapping[p] for p in e.pages]
                self._track(e)
                n += 1
        return n

    def clear(self) -> None:
        for e in list(self._full.values()) + list(self._exact.values()):
            e.dead = True
        for tails in self._tails.values():
            for e in tails:
                e.dead = True
        self._full.clear()
        self._tails.clear()
        self._exact.clear()
        self._exact_lens.clear()
        self._by_page.clear()


# ---------------------------------------------------------------------------
# cache pytree construction
# ---------------------------------------------------------------------------

def _attn_pool_leaves(cfg: ModelConfig, num_pages: int, page_size: int,
                      tp: int = 1) -> Dict[str, jnp.ndarray]:
    if cfg.attn_impl == "mla":
        raise NotImplementedError(
            "paged serving covers GQA archs; MLA decode keeps the dense "
            "compressed-cache path (see docs/serving.md)")
    hd = cfg.resolved_head_dim
    KVH = cfg.n_kv_heads
    mode = kv_quant_mode(cfg)
    kv_dt = {"int8": jnp.int8, "fp8": jnp.float8_e4m3fn,
             None: jnp.dtype(cfg.dtype)}[mode]
    if tp > 1 and KVH % tp:
        raise ValueError(f"tp={tp} must divide n_kv_heads {KVH}")
    shard = (tp,) if tp > 1 else ()
    KVH_s = KVH // tp if tp > 1 else KVH
    out = {
        "k_pages": jnp.zeros(shard + (num_pages, page_size, KVH_s, hd), kv_dt),
        "v_pages": jnp.zeros(shard + (num_pages, page_size, KVH_s, hd), kv_dt),
    }
    if cfg.cache_quant:
        out["k_scale_pages"] = jnp.zeros(shard + (num_pages, page_size, KVH_s),
                                         jnp.float32)
        out["v_scale_pages"] = jnp.zeros(shard + (num_pages, page_size, KVH_s),
                                         jnp.float32)
    return out


def _ssm_slot_leaves(cfg: ModelConfig, max_slots: int) -> Dict[str, jnp.ndarray]:
    raw = ssm_mod.ssm_cache_spec(cfg, max_slots)
    return {k: jnp.zeros(shape, jnp.dtype(str(dt)))
            for k, (shape, _axes, dt) in raw.items()}


def _layer_leaves(cfg: ModelConfig, idx: int, num_pages: int, page_size: int,
                  max_slots: int, tp: int = 1) -> Dict[str, jnp.ndarray]:
    if cfg.block_kind(idx) == "ssm":
        return _ssm_slot_leaves(cfg, max_slots)
    return _attn_pool_leaves(cfg, num_pages, page_size, tp)


def page_axis(stacked: bool, tp: int = 1) -> int:
    """Index of the page axis in an attention pool leaf: the scanned stack
    adds a leading layers axis, a shard group adds a leading shard axis
    (stack outermost: scan slices it away before model code sees leaves)."""
    return int(stacked) + int(tp > 1)


def init_paged_cache(cfg: ModelConfig, num_pages: int, page_size: int,
                     max_slots: int, tp: int = 1) -> Any:
    """Zero page pools in the same prefix/stack pytree shape the dense cache
    uses (``repro.models.model.cache_schema``), so the transformer's scanned
    stack threads them identically. With ``tp > 1`` attention pool leaves
    carry a leading shard axis holding each shard's kv-head slice; SSM slot
    leaves stay replicated."""
    if cfg.is_encdec:
        raise NotImplementedError("paged serving targets decoder-only archs")
    prefix, period, n_periods = depth_plan(cfg)
    out: Dict[str, Any] = {}
    if prefix:
        out["prefix"] = {str(i): _layer_leaves(cfg, i, num_pages, page_size,
                                               max_slots, tp)
                         for i in range(prefix)}
    out["stack"] = {
        str(p): jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_periods,) + x.shape).copy(),
            _layer_leaves(cfg, prefix + p, num_pages, page_size, max_slots,
                          tp))
        for p in range(period)}
    return out


# ---------------------------------------------------------------------------
# prefill insertion
# ---------------------------------------------------------------------------

def _shard_kv(kv: jnp.ndarray, tp: int, stacked: bool) -> jnp.ndarray:
    """Split a prefill K/V block's kv-head axis into per-shard slices.

    kv: ([L,] n, KVH, hd) -> ([L,] tp, n, KVH/tp, hd) — shard s receives
    the same contiguous head block the sharded decode path owns."""
    lead = kv.shape[:-3] if stacked else ()
    n, KVH, hd = kv.shape[-3:]
    kv = kv.reshape(lead + (n, tp, KVH // tp, hd))
    return jnp.moveaxis(kv, -3, -4)


def _write_attn_prefill(cfg: ModelConfig, node: Dict[str, jnp.ndarray],
                        pre: Dict[str, jnp.ndarray], page_ids: jnp.ndarray,
                        page_slots: jnp.ndarray, stacked: bool,
                        tp: int = 1) -> Dict[str, jnp.ndarray]:
    """Scatter one sequence's prefill K/V (B=1) into its pages.

    ``page_ids``/``page_slots``: (n_write,) int32 — padding positions past
    the live length are routed to the sink page by the caller. With
    ``tp > 1`` the prefill's full-KVH block splits into per-shard head
    slices and every shard's pool is written in this one call."""
    out = dict(node)
    n_write = page_ids.shape[0]
    # leading axes before the page axis: optional stack, optional shard
    lead = (slice(None),) * page_axis(stacked, tp)
    for name in ("k", "v"):
        kv = pre[name][..., 0, :n_write, :, :] if stacked \
            else pre[name][0, :n_write]                   # ([L,]n,KVH,hd)
        if cfg.cache_quant:
            q8, sc = quantize_kv(kv, kv_quant_mode(cfg))
            if tp > 1:
                q8, sc = _shard_kv(q8, tp, stacked), _shard_kv(
                    sc[..., None], tp, stacked)[..., 0]
            out[f"{name}_pages"] = node[f"{name}_pages"].at[
                lead + (page_ids, page_slots)].set(q8)
            out[f"{name}_scale_pages"] = node[f"{name}_scale_pages"].at[
                lead + (page_ids, page_slots)].set(sc)
        else:
            dt = node[f"{name}_pages"].dtype
            if tp > 1:
                kv = _shard_kv(kv, tp, stacked)
            out[f"{name}_pages"] = node[f"{name}_pages"].at[
                lead + (page_ids, page_slots)].set(kv.astype(dt))
    return out


def _write_ssm_prefill(node: Dict[str, jnp.ndarray],
                       pre: Dict[str, jnp.ndarray], slot,
                       stacked: bool) -> Dict[str, jnp.ndarray]:
    out = dict(node)
    for name in node:
        val = pre[name]
        if stacked:
            out[name] = node[name].at[:, slot].set(
                val[:, 0].astype(node[name].dtype))
        else:
            out[name] = node[name].at[slot].set(
                val[0].astype(node[name].dtype))
    return out


def write_prefill(cfg: ModelConfig, paged: Any, pre: Any, block_row,
                  slot, plen, n_write: int, page_size: int,
                  tp: int = 1) -> Any:
    """Insert a freshly prefilled sequence (batch 1) into the paged cache.

    ``pre`` is the cache returned by a batch-1 prefill on an ``n_write``-long
    (possibly right-padded) prompt; ``plen`` (dynamic) is the live length —
    padding positions are scattered to the sink page, so one compilation per
    prefill *bucket* serves every prompt length in it. ``block_row``:
    (n_pg,) int32 page ids for this sequence (unused tail = sink).
    Returns the updated cache pytree; jit with ``n_write``/``page_size``/
    ``tp`` static. For archs with SSM layers the caller must use ``n_write
    == plen`` — an SSM final state folds padding tokens in. Prefill always
    produces full-KVH K/V (it runs replicated across a shard group);
    ``tp > 1`` splits it into per-shard slices on insert.
    """
    t = jnp.arange(n_write)
    live = t < jnp.asarray(plen)
    page_ids = jnp.where(live, jnp.asarray(block_row)[t // page_size],
                         SINK_PAGE).astype(jnp.int32)
    page_slots = (t % page_size).astype(jnp.int32)

    def walk(node: Any, pnode: Any, stacked: bool) -> Any:
        if "k_pages" in node:
            return _write_attn_prefill(cfg, node, pnode, page_ids,
                                       page_slots, stacked, tp)
        if "h" in node and "conv" in node:
            return _write_ssm_prefill(node, pnode, slot, stacked)
        return {k: walk(node[k], pnode[k], stacked or k == "stack")
                for k in node}

    return walk(paged, pre, False)


# ---------------------------------------------------------------------------
# copy-on-write fork + SSM slot views (shared-prefix machinery)
# ---------------------------------------------------------------------------

def _is_attn(node: Any) -> bool:
    return isinstance(node, dict) and "k_pages" in node


def _is_ssm(node: Any) -> bool:
    return isinstance(node, dict) and "h" in node and "conv" in node


def copy_page(cache: Any, src, dst, tp: int = 1) -> Any:
    """COW fork: copy page ``src``'s contents into page ``dst`` in every
    attention pool leaf (all layers — and, for a shard group, every shard's
    slice in the same call: the fork is atomic across shards, so no shard
    can ever hold a forked page the others don't). Jit with the cache
    donated — the fork happens between decode ticks, exactly like a
    prefill insert."""
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)

    def walk(node: Any, stacked: bool) -> Any:
        if _is_attn(node):
            axis = page_axis(stacked, tp)
            out = dict(node)
            for k in PAGE_LEAVES:
                if k not in node:
                    continue
                leaf = node[k]
                row = jax.lax.dynamic_index_in_dim(leaf, src, axis=axis)
                out[k] = jax.lax.dynamic_update_index_in_dim(
                    leaf, row, dst, axis=axis)
            return out
        if _is_ssm(node):
            return node
        return {k: walk(node[k], stacked or k == "stack") for k in node}

    return walk(cache, False)


def extract_ssm_state(pre: Any) -> Any:
    """Pull the SSM leaves (batch-1 state at the prefilled length) out of a
    prefill-produced cache (or of a stepped ``ssm_slot_view``) — the
    snapshot a hybrid prefix-index entry stores. Returns None when the arch
    has no SSM layers."""
    def walk(node: Any) -> Any:
        if not isinstance(node, dict):
            return None
        if _is_ssm(node):
            return dict(node)
        out = {k: walk(v) for k, v in node.items()}
        out = {k: v for k, v in out.items() if v is not None}
        return out or None
    return walk(pre)


def extract_ssm_slot(cache: Any, slot: int) -> Any:
    """Batch-1 snapshot of ``slot``'s SSM state sliced out of the paged
    cache — the live analogue of ``extract_ssm_state`` (which reads a
    prefill-produced cache). Chunked prefill resumes a mid-prompt sequential
    scan from it, and a disaggregation handoff carries it to the adopting
    replica. Runs eagerly (host round-trip); returns None when the arch has
    no SSM layers."""
    def walk(node: Any, stacked: bool) -> Any:
        if not isinstance(node, dict):
            return None
        if _is_attn(node):
            return None
        if _is_ssm(node):
            if stacked:
                return {k: v[:, slot:slot + 1] for k, v in node.items()}
            return {k: v[slot:slot + 1] for k, v in node.items()}
        out = {k: walk(v, stacked or k == "stack") for k, v in node.items()}
        out = {k: v for k, v in out.items() if v is not None}
        return out or None
    return walk(cache, False)


def migrate_pages(src_cache: Any, dst_cache: Any, src_pages: List[int],
                  dst_pages: List[int], tp: int = 1) -> Any:
    """Verbatim KV-page handoff between two replicas' caches.

    Copies page ``src_pages[i]`` of every attention pool leaf in
    ``src_cache`` into page ``dst_pages[i]`` of the corresponding leaf in
    ``dst_cache`` — all layers and (``tp > 1``) every shard's slice in one
    call, the same atomicity contract as ``copy_page``. The two caches must
    share layout (same arch/page_size/tp); pool *sizes* may differ — only
    the listed page ids are touched, so a prefill replica's prompt pages
    land bit-identically in a decode replica's pool. Partial trailing pages
    copy whole-page: unwritten slots are zeros on both sides. SSM slot
    state moves separately (``extract_ssm_slot`` / ``merge_ssm_slot``).
    Runs eagerly — handoffs are per-request events between ticks. The
    two caches may sit on different devices (one device per replica): the
    copied rows move to the destination leaf's device.
    """
    assert len(src_pages) == len(dst_pages)
    if not src_pages:
        return dst_cache
    src_ids = jnp.asarray(src_pages, jnp.int32)
    dst_ids = jnp.asarray(dst_pages, jnp.int32)

    def walk(snode: Any, dnode: Any, stacked: bool) -> Any:
        if _is_attn(dnode):
            lead = (slice(None),) * page_axis(stacked, tp)
            out = dict(dnode)
            for k in PAGE_LEAVES:
                if k not in dnode:
                    continue
                rows = snode[k][lead + (src_ids,)]
                if rows.devices() != dnode[k].devices():
                    rows = jax.device_put(rows, dnode[k].sharding)
                out[k] = dnode[k].at[lead + (dst_ids,)].set(
                    rows.astype(dnode[k].dtype))
            return out
        if _is_ssm(dnode):
            return dnode
        return {k: walk(snode[k], dnode[k], stacked or k == "stack")
                for k in dnode}

    return walk(src_cache, dst_cache, False)


# ---------------------------------------------------------------------------
# host-RAM page tier (second tier of the paged pool)
# ---------------------------------------------------------------------------

class HostPageTier:
    """Host-RAM page plane: a second tier of the paged KV pool.

    Same control-plane shape as the device tier — a ``PageAllocator`` over
    its own page-id space (page 0 mirrors the sink and is never handed
    out) — but storage is host-side numpy: each resident page keeps the
    verbatim rows of every attention pool leaf (all layers, all shards,
    including int8/fp8 pools and their fp32 scale pages), keyed by the
    leaf's path in the cache pytree. Rows round-trip bit-exactly, which is
    what makes swap-in byte-identical to never having been preempted.

    Host page ids are tagged with ``HOST_BIT`` wherever they appear in
    shared id spaces (saved block-table rows, prefix-index chains); the
    tier's own allocator works on untagged ids.
    """

    def __init__(self, num_pages: int):
        assert num_pages >= 1, "host tier needs at least one page"
        # +1: id 0 mirrors the device sink so `num_pages` is the real budget
        self.alloc = PageAllocator(num_pages + 1)
        self._rows: Dict[int, Dict[str, np.ndarray]] = {}
        self.bytes_used = 0

    @property
    def capacity(self) -> int:
        return self.alloc.capacity

    @property
    def pages_used(self) -> int:
        return self.alloc.num_allocated

    def can_hold(self, n: int) -> bool:
        return self.alloc.can_alloc(n)

    def store(self, page: int, rows: Dict[str, np.ndarray]) -> None:
        """Attach leaf rows to an allocated host page (swap-out path)."""
        assert page in self.alloc._ref, f"store to unallocated host page {page}"
        old = self._rows.get(page)
        if old is not None:
            self.bytes_used -= sum(r.nbytes for r in old.values())
        self._rows[page] = rows
        self.bytes_used += sum(r.nbytes for r in rows.values())

    def rows(self, page: int) -> Dict[str, np.ndarray]:
        return self._rows[page]

    def free(self, pages: List[int]) -> None:
        """Release host pages and drop their row storage."""
        self.alloc.free(pages)
        for p in pages:
            rows = self._rows.pop(p, None)
            if rows is not None:
                self.bytes_used -= sum(r.nbytes for r in rows.values())

    def clear(self) -> None:
        """Drop every resident page (replica failure: the node's host RAM
        is gone with its HBM)."""
        live = list(self.alloc._ref)
        if live:
            self.free(live)


def swap_out_pages(cache: Any, tier: HostPageTier, pages: List[int],
                   tp: int = 1, owner: Any = None) -> List[int]:
    """Move device pages' contents to the host tier.

    Gathers page ``pages[i]`` of every attention pool leaf (all layers
    and, ``tp > 1``, every shard's slice in one pass — the same atomicity
    contract as ``migrate_pages``) into host RAM as verbatim numpy rows
    (int8/fp8 pools and fp32 scale pages byte-preserved), under freshly
    allocated host page ids. Returns the untagged host ids, parallel to
    ``pages``. The device pages are *not* freed here — the caller owns the
    device control plane and releases them (and re-points the prefix index
    via ``swap_chain``) as part of the same preemption step. SSM slot
    state travels separately (``extract_ssm_slot``), exactly as in a
    migration handoff. Runs eagerly — preemptions are between-tick events.
    """
    if not pages:
        return []
    host = tier.alloc.alloc(len(pages), owner)
    ids = jnp.asarray(pages, jnp.int32)
    rows_by_path: Dict[str, np.ndarray] = {}

    def walk(node: Any, stacked: bool, path: str) -> None:
        if _is_attn(node):
            ax = page_axis(stacked, tp)
            lead = (slice(None),) * ax
            for k in PAGE_LEAVES:
                if k not in node:
                    continue
                got = np.asarray(jax.device_get(node[k][lead + (ids,)]))
                # page axis to the front: rows_by_path[p][i] is page i's row
                rows_by_path[path + k] = np.moveaxis(got, ax, 0)
            return
        if _is_ssm(node):
            return
        for k in node:
            walk(node[k], stacked or k == "stack", path + k + "/")

    walk(cache, False, "")
    for i, h in enumerate(host):
        tier.store(h, {p: np.ascontiguousarray(r[i])
                       for p, r in rows_by_path.items()})
    return host


def swap_in_pages(cache: Any, tier: HostPageTier, host_pages: List[int],
                  dst_pages: List[int], tp: int = 1) -> Any:
    """Restore host-resident pages into device pages ``dst_pages``.

    The inverse of ``swap_out_pages``: scatters each host page's stored
    rows into page ``dst_pages[i]`` of every attention pool leaf, dtype-
    preserved, then frees the host pages. The caller allocated
    ``dst_pages`` and re-points the prefix index (``swap_chain`` with the
    tagged-host → device mapping) in the same step, so no block table or
    index entry ever observes the chain mid-move. Returns the updated
    cache pytree.
    """
    assert len(host_pages) == len(dst_pages)
    if not host_pages:
        return cache
    dst_ids = jnp.asarray(dst_pages, jnp.int32)

    def walk(node: Any, stacked: bool, path: str) -> Any:
        if _is_attn(node):
            ax = page_axis(stacked, tp)
            lead = (slice(None),) * ax
            out = dict(node)
            for k in PAGE_LEAVES:
                if k not in node:
                    continue
                rows = np.stack([tier.rows(h)[path + k] for h in host_pages])
                rows = np.moveaxis(rows, 0, ax)
                out[k] = node[k].at[lead + (dst_ids,)].set(
                    jnp.asarray(rows).astype(node[k].dtype))
            return out
        if _is_ssm(node):
            return node
        return {k: walk(node[k], stacked or k == "stack", path + k + "/")
                for k in node}

    out = walk(cache, False, "")
    tier.free(host_pages)
    return out


def swap_resume_cost(cfg: ModelConfig, tokens: int, pages: int,
                     page_size: int) -> tuple:
    """Modeled ``(transfer_s, recompute_s)`` for resuming a preempted chain.

    Transfer: PCIe setup latency plus the chain's whole-page KV bytes at
    sustained PCIe bandwidth. Recompute: re-running the prefill for the
    chain's tokens at peak FLOPs (2 * active params per token). Both sides
    are *modeled* from the roofline constants in ``repro.obs.profile`` —
    deterministic, so the swap-in-vs-re-prefill decision never depends on
    wall clock and byte-identity runs reproduce exactly. The fixed latency
    term makes short chains cheaper to recompute and long ones cheaper to
    move.
    """
    from repro.obs.profile import PCIE_BW, PCIE_LATENCY, PEAK_FLOPS
    moved = page_bytes_per_token(cfg) * pages * page_size
    transfer = PCIE_LATENCY + moved / PCIE_BW
    recompute = 2.0 * float(cfg.active_param_count()) * tokens / PEAK_FLOPS
    return transfer, recompute


def swap_crossover_tokens(cfg: ModelConfig, page_size: int,
                          max_tokens: int = 65536) -> Optional[int]:
    """Smallest chain length (tokens) where swap-in beats re-prefill, or
    None if transfer never wins below ``max_tokens`` (tiny models whose
    per-token recompute undercuts per-token PCIe traffic). The session
    bench shapes its workload around this point so both cost-model paths
    are exercised."""
    def swap_wins(T: int) -> bool:
        t, r = swap_resume_cost(cfg, T, pages_for_len(T, page_size),
                                page_size)
        return t <= r
    if not swap_wins(max_tokens):
        return None
    lo, hi = 1, max_tokens
    while lo < hi:
        mid = (lo + hi) // 2
        if swap_wins(mid):
            hi = mid
        else:
            lo = mid + 1
    return lo


def ssm_slot_view(cache: Any, state: Any) -> Any:
    """Batch-1 view of the cache for sequential suffix decode: attention
    pools shared as-is (the block-table row selects pages), SSM leaves
    replaced by ``state`` (a batch-1 snapshot). ``state=None`` (pure-attn
    or MoE archs) returns the cache unchanged."""
    if state is None:
        return cache

    def walk(node: Any, snode: Any) -> Any:
        if _is_attn(node):
            return node
        if _is_ssm(node):
            return {k: snode[k].astype(node[k].dtype) for k in node}
        return {k: walk(node[k], snode.get(k) if snode else None)
                for k in node}

    return walk(cache, state)


def ssm_leaves(cache: Any) -> Any:
    """The SSM sub-tree of the paged cache (attention pools pruned).

    The speculative verify scan emits this per step, stacking one snapshot
    per verified token along a new leading axis — the rollback ledger
    ``select_ssm_steps`` indexes into. Returns None when the arch has no
    SSM layers. Safe to call under trace (pure pytree restructuring).
    """
    def walk(node: Any) -> Any:
        if not isinstance(node, dict) or _is_attn(node):
            return None
        if _is_ssm(node):
            return dict(node)
        out = {k: walk(v) for k, v in node.items()}
        out = {k: v for k, v in out.items() if v is not None}
        return out or None
    return walk(cache)


def select_ssm_steps(cache: Any, stacked: Any, idx) -> Any:
    """Speculative rollback for hybrid archs: set each slot's SSM state to
    ``stacked[idx[slot], ..., slot, ...]``.

    ``stacked`` is the ``ssm_leaves`` tree with a leading verify-step axis
    (one snapshot per teacher-forced token, from the scan's ys); ``idx``
    (max_slots,) holds each slot's accepted draft count, so the selected
    state is the one after folding exactly the accepted tokens — the PR-6
    snapshot rule applied per step instead of per chunk. Attention pools
    pass through (rejected K/V is masked by ``seq_lens`` and overwritten
    in place later). Traceable — the verify program calls it in-dispatch.
    """
    idx = jnp.asarray(idx, jnp.int32)

    def walk(node: Any, snode: Any, stacked_ax: bool) -> Any:
        if _is_attn(node):
            return node
        if _is_ssm(node):
            out = {}
            for k in node:
                s = snode[k]              # (steps, [L,] max_slots, ...)
                slot_ax = 2 if stacked_ax else 1
                ish = [1] * s.ndim
                ish[slot_ax] = s.shape[slot_ax]
                ix = idx.reshape(ish)
                out[k] = jnp.take_along_axis(s, ix, axis=0)[0].astype(
                    node[k].dtype)
            return out
        return {k: walk(node[k], snode[k], stacked_ax or k == "stack")
                for k in node}

    return walk(cache, stacked, False)


def merge_ssm_slot(cache: Any, view: Any, slot) -> Any:
    """Fold a stepped batch-1 view back: attention pools are taken from the
    view (they were updated in place), SSM leaves written at ``slot``."""
    slot = jnp.asarray(slot, jnp.int32)

    def walk(node: Any, vnode: Any, stacked: bool) -> Any:
        if _is_attn(node):
            return vnode
        if _is_ssm(node):
            out = {}
            for k in node:
                val = vnode[k].astype(node[k].dtype)
                if stacked:
                    out[k] = jax.vmap(
                        lambda buf, v: jax.lax.dynamic_update_index_in_dim(
                            buf, v, slot, axis=0))(node[k], val[:, 0])
                else:
                    out[k] = jax.lax.dynamic_update_index_in_dim(
                        node[k], val[0], slot, axis=0)
            return out
        return {k: walk(node[k], vnode[k], stacked or k == "stack")
                for k in node}

    return walk(cache, view, False)


# ---------------------------------------------------------------------------
# live resize (the autoscaler's actuation path)
# ---------------------------------------------------------------------------

def _resize_axis(leaf: jnp.ndarray, axis: int, new: int) -> jnp.ndarray:
    """Grow (zero-pad) or shrink (slice) one leaf along ``axis``."""
    cur = leaf.shape[axis]
    if new == cur:
        return leaf
    if new > cur:
        pad_shape = leaf.shape[:axis] + (new - cur,) + leaf.shape[axis + 1:]
        return jnp.concatenate([leaf, jnp.zeros(pad_shape, leaf.dtype)],
                               axis=axis)
    idx = [slice(None)] * leaf.ndim
    idx[axis] = slice(0, new)
    return leaf[tuple(idx)]


def resize_cache_pages(cache: Any, new_num_pages: int, tp: int = 1) -> Any:
    """Resize every page pool to ``new_num_pages``.

    Growth appends zero pages — existing page ids (and everything any block
    table references) are untouched, so decoded tokens are unaffected.
    Shrink slices the tail; the caller (scheduler) guarantees every page
    with id >= ``new_num_pages`` is free and out of every live block table
    before calling. Every shard's pool resizes in the same call (the
    logical page-id space is shared). SSM slot leaves are untouched. Runs
    eagerly (outside jit) — resizes are rare, bucketed events.
    """
    def walk(node: Any, stacked: bool) -> Any:
        if "k_pages" in node:
            axis = page_axis(stacked, tp)
            return {k: (_resize_axis(v, axis, new_num_pages)
                        if k in PAGE_LEAVES else v) for k, v in node.items()}
        if "h" in node and "conv" in node:
            return node
        return {k: walk(node[k], stacked or k == "stack") for k in node}

    return walk(cache, False)


def resize_cache_slots(cache: Any, new_slots: int) -> Any:
    """Resize the dense per-slot SSM state rows to ``new_slots``.

    New slots get zero state — identical to a fresh ``init_paged_cache``
    slot, so a request later admitted there prefills exactly as it would
    have at construction time. Shrink slices the tail; the caller drains
    those slots first. Attention page pools are untouched (they have no
    slot axis).
    """
    def walk(node: Any, stacked: bool) -> Any:
        if "k_pages" in node:
            return node
        if "h" in node and "conv" in node:
            axis = 1 if stacked else 0
            return {k: _resize_axis(v, axis, new_slots)
                    for k, v in node.items()}
        return {k: walk(node[k], stacked or k == "stack") for k in node}

    return walk(cache, False)


# ---------------------------------------------------------------------------
# sizing helpers (used by core.blueprint.serving_page_plan and the bench)
# ---------------------------------------------------------------------------

def page_bytes_per_token(cfg: ModelConfig) -> int:
    """KV bytes one token occupies across all attention layers' pools."""
    hd, KVH = cfg.resolved_head_dim, cfg.n_kv_heads
    per = 2 * KVH * hd * (1 if cfg.cache_quant else 2)
    if cfg.cache_quant:
        per += 2 * KVH * 4                       # fp32 scales
    n_attn = sum(1 for i in range(cfg.n_layers)
                 if cfg.block_kind(i) != "ssm")
    return per * n_attn


def shard_page_bytes_per_token(cfg: ModelConfig, tp: int) -> int:
    """KV bytes one token occupies on *one shard* of a ``tp``-way group —
    the per-member slice of ``page_bytes_per_token``. Exact: every byte
    term is proportional to ``n_kv_heads``, which ``tp`` must divide."""
    total = page_bytes_per_token(cfg)
    if tp > 1 and cfg.n_kv_heads % tp:
        raise ValueError(f"tp={tp} must divide n_kv_heads {cfg.n_kv_heads}")
    return total // tp


def pool_bytes(cfg: ModelConfig, num_pages: int, page_size: int,
               tp: int = 1) -> int:
    """HBM the page pools occupy: all layers, one shard's slice when
    ``tp > 1`` (multiply by ``tp`` for the whole group)."""
    return shard_page_bytes_per_token(cfg, tp) * num_pages * page_size


def dense_cache_bytes(cfg: ModelConfig, batch: int, capacity: int) -> int:
    """Footprint of the dense engine's capacity-padded ring buffers, for the
    memory comparison in ``benchmarks/serve_bench.py``."""
    return page_bytes_per_token(cfg) * batch * capacity


def migration_bytes(cfg: ModelConfig, num_pages: int, page_size: int) -> int:
    """KV bytes a disaggregation page handoff moves for ``num_pages``
    donor pages — all layers, all shards (the whole logical page travels
    whatever the tp split is). The router's trace detail for
    ``page_migration`` events."""
    return page_bytes_per_token(cfg) * num_pages * page_size
