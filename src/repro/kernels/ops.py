"""jit'd wrappers over the Pallas kernels (+ pure-jnp combines).

``interpret`` defaults to None: ``repro.kernels.resolve_interpret`` then
picks the Pallas interpreter on the CPU backend and compiles the kernel for
the accelerator everywhere else. Tests pass ``interpret=True`` to run a
kernel body on the CPU explicitly.

**Profiling hook** (``set_profile_hook``): an opt-in callback wrapped
around every public entry point, fed the kind, the post-
``block_until_ready`` wall seconds, and the call's array arguments (for
byte accounting) — ``repro.obs.profile.KernelProfiler.hook()`` is the
intended consumer. The hook only fires for calls with concrete operands:
a call made *inside* an outer jit trace sees abstract tracers, where wall
time is meaningless (and a host callback would break tracing), so those
pass straight through. Hooked or not, results are identical — timing
reads the clock around the call and touches nothing else.
"""
from __future__ import annotations

import functools
import json
import os
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as _fd
from repro.kernels import flash_attention as _fa
from repro.kernels import paged_decode as _pd
from repro.kernels import paged_prefill as _pp
from repro.kernels import ssd as _ssd

_PROFILE_HOOK = None


def set_profile_hook(hook) -> Optional[object]:
    """Install ``hook(kind, wall_seconds, args)`` around every public op
    (None uninstalls); returns the previous hook so callers can restore
    it (``prev = set_profile_hook(p.hook()) ... set_profile_hook(prev)``)."""
    global _PROFILE_HOOK
    prev = _PROFILE_HOOK
    _PROFILE_HOOK = hook
    return prev


def _traced(tree) -> bool:
    return any(isinstance(leaf, jax.core.Tracer)
               for leaf in jax.tree_util.tree_leaves(tree))


def _profiled(kind, fn, *args, **kw):
    hook = _PROFILE_HOOK
    if hook is None or _traced((args, kw)):
        return fn(*args, **kw)
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args, **kw))
    hook(kind, time.perf_counter() - t0, args)
    return out


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "scale", "block_q", "block_k",
                                             "interpret"))
def _flash_attention_jit(q, k, v, *, causal=True, window=None, softcap=None,
                         scale=None, block_q=128, block_k=128,
                         interpret=None):
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               softcap=softcap, scale=scale, block_q=block_q,
                               block_k=block_k, interpret=interpret)


def flash_attention(q, k, v, *, causal=True, window=None, softcap=None,
                    scale=None, block_q=128, block_k=128, interpret=None):
    return _profiled("flash_attention", _flash_attention_jit, q, k, v,
                     causal=causal, window=window, softcap=softcap,
                     scale=scale, block_q=block_q, block_k=block_k,
                     interpret=interpret)


@functools.partial(jax.jit, static_argnames=("softcap", "scale", "block_k",
                                             "interpret"))
def _decode_attention_jit(q, k_cache, v_cache, *, softcap=None, scale=None,
                          block_k=512, interpret=None):
    """Flash-decode: partials from the kernel, LSE combine in jnp.

    q: (B,H,d); caches (B,S,KVH,d) -> (B,H,d).
    """
    B, H, d = q.shape
    KVH = k_cache.shape[2]
    G = H // KVH
    m, lse, o = _fd.decode_attention_partials(
        q, k_cache, v_cache, softcap=softcap, scale=scale, block_k=block_k,
        interpret=interpret)
    m_glob = m.max(axis=1, keepdims=True)                   # (BK,1,G)
    w = jnp.exp(m - m_glob)
    l_glob = (lse * w).sum(axis=1)                          # (BK,G)
    o_glob = (o * w[..., None]).sum(axis=1)                 # (BK,G,d)
    out = o_glob / jnp.maximum(l_glob, 1e-30)[..., None]
    return out.reshape(B, KVH, G, d).reshape(B, H, d).astype(q.dtype)


def decode_attention(q, k_cache, v_cache, *, softcap=None, scale=None,
                     block_k=512, interpret=None):
    """Flash-decode: partials from the kernel, LSE combine in jnp.

    q: (B,H,d); caches (B,S,KVH,d) -> (B,H,d).
    """
    return _profiled("decode_attention", _decode_attention_jit, q, k_cache,
                     v_cache, softcap=softcap, scale=scale, block_k=block_k,
                     interpret=interpret)


def _paged_decode_one(q, k_pages, v_pages, block_table, seq_lens, *,
                      k_scale_pages, v_scale_pages, softcap, window, scale,
                      interpret):
    """One pool's paged flash-decode: kernel partials + jnp LSE combine."""
    B, H, d = q.shape
    m, lse, o = _pd.paged_decode_partials(
        q, k_pages, v_pages, block_table, seq_lens,
        k_scale_pages=k_scale_pages, v_scale_pages=v_scale_pages,
        softcap=softcap, window=window, scale=scale, interpret=interpret)
    m_glob = m.max(axis=1, keepdims=True)                   # (B,1,KVH,G,1)
    w = jnp.exp(m - m_glob)
    l_glob = (lse * w).sum(axis=1)                          # (B,KVH,G,1)
    o_glob = (o * w).sum(axis=1)                            # (B,KVH,G,d)
    out = o_glob / jnp.maximum(l_glob, 1e-30)
    return out.reshape(B, H, d).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("softcap", "window", "scale",
                                             "interpret"))
def _paged_decode_attention_jit(q, k_pages, v_pages, block_table, seq_lens, *,
                                k_scale_pages=None, v_scale_pages=None,
                                softcap=None, window=None, scale=None,
                                interpret=None):
    return _paged_decode_one(q, k_pages, v_pages, block_table, seq_lens,
                             k_scale_pages=k_scale_pages,
                             v_scale_pages=v_scale_pages, softcap=softcap,
                             window=window, scale=scale, interpret=interpret)


def paged_decode_attention(q, k_pages, v_pages, block_table, seq_lens, *,
                           k_scale_pages=None, v_scale_pages=None,
                           softcap=None, window=None, scale=None,
                           interpret=None):
    """Paged flash-decode: per-page partials from the kernel, LSE combine
    in jnp (same structure as ``decode_attention``).

    q: (B,H,d); pools (P,ps,KVH,d); block_table (B,n_pg); seq_lens (B,)
    -> (B,H,d). See ``repro.kernels.paged_decode`` for the page gather.
    """
    return _profiled("paged_decode_attention", _paged_decode_attention_jit,
                     q, k_pages, v_pages, block_table, seq_lens,
                     k_scale_pages=k_scale_pages, v_scale_pages=v_scale_pages,
                     softcap=softcap, window=window, scale=scale,
                     interpret=interpret)


@functools.partial(jax.jit, static_argnames=("softcap", "window", "scale",
                                             "interpret"))
def _paged_decode_attention_sharded_jit(q, k_pages, v_pages, block_table,
                                        seq_lens, *, k_scale_pages=None,
                                        v_scale_pages=None, softcap=None,
                                        window=None, scale=None,
                                        interpret=None):
    """Shard-group paged flash-decode: pools carry a leading shard axis
    (tp, P, ps, KVH/tp, d) and the kernel is invoked once per shard on
    that shard's query-head slice of ``q`` (B, H, d); the head-axis concat
    of the per-shard results is the group's all_gather. The block table and
    sequence lengths are the shared control plane — identical operands on
    every shard.
    """
    tp = k_pages.shape[0]
    B, H, d = q.shape
    Hs = H // tp
    outs = []
    for s in range(tp):
        outs.append(_paged_decode_one(
            q[:, s * Hs:(s + 1) * Hs], k_pages[s], v_pages[s], block_table,
            seq_lens,
            k_scale_pages=None if k_scale_pages is None else k_scale_pages[s],
            v_scale_pages=None if v_scale_pages is None else v_scale_pages[s],
            softcap=softcap, window=window, scale=scale, interpret=interpret))
    return jnp.concatenate(outs, axis=1)


def paged_decode_attention_sharded(q, k_pages, v_pages, block_table,
                                   seq_lens, *, k_scale_pages=None,
                                   v_scale_pages=None, softcap=None,
                                   window=None, scale=None,
                                   interpret=None):
    """Shard-group paged flash-decode (see ``_paged_decode_attention_sharded_jit``
    for the shard/head-slice structure)."""
    return _profiled("paged_decode_attention_sharded",
                     _paged_decode_attention_sharded_jit,
                     q, k_pages, v_pages, block_table, seq_lens,
                     k_scale_pages=k_scale_pages, v_scale_pages=v_scale_pages,
                     softcap=softcap, window=window, scale=scale,
                     interpret=interpret)


# --- paged prefill (chunked flash-prefill with direct-to-page KV writes) ---

# Autotuned block sizes, keyed by shape signature (see prefill_tuning_key).
# benchmarks/prefill_autotune.py sweeps candidates and writes the cache
# JSON; it is consumed here either via register_prefill_tuning() or lazily
# from $REPRO_PREFILL_TUNE / ./BENCH_prefill_tune.json on first lookup.
_PREFILL_TUNE: Dict[str, Dict] = {}
_PREFILL_TUNE_LOADED = False
_PREFILL_TUNE_DEFAULT_PATH = "BENCH_prefill_tune.json"


def prefill_tuning_key(H: int, d: int, KVH: int, chunk: int,
                       page_size: int) -> str:
    return f"h{H}xd{d}xkv{KVH}|chunk{chunk}|ps{page_size}"


def register_prefill_tuning(table: Dict[str, Dict]) -> Dict[str, Dict]:
    """Install autotuned prefill configs ({key: {"block_q": int, ...}});
    returns the previous table. Entries merge over defaults — an unknown
    key falls back to ``paged_prefill.default_block_q``."""
    global _PREFILL_TUNE, _PREFILL_TUNE_LOADED
    prev = _PREFILL_TUNE
    _PREFILL_TUNE = dict(table)
    _PREFILL_TUNE_LOADED = True
    return prev


def _prefill_tuned_block_q(H, d, KVH, chunk, page_size) -> int:
    global _PREFILL_TUNE_LOADED
    if not _PREFILL_TUNE_LOADED:
        _PREFILL_TUNE_LOADED = True
        path = os.environ.get("REPRO_PREFILL_TUNE", _PREFILL_TUNE_DEFAULT_PATH)
        if path and os.path.exists(path):
            with open(path) as f:
                _PREFILL_TUNE.update(json.load(f)["entries"])
    entry = _PREFILL_TUNE.get(prefill_tuning_key(H, d, KVH, chunk, page_size))
    if entry and "block_q" in entry:
        # clamp to the chunk width: speculative verify reuses this path at
        # chunk = spec_k + 1 (a handful of rows), and a stale or hand-edited
        # tune entry must never produce a query tile wider than the array
        return min(int(entry["block_q"]), chunk)
    return _pp.default_block_q(H, chunk)


def _paged_prefill_one(q, k_new, v_new, pool, block_table, start, chunk_lens,
                       *, quant, softcap, window, scale, block_q, interpret):
    """One pool's fused chunk prefill: write kernel then attend kernel.

    The write must land first — the attend kernel streams the chunk's own
    K/V back out of the pages (which is also what gives quantised pools
    the same quantise->dequantise roundtrip as the XLA scatter+gather
    path)."""
    new_pool = _pp.paged_prefill_write(
        k_new, v_new, pool["k_pages"], pool["v_pages"], block_table, start,
        chunk_lens, k_scale_pages=pool.get("k_scale_pages"),
        v_scale_pages=pool.get("v_scale_pages"), quant=quant,
        interpret=interpret)
    o = _pp.paged_prefill_attend(
        q, new_pool["k_pages"], new_pool["v_pages"], block_table, start,
        chunk_lens, k_scale_pages=new_pool.get("k_scale_pages"),
        v_scale_pages=new_pool.get("v_scale_pages"), softcap=softcap,
        window=window, scale=scale, block_q=block_q, interpret=interpret)
    return o, new_pool


@functools.partial(jax.jit, static_argnames=("quant", "softcap", "window",
                                             "scale", "block_q", "interpret"))
def _paged_prefill_jit(q, k_new, v_new, pool, block_table, start, chunk_lens,
                       *, quant=None, softcap=None, window=None, scale=None,
                       block_q=None, interpret=None):
    return _paged_prefill_one(q, k_new, v_new, pool, block_table, start,
                              chunk_lens, quant=quant, softcap=softcap,
                              window=window, scale=scale, block_q=block_q,
                              interpret=interpret)


def paged_prefill(q, k_new, v_new, pool, block_table, start, chunk_lens, *,
                  quant=None, softcap=None, window=None, scale=None,
                  block_q=None, interpret=None):
    """Fused chunked prefill: scatter the chunk's K/V directly into the
    pool pages (no contiguous intermediate, no post-hoc ``write_prefill``
    copy), then flash-attend prefix+chunk from the pages.

    q: (B,S,H,d); k_new/v_new: (B,S,KVH,d); ``pool`` dict holds one
    layer's pages (k_pages/v_pages (P,ps,KVH,d) + scale planes when
    ``quant``); start/chunk_lens: (B,) int32. Returns (o (B,S,H,d),
    new_pool). ``block_q`` defaults to the autotuned value for the shape
    (benchmarks/prefill_autotune.py).
    """
    if block_q is None:
        B, S, H, d = q.shape
        block_q = _prefill_tuned_block_q(H, d, k_new.shape[2], S,
                                         pool["k_pages"].shape[1])
    return _profiled("paged_prefill", _paged_prefill_jit, q, k_new, v_new,
                     pool, block_table, start, chunk_lens, quant=quant,
                     softcap=softcap, window=window, scale=scale,
                     block_q=block_q, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("quant", "softcap", "window",
                                             "scale", "block_q", "interpret"))
def _paged_prefill_sharded_jit(q, k_new, v_new, pool, block_table, start,
                               chunk_lens, *, quant=None, softcap=None,
                               window=None, scale=None, block_q=None,
                               interpret=None):
    """Shard-group fused prefill: pool leaves carry a leading shard axis
    (tp, P, ps, KVH/tp, d); shard ``s`` runs the write+attend pair on its
    query/kv head slices and the head-axis concat is the group's
    all_gather (same structure as ``_paged_decode_attention_sharded_jit``)."""
    tp = pool["k_pages"].shape[0]
    B, S, H, d = q.shape
    KVH = k_new.shape[2]
    Hs, KVHs = H // tp, KVH // tp
    outs, pools = [], []
    for s in range(tp):
        o_s, pool_s = _paged_prefill_one(
            q[:, :, s * Hs:(s + 1) * Hs],
            k_new[:, :, s * KVHs:(s + 1) * KVHs],
            v_new[:, :, s * KVHs:(s + 1) * KVHs],
            {k: v[s] for k, v in pool.items()}, block_table, start,
            chunk_lens, quant=quant, softcap=softcap, window=window,
            scale=scale, block_q=block_q, interpret=interpret)
        outs.append(o_s)
        pools.append(pool_s)
    new_pool = {k: jnp.stack([pools[s][k] for s in range(tp)])
                for k in pool}
    return jnp.concatenate(outs, axis=2), new_pool


def paged_prefill_sharded(q, k_new, v_new, pool, block_table, start,
                          chunk_lens, *, quant=None, softcap=None,
                          window=None, scale=None, block_q=None,
                          interpret=None):
    """Shard-group fused chunked prefill (see ``_paged_prefill_sharded_jit``
    for the shard/head-slice structure)."""
    if block_q is None:
        B, S, H, d = q.shape
        block_q = _prefill_tuned_block_q(H, d, k_new.shape[2], S,
                                         pool["k_pages"].shape[2])
    return _profiled("paged_prefill_sharded", _paged_prefill_sharded_jit,
                     q, k_new, v_new, pool, block_table, start, chunk_lens,
                     quant=quant, softcap=softcap, window=window, scale=scale,
                     block_q=block_q, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _ssd_jit(x, dt, A, Bm, Cm, *, chunk=128, h0=None, interpret=None):
    """Full SSD forward via the intra-chunk kernel + jnp inter-chunk scan.

    Same contract as ``repro.models.ssm.ssd_chunked``:
    x: (B,S,H,P), dt: (B,S,H) fp32, A: (H,), Bm/Cm: (B,S,G,N).
    Returns (y, h_final).
    """
    B, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    nc = S // chunk
    assert nc * chunk == S, (S, chunk)

    xc = x.reshape(B, nc, chunk, H, P)
    dtc = dt.reshape(B, nc, chunk, H).astype(jnp.float32)
    bc = jnp.repeat(Bm.reshape(B, nc, chunk, G, N), rep, axis=3)
    cc = jnp.repeat(Cm.reshape(B, nc, chunk, G, N), rep, axis=3)
    a = dtc * A.astype(jnp.float32)
    cum = jnp.cumsum(a, axis=2)                             # (B,nc,Q,H)
    total = cum[:, :, -1]                                   # (B,nc,H)

    y_diag, states = _ssd.ssd_intra_chunk(xc, bc, cc, cum, dtc,
                                          interpret=interpret)

    if h0 is None:
        h0 = jnp.zeros((B, H, N, P), jnp.float32)

    def body(h_prev, xs):
        s_c, tot_c = xs
        return h_prev * jnp.exp(tot_c)[..., None, None] + s_c, h_prev

    h_final, h_prevs = jax.lax.scan(
        body, h0, (jnp.moveaxis(states, 1, 0), jnp.moveaxis(total, 1, 0)))
    h_prevs = jnp.moveaxis(h_prevs, 0, 1)                   # (B,nc,H,N,P)

    y_off = jnp.einsum("bcihn,bchnp->bcihp",
                       cc.astype(jnp.float32) * jnp.exp(cum)[..., None],
                       h_prevs, preferred_element_type=jnp.float32)
    y = (y_diag.astype(jnp.float32) + y_off).reshape(B, S, H, P)
    return y.astype(x.dtype), h_final


def ssd(x, dt, A, Bm, Cm, *, chunk=128, h0=None, interpret=None):
    """Full SSD forward via the intra-chunk kernel + jnp inter-chunk scan.

    Same contract as ``repro.models.ssm.ssd_chunked``:
    x: (B,S,H,P), dt: (B,S,H) fp32, A: (H,), Bm/Cm: (B,S,G,N).
    Returns (y, h_final).
    """
    return _profiled("ssd", _ssd_jit, x, dt, A, Bm, Cm, chunk=chunk, h0=h0,
                     interpret=interpret)
