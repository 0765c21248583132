"""Attention: GQA (+bias/qk-norm/softcap/sliding-window) and MLA (deepseek-v2).

The sequence-level math lives in ``attend`` — a chunked, online-softmax
(flash-structured) implementation in pure XLA ops. It is the reference path
used for CPU smoke tests and the multi-pod dry-run; ``repro.kernels``
contains the Pallas TPU kernels that compute the same function (allclose
tested) for real deployments.

Causality is exploited *structurally*: the python-level loop over query
blocks only visits the key/value chunks a block can see, so compiled HLO
FLOPs match optimal causal attention (this matters for the roofline's
useful-FLOP ratio).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import soft_cap
from repro.models.rope import apply_rope, rotary_dim
from repro.models.schema import ParamSpec

_NEG = -1e30


# ---------------------------------------------------------------------------
# core chunked attention
# ---------------------------------------------------------------------------

def attend(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
           causal: bool = True,
           window: Optional[int] = None,
           softcap: Optional[float] = None,
           scale: Optional[float] = None,
           q_offset: int = 0,
           q_block: int = 512,
           kv_block: int = 1024,
           mask_opt: bool = False) -> jnp.ndarray:
    """q: (B,Sq,H,hd) k/v: (B,Skv,KVH,hd_v) -> (B,Sq,H,hd_v)."""
    B, Sq, H, hd = q.shape
    _, Skv, KVH, hdv = v.shape
    G = H // KVH
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    qr = q.reshape(B, Sq, KVH, G, hd)

    small = Sq * Skv <= 4096 * 4096 and (Sq <= q_block or Skv <= kv_block
                                         or not causal)
    if small or (not causal and Skv <= 4096):
        # short-kv non-causal (e.g. cross-attention to a 1500-frame encoder)
        return _direct(qr, k, v, causal, window, softcap, scale, q_offset
                       ).reshape(B, Sq, H, hdv)

    # scale blocks with sequence length to bound HLO op count
    q_block = min(2048, max(q_block, Sq // 32))
    kv_block = min(4096, max(kv_block, Skv // 16))
    n_q = -(-Sq // q_block)
    outs = []
    for i in range(n_q):
        q0, q1 = i * q_block, min((i + 1) * q_block, Sq)
        qi = qr[:, q0:q1]
        if causal:
            kend = min(Skv, -(-(q_offset + q1) // kv_block) * kv_block)
        else:
            kend = Skv
        kstart = 0
        if window is not None:
            kstart = max(0, (q_offset + q0 - (window - 1)) // kv_block * kv_block)
        if not mask_opt:
            outs.append(_scan_chunk(qi, k[:, kstart:kend], v[:, kstart:kend],
                                    causal, window, softcap, scale,
                                    q_offset + q0, kstart, kv_block))
            continue
        # §Perf lever: interior kv chunks are fully visible to every query in
        # the block — no mask tensors needed there. Only the diagonal chunk
        # (causal) and the window's trailing edge get the masked path.
        qlo, qhi = q_offset + q0, q_offset + q1 - 1
        interior_end = kstart
        for j in range(kstart, kend, kv_block):
            k_hi = j + kv_block - 1
            ok = (not causal or k_hi <= qlo) and \
                (window is None or (qhi - j) < window)
            if ok and j == interior_end:
                interior_end = j + kv_block
            else:
                break
        carry = None
        if interior_end > kstart:
            carry = _scan_chunk(qi, k[:, kstart:interior_end],
                                v[:, kstart:interior_end],
                                False, None, softcap, scale,
                                q_offset + q0, kstart, kv_block,
                                return_carry=True)
        if interior_end < kend:
            carry = _scan_chunk(qi, k[:, interior_end:kend],
                                v[:, interior_end:kend],
                                causal, window, softcap, scale,
                                q_offset + q0, interior_end, kv_block,
                                carry=carry, return_carry=True)
        m, lse, acc = carry
        o = acc / jnp.maximum(lse, 1e-30)[..., None]
        outs.append(jnp.moveaxis(o, 3, 1).astype(v.dtype))
    return jnp.concatenate(outs, axis=1).reshape(B, Sq, H, hdv)


def _mask(q_pos, k_pos, causal, window):
    m = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        m &= k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        m &= (q_pos[:, None] - k_pos[None, :]) < window
    return m


def _direct(qr, k, v, causal, window, softcap, scale, q_offset):
    B, Sq, KVH, G, hd = qr.shape
    Skv = k.shape[1]
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qr, k,
                   preferred_element_type=jnp.float32) * scale
    s = soft_cap(s, softcap)
    if causal or window is not None:
        q_pos = q_offset + jnp.arange(Sq)
        m = _mask(q_pos, jnp.arange(Skv), causal, window)
        s = jnp.where(m[None, None, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(v.dtype)


def _scan_chunk(qi, ks, vs, causal, window, softcap, scale,
                q_pos0, k_pos0, kv_block, carry=None, return_carry=False):
    """Online-softmax over kv chunks for one query block.

    ``causal=False, window=None`` is the unmasked interior path — no mask
    tensors are materialised (§Perf lever ``attn_mask_opt``).
    """
    B, qb, KVH, G, hd = qi.shape
    Sk = ks.shape[1]
    hdv = vs.shape[-1]
    nkc = Sk // kv_block
    assert nkc * kv_block == Sk, (Sk, kv_block)
    kc = jnp.moveaxis(ks.reshape(B, nkc, kv_block, KVH, -1), 1, 0)
    vc = jnp.moveaxis(vs.reshape(B, nkc, kv_block, KVH, -1), 1, 0)
    kpos = (k_pos0 + jnp.arange(Sk)).reshape(nkc, kv_block)
    q_pos = q_pos0 + jnp.arange(qb)

    if carry is None:
        carry = (jnp.full((B, KVH, G, qb), _NEG, jnp.float32),
                 jnp.zeros((B, KVH, G, qb), jnp.float32),
                 jnp.zeros((B, KVH, G, qb, hdv), jnp.float32))

    masked = causal or window is not None

    def body(c, xs):
        m, lse, acc = c
        kcb, vcb, kp = xs
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qi, kcb,
                       preferred_element_type=jnp.float32) * scale
        s = soft_cap(s, softcap)
        if masked:
            msk = _mask(q_pos, kp, causal, window)
            s = jnp.where(msk[None, None, None], s, _NEG)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = lse * alpha + p.sum(axis=-1)
        pv = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(vcb.dtype), vcb,
                        preferred_element_type=jnp.float32)
        acc_new = acc * alpha[..., None] + pv
        return (m_new, l_new, acc_new), None

    from repro.models.flags import unroll_scans
    if unroll_scans():
        for j in range(nkc):
            carry, _ = body(carry, (kc[j], vc[j], kpos[j]))
    else:
        carry, _ = jax.lax.scan(body, carry, (kc, vc, kpos))
    if return_carry:
        return carry
    m, lse, acc = carry
    out = acc / jnp.maximum(lse, 1e-30)[..., None]
    return jnp.moveaxis(out, 3, 1).astype(vs.dtype)  # (B,qb,KVH,G,hdv)


def decode_attend(q: jnp.ndarray, k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                  *, valid_len: Optional[jnp.ndarray] = None,
                  start_len: Optional[jnp.ndarray] = None,
                  softcap: Optional[float] = None,
                  scale: Optional[float] = None) -> jnp.ndarray:
    """Single-position attention over a full cache.

    q: (B,1,H,hd); caches: (B,S,KVH,hd). valid_len masks slots >= valid_len;
    start_len (paged sliding-window layers) additionally masks slots below
    it — the paged cache stores absolute positions, so the window is a mask
    rather than a ring write (cf. ``gqa_decode``).
    """
    B, _, H, hd = q.shape
    S, KVH = k_cache.shape[1], k_cache.shape[2]
    G = H // KVH
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    qr = q.reshape(B, KVH, G, hd)
    s = jnp.einsum("bhgd,bkhd->bhgk", qr, k_cache,
                   preferred_element_type=jnp.float32) * scale
    s = soft_cap(s, softcap)
    if valid_len is not None:
        ok = jnp.arange(S)[None] < valid_len[:, None]          # (B,S)
        if start_len is not None:
            ok &= jnp.arange(S)[None] >= start_len[:, None]
        s = jnp.where(ok[:, None, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, 1, H, -1).astype(v_cache.dtype)


# ---------------------------------------------------------------------------
# GQA module
# ---------------------------------------------------------------------------

def gqa_schema(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    H, KVH = cfg.n_heads, cfg.n_kv_heads
    p = {
        "wq": ParamSpec((d, H * hd), ("embed", "heads")),
        "wk": ParamSpec((d, KVH * hd), ("embed", "kv_heads")),
        "wv": ParamSpec((d, KVH * hd), ("embed", "kv_heads")),
        "wo": ParamSpec((H * hd, d), ("heads", "embed")),
    }
    if cfg.qkv_bias:
        p["bq"] = ParamSpec((H * hd,), ("heads",), init="zeros")
        p["bk"] = ParamSpec((KVH * hd,), ("kv_heads",), init="zeros")
        p["bv"] = ParamSpec((KVH * hd,), ("kv_heads",), init="zeros")
    if cfg.qk_norm:
        p["q_norm"] = ParamSpec((hd,), (None,), init="ones")
        p["k_norm"] = ParamSpec((hd,), (None,), init="ones")
    return p


def _qkv(cfg: ModelConfig, p, x, cos, sin, positions_offset_rope=True,
         n_heads=None, n_kv_heads=None):
    """QKV projections. ``n_heads``/``n_kv_heads`` override the config when
    ``p`` holds one shard's head-slice of the weights (tensor-parallel
    paged decode) — every per-head op below is independent of the head
    count, so a slice computes exactly the corresponding slice of the
    full-width result."""
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    H = cfg.n_heads if n_heads is None else n_heads
    KVH = cfg.n_kv_heads if n_kv_heads is None else n_kv_heads
    dt = x.dtype
    q = x @ p["wq"].astype(dt)
    k = x @ p["wk"].astype(dt)
    v = x @ p["wv"].astype(dt)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, KVH, hd)
    v = v.reshape(B, S, KVH, hd)
    if cfg.qk_norm:
        from repro.models.layers import rmsnorm
        q = rmsnorm(q, p["q_norm"], cfg.rms_eps)
        k = rmsnorm(k, p["k_norm"], cfg.rms_eps)
    rd = rotary_dim(cfg)
    q = apply_rope(q, cos, sin, rd)
    k = apply_rope(k, cos, sin, rd)
    return q, k, v


def gqa_train(cfg: ModelConfig, p, x, cos, sin, *, local: bool,
              causal: bool = True, q_offset: int = 0):
    q, k, v = _qkv(cfg, p, x, cos, sin)
    window = cfg.sliding_window if local else None
    o = attend(q, k, v, causal=causal, window=window, softcap=cfg.attn_softcap,
               q_offset=q_offset, mask_opt=cfg.attn_mask_opt)
    B, S = x.shape[:2]
    return o.reshape(B, S, -1) @ p["wo"].astype(x.dtype)


def gqa_prefill(cfg: ModelConfig, p, x, cos, sin, *, local: bool):
    """Returns (y, kv_to_cache)."""
    q, k, v = _qkv(cfg, p, x, cos, sin)
    window = cfg.sliding_window if local else None
    o = attend(q, k, v, causal=True, window=window, softcap=cfg.attn_softcap,
               mask_opt=cfg.attn_mask_opt)
    B, S = x.shape[:2]
    y = o.reshape(B, S, -1) @ p["wo"].astype(x.dtype)
    return y, {"k": k, "v": v}


FP8_MAX = 448.0  # float8_e4m3 largest finite value


def kv_quant_mode(cfg: ModelConfig) -> Optional[str]:
    """Resolve ``cfg.cache_quant`` to a quantisation mode.

    ``False`` -> None, ``True``/``"int8"`` -> "int8", ``"fp8"`` -> "fp8"
    (float8_e4m3 values + fp32 scales). Non-empty strings are truthy, so
    every existing ``if cfg.cache_quant:`` branch keeps working for fp8.
    """
    q = cfg.cache_quant
    if not q:
        return None
    if q is True:
        return "int8"
    if q not in ("int8", "fp8"):
        raise ValueError(f"cache_quant must be bool, 'int8' or 'fp8': {q!r}")
    return q


def quantize_kv(x: jnp.ndarray, mode: str = "int8"):
    """Per-(position, kv-head) symmetric quantisation. x: (..., hd).

    "int8": values in [-127, 127], scale = absmax/127. "fp8": values cast
    to float8_e4m3fn after scaling absmax onto the format's max normal
    (448) — the cast itself performs the 4-bit-mantissa rounding. Both
    return (q, fp32 scale) with dequant ``q.astype(f32) * scale``.
    """
    a = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    # explicit reciprocal multiply: XLA strength-reduces x/const to it
    # under jit anyway — writing it out keeps eager calls and the Pallas
    # in-kernel quantisation bit-identical to the jitted path
    if mode == "fp8":
        scale = jnp.maximum(a * jnp.float32(1.0 / FP8_MAX), 1e-12)
        q = (x.astype(jnp.float32) / scale[..., None]).astype(
            jnp.float8_e4m3fn)
    else:
        scale = jnp.maximum(a * jnp.float32(1.0 / 127.0), 1e-12)
        q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                     -127, 127).astype(jnp.int8)
    return q, scale


def gqa_decode(cfg: ModelConfig, p, x, cos, sin, cache: Dict[str, jnp.ndarray],
               cur_len: jnp.ndarray, *, local: bool):
    """x: (B,1,D). Writes new kv at slot cur_len % capacity, attends cache.

    Returns (y, new_cache). The cache for a local (sliding-window) layer has
    capacity == window, so the ring-write implements the window eviction.
    With ``cfg.cache_quant`` the cache holds int8 values + per-(pos, head)
    scales (§Perf lever: halves cache HBM footprint and read bytes).
    """
    B = x.shape[0]
    dt = x.dtype
    q, k_new, v_new = _qkv(cfg, p, x, cos, sin)
    cap = cache["k"].shape[1]
    slot = (cur_len % cap).astype(jnp.int32)
    if cfg.cache_quant:
        mode = kv_quant_mode(cfg)
        k8, ks = quantize_kv(k_new, mode)
        v8, vs_ = quantize_kv(v_new, mode)
        k_cache = jax.lax.dynamic_update_slice(cache["k"], k8, (0, slot, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(cache["v"], v8, (0, slot, 0, 0))
        k_scale = jax.lax.dynamic_update_slice(cache["k_scale"], ks,
                                               (0, slot, 0))
        v_scale = jax.lax.dynamic_update_slice(cache["v_scale"], vs_,
                                               (0, slot, 0))
        k_deq = (k_cache.astype(dt) * k_scale[..., None].astype(dt))
        v_deq = (v_cache.astype(dt) * v_scale[..., None].astype(dt))
        new_cache = {"k": k_cache, "v": v_cache,
                     "k_scale": k_scale, "v_scale": v_scale}
    else:
        k_deq = k_cache = jax.lax.dynamic_update_slice(cache["k"], k_new,
                                                       (0, slot, 0, 0))
        v_deq = v_cache = jax.lax.dynamic_update_slice(cache["v"], v_new,
                                                       (0, slot, 0, 0))
        new_cache = {"k": k_cache, "v": v_cache}
    valid = jnp.minimum(cur_len + 1, cap) * jnp.ones((B,), jnp.int32)
    o = decode_attend(q, k_deq, v_deq, valid_len=valid,
                      softcap=cfg.attn_softcap)
    y = o.reshape(B, 1, -1) @ p["wo"].astype(x.dtype)
    return y, new_cache


def _scatter_rows(pool: Dict[str, jnp.ndarray], lead: Tuple,
                  page: jnp.ndarray, slot: jnp.ndarray,
                  rows: Dict[str, jnp.ndarray],
                  live: Optional[jnp.ndarray] = None) -> Dict[str, jnp.ndarray]:
    """Scatter new K/V (and scale) rows into the pool leaves at
    ``lead + (page, slot)``, in place. ``rows[name]`` is shaped like
    ``page`` plus the leaf's trailing dims; with ``live`` set, dead rows
    re-write the sink page's slot 0 with its own value, so scatter
    duplicate-index resolution can't clobber a live slot."""
    out = dict(pool)
    for name, r in rows.items():
        leaf = pool[name]
        r = r.astype(leaf.dtype)
        if live is not None:
            keep = live.reshape(live.shape + (1,) * (r.ndim - live.ndim))
            r = jnp.where(keep, r, leaf[lead + (0, 0)])
        out[name] = leaf.at[lead + (page, slot)].set(r)
    return out


def _gather_pages(pool: Dict[str, jnp.ndarray], lead: Tuple,
                  block_table: jnp.ndarray, dt):
    """K and V of the ``block_table`` pages of the pool ``lead`` selects,
    one gather per leaf, dequantised for int8/fp8 pools:
    (B, n_pg * page_size, KVH', hd) each."""
    idx = lead + (block_table,)
    out = []
    for name in ("k", "v"):
        x = pool[f"{name}_pages"][idx]
        if f"{name}_scale_pages" in pool:
            x = (x.astype(dt)
                 * pool[f"{name}_scale_pages"][idx][..., None].astype(dt))
        out.append(x.reshape(x.shape[:1] + (-1,) + x.shape[-2:]))
    return out


def _quant_rows(cfg: ModelConfig, k_new: jnp.ndarray, v_new: jnp.ndarray):
    """The rows a write lands: K/V as they are for a plain pool, quantised
    values plus their fp32 scales for an int8/fp8 one."""
    mode = kv_quant_mode(cfg)
    if not mode:
        return {"k_pages": k_new, "v_pages": v_new}
    k8, ks = quantize_kv(k_new, mode)
    v8, vs_ = quantize_kv(v_new, mode)
    return {"k_pages": k8, "v_pages": v8,
            "k_scale_pages": ks, "v_scale_pages": vs_}


def _paged_write_attend(cfg: ModelConfig, pool: Dict[str, jnp.ndarray],
                        lead: Tuple, q: jnp.ndarray, k_new: jnp.ndarray,
                        v_new: jnp.ndarray, seq_lens: jnp.ndarray,
                        block_table: jnp.ndarray, *, local: bool):
    """Write one decode token's K/V into its page and attend the pages.

    The head-width-agnostic core of the paged decode step. ``pool`` holds
    the pool leaves as the cache carries them — for a scanned layer the
    whole stack, (n_periods, [tp,] num_pages, page_size, KVH', hd) — and
    ``lead`` the indices that pick this layer's pool out of them: the
    layer index, then the shard index on the tensor-parallel loop path;
    () for a pool of its own. KVH' is the full kv-head count (tp=1) or one
    shard's slice, which keeps the tensor-parallel math identical to the
    unsharded one per head. The new row lands in place at
    ``lead + (page, slot)`` and the pages are read by one gather at
    ``lead + (block_table,)``: no op copies a pool out of the stack or
    back in. q: (B,1,H',hd); k_new/v_new: (B,1,KVH',hd). Returns
    (o (B,1,H',hd), new_pool), new_pool shaped like ``pool``.
    """
    ps = pool["k_pages"].shape[len(lead) + 1]
    pos = seq_lens.astype(jnp.int32)                       # write position
    page = jnp.take_along_axis(block_table, (pos // ps)[:, None],
                               axis=1)[:, 0]
    slot = pos % ps
    rows = {k: v[:, 0] for k, v in _quant_rows(cfg, k_new, v_new).items()}
    new_pool = _scatter_rows(pool, lead, page, slot, rows)
    k_deq, v_deq = _gather_pages(new_pool, lead, block_table, q.dtype)
    valid = pos + 1
    start = None
    if local and cfg.sliding_window:
        start = jnp.maximum(valid - cfg.sliding_window, 0)
    o = decode_attend(q, k_deq, v_deq, valid_len=valid, start_len=start,
                      softcap=cfg.attn_softcap)
    return o, new_pool


def shard_gqa_params(cfg: ModelConfig, p, s: int, tp: int):
    """Head-slice of one GQA layer's projection params for shard ``s``.

    Columns of wq/wk/wv are head-major, so shard ``s`` owns the contiguous
    column blocks of its query heads ``[s*H/tp, (s+1)*H/tp)`` and kv heads
    ``[s*KVH/tp, (s+1)*KVH/tp)``. qk-norm scales are per-head-dim and stay
    replicated; ``wo`` is not sliced — the combine concatenates head
    outputs (the shard_map path all-gathers them) and applies the full
    output projection, which keeps tp>1 bitwise identical to tp=1.
    """
    hd = cfg.resolved_head_dim
    Hs = cfg.n_heads // tp * hd
    Ks = cfg.n_kv_heads // tp * hd
    out = {"wq": p["wq"][:, s * Hs:(s + 1) * Hs],
           "wk": p["wk"][:, s * Ks:(s + 1) * Ks],
           "wv": p["wv"][:, s * Ks:(s + 1) * Ks]}
    if cfg.qkv_bias:
        out["bq"] = p["bq"][s * Hs:(s + 1) * Hs]
        out["bk"] = p["bk"][s * Ks:(s + 1) * Ks]
        out["bv"] = p["bv"][s * Ks:(s + 1) * Ks]
    if cfg.qk_norm:
        out["q_norm"] = p["q_norm"]
        out["k_norm"] = p["k_norm"]
    return out


def _gqa_paged_decode_loop(cfg, p, x, cos, sin, cache, lead, seq_lens,
                           block_table, *, local, tp):
    """Unrolled shard-group decode: the per-shard body runs ``tp`` times in
    one program (single-host simulation of the shard_map layout), each
    writing and reading its own slice ``lead + (s,)`` of the carried
    pools."""
    B = x.shape[0]
    Hs = cfg.n_heads // tp
    KVHs = cfg.n_kv_heads // tp
    o_parts = []
    for s in range(tp):
        p_s = shard_gqa_params(cfg, p, s, tp)
        q, k_new, v_new = _qkv(cfg, p_s, x, cos, sin,
                               n_heads=Hs, n_kv_heads=KVHs)
        o_s, cache = _paged_write_attend(cfg, cache, lead + (s,), q, k_new,
                                         v_new, seq_lens, block_table,
                                         local=local)
        o_parts.append(o_s)
    o = jnp.concatenate(o_parts, axis=2)         # head-axis "all_gather"
    y = o.reshape(B, 1, -1) @ p["wo"].astype(x.dtype)
    return y, cache


def _gqa_paged_decode_shard_map(cfg, p, x, cos, sin, cache, lead, seq_lens,
                                block_table, *, local, shard):
    """Shard-group decode as one program per device: pools and projection
    weights partition on the group's mesh axis (the pools' shard axis,
    after the layer axis of a stacked pool), the per-shard body is the
    same ``_qkv`` + ``_paged_write_attend`` the loop path runs, and the
    only wire traffic is the tiny (B,1,H,hd) head all_gather before the
    replicated output projection."""
    from jax.sharding import PartitionSpec as P

    tp, ax = shard.tp, shard.axis
    B = x.shape[0]
    Hs = cfg.n_heads // tp
    KVHs = cfg.n_kv_heads // tp
    sliced = {"wq": p["wq"].reshape(x.shape[-1], tp, -1),
              "wk": p["wk"].reshape(x.shape[-1], tp, -1),
              "wv": p["wv"].reshape(x.shape[-1], tp, -1)}
    sliced_specs = {k: P(None, ax, None) for k in sliced}
    if cfg.qkv_bias:
        for k in ("bq", "bk", "bv"):
            sliced[k] = p[k].reshape(tp, -1)
            sliced_specs[k] = P(ax, None)
    repl = {k: p[k] for k in ("q_norm", "k_norm") if cfg.qk_norm}
    pool_specs = {k: P(*(None,) * len(lead), ax) for k in cache}
    lead = tuple(jnp.asarray(i, jnp.int32) for i in lead)

    def body(sl, rp, pool, ld, xx, cc, ss, lens, bt):
        p_s = {k: v[:, 0] if v.ndim == 3 else v[0] for k, v in sl.items()}
        p_s.update(rp)
        q, k_new, v_new = _qkv(cfg, p_s, xx, cc, ss,
                               n_heads=Hs, n_kv_heads=KVHs)
        o_s, pool = _paged_write_attend(cfg, pool, ld + (0,), q, k_new,
                                        v_new, lens, bt, local=local)
        o = jax.lax.all_gather(o_s, ax, axis=2, tiled=True)  # (B,1,H,hd)
        return o, pool

    fn = jax.shard_map(
        body, mesh=shard.mesh,
        in_specs=(sliced_specs, {k: P() for k in repl}, pool_specs,
                  P(), P(), P(), P(), P(), P()),
        out_specs=(P(), pool_specs), check_vma=False)
    o, new_cache = fn(sliced, repl, cache, lead, x, cos, sin, seq_lens,
                      block_table.astype(jnp.int32))
    y = o.reshape(B, 1, -1) @ p["wo"].astype(x.dtype)
    return y, new_cache


def gqa_paged_decode(cfg: ModelConfig, p, x, cos, sin,
                     cache: Dict[str, jnp.ndarray], seq_lens: jnp.ndarray,
                     block_table: jnp.ndarray, *, local: bool, shard=None,
                     layer=None):
    """Paged-KV decode step: write the new token's K/V into its page, then
    attend the sequence's pages via the block table.

    x: (B,1,D); seq_lens: (B,) live token counts (the new token lands at
    position ``seq_lens[b]``); block_table: (B, n_pg) page ids into the
    layer's pools ``cache["k_pages"]``/``cache["v_pages"]`` of shape
    (num_pages, page_size, KVH, hd). Inside the layer scan ``cache``
    holds the whole stack of a period position's pools, with a leading
    layers axis, and ``layer`` is the scanned layer's index into it: the
    step then writes and reads that layer's pages in place (see
    ``_paged_write_attend``). This is the pure-XLA path (CPU smoke
    tests / dry-run); ``repro.kernels.paged_decode`` computes the same
    function on TPU without materialising the gathered cache. Sliding-window
    layers mask ``[len+1-window, len]`` instead of ring-writing — pages hold
    absolute positions.

    ``shard`` (a ``repro.parallel.context.ShardGroup`` with tp > 1) runs
    the head-sharded tensor-parallel path instead: pool leaves carry a
    shard axis (after the layers axis), each shard computes its query/kv
    head slice against its own pool slice, and the head-axis concat + full
    output projection keep the result byte-identical to tp=1 (see
    docs/sharding.md).
    """
    lead = () if layer is None else (layer,)
    if shard is not None and shard.tp > 1:
        if shard.use_shard_map:
            return _gqa_paged_decode_shard_map(
                cfg, p, x, cos, sin, cache, lead, seq_lens, block_table,
                local=local, shard=shard)
        return _gqa_paged_decode_loop(cfg, p, x, cos, sin, cache, lead,
                                      seq_lens, block_table, local=local,
                                      tp=shard.tp)
    B = x.shape[0]
    q, k_new, v_new = _qkv(cfg, p, x, cos, sin)
    o, new_cache = _paged_write_attend(cfg, cache, lead, q, k_new, v_new,
                                       seq_lens, block_table, local=local)
    y = o.reshape(B, 1, -1) @ p["wo"].astype(x.dtype)
    return y, new_cache


def _chunk_attend(q, k, v, *, q_abs, total, window, softcap, scale=None):
    """Masked direct-softmax attention for a prompt chunk over the gathered
    (dequantised) pages. q: (B,S,H,hd); k/v: (B,K,KVH,hd); q_abs: (B,S)
    absolute query positions; total: (B,) live token count after the chunk
    lands (= start + chunk_len). Same einsum/precision structure as
    ``decode_attend``, batched over the chunk's query rows."""
    B, S, H, hd = q.shape
    K, KVH = k.shape[1], k.shape[2]
    G = H // KVH
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    qr = q.reshape(B, S, KVH, G, hd)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qr, k,
                   preferred_element_type=jnp.float32) * scale
    s = soft_cap(s, softcap)
    k_pos = jnp.arange(K, dtype=jnp.int32)
    ok = (k_pos[None, None] <= q_abs[..., None]) \
        & (k_pos[None, None] < total[:, None, None])
    if window is not None:
        ok &= (q_abs[..., None] - k_pos[None, None]) < window
    s = jnp.where(ok[:, None, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, S, H, hd).astype(v.dtype)


def _paged_prefill_write_attend(cfg: ModelConfig, pool: Dict[str, jnp.ndarray],
                                lead: Tuple, q: jnp.ndarray,
                                k_new: jnp.ndarray, v_new: jnp.ndarray,
                                start: jnp.ndarray, chunk_len: jnp.ndarray,
                                block_table: jnp.ndarray, *, local: bool):
    """Write one prompt chunk's K/V into its pages, then attend
    prefix+chunk — the chunk-width sibling of ``_paged_write_attend``,
    with the same ``pool``/``lead`` convention (the chunk lands in place
    at ``lead + (page, slot)``).

    q: (B,S,H',hd); k_new/v_new: (B,S,KVH',hd) — chunk token ``t`` lands
    at absolute position ``start[b] + t``; rows past ``chunk_len[b]`` are
    padding (not written, output rows unspecified). Head-width-agnostic
    like the decode core, so the tp loop/shard paths reuse it per shard.

    Two callers, and B>1 with ragged ``chunk_len`` (including 0 — all rows
    dead, routed to the sink) is load-bearing for both: chunked prefill
    (one chunk per prefilling slot) and speculative verify
    (``model.paged_verify_step`` — a last-token+drafts chunk per decoding
    slot, where every row's output feeds acceptance).

    With ``repro.models.flags.prefill_kernel()`` set (a trace-time flag)
    the Pallas write+attend pair from ``repro.kernels.paged_prefill``
    computes the same function without materialising the gathered cache;
    the kernels take one layer's pool, so that path slices it out of the
    stack and writes it back.
    """
    from repro.models import flags
    S = q.shape[1]
    dt = q.dtype
    window = cfg.sliding_window if (local and cfg.sliding_window) else None
    if flags.prefill_kernel():
        from repro.kernels import ops as kops
        layer_pool = {k: v[lead] for k, v in pool.items()}
        o, layer_pool = kops.paged_prefill(
            q, k_new, v_new, layer_pool, block_table, start, chunk_len,
            quant=kv_quant_mode(cfg), softcap=cfg.attn_softcap,
            window=window)
        new_pool = {k: v.at[lead].set(layer_pool[k]) if lead
                    else layer_pool[k] for k, v in pool.items()}
        return o.astype(dt), new_pool
    ps = pool["k_pages"].shape[len(lead) + 1]
    n_pg = block_table.shape[1]
    pos = start[:, None] + jnp.arange(S, dtype=jnp.int32)[None]     # (B,S)
    live = jnp.arange(S, dtype=jnp.int32)[None] < chunk_len[:, None]
    pg_idx = jnp.clip(pos // ps, 0, n_pg - 1)
    page = jnp.where(live, jnp.take_along_axis(block_table, pg_idx, axis=1), 0)
    slot = jnp.where(live, pos % ps, 0)
    new_pool = _scatter_rows(pool, lead, page, slot,
                             _quant_rows(cfg, k_new, v_new), live=live)
    k_deq, v_deq = _gather_pages(new_pool, lead, block_table, dt)
    o = _chunk_attend(q, k_deq, v_deq, q_abs=pos,
                      total=start + chunk_len, window=window,
                      softcap=cfg.attn_softcap)
    return o, new_pool


def _gqa_paged_prefill_loop(cfg, p, x, cos, sin, cache, lead, start,
                            chunk_len, block_table, *, local, tp):
    """Unrolled shard-group fused prefill: the per-shard body runs ``tp``
    times in one program (mirrors ``_gqa_paged_decode_loop`` — prefill
    always takes the loop path; chunk dispatches are rare enough that a
    shard_map variant buys nothing on the simulator)."""
    B, S = x.shape[:2]
    Hs = cfg.n_heads // tp
    KVHs = cfg.n_kv_heads // tp
    o_parts = []
    for s in range(tp):
        p_s = shard_gqa_params(cfg, p, s, tp)
        q, k_new, v_new = _qkv(cfg, p_s, x, cos, sin,
                               n_heads=Hs, n_kv_heads=KVHs)
        o_s, cache = _paged_prefill_write_attend(
            cfg, cache, lead + (s,), q, k_new, v_new, start, chunk_len,
            block_table, local=local)
        o_parts.append(o_s)
    o = jnp.concatenate(o_parts, axis=2)         # head-axis "all_gather"
    y = o.reshape(B, S, -1) @ p["wo"].astype(x.dtype)
    return y, cache


def gqa_paged_prefill(cfg: ModelConfig, p, x, cos, sin,
                      cache: Dict[str, jnp.ndarray], start: jnp.ndarray,
                      chunk_len: jnp.ndarray, block_table: jnp.ndarray, *,
                      local: bool, shard=None, layer=None):
    """Fused chunked-prefill step: write the chunk's K/V directly into its
    pages and attend prefix+chunk in one pass — no dense intermediate, no
    post-hoc ``write_prefill`` copy.

    x: (B,S,D) chunk hidden states; start: (B,) tokens already in the
    pages; chunk_len: (B,) live rows of this chunk; block_table: (B,n_pg);
    ``cache``/``layer`` as in ``gqa_paged_decode``. Returns (y (B,S,D),
    new_cache). ``shard`` with tp > 1 runs the head-sharded loop path
    (byte-identical to tp=1, like decode).
    """
    lead = () if layer is None else (layer,)
    if shard is not None and shard.tp > 1:
        return _gqa_paged_prefill_loop(cfg, p, x, cos, sin, cache, lead,
                                       start, chunk_len, block_table,
                                       local=local, tp=shard.tp)
    B, S = x.shape[:2]
    q, k_new, v_new = _qkv(cfg, p, x, cos, sin)
    o, new_cache = _paged_prefill_write_attend(cfg, cache, lead, q, k_new,
                                               v_new, start, chunk_len,
                                               block_table, local=local)
    y = o.reshape(B, S, -1) @ p["wo"].astype(x.dtype)
    return y, new_cache


# ---------------------------------------------------------------------------
# MLA (deepseek-v2): low-rank kv compression + decoupled rope
# ---------------------------------------------------------------------------

def mla_schema(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    d, H = cfg.d_model, cfg.n_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ql, kl = cfg.q_lora_rank, cfg.kv_lora_rank
    # "lora": up-projections sharded on their *input* dim (baseline) — every
    # layer all-reduces the (B,S,H*dh) outputs. "heads": Megatron
    # column-parallel — lora activations replicated (tiny), outputs
    # head-sharded, single AR after wo (§Perf lever, deepseek train cell).
    heads_mode = cfg.mla_shard == "heads"
    lora_axes = ("embed", None) if heads_mode else ("embed", "lora")
    up_axes = (None, "heads") if heads_mode else ("lora", "heads")
    p: Dict[str, ParamSpec] = {
        "wkv_a": ParamSpec((d, kl), lora_axes),
        "wk_pe": ParamSpec((d, dr), ("embed", None)),
        "kv_norm": ParamSpec((kl,), (None,), init="ones"),
        "wkv_b": ParamSpec((kl, H * (dn + dv)), up_axes),
        "wo": ParamSpec((H * dv, d), ("heads", "embed")),
    }
    if ql:
        p["wq_a"] = ParamSpec((d, ql), lora_axes)
        p["q_norm"] = ParamSpec((ql,), (None,), init="ones")
        p["wq_b"] = ParamSpec((ql, H * (dn + dr)), up_axes)
    else:
        p["wq"] = ParamSpec((d, H * (dn + dr)), ("embed", "heads"))
    return p


def _mla_q(cfg, p, x, cos, sin):
    from repro.models.layers import rmsnorm
    B, S, _ = x.shape
    H = cfg.n_heads
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    dt = x.dtype
    if cfg.q_lora_rank:
        qc = rmsnorm(x @ p["wq_a"].astype(dt), p["q_norm"], cfg.rms_eps)
        q = qc @ p["wq_b"].astype(dt)
    else:
        q = x @ p["wq"].astype(dt)
    q = q.reshape(B, S, H, dn + dr)
    q_nope, q_pe = q[..., :dn], q[..., dn:]
    q_pe = apply_rope(q_pe, cos, sin, dr)
    return q_nope, q_pe


def _mla_ckv(cfg, p, x, cos, sin):
    from repro.models.layers import rmsnorm
    dt = x.dtype
    c_kv = rmsnorm(x @ p["wkv_a"].astype(dt), p["kv_norm"], cfg.rms_eps)
    k_pe = (x @ p["wk_pe"].astype(dt))[:, :, None, :]       # (B,S,1,dr)
    k_pe = apply_rope(k_pe, cos, sin, cfg.qk_rope_head_dim)
    return c_kv, k_pe[:, :, 0, :]


def mla_train(cfg: ModelConfig, p, x, cos, sin, **_):
    """Direct (non-absorbed) MLA for train/prefill."""
    B, S, _ = x.shape
    H = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    dt = x.dtype
    q_nope, q_pe = _mla_q(cfg, p, x, cos, sin)
    c_kv, k_pe = _mla_ckv(cfg, p, x, cos, sin)
    kv = (c_kv @ p["wkv_b"].astype(dt)).reshape(B, S, H, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, :, None, :], (B, S, H, dr))], axis=-1)
    o = attend(q, k, v, causal=True, scale=1.0 / math.sqrt(dn + dr),
               mask_opt=cfg.attn_mask_opt)
    return o.reshape(B, S, -1) @ p["wo"].astype(dt)


def mla_prefill(cfg: ModelConfig, p, x, cos, sin, **_):
    y = mla_train(cfg, p, x, cos, sin)
    c_kv, k_pe = _mla_ckv(cfg, p, x, cos, sin)
    return y, {"c_kv": c_kv, "k_pe": k_pe}


def mla_decode(cfg: ModelConfig, p, x, cos, sin, cache, cur_len, **_):
    """Weight-absorbed MLA decode: attends the *compressed* cache directly."""
    B = x.shape[0]
    H = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    kl = cfg.kv_lora_rank
    dt = x.dtype
    q_nope, q_pe = _mla_q(cfg, p, x, cos, sin)        # (B,1,H,dn),(B,1,H,dr)
    c_new, pe_new = _mla_ckv(cfg, p, x, cos, sin)     # (B,1,kl),(B,1,dr)
    cap = cache["c_kv"].shape[1]
    slot = (cur_len % cap).astype(jnp.int32)
    c_kv = jax.lax.dynamic_update_slice(cache["c_kv"], c_new, (0, slot, 0))
    k_pe = jax.lax.dynamic_update_slice(cache["k_pe"], pe_new, (0, slot, 0))
    wkv_b = p["wkv_b"].astype(dt).reshape(kl, H, dn + dv)
    w_uk, w_uv = wkv_b[..., :dn], wkv_b[..., dn:]
    # absorb W_UK into q: (B,H,kl)
    q_abs = jnp.einsum("bohd,chd->bhc", q_nope, w_uk)
    s = (jnp.einsum("bhc,bkc->bhk", q_abs, c_kv,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bohd,bkd->bhk", q_pe, k_pe,
                      preferred_element_type=jnp.float32))
    s = s / math.sqrt(dn + dr)
    valid = jnp.minimum(cur_len + 1, cap) * jnp.ones((B,), jnp.int32)
    ok = jnp.arange(cap)[None] < valid[:, None]
    s = jnp.where(ok[:, None], s, _NEG)
    attn = jax.nn.softmax(s, axis=-1).astype(dt)
    ctx = jnp.einsum("bhk,bkc->bhc", attn, c_kv)      # (B,H,kl)
    o = jnp.einsum("bhc,chd->bhd", ctx, w_uv)         # (B,H,dv)
    y = o.reshape(B, 1, H * dv) @ p["wo"].astype(dt)
    return y, {"c_kv": c_kv, "k_pe": k_pe}


# dispatch tables -----------------------------------------------------------

def attn_schema(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    return mla_schema(cfg) if cfg.attn_impl == "mla" else gqa_schema(cfg)


def attn_train(cfg, p, x, cos, sin, *, local=False):
    if cfg.attn_impl == "mla":
        return mla_train(cfg, p, x, cos, sin)
    return gqa_train(cfg, p, x, cos, sin, local=local)


def attn_prefill(cfg, p, x, cos, sin, *, local=False):
    if cfg.attn_impl == "mla":
        return mla_prefill(cfg, p, x, cos, sin)
    return gqa_prefill(cfg, p, x, cos, sin, local=local)


def attn_decode(cfg, p, x, cos, sin, cache, cur_len, *, local=False):
    if cfg.attn_impl == "mla":
        return mla_decode(cfg, p, x, cos, sin, cache, cur_len)
    return gqa_decode(cfg, p, x, cos, sin, cache, cur_len, local=local)


def attn_paged_decode(cfg, p, x, cos, sin, cache, seq_lens, block_table, *,
                      local=False, shard=None, layer=None):
    if cfg.attn_impl == "mla":
        raise NotImplementedError(
            "paged decode covers GQA; MLA serves via the dense absorbed path")
    return gqa_paged_decode(cfg, p, x, cos, sin, cache, seq_lens, block_table,
                            local=local, shard=shard, layer=layer)


def attn_paged_prefill(cfg, p, x, cos, sin, cache, start, chunk_len,
                       block_table, *, local=False, shard=None, layer=None):
    if cfg.attn_impl == "mla":
        raise NotImplementedError(
            "fused paged prefill covers GQA; MLA serves via the dense path")
    return gqa_paged_prefill(cfg, p, x, cos, sin, cache, start, chunk_len,
                             block_table, local=local, shard=shard,
                             layer=layer)


def kv_cache_spec(cfg: ModelConfig, batch: int, capacity: int,
                  local: bool = False) -> Dict[str, Any]:
    """(shape, dtype, logical axes) for one layer's cache entries."""
    dt = cfg.dtype
    if cfg.attn_impl == "mla":
        return {
            "c_kv": ((batch, capacity, cfg.kv_lora_rank),
                     ("batch", "cache_seq", None), dt),
            "k_pe": ((batch, capacity, cfg.qk_rope_head_dim),
                     ("batch", "cache_seq", None), dt),
        }
    hd = cfg.resolved_head_dim
    cap = min(capacity, cfg.sliding_window) if (local and cfg.sliding_window) \
        else capacity
    mode = kv_quant_mode(cfg)
    kv_dt = {"int8": "int8", "fp8": "float8_e4m3fn", None: dt}[mode]
    out = {
        "k": ((batch, cap, cfg.n_kv_heads, hd),
              ("batch", "cache_seq", "kv_heads", None), kv_dt),
        "v": ((batch, cap, cfg.n_kv_heads, hd),
              ("batch", "cache_seq", "kv_heads", None), kv_dt),
    }
    if cfg.cache_quant:
        out["k_scale"] = ((batch, cap, cfg.n_kv_heads),
                          ("batch", "cache_seq", "kv_heads"), "float32")
        out["v_scale"] = ((batch, cap, cfg.n_kv_heads),
                          ("batch", "cache_seq", "kv_heads"), "float32")
    return out
