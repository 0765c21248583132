"""Transformer assembly: blocks, scan-over-periods stacking, enc-dec.

Depth is organised as ``prefix`` (unrolled layers, e.g. deepseek's first
dense layer) + ``stack`` (a period of block kinds scanned ``n_periods``
times with params stacked on a leading "layers" dim). The period is
``lcm(len(layer_pattern), moe_period)`` so every scanned position has a
uniform kind across scan steps (jamba: 8, gemma2: 2, most: 1).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (embed, embed_schema, logits, mlp, mlp_schema,
                                 rmsnorm, rmsnorm_schema)
from repro.models.rope import rope_cos_sin
from repro.models.schema import ParamSpec, is_spec
from repro.parallel.context import constrain


def _maybe_scan(body, carry, xs, length: int):
    """lax.scan, or an unrolled python loop when the dry-run measurement flag
    is set (XLA cost_analysis counts while bodies once)."""
    from repro.models.flags import unroll_scans
    if not unroll_scans():
        return jax.lax.scan(body, carry, xs)
    ys_list = []
    for c in range(length):
        xs_c = jax.tree.map(lambda a: a[c], xs)
        carry, y = body(carry, xs_c)
        ys_list.append(y)
    if ys_list and ys_list[0] is not None:
        ys = jax.tree.map(lambda *a: jnp.stack(a), *ys_list)
    else:
        ys = None
    return carry, ys


# ---------------------------------------------------------------- depth plan

def depth_plan(cfg: ModelConfig) -> Tuple[int, int, int]:
    """-> (n_prefix_layers, period, n_periods)."""
    pat = len(cfg.layer_pattern)
    period = pat
    if cfg.n_routed_experts:
        period = math.lcm(pat, cfg.moe_period)
    prefix = cfg.first_k_dense
    rest = cfg.n_layers - prefix
    assert rest % period == 0, (cfg.name, rest, period)
    return prefix, period, rest // period


def stack_schema(tree: Any, n: int) -> Any:
    if is_spec(tree):
        return ParamSpec((n,) + tree.shape, ("layers",) + tree.axes,
                         init=tree.init, dtype=tree.dtype, fan_in=tree.fan_in)
    return {k: stack_schema(v, n) for k, v in tree.items()}


# ---------------------------------------------------------------- blocks

def block_schema(cfg: ModelConfig, idx: int) -> Dict[str, Any]:
    kind = cfg.block_kind(idx)
    d: Dict[str, Any] = {"ln1": rmsnorm_schema(cfg.d_model)}
    d["mixer"] = (ssm_mod.ssm_schema(cfg) if kind == "ssm"
                  else attn.attn_schema(cfg))
    if cfg.is_moe_layer(idx):
        d["ln2"] = rmsnorm_schema(cfg.d_model)
        d["ffn"] = moe_mod.moe_schema(cfg)
    elif cfg.d_ff > 0:  # mamba2: mixer-only blocks, no FFN
        d["ln2"] = rmsnorm_schema(cfg.d_model)
        d["ffn"] = mlp_schema(cfg, cfg.d_ff)
    if cfg.use_post_norm:
        d["post_ln1"] = rmsnorm_schema(cfg.d_model)
        d["post_ln2"] = rmsnorm_schema(cfg.d_model)
    return d


def block_apply(cfg: ModelConfig, p: Dict[str, Any], x: jnp.ndarray, idx: int,
                cos, sin, mode: str, cache: Optional[Dict] = None,
                cur_len: Optional[jnp.ndarray] = None,
                block_table: Optional[jnp.ndarray] = None,
                shard=None, chunk_len: Optional[jnp.ndarray] = None,
                layer: Optional[jnp.ndarray] = None):
    """-> (x, aux, cache_update). ``layer`` (paged modes, inside the layer
    scan): ``cache`` holds the whole stack of this period position's
    leaves and ``layer`` indexes it; the block updates its own layer's
    rows in place and returns the stack. ``shard`` (a ShardGroup) activates the
    tensor-parallel paged-decode path: head-sharded attention over per-shard
    page pools, expert-sharded MoE; SSM mixers stay replicated (their state
    is O(1) per sequence — nothing to split). ``mode == "paged_prefill"``
    lands a chunk (x: (B,S,D), live rows per ``chunk_len``) directly into
    the pages at offset ``cur_len`` — a prompt chunk, or a speculative
    verify batch of last-token+drafts rows (attention-only archs — SSM/MoE
    archs keep the exact sequential path, see scheduler)."""
    kind = cfg.block_kind(idx)
    local = kind == "attn_local"
    h = rmsnorm(x, p["ln1"], cfg.rms_eps)
    cache_update = None
    if kind == "ssm":
        if mode == "paged_prefill":
            raise NotImplementedError(
                "fused paged prefill covers attention-only archs; SSM archs "
                "use the exact sequential chunk path")
        if mode == "train":
            mix = ssm_mod.ssm_train(cfg, p["mixer"], h)
        elif mode == "prefill":
            mix, cache_update = ssm_mod.ssm_prefill(cfg, p["mixer"], h)
        elif layer is None:
            mix, cache_update = ssm_mod.ssm_decode(cfg, p["mixer"], h, cache)
        else:
            mix, state = ssm_mod.ssm_decode(
                cfg, p["mixer"], h, {k: v[layer] for k, v in cache.items()})
            cache_update = {k: jax.lax.dynamic_update_index_in_dim(
                v, state[k], layer, 0) for k, v in cache.items()}
    else:
        if mode == "train":
            mix = attn.attn_train(cfg, p["mixer"], h, cos, sin, local=local)
        elif mode == "prefill":
            mix, cache_update = attn.attn_prefill(cfg, p["mixer"], h, cos, sin,
                                                  local=local)
        elif mode == "paged_decode":
            mix, cache_update = attn.attn_paged_decode(
                cfg, p["mixer"], h, cos, sin, cache, cur_len, block_table,
                local=local, shard=shard, layer=layer)
        elif mode == "paged_prefill":
            mix, cache_update = attn.attn_paged_prefill(
                cfg, p["mixer"], h, cos, sin, cache, cur_len, chunk_len,
                block_table, local=local, shard=shard, layer=layer)
        else:
            mix, cache_update = attn.attn_decode(cfg, p["mixer"], h, cos, sin,
                                                 cache, cur_len, local=local)
    if cfg.use_post_norm:
        mix = rmsnorm(mix, p["post_ln1"], cfg.rms_eps)
    x = x + mix
    aux = jnp.zeros((), jnp.float32)
    if "ffn" in p:
        h2 = rmsnorm(x, p["ln2"], cfg.rms_eps)
        if cfg.is_moe_layer(idx):
            ff, aux = moe_mod.moe_apply(
                cfg, p["ffn"], h2,
                decode=(mode in ("decode", "paged_decode")),
                shard=shard if mode == "paged_decode" else None)
        else:
            ff = mlp(cfg, p["ffn"], h2)
        if cfg.use_post_norm:
            ff = rmsnorm(ff, p["post_ln2"], cfg.rms_eps)
        x = x + ff
    x = constrain(x, ("batch", None, None))
    return x, aux, cache_update


# ------------------------------------------------------------- full schema

def lm_schema(cfg: ModelConfig) -> Dict[str, Any]:
    if cfg.is_encdec:
        return _encdec_schema(cfg)
    prefix, period, n_periods = depth_plan(cfg)
    sch: Dict[str, Any] = {"embed": embed_schema(cfg),
                           "final_ln": rmsnorm_schema(cfg.d_model)}
    if prefix:
        sch["prefix"] = {str(i): block_schema(cfg, i) for i in range(prefix)}
    sch["stack"] = {str(p): stack_schema(block_schema(cfg, prefix + p), n_periods)
                    for p in range(period)}
    return sch


# -------------------------------------------------------------- positions

def default_positions(cfg: ModelConfig, batch: int, seq: int,
                      offset=0) -> jnp.ndarray:
    pos = offset + jnp.arange(seq, dtype=jnp.int32)[None, :]
    pos = jnp.broadcast_to(pos, (batch, seq))
    if cfg.rope_variant == "mrope":
        return jnp.broadcast_to(pos[None], (3, batch, seq))
    return pos


# ---------------------------------------------------------------- forward

def lm_forward(cfg: ModelConfig, params: Dict[str, Any], tokens: jnp.ndarray,
               positions: Optional[jnp.ndarray] = None, *, mode: str = "train",
               cache: Optional[Dict] = None, cur_len=None,
               block_table: Optional[jnp.ndarray] = None,
               remat: str = "none", shard=None, chunk_len=None):
    """Decoder-only forward.

    train        -> (hidden, aux)
    prefill      -> (hidden, aux, cache)
    decode       -> (hidden, aux, cache)   tokens: (B, 1)
    paged_decode -> (hidden, aux, cache)   tokens: (B, 1); ``cache`` holds
        page pools (``repro.serving.paged_cache``), ``cur_len`` is the (B,)
        per-sequence length vector and ``block_table`` (B, n_pg) maps each
        sequence to its pages — this is what lets the continuous-batching
        scheduler decode sequences of different lengths in one step.
        ``shard`` (a ``repro.parallel.context.ShardGroup``, tp > 1) selects
        the tensor-parallel path: pool leaves carry a shard axis (behind
        the stack's layers axis) and attention/MoE split across the group
        (docs/sharding.md).
    paged_prefill -> (hidden, aux, cache)  tokens: (B, S) one prompt chunk
        per sequence; ``cur_len`` (B,) tokens already landed in the pages
        (chunk row t sits at absolute position cur_len+t), ``chunk_len``
        (B,) live rows. The chunk's K/V is written directly into the pages
        and its queries attend prefix+chunk in the same pass (fused
        chunked prefill — no dense intermediate, no ``write_prefill``).
        Speculative verify (``model.paged_verify_step``) rides the same
        mode with a last-token+drafts chunk per decoding slot, so the
        batch is the full slot table and ``chunk_len`` may be 0.
    """
    assert not cfg.is_encdec
    B, S = tokens.shape
    decoding = mode in ("decode", "paged_decode", "paged_prefill")
    prefix, period, n_periods = depth_plan(cfg)
    if positions is None:
        if mode == "paged_prefill":
            cl = jnp.asarray(cur_len, jnp.int32).reshape(-1)
            base = cl[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
            positions = jnp.broadcast_to(base, (B, S))
            if cfg.rope_variant == "mrope":
                positions = jnp.broadcast_to(positions[None], (3, B, S))
        elif decoding:
            cl = jnp.asarray(cur_len, jnp.int32)
            base = jnp.broadcast_to(
                cl[None, None] if cl.ndim == 0 else
                cl[:, None] if cl.ndim == 1 else cl, (B, 1))
            positions = base
            if cfg.rope_variant == "mrope":
                positions = jnp.broadcast_to(base[None], (3, B, 1))
        else:
            positions = default_positions(cfg, B, S)
    cos, sin = rope_cos_sin(cfg, positions)

    x = embed(cfg, params["embed"], tokens)
    x = constrain(x, ("batch", None, None))
    aux_total = jnp.zeros((), jnp.float32)

    # ---- prefix layers (unrolled) ---------------------------------------
    prefix_cache_out = {}
    for i in range(prefix):
        c_in = cache["prefix"][str(i)] if (cache and decoding) else None
        x, aux, c_out = block_apply(cfg, params["prefix"][str(i)], x, i,
                                    cos, sin, mode, c_in, cur_len,
                                    block_table, shard, chunk_len=chunk_len)
        aux_total = aux_total + aux
        if c_out is not None:
            prefix_cache_out[str(i)] = c_out

    # ---- scanned stack ----------------------------------------------------
    stack_params = params["stack"]

    if mode == "train":
        def body(carry, xs_p):
            xx, aux_c = carry
            for p in range(period):
                xx, aux, _ = block_apply(cfg, xs_p[str(p)], xx, prefix + p,
                                         cos, sin, "train")
                aux_c = aux_c + aux
            return (xx, aux_c), None

        if remat == "full":
            body = jax.checkpoint(body, prevent_cse=False)
        elif remat == "dots":
            body = jax.checkpoint(
                body, prevent_cse=False,
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
        (x, aux_total), _ = _maybe_scan(body, (x, aux_total), stack_params,
                                        n_periods)

    elif mode == "prefill":
        def body(carry, xs_p):
            xx, aux_c = carry
            outs = {}
            for p in range(period):
                xx, aux, c_out = block_apply(cfg, xs_p[str(p)], xx, prefix + p,
                                             cos, sin, "prefill")
                aux_c = aux_c + aux
                outs[str(p)] = c_out
            return (xx, aux_c), outs

        (x, aux_total), stack_cache = _maybe_scan(body, (x, aux_total),
                                                  stack_params, n_periods)
        cache_out = {"stack": stack_cache}
        if prefix_cache_out:
            cache_out["prefix"] = prefix_cache_out

    elif mode == "decode":
        def body(xx, xs_p):
            ps, cs = xs_p
            new_cs = {}
            for p in range(period):
                xx, _, c_out = block_apply(cfg, ps[str(p)], xx, prefix + p,
                                           cos, sin, mode, cs[str(p)],
                                           cur_len)
                new_cs[str(p)] = c_out
            return xx, new_cs

        x, stack_cache = _maybe_scan(body, x, (stack_params, cache["stack"]),
                                     n_periods)
        cache_out = {"stack": stack_cache}
        if prefix_cache_out:
            cache_out["prefix"] = prefix_cache_out

    else:  # paged_decode / paged_prefill
        # The stacked page pools ride in the scan's carry and each layer
        # updates its own rows in place at [layer, page, slot]: as scan
        # xs/ys every layer's pool would be sliced out of the stack and
        # restacked into a fresh one, a copy of the whole pool per tick.
        def body(carry, xs_p):
            xx, cs = carry
            ps, layer = xs_p
            cs = dict(cs)
            for p in range(period):
                xx, _, cs[str(p)] = block_apply(
                    cfg, ps[str(p)], xx, prefix + p, cos, sin, mode,
                    cs[str(p)], cur_len, block_table, shard,
                    chunk_len=chunk_len, layer=layer)
            return (xx, cs), None

        layers = jnp.arange(n_periods, dtype=jnp.int32)
        (x, stack_cache), _ = _maybe_scan(
            body, (x, cache["stack"]), (stack_params, layers), n_periods)
        cache_out = {"stack": stack_cache}
        if prefix_cache_out:
            cache_out["prefix"] = prefix_cache_out

    x = rmsnorm(x, params["final_ln"], cfg.rms_eps)
    if mode == "train":
        return x, aux_total
    return x, aux_total, cache_out


# ---------------------------------------------------------------------------
# encoder-decoder (whisper): small depth -> unrolled
# ---------------------------------------------------------------------------

def _xattn_schema(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    H = cfg.n_heads
    return {
        "wq": ParamSpec((d, H * hd), ("embed", "heads")),
        "wk": ParamSpec((d, H * hd), ("embed", "heads")),
        "wv": ParamSpec((d, H * hd), ("embed", "heads")),
        "wo": ParamSpec((H * hd, d), ("heads", "embed")),
    }


def _encdec_schema(cfg: ModelConfig) -> Dict[str, Any]:
    sch: Dict[str, Any] = {"embed": embed_schema(cfg)}
    sch["dec_pos"] = ParamSpec((36864, cfg.d_model), ("pos", None),
                               init="embed")
    sch["enc"] = {str(i): {
        "ln1": rmsnorm_schema(cfg.d_model),
        "mixer": attn.gqa_schema(cfg),
        "ln2": rmsnorm_schema(cfg.d_model),
        "ffn": mlp_schema(cfg, cfg.d_ff),
    } for i in range(cfg.n_enc_layers)}
    sch["enc_ln"] = rmsnorm_schema(cfg.d_model)
    sch["dec"] = {str(i): {
        "ln1": rmsnorm_schema(cfg.d_model),
        "mixer": attn.gqa_schema(cfg),
        "ln_x": rmsnorm_schema(cfg.d_model),
        "xattn": _xattn_schema(cfg),
        "ln2": rmsnorm_schema(cfg.d_model),
        "ffn": mlp_schema(cfg, cfg.d_ff),
    } for i in range(cfg.n_layers)}
    sch["final_ln"] = rmsnorm_schema(cfg.d_model)
    return sch


def _sinusoid(seq: int, d: int) -> jnp.ndarray:
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    dim = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10000.0, 2 * dim / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def encoder_forward(cfg: ModelConfig, params, enc_embeds: jnp.ndarray):
    """enc_embeds: (B, T, D) precomputed frame embeddings (conv stub)."""
    x = enc_embeds.astype(jnp.dtype(cfg.dtype))
    x = x + _sinusoid(x.shape[1], cfg.d_model).astype(x.dtype)[None]
    for i in range(cfg.n_enc_layers):
        p = params["enc"][str(i)]
        h = rmsnorm(x, p["ln1"], cfg.rms_eps)
        x = x + attn.gqa_train(cfg, p["mixer"], h, None, None, local=False,
                               causal=False)
        h = rmsnorm(x, p["ln2"], cfg.rms_eps)
        x = x + mlp(cfg, p["ffn"], h)
    return rmsnorm(x, params["enc_ln"], cfg.rms_eps)


def _cross_attend(cfg, p, x, enc_kv):
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    q = (x @ p["wq"].astype(x.dtype)).reshape(B, S, cfg.n_heads, hd)
    o = attn.attend(q, enc_kv["k"], enc_kv["v"], causal=False)
    return o.reshape(B, S, -1) @ p["wo"].astype(x.dtype)


def _cross_kv(cfg, p, enc_out):
    B, T, _ = enc_out.shape
    hd = cfg.resolved_head_dim
    k = (enc_out @ p["wk"].astype(enc_out.dtype)).reshape(B, T, cfg.n_heads, hd)
    v = (enc_out @ p["wv"].astype(enc_out.dtype)).reshape(B, T, cfg.n_heads, hd)
    return {"k": k, "v": v}


def encdec_forward(cfg: ModelConfig, params, tokens, enc_embeds=None, *,
                   mode="train", cache=None, cur_len=None, remat="none"):
    """Whisper-style enc-dec. train/prefill need enc_embeds; decode uses the
    cross-kv stored in the cache."""
    B, S = tokens.shape
    x = embed(cfg, params["embed"], tokens)
    if mode == "decode":
        pos = jnp.broadcast_to(jnp.reshape(cur_len, (1, 1)), (B, 1))
    else:
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    x = x + jnp.take(params["dec_pos"], pos, axis=0).astype(x.dtype)

    if mode != "decode":
        enc_out = encoder_forward(cfg, params, enc_embeds)

    aux = jnp.zeros((), jnp.float32)
    new_cache: Dict[str, Any] = {"self": {}, "cross": {}}
    for i in range(cfg.n_layers):
        p = params["dec"][str(i)]
        h = rmsnorm(x, p["ln1"], cfg.rms_eps)
        if mode == "train":
            mix = attn.gqa_train(cfg, p["mixer"], h, None, None, local=False)
        elif mode == "prefill":
            mix, c = attn.gqa_prefill(cfg, p["mixer"], h, None, None,
                                      local=False)
            new_cache["self"][str(i)] = c
        else:
            mix, c = attn.gqa_decode(cfg, p["mixer"], h, None, None,
                                     cache["self"][str(i)], cur_len,
                                     local=False)
            new_cache["self"][str(i)] = c
        x = x + mix
        hx = rmsnorm(x, p["ln_x"], cfg.rms_eps)
        if mode == "decode":
            ekv = cache["cross"][str(i)]
        else:
            ekv = _cross_kv(cfg, p["xattn"], enc_out)
        if mode == "prefill":
            new_cache["cross"][str(i)] = ekv
        elif mode == "decode":
            new_cache["cross"][str(i)] = ekv
        x = x + _cross_attend(cfg, p["xattn"], hx, ekv)
        h2 = rmsnorm(x, p["ln2"], cfg.rms_eps)
        x = x + mlp(cfg, p["ffn"], h2)
        x = constrain(x, ("batch", None, None))
    x = rmsnorm(x, params["final_ln"], cfg.rms_eps)
    if mode == "train":
        return x, aux
    return x, aux, new_cache
