"""Pallas TPU paged flash-decode kernel (block-table KV gather).

The paged variant of ``repro.kernels.decode_attention``: instead of a
contiguous per-sequence ring buffer, K/V live in a shared page pool of
shape (num_pages, page_size, KVH, d) and each sequence owns a list of
pages recorded in a *block table* (B, pages_per_seq). The block table and
the per-sequence lengths are passed as scalar-prefetch operands
(``pltpu.PrefetchScalarGridSpec``) so the BlockSpec index maps can resolve
``pages[block_table[b, i]]`` before the kernel body runs — the page gather
happens in the DMA engine, never materialising a contiguous copy in HBM.

Grid: (B, pages_per_seq). Each step fetches one whole page — every KV
head of it, since the TPU lowering tiles a block's last two dimensions
(KVH, d) and a single-head slice of the KVH axis is not a legal block —
and attends it for each KV head in turn, emitting a partial (max, sum,
weighted-V) triple per head; the log-sum-exp combine over the page axis
runs as plain jnp in ``repro.kernels.ops.paged_decode_attention`` —
identical structure to the dense flash-decode split-KV combine.

Pages wholly past a sequence's length produce masked partials with
``m = -1e30``; the combine weights them by ``exp(m - m_glob) == 0`` so they
never contribute. Page 0 is the serving layer's sink page (see
``repro.serving.paged_cache``) and may be referenced by idle slots — it is
masked the same way.

Supports the int8-quantised cache (§Perf ``cache_quant``): quantised pools
carry per-(position, kv-head) fp32 scale pages and the dequantise happens
in-kernel on the VMEM block.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

_NEG = -1e30


def _pd_kernel(bt_ref, lens_ref, q_ref, k_ref, v_ref, *refs,
               scale: float, softcap: Optional[float],
               window: Optional[int], page_size: int, quant: bool):
    if quant:                                   # int8 pools + fp32 scales
        ks_ref, vs_ref, m_ref, l_ref, o_ref = refs
    else:
        m_ref, l_ref, o_ref = refs
    b = pl.program_id(0)
    pi = pl.program_id(1)                       # page slot within the sequence
    valid = lens_ref[b]                         # tokens 0..valid-1 are live
    k_pos = pi * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, page_size), 1)
    ok = k_pos < valid
    if window is not None:
        ok &= k_pos >= valid - window
    for h in range(q_ref.shape[1]):             # KV heads of the page block
        q = q_ref[0, h].astype(jnp.float32)     # (G, d)
        k = k_ref[0, :, h, :].astype(jnp.float32)   # (ps, d)
        if quant:
            k = k * ks_ref[0, :, h:h + 1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        s = jnp.where(ok, s, _NEG)              # (G, ps)
        m = s.max(axis=-1, keepdims=True)       # (G, 1)
        p = jnp.exp(s - m)
        v = v_ref[0, :, h, :].astype(jnp.float32)   # (ps, d)
        if quant:
            v = v * vs_ref[0, :, h:h + 1]
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        m_ref[0, 0, h] = m
        l_ref[0, 0, h] = p.sum(axis=-1, keepdims=True)
        o_ref[0, 0, h] = pv


def paged_decode_partials(q: jnp.ndarray, k_pages: jnp.ndarray,
                          v_pages: jnp.ndarray, block_table: jnp.ndarray,
                          seq_lens: jnp.ndarray, *,
                          k_scale_pages: Optional[jnp.ndarray] = None,
                          v_scale_pages: Optional[jnp.ndarray] = None,
                          softcap: Optional[float] = None,
                          window: Optional[int] = None,
                          scale: Optional[float] = None,
                          interpret: Optional[bool] = None):
    """q: (B, H, d); pools: (P, page_size, KVH, d); block_table: (B, n_pg)
    int32; seq_lens: (B,) int32 — number of live tokens per sequence.

    Returns partials (m, l, o) with a page axis for the LSE combine:
    m/l (B, n_pg, KVH, G, 1), o (B, n_pg, KVH, G, d).
    """
    B, H, d = q.shape
    _, page_size, KVH, _ = k_pages.shape
    n_pg = block_table.shape[1]
    G = H // KVH
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    qr = q.reshape(B, KVH, G, d)
    quant = k_scale_pages is not None

    kernel = functools.partial(_pd_kernel, scale=scale, softcap=softcap,
                               window=window, page_size=page_size,
                               quant=quant)
    page_spec = pl.BlockSpec((1, page_size, KVH, d),
                             lambda b, i, bt, lens: (bt[b, i], 0, 0, 0))
    in_specs = [
        pl.BlockSpec((1, KVH, G, d), lambda b, i, bt, lens: (b, 0, 0, 0)),
        page_spec,
        page_spec,
    ]
    args = [qr, k_pages, v_pages]
    if quant:
        scale_spec = pl.BlockSpec(
            (1, page_size, KVH), lambda b, i, bt, lens: (bt[b, i], 0, 0))
        in_specs += [scale_spec, scale_spec]
        args += [k_scale_pages, v_scale_pages]

    stat_spec = pl.BlockSpec((1, 1, KVH, G, 1),
                             lambda b, i, bt, lens: (b, i, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_pg),
        in_specs=in_specs,
        out_specs=[
            stat_spec,
            stat_spec,
            pl.BlockSpec((1, 1, KVH, G, d),
                         lambda b, i, bt, lens: (b, i, 0, 0, 0)),
        ],
    )
    m, lse, o = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, n_pg, KVH, G, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, n_pg, KVH, G, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, n_pg, KVH, G, d), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(block_table.astype(jnp.int32), seq_lens.astype(jnp.int32), *args)
    return m, lse, o
