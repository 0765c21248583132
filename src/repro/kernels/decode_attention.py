"""Pallas TPU flash-decode kernel (split-KV single-token attention).

One query token attends a long KV cache. The cache's sequence dim is split
across the grid; each split emits a partial (max, sum, weighted-V)
triple, and the tiny log-sum-exp combine runs as plain jnp in the wrapper
(``repro.kernels.ops.decode_attention``). This is the same structure the
serving engine's sequence-sharded distributed decode uses across chips —
here it is the *within-chip* version that turns HBM cache reads into
streamed VMEM blocks.

This kernel assumes a contiguous per-sequence cache (the static-batch
engine's ring buffers). ``repro.kernels.paged_decode`` is the block-table
variant for the continuous-batching scheduler's paged KV cache: same
partials and the same LSE combine, but each grid step DMAs one *page*
resolved through a scalar-prefetched block table.
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


def _fd_kernel(q_ref, k_ref, v_ref, m_ref, l_ref, o_ref, *,
               scale: float, softcap: Optional[float], block_k: int,
               kv_len: int):
    si = pl.program_id(1)                     # kv split index
    q = q_ref[0].astype(jnp.float32)          # (G, d)
    k = k_ref[0].astype(jnp.float32)          # (bk, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    k_pos = si * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
    s = jnp.where(k_pos < kv_len, s, _NEG)    # (G, bk)
    m = s.max(axis=-1)                        # (G,)
    p = jnp.exp(s - m[:, None])
    lse = p.sum(axis=-1)
    v = v_ref[0].astype(jnp.float32)          # (bk, d)
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    m_ref[0, 0] = m
    l_ref[0, 0] = lse
    o_ref[0, 0] = pv


def decode_attention_partials(q: jnp.ndarray, k_cache: jnp.ndarray,
                              v_cache: jnp.ndarray, *,
                              softcap: Optional[float] = None,
                              scale: Optional[float] = None,
                              block_k: int = 512,
                              interpret: Optional[bool] = None):
    """q: (B, H, d); caches: (B, S, KVH, d).

    Returns partials (m, l, o) with a leading kv-split dim for the LSE
    combine: m/l (B*KVH, splits, G), o (B*KVH, splits, G, d).
    """
    B, H, d = q.shape
    _, S, KVH, _ = k_cache.shape
    G = H // KVH
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    block_k = min(block_k, S)
    pk = (-S) % block_k
    kp = jnp.pad(k_cache, ((0, 0), (0, pk), (0, 0), (0, 0))) if pk else k_cache
    vp = jnp.pad(v_cache, ((0, 0), (0, pk), (0, 0), (0, 0))) if pk else v_cache
    n_s = (S + pk) // block_k

    qf = q.reshape(B * KVH, G, d)
    kf = jnp.moveaxis(kp, 2, 1).reshape(B * KVH, S + pk, d)
    vf = jnp.moveaxis(vp, 2, 1).reshape(B * KVH, S + pk, d)

    kernel = functools.partial(_fd_kernel, scale=scale, softcap=softcap,
                               block_k=block_k, kv_len=S)
    m, lse, o = pl.pallas_call(
        kernel,
        grid=(B * KVH, n_s),
        in_specs=[
            pl.BlockSpec((1, G, d), lambda b, si: (b, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, si: (b, si, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, si: (b, si, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, G), lambda b, si: (b, si, 0)),
            pl.BlockSpec((1, 1, G), lambda b, si: (b, si, 0)),
            pl.BlockSpec((1, 1, G, d), lambda b, si: (b, si, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * KVH, n_s, G), jnp.float32),
            jax.ShapeDtypeStruct((B * KVH, n_s, G), jnp.float32),
            jax.ShapeDtypeStruct((B * KVH, n_s, G, d), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(qf, kf, vf)
    return m, lse, o
