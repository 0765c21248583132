"""Pallas TPU flash-attention forward kernel.

Blocked online-softmax with explicit VMEM tiling: grid is
(batch*q_heads, q_blocks, kv_blocks) with the kv dimension innermost and
sequential; running max / sum / accumulator live in VMEM scratch that
persists across kv iterations. GQA is handled in the BlockSpec index maps
(each q head reads its kv group's block — kv is never duplicated in HBM).

Supports causal masking, sliding windows (gemma2 local layers) and logit
soft-capping. Block sizes default to 128x128 — MXU-aligned on v5e.

Target is TPU; correctness on this CPU-only container is established in
interpret mode against ``repro.kernels.ref`` (see tests/test_kernels.py).
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


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
               scale: float, causal: bool, window: Optional[int],
               softcap: Optional[float], block_q: int, block_k: int,
               kv_len: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_kv = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)

    # causal: skip compute for blocks fully above the diagonal
    needed = jnp.logical_or(
        jnp.logical_not(causal),
        ki * block_k <= qi * block_q + block_q - 1)

    @pl.when(needed)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                  # (bq, d)
        k = k_ref[0].astype(jnp.float32)                  # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        mask = k_pos < kv_len
        if causal:
            mask = jnp.logical_and(mask, k_pos <= q_pos)
        if window is not None:
            mask = jnp.logical_and(mask, q_pos - k_pos < window)
        s = jnp.where(mask, s, _NEG)

        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_scr[...] = l_prev * alpha + p.sum(axis=-1)
        m_scr[...] = m_new
        v = v_ref[0].astype(jnp.float32)                  # (bk, d)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + pv

    @pl.when(ki == n_kv - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """q: (B, S, H, d)  k/v: (B, S, KVH, d)  ->  (B, S, H, d)."""
    B, Sq, H, d = q.shape
    _, Skv, KVH, _ = k.shape
    G = H // KVH
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)

    # pad sequence dims to block multiples (masked out via kv_len)
    pq = (-Sq) % block_q
    pk = (-Skv) % block_k
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0))) if pq else q
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0))) if pk else k
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0))) if pk else v

    qf = jnp.moveaxis(qp, 2, 1).reshape(B * H, Sq + pq, d)
    kf = jnp.moveaxis(kp, 2, 1).reshape(B * KVH, Skv + pk, d)
    vf = jnp.moveaxis(vp, 2, 1).reshape(B * KVH, Skv + pk, d)

    n_q = (Sq + pq) // block_q
    n_kv = (Skv + pk) // block_k

    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, block_q=block_q, block_k=block_k, kv_len=Skv)

    out = pl.pallas_call(
        kernel,
        grid=(B * H, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, qi, ki, H=H, KVH=KVH, G=G:
                         ((b // H) * KVH + (b % H) // G, ki, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, qi, ki, H=H, KVH=KVH, G=G:
                         ((b // H) * KVH + (b % H) // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq + pq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(qf, kf, vf)

    out = out.reshape(B, H, Sq + pq, d)[:, :, :Sq]
    return jnp.moveaxis(out, 1, 2)
