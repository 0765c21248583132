"""Pallas TPU chunked flash-prefill kernels (direct-to-page KV writes).

Chunked prefill lands one prompt chunk per sequence per tick: the chunk's
K/V must end up in the paged pool (``repro.serving.paged_cache``) and the
chunk's queries must attend everything written so far — the already-paged
prefix *and* the chunk itself — under the causal (and optionally sliding-
window) mask. The XLA path does this as quantise → scatter → full-pool
gather → dense masked softmax; these kernels compute the same function
without ever materialising the gathered cache or a contiguous K/V
intermediate:

``paged_prefill_write``
    Scatters the chunk's K/V **directly into the pool pages** through the
    scalar-prefetched block table, aliasing the pools in-place
    (``input_output_aliases``). Grid (B, pages_per_seq): each step owns
    one page — every page block is visited exactly once, so the aliased
    read-modify-write never races itself. Rows of the page whose absolute
    position falls inside ``[start, start+chunk_len)`` take the chunk
    values (routed via a one-hot position matmul — a gather phrased for
    the MXU); all other rows keep the page's previous contents. Pool
    quantisation (int8 / fp8) happens in-kernel, emitting the per-
    (position, kv-head) scale planes bit-identically to
    ``repro.models.attention.quantize_kv``.

    A tighter grid over only the chunk's own pages (start//ps ..
    (start+len)//ps) would skip the untouched page slots, but with a
    clamped index map two grid steps can resolve to the same page and the
    later step's input fetch is not ordered against the earlier step's
    aliased write. Correct-by-construction wins here; the full-table sweep
    is the documented cost (pages_per_seq is small at serving block sizes)
    and the range-restricted grid is TPU future work.

``paged_prefill_attend``
    Flash attention (online-softmax, same scratch discipline as
    ``flash_attention``) where **all** K/V — prefix and chunk — stream
    from the pool pages via the block table, after the write kernel has
    landed the chunk. Grid (B, q_blocks, pages_per_seq) with the page axis
    innermost and sequential. Each step fetches one whole page — all KV
    heads: the TPU lowering tiles a block's last two dimensions, which are
    (KVH, d) here, so a one-head slice of the pool is not a legal block —
    and updates every query head's running max / sum / accumulator in
    VMEM scratch. Queries enter head-major, (B, KVH, G, S, d), so each
    head's (block_q, d) tile is a plain block. Masking is absolute-position
    causal (``k_pos <= start + q_row`` and ``k_pos < start + chunk_len``)
    plus the optional sliding window; quantised pools dequantise in-kernel
    from the scale planes. Pages wholly outside a q block's visible range
    are skipped (causal skip, window skip, past-the-end skip).

Run order matters: attend reads the chunk's K/V *from the pages*, so the
write kernel must run first. That ordering is also what makes the
quantised paths bit-identical to the XLA write-then-gather reference —
chunk tokens go through the same quantise→dequantise roundtrip on both.

Both kernels compile for TPU v5e at published widths
(tests/test_chip_compile.py); on the CPU backend they run in interpret
mode against ``repro.kernels.ref`` (tests/test_paged_prefill.py).
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
_FP8_MAX = 448.0  # float8_e4m3 largest finite value


# ---------------------------------------------------------------------------
# write kernel: chunk K/V -> pool pages (aliased, in-kernel quantisation)
# ---------------------------------------------------------------------------

def _pw_kernel(bt_ref, start_ref, lens_ref, k_new_ref, v_new_ref,
               k_in_ref, v_in_ref, *refs, page_size: int, chunk: int,
               quant: Optional[str]):
    if quant:
        ks_in_ref, vs_in_ref, k_out_ref, v_out_ref, ks_out_ref, \
            vs_out_ref = refs
    else:
        ks_in_ref = vs_in_ref = ks_out_ref = vs_out_ref = None
        k_out_ref, v_out_ref = refs
    b = pl.program_id(0)
    i = pl.program_id(1)
    st = start_ref[b]
    ln = lens_ref[b]

    # absolute position of each page row -> chunk-relative index + liveness
    k_pos = jax.lax.broadcasted_iota(jnp.int32, (page_size, 1), 0) \
        + i * page_size
    j = k_pos - st                                        # (ps, 1)
    sel = jnp.logical_and(j >= 0, j < ln)                 # (ps, 1)
    idx = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
    onehot = jnp.where(jnp.logical_and(j == idx, sel), 1.0, 0.0)  # (ps, chunk)

    def scatter_one(new_ref, in_ref, out_ref, sc_in_ref, sc_out_ref):
        KVH, d = new_ref.shape[2], new_ref.shape[3]
        flat = new_ref[0].astype(jnp.float32).reshape(chunk, KVH * d)
        g = jax.lax.dot_general(onehot, flat, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        g = g.reshape(page_size, KVH, d)                  # chunk rows routed
        old = in_ref[0]
        # reciprocal multiply matches attention.quantize_kv bit-for-bit
        # (jit strength-reduces x/const to it; the kernel writes it out)
        if quant == "fp8":
            a = jnp.max(jnp.abs(g), axis=-1)              # (ps, KVH)
            scale = jnp.maximum(a * jnp.float32(1.0 / _FP8_MAX), 1e-12)
            qv = (g / scale[..., None]).astype(jnp.float8_e4m3fn)
        elif quant:
            a = jnp.max(jnp.abs(g), axis=-1)
            scale = jnp.maximum(a * jnp.float32(1.0 / 127.0), 1e-12)
            qv = jnp.clip(jnp.round(g / scale[..., None]),
                          -127, 127).astype(jnp.int8)
        else:
            scale = None
            qv = g.astype(old.dtype)
        live = sel[:, :1][..., None]                      # (ps, 1, 1)
        out_ref[0] = jnp.where(live, qv, old)
        if quant:
            sc_out_ref[0] = jnp.where(sel[:, :1], scale, sc_in_ref[0])

    scatter_one(k_new_ref, k_in_ref, k_out_ref, ks_in_ref, ks_out_ref)
    scatter_one(v_new_ref, v_in_ref, v_out_ref, vs_in_ref, vs_out_ref)


def paged_prefill_write(k_new: jnp.ndarray, v_new: jnp.ndarray,
                        k_pages: jnp.ndarray, v_pages: jnp.ndarray,
                        block_table: jnp.ndarray, start: jnp.ndarray,
                        chunk_lens: jnp.ndarray, *,
                        k_scale_pages: Optional[jnp.ndarray] = None,
                        v_scale_pages: Optional[jnp.ndarray] = None,
                        quant: Optional[str] = None,
                        interpret: Optional[bool] = None):
    """Scatter a ragged batch of prompt chunks into their pool pages.

    k_new/v_new: (B, S, KVH, d) — rows past ``chunk_lens[b]`` are padding
    and are not written. Pools: (P, page_size, KVH, d); block_table:
    (B, n_pg); start/chunk_lens: (B,) int32 — chunk token ``t`` lands at
    absolute position ``start[b] + t``. ``quant`` in (None, "int8",
    "fp8") must match the pool dtype; quantised calls also take/return the
    fp32 scale planes (P, page_size, KVH).

    Returns the updated pools dict (k_pages, v_pages[, k_scale_pages,
    v_scale_pages]). Inputs are donated via ``input_output_aliases``.
    """
    B, S, KVH, d = k_new.shape
    P, page_size = k_pages.shape[0], k_pages.shape[1]
    n_pg = block_table.shape[1]
    if quant not in (None, "int8", "fp8"):
        raise ValueError(f"quant must be None, 'int8' or 'fp8': {quant!r}")
    if (quant is not None) != (k_scale_pages is not None):
        raise ValueError("scale planes required iff quant is set")

    kernel = functools.partial(_pw_kernel, page_size=page_size, chunk=S,
                               quant=quant)
    chunk_spec = pl.BlockSpec((1, S, KVH, d),
                              lambda b, i, bt, st, ln: (b, 0, 0, 0))
    page_spec = pl.BlockSpec((1, page_size, KVH, d),
                             lambda b, i, bt, st, ln: (bt[b, i], 0, 0, 0))
    scale_spec = pl.BlockSpec((1, page_size, KVH),
                              lambda b, i, bt, st, ln: (bt[b, i], 0, 0))

    in_specs = [chunk_spec, chunk_spec, page_spec, page_spec]
    args = [k_new, v_new, k_pages, v_pages]
    out_specs = [page_spec, page_spec]
    out_shape = [jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
                 jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype)]
    # alias indices count the scalar-prefetch operands (bt, start, lens)
    aliases = {5: 0, 6: 1}
    if quant:
        in_specs += [scale_spec, scale_spec]
        args += [k_scale_pages, v_scale_pages]
        out_specs += [scale_spec, scale_spec]
        out_shape += [jax.ShapeDtypeStruct(k_scale_pages.shape, jnp.float32),
                      jax.ShapeDtypeStruct(v_scale_pages.shape, jnp.float32)]
        aliases.update({7: 2, 8: 3})

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, n_pg),
        in_specs=in_specs,
        out_specs=out_specs,
    )
    outs = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=resolve_interpret(interpret),
    )(block_table.astype(jnp.int32), start.astype(jnp.int32),
      chunk_lens.astype(jnp.int32), *args)
    pool = {"k_pages": outs[0], "v_pages": outs[1]}
    if quant:
        pool["k_scale_pages"] = outs[2]
        pool["v_scale_pages"] = outs[3]
    return pool


# ---------------------------------------------------------------------------
# attend kernel: chunk queries vs paged prefix+chunk K/V (online softmax)
# ---------------------------------------------------------------------------

def default_block_q(n_heads: int, chunk: int) -> int:
    """Query rows per attend block when no tuned value exists: at most 128,
    and at most 4096 // n_heads so the per-query-head softmax scratch
    (n_heads x block_q rows of max, sum and accumulator) fits the default
    scoped VMEM of a TPU v5e core at head_dim 128."""
    return min(chunk, 128, max(8, 4096 // n_heads))


def _pa_kernel(bt_ref, start_ref, lens_ref, q_ref, k_ref, v_ref, *refs,
               scale: float, softcap: Optional[float],
               window: Optional[int], page_size: int, block_q: int,
               quant: bool):
    if quant:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = refs
    else:
        ks_ref = vs_ref = None
        o_ref, m_scr, l_scr, acc_scr = refs
    b = pl.program_id(0)
    qi = pl.program_id(1)
    pi = pl.program_id(2)
    n_pg = pl.num_programs(2)
    KVH, G = q_ref.shape[1], q_ref.shape[2]

    @pl.when(pi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    st = start_ref[b]
    ln = lens_ref[b]
    total = st + ln                           # tokens 0..total-1 are live
    q_abs = st + qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, 1), 0)                       # (bq, 1)
    k_pos = pi * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, page_size), 1)

    # skip pages no row of this q block can see: past the live length,
    # above the causal diagonal, or wholly below the sliding window
    needed = pi * page_size < total
    needed = jnp.logical_and(
        needed, pi * page_size <= st + qi * block_q + block_q - 1)
    if window is not None:
        needed = jnp.logical_and(
            needed,
            (st + qi * block_q) - (pi * page_size + page_size - 1) < window)

    @pl.when(needed)
    def _compute():
        ok = jnp.logical_and(k_pos <= q_abs, k_pos < total)
        if window is not None:
            ok = jnp.logical_and(ok, q_abs - k_pos < window)
        for h in range(KVH):
            k = k_ref[0, :, h, :].astype(jnp.float32)     # (ps, d)
            v = v_ref[0, :, h, :].astype(jnp.float32)
            if quant:
                k = k * ks_ref[0, :, h:h + 1]
                v = v * vs_ref[0, :, h:h + 1]

            # the G query heads of KV head h share its K/V tile; a loop
            # rather than an unrolled body keeps the kernel's compile short
            def one_query_head(g, carry, h=h, k=k, v=v):
                r = h * G + g                             # query head
                q = q_ref[0, h, g].astype(jnp.float32)    # (bq, d)
                s = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                if softcap is not None:
                    s = softcap * jnp.tanh(s / softcap)
                s = jnp.where(ok, s, _NEG)                # (bq, ps)
                m_prev = m_scr[r]                         # (bq, 1)
                m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                p = jnp.exp(s - m_new)
                l_scr[r] = l_scr[r] * alpha + p.sum(axis=-1, keepdims=True)
                m_scr[r] = m_new
                pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
                acc_scr[r] = acc_scr[r] * alpha + pv
                return carry

            jax.lax.fori_loop(0, G, one_query_head, 0)
    @pl.when(pi == n_pg - 1)
    def _finalize():
        for h in range(KVH):
            for g in range(G):
                r = h * G + g
                denom = jnp.maximum(l_scr[r], 1e-30)
                o_ref[0, h, g] = (acc_scr[r] / denom).astype(o_ref.dtype)


def paged_prefill_attend(q: jnp.ndarray, k_pages: jnp.ndarray,
                         v_pages: jnp.ndarray, block_table: jnp.ndarray,
                         start: jnp.ndarray, chunk_lens: jnp.ndarray, *,
                         k_scale_pages: Optional[jnp.ndarray] = None,
                         v_scale_pages: Optional[jnp.ndarray] = None,
                         softcap: Optional[float] = None,
                         window: Optional[int] = None,
                         scale: Optional[float] = None,
                         block_q: Optional[int] = None,
                         interpret: Optional[bool] = None) -> jnp.ndarray:
    """Chunk queries attend the paged prefix+chunk K/V. Call *after*
    ``paged_prefill_write`` — the chunk's own K/V stream from the pages.

    q: (B, S, H, d) — query ``t`` of sequence ``b`` sits at absolute
    position ``start[b] + t``; rows past ``chunk_lens[b]`` are padding
    (their output is unspecified — callers slice live rows). Pools:
    (P, page_size, KVH, d); block_table: (B, n_pg). Returns (B, S, H, d).
    """
    B, S, H, d = q.shape
    page_size, KVH = k_pages.shape[1], k_pages.shape[2]
    n_pg = block_table.shape[1]
    G = H // KVH
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    block_q = min(block_q or default_block_q(H, S), S)
    quant = k_scale_pages is not None

    pq = (-S) % block_q
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0))) if pq else q
    # head-major queries: (B, KVH, G, S, d)
    qr = qp.reshape(B, S + pq, KVH, G, d).transpose(0, 2, 3, 1, 4)
    n_qb = (S + pq) // block_q

    kernel = functools.partial(_pa_kernel, scale=scale, softcap=softcap,
                               window=window, page_size=page_size,
                               block_q=block_q, quant=quant)
    q_spec = pl.BlockSpec((1, KVH, G, block_q, d),
                          lambda b, qi, i, bt, st, ln: (b, 0, 0, qi, 0))
    page_spec = pl.BlockSpec((1, page_size, KVH, d),
                             lambda b, qi, i, bt, st, ln:
                             (bt[b, i], 0, 0, 0))
    in_specs = [q_spec, page_spec, page_spec]
    args = [qr, k_pages, v_pages]
    if quant:
        scale_spec = pl.BlockSpec((1, page_size, KVH),
                                  lambda b, qi, i, bt, st, ln:
                                  (bt[b, i], 0, 0))
        in_specs += [scale_spec, scale_spec]
        args += [k_scale_pages, v_scale_pages]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, n_qb, n_pg),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((H, block_q, 1), jnp.float32),
            pltpu.VMEM((H, block_q, 1), jnp.float32),
            pltpu.VMEM((H, block_q, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, G, S + pq, d), q.dtype),
        interpret=resolve_interpret(interpret),
    )(block_table.astype(jnp.int32), start.astype(jnp.int32),
      chunk_lens.astype(jnp.int32), *args)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, S + pq, H, d)[:, :S]
