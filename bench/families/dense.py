"""Dense GQA decoders: the architecture, the plain float32 forward, the
parameter leaves and the work of the configurations whose family modules
(``qwen3.py``, ``chatglm.py``) map their published keys onto ``Arch``.

Work is counted from the published architecture and the live lengths, so
it is the same whatever implements it: empty slots, padding rows and pages,
and copies of pages gathered for attention are never work. Weights are
bfloat16 (2 bytes); the K/V cache holds bfloat16 keys and values.

* FLOPs: 2 per multiply-add of every linear layer for every token computed,
  4 * heads * head_dim per (query, visible key) pair per layer for the two
  attention products, and 2 * d * vocab for every row whose logits are
  computed (every decode row; the last row of a prefill dispatch).
* Bytes: every weight once per dispatched step (the embedding only for the
  rows looked up), the K/V of the live context read once, and the new
  tokens' K/V written once.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import HIGHEST, ROW_BLOCK, blocks, mm, rms

VOCAB_BLOCK = 20000       # most head columns converted to float32 at once
W_BYTES = 2
KV_BYTES = 2

# the attention's leaves; the rest are bench/weights.py's defaults
LEAVES = {"wq": "matrix", "wk": "matrix", "wv": "matrix", "wo": "matrix",
          "bq": "bias", "bk": "bias", "bv": "bias",
          "q_norm": "norm", "k_norm": "norm"}


@dataclasses.dataclass(frozen=True)
class Arch:
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    layers: int
    eps: float
    theta: float
    rot_dim: int
    qk_norm: bool
    qkv_bias: bool


def check_program(cfg, a: Arch) -> None:
    """Raises if the program's ModelConfig is not the published widths."""
    got = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim,
           cfg.d_ff, cfg.vocab_size, cfg.n_layers, cfg.qkv_bias, cfg.qk_norm)
    want = (a.d, a.heads, a.kv_heads, a.head_dim, a.ff, a.vocab, a.layers,
            a.qkv_bias, a.qk_norm)
    if got != want:
        raise ValueError(f"program config {got} differs from the published "
                         f"configuration {want}")


# ---- the plain forward -------------------------------------------------

def _rope(x, pos, a: Arch):
    half = a.rot_dim // 2
    inv = a.theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv          # (T, half)
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:a.rot_dim]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s,
                            x[..., a.rot_dim:]], -1)


@functools.partial(jax.jit, static_argnames=("a", "fp8"))
def _layer(x, stack, l, a: Arch, fp8: bool):
    """One decoder layer over a padded sequence x (T, d), float32."""
    w = jax.tree.map(lambda v: v[l], stack)
    at, ff = w["mixer"], w["ffn"]
    T = x.shape[0]
    pos = jnp.arange(T)
    h = rms(x, w["ln1"], a.eps)
    q = mm(h, at["wq"], fp8)
    k = mm(h, at["wk"], fp8)
    v = mm(h, at["wv"], fp8)
    if a.qkv_bias:
        q = q + at["bq"].astype(jnp.float32)
        k = k + at["bk"].astype(jnp.float32)
        v = v + at["bv"].astype(jnp.float32)
    q = q.reshape(T, a.heads, a.head_dim)
    k = k.reshape(T, a.kv_heads, a.head_dim)
    v = v.reshape(T, a.kv_heads, a.head_dim)
    if a.qk_norm:
        q = rms(q, at["q_norm"], a.eps)
        k = rms(k, at["k_norm"], a.eps)
    q, k = _rope(q, pos, a), _rope(k, pos, a)
    g = a.heads // a.kv_heads
    scale = 1.0 / np.sqrt(a.head_dim)

    def attend(qb, pb):
        qb = qb.reshape(-1, a.kv_heads, g, a.head_dim)
        s = jnp.einsum("qhgd,khd->hgqk", qb, k, precision=HIGHEST) * scale
        s = jnp.where(pos[None, None, None, :] <= pb[None, None, :, None],
                      s, -jnp.inf)
        o = jnp.einsum("hgqk,khd->qhgd", jax.nn.softmax(s, -1), v,
                       precision=HIGHEST)
        return o.reshape(-1, a.heads * a.head_dim)

    o = blocks(attend, T, q, pos)
    x = x + mm(o, at["wo"], fp8)

    def mlp(xb):
        hb = rms(xb, w["ln2"], a.eps)
        u = jax.nn.silu(mm(hb, ff["w_gate"], fp8)) * mm(hb, ff["w_up"], fp8)
        return xb + mm(u, ff["w_down"], fp8)

    return blocks(mlp, T, x)


@functools.partial(jax.jit, static_argnames=("a", "fp8"))
def logits(h, params, a: Arch, fp8: bool):
    """Logits of the rows ``h``, the head taken in column blocks so that
    no float32 copy of the whole head is made."""
    h = rms(h, params["final_ln"], a.eps)
    w = params["embed"]["unembed"]
    n = next(n for n in range(1, w.shape[1] + 1)
             if w.shape[1] % n == 0 and w.shape[1] // n <= VOCAB_BLOCK)
    c = w.shape[1] // n
    lg = jax.lax.map(lambda i: mm(
        h, jax.lax.dynamic_slice_in_dim(w, i * c, c, axis=1), fp8),
        jnp.arange(n))
    return jnp.moveaxis(lg, 0, 1).reshape(h.shape[0], -1)[:, :a.vocab]


def hidden(params, tokens: np.ndarray, a: Arch, fp8: bool = False):
    """Last-layer hidden states of ``tokens``, padded to whole blocks."""
    T = -(-len(tokens) // ROW_BLOCK) * ROW_BLOCK
    ids = np.zeros((T,), np.int32)
    ids[:len(tokens)] = tokens
    x = jnp.take(params["embed"]["tok"], jnp.asarray(ids), axis=0).astype(
        jnp.float32)
    stack = params["stack"]["0"]
    for l in range(a.layers):
        x = _layer(x, stack, jnp.int32(l), a=a, fp8=fp8)
    return x


# ---- the work ----------------------------------------------------------

def layer_params(a: Arch) -> int:
    """Weights of one decoder layer (norms and biases included)."""
    q, kv = a.heads * a.head_dim, a.kv_heads * a.head_dim
    n = a.d * q + 2 * a.d * kv + q * a.d + 3 * a.d * a.ff + 2 * a.d
    if a.qkv_bias:
        n += q + 2 * kv
    if a.qk_norm:
        n += 2 * a.head_dim
    return n


def linear_flops_per_token(a: Arch) -> int:
    q, kv = a.heads * a.head_dim, a.kv_heads * a.head_dim
    return 2 * a.layers * (a.d * q + 2 * a.d * kv + q * a.d + 3 * a.d * a.ff)


def head_flops_per_row(a: Arch) -> int:
    return 2 * a.d * a.vocab


def attn_flops(a: Arch, pairs: int) -> int:
    """``pairs`` (query, visible key) pairs through every layer."""
    return 4 * a.layers * a.heads * a.head_dim * pairs


def weight_bytes(a: Arch) -> int:
    """Weights one step reads: every layer, the final norm and the head."""
    return W_BYTES * (a.layers * layer_params(a) + a.d + a.d * a.vocab)


def kv_bytes_per_token(a: Arch) -> int:
    return KV_BYTES * 2 * a.layers * a.kv_heads * a.head_dim


def decode_work(a: Arch, k: int, rows: int, ctx: int) -> Tuple[int, int]:
    """(FLOPs, bytes) of ``k`` fused decode ticks of ``rows`` sequences
    whose live lengths sum to ``ctx`` before the first tick. Tick j's row i
    attends its len_i + j + 1 keys."""
    pairs = k * ctx + rows * k * (k + 1) // 2
    toks = k * rows
    flops = toks * (linear_flops_per_token(a) + head_flops_per_row(a)) \
        + attn_flops(a, pairs)
    byt = k * weight_bytes(a) + toks * W_BYTES * a.d \
        + (pairs - toks) * kv_bytes_per_token(a) \
        + toks * kv_bytes_per_token(a)
    return flops, byt


def prefill_work(a: Arch, s: int, start: int) -> Tuple[int, int]:
    """(FLOPs, bytes) of one prefill dispatch: ``s`` prompt tokens landed
    after ``start`` tokens already in the cache, logits for the last row."""
    pairs = s * start + s * (s + 1) // 2
    flops = s * linear_flops_per_token(a) + attn_flops(a, pairs) \
        + head_flops_per_row(a)
    byt = weight_bytes(a) + s * W_BYTES * a.d \
        + start * kv_bytes_per_token(a) + s * kv_bytes_per_token(a)
    return flops, byt
