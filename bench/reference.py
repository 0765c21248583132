"""Plain float32 reference of the served models, and its lower-precision
control.

It imports nothing of the program. It reads the architecture from the
published configuration (``model_type`` and its keys) and the weights from
the tree the benchmark made, in the program's layout. Matrix products run
in float32 at ``Precision.HIGHEST``. With ``fp8=True`` the same forward
rounds the inputs of every linear layer and of the head (activations per
row, weights per output column) to float8_e4m3: the control, the step below
the bfloat16 the configurations state.

Departure from the published ChatGLM3 description: ChatGLM3 rotates
interleaved pairs of the first half of each head; this reference rotates
the pairs (i, i + 32) of that half, the layout in which the weights are
stored. That is the same model under a fixed permutation of the query and
key projection columns, which is how a converted checkpoint stores it.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0
ROW_BLOCK = 1024          # query rows per attention / MLP block
HEAD_BLOCK = 256          # rows per block of vocabulary logits
VOCAB_BLOCK = 20000       # most head columns converted to float32 at once


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


def arch_of(conf: dict) -> Arch:
    """The architecture a published configuration describes."""
    t = conf["model_type"]
    if t == "qwen3":
        hd = conf["head_dim"]
        return Arch(conf["hidden_size"], conf["num_attention_heads"],
                    conf["num_key_value_heads"], hd,
                    conf["intermediate_size"], conf["vocab_size"],
                    conf["num_hidden_layers"], conf["rms_norm_eps"],
                    float(conf["rope_theta"]), hd, True,
                    bool(conf["attention_bias"]))
    if t == "chatglm":
        hd = conf["kv_channels"]
        return Arch(conf["hidden_size"], conf["num_attention_heads"],
                    conf["multi_query_group_num"], hd,
                    conf["ffn_hidden_size"], conf["padded_vocab_size"],
                    conf["num_layers"], conf["layernorm_epsilon"],
                    10000.0 * conf.get("rope_ratio", 1), hd // 2, False,
                    bool(conf["add_qkv_bias"]))
    raise ValueError(f"no reference for model_type {t!r}")


def _q8(x, axis):
    """Round to float8_e4m3 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, fp8):
    w = w.astype(jnp.float32)
    if fp8:
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.dot(x, w, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * \
        w.astype(jnp.float32)


def _rope(x, pos, a: Arch):
    half = a.rot_dim // 2
    inv = a.theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv          # (T, half)
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:a.rot_dim]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s,
                            x[..., a.rot_dim:]], -1)


def _blocks(f, n_rows, *xs):
    """Apply ``f`` to ROW_BLOCK-row blocks of ``xs`` one after another."""
    nb = n_rows // ROW_BLOCK
    ys = jax.lax.map(lambda b: f(*b), tuple(
        x.reshape((nb, ROW_BLOCK) + x.shape[1:]) for x in xs))
    return ys.reshape((n_rows,) + ys.shape[2:])


@functools.partial(jax.jit, static_argnames=("a", "fp8"))
def _layer(x, stack, l, a: Arch, fp8: bool):
    """One decoder layer over a padded sequence x (T, d), float32."""
    w = jax.tree.map(lambda v: v[l], stack)
    at, ff = w["mixer"], w["ffn"]
    T = x.shape[0]
    pos = jnp.arange(T)
    h = _rms(x, w["ln1"], a.eps)
    q = _mm(h, at["wq"], fp8)
    k = _mm(h, at["wk"], fp8)
    v = _mm(h, at["wv"], fp8)
    if a.qkv_bias:
        q = q + at["bq"].astype(jnp.float32)
        k = k + at["bk"].astype(jnp.float32)
        v = v + at["bv"].astype(jnp.float32)
    q = q.reshape(T, a.heads, a.head_dim)
    k = k.reshape(T, a.kv_heads, a.head_dim)
    v = v.reshape(T, a.kv_heads, a.head_dim)
    if a.qk_norm:
        q = _rms(q, at["q_norm"], a.eps)
        k = _rms(k, at["k_norm"], a.eps)
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

    o = _blocks(attend, T, q, pos)
    x = x + _mm(o, at["wo"], fp8)

    def mlp(xb):
        hb = _rms(xb, w["ln2"], a.eps)
        u = jax.nn.silu(_mm(hb, ff["w_gate"], fp8)) * _mm(hb, ff["w_up"], fp8)
        return xb + _mm(u, ff["w_down"], fp8)

    return _blocks(mlp, T, x)


@functools.partial(jax.jit, static_argnames=("a", "fp8"))
def _logits(h, params, a: Arch, fp8: bool):
    """Logits of the rows ``h``, the head taken in column blocks so that
    no float32 copy of the whole head is made."""
    h = _rms(h, params["final_ln"], a.eps)
    w = params["embed"]["unembed"]
    n = next(n for n in range(1, w.shape[1] + 1)
             if w.shape[1] % n == 0 and w.shape[1] // n <= VOCAB_BLOCK)
    c = w.shape[1] // n
    lg = jax.lax.map(lambda i: _mm(
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


@jax.jit
def _gap_rows(ref, picked):
    best = jnp.max(ref, -1)
    return best - jnp.take_along_axis(ref, picked[:, None], -1)[:, 0]


def _positions(prompt: np.ndarray, served: Sequence[int]):
    """The sequence a request's served tokens were read from (the prompt
    and every served token but the last), the rows whose logits chose each
    served token, padded to whole head blocks, and their number."""
    served = np.asarray(served, np.int32)
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    rows = np.arange(len(prompt) - 1, len(seq))
    n = len(rows)
    nb = -(-n // HEAD_BLOCK) * HEAD_BLOCK
    return seq, np.concatenate([rows, np.full(nb - n, rows[-1])]), n


def _row_logits(params, a: Arch, seq, rows, fp8: bool):
    """Logits of ``rows`` of ``seq``, one HEAD_BLOCK of rows at a time."""
    h = hidden(params, seq, a, fp8=fp8)[jnp.asarray(rows)]
    for b in range(0, len(rows), HEAD_BLOCK):
        yield b, _logits(h[b:b + HEAD_BLOCK], params, a=a, fp8=fp8)


def control_tokens(params, conf: dict, prompt: np.ndarray,
                   served: Sequence[int]) -> np.ndarray:
    """The control: at each served position, on the same prompt and served
    tokens, the token the fp8 forward puts first."""
    a = arch_of(conf)
    seq, rows, n = _positions(prompt, served)
    toks = [np.asarray(jnp.argmax(lg, -1))
            for _, lg in _row_logits(params, a, seq, rows, fp8=True)]
    return np.concatenate(toks)[:n].astype(np.int32)


def token_gaps(params, conf: dict, prompt: np.ndarray,
               served: Sequence[int], picked: Sequence[int] = None
               ) -> np.ndarray:
    """For each served token, how far the reference's logit of it lies
    below the reference's best logit at its position. ``picked`` puts other
    tokens (the control's) in the served ones' place, on the same context.
    """
    a = arch_of(conf)
    seq, rows, n = _positions(prompt, served)
    pk = np.asarray(served if picked is None else picked, np.int32)
    pk = np.concatenate([pk, np.zeros(len(rows) - n, np.int32)])
    gaps = [np.asarray(_gap_rows(lg, jnp.asarray(pk[b:b + HEAD_BLOCK])))
            for b, lg in _row_logits(params, a, seq, rows, fp8=False)]
    return np.concatenate(gaps)[:n]
