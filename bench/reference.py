"""Plain float32 reference of the served models, and its lower-precision
control.

It imports nothing of the program. The configuration's family
(``bench/families/<model_type>.py``) reads the architecture from the
published keys and runs the forward over the weights the benchmark made, in
the program's layout; this module drives it and keeps what every family
shares. Matrix products run in float32 at ``Precision.HIGHEST`` (``mm``).
With ``fp8=True`` the same forward rounds the inputs of every linear layer
and of the head (activations per row, weights per output column) to
float8_e4m3: the control, the step below the bfloat16 the configurations
state.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import families

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0
ROW_BLOCK = 1024          # query rows per attention / MLP block
HEAD_BLOCK = 256          # rows per block of vocabulary logits


def _q8(x, axis):
    """Round to float8_e4m3 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(x, w, fp8):
    """``x @ w`` in float32 at HIGHEST; with ``fp8`` the control's
    rounding of both inputs first."""
    w = w.astype(jnp.float32)
    if fp8:
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.dot(x, w, precision=HIGHEST)


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * \
        w.astype(jnp.float32)


def blocks(f, n_rows, *xs):
    """Apply ``f`` to ROW_BLOCK-row blocks of ``xs`` one after another."""
    nb = n_rows // ROW_BLOCK
    ys = jax.lax.map(lambda b: f(*b), tuple(
        x.reshape((nb, ROW_BLOCK) + x.shape[1:]) for x in xs))
    return ys.reshape((n_rows,) + ys.shape[2:])


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


def _row_logits(params, conf: dict, seq, rows, fp8: bool):
    """Logits of ``rows`` of ``seq``, one HEAD_BLOCK of rows at a time."""
    fam = families.of(conf)
    a = fam.arch(conf)
    h = fam.hidden(params, seq, a, fp8)[jnp.asarray(rows)]
    for b in range(0, len(rows), HEAD_BLOCK):
        yield b, fam.logits(h[b:b + HEAD_BLOCK], params, a, fp8)


def control_tokens(params, conf: dict, prompt: np.ndarray,
                   served: Sequence[int]) -> np.ndarray:
    """The control: at each served position, on the same prompt and served
    tokens, the token the fp8 forward puts first."""
    seq, rows, n = _positions(prompt, served)
    toks = [np.asarray(jnp.argmax(lg, -1))
            for _, lg in _row_logits(params, conf, seq, rows, fp8=True)]
    return np.concatenate(toks)[:n].astype(np.int32)


def token_gaps(params, conf: dict, prompt: np.ndarray,
               served: Sequence[int], picked: Sequence[int] = None
               ) -> np.ndarray:
    """For each served token, how far the reference's logit of it lies
    below the reference's best logit at its position. ``picked`` puts other
    tokens (the control's) in the served ones' place, on the same context.
    """
    seq, rows, n = _positions(prompt, served)
    pk = np.asarray(served if picked is None else picked, np.int32)
    pk = np.concatenate([pk, np.zeros(len(rows) - n, np.int32)])
    gaps = [np.asarray(_gap_rows(lg, jnp.asarray(pk[b:b + HEAD_BLOCK])))
            for b, lg in _row_logits(params, conf, seq, rows, fp8=False)]
    return np.concatenate(gaps)[:n]
