"""Pallas TPU kernel for the Mamba-2 SSD intra-chunk term.

Per (batch, head, chunk) tile the kernel computes, entirely in VMEM:
    scores[i,j] = (C_i . B_j) * exp(cum_i - cum_j) * dt_j        (j <= i)
    y_diag      = scores @ X                                     (Q, P)
    state       = sum_j B_j * exp(cum_Q - cum_j) * dt_j * X_j    (N, P)
i.e. the quadratic-in-chunk matmuls that hit the MXU. The cheap inter-chunk
recurrence and the C_i*h_prev correction run as jnp in the wrapper
(``repro.kernels.ops.ssd``), mirroring ``repro.models.ssm.ssd_chunked``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


def _ssd_kernel(x_ref, b_ref, c_ref, cum_ref, dt_ref, y_ref, st_ref, *,
                chunk: int):
    x = x_ref[0].astype(jnp.float32)          # (Q, P)
    bm = b_ref[0].astype(jnp.float32)         # (Q, N)
    cm = c_ref[0].astype(jnp.float32)         # (Q, N)
    cum = cum_ref[0].astype(jnp.float32)      # (1, Q) row vector
    dt = dt_ref[0].astype(jnp.float32)        # (1, Q)
    cum_i = cum.reshape(chunk, 1)
    cum_j = cum.reshape(1, chunk)
    dt_j = dt.reshape(1, chunk)

    cb = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q,Q)
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    decay = jnp.exp(cum_i - cum_j)
    w = jnp.where(ii >= jj, cb * decay * dt_j, 0.0)
    y_ref[0] = jax.lax.dot_general(
        w, x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(y_ref.dtype)

    total = cum[0, chunk - 1]
    wb = bm * (jnp.exp(total - cum.reshape(chunk, 1))
               * dt.reshape(chunk, 1))                            # (Q,N)
    st_ref[0] = jax.lax.dot_general(
        wb, x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                       # (N,P)


def ssd_intra_chunk(x: jnp.ndarray, Bm: jnp.ndarray, Cm: jnp.ndarray,
                    cum: jnp.ndarray, dt: jnp.ndarray, *,
                    interpret: Optional[bool] = None):
    """x: (B,nc,Q,H,P)  Bm/Cm: (B,nc,Q,H,N) (pre-broadcast to heads)
    cum/dt: (B,nc,Q,H) float32.

    Returns y_diag (B,nc,Q,H,P) and chunk states (B,nc,H,N,P) fp32.
    """
    B, nc, Q, H, P = x.shape
    N = Bm.shape[-1]
    xf = jnp.moveaxis(x, 3, 2).reshape(B * nc * H, Q, P)
    bf = jnp.moveaxis(Bm, 3, 2).reshape(B * nc * H, Q, N)
    cf = jnp.moveaxis(Cm, 3, 2).reshape(B * nc * H, Q, N)
    cumf = jnp.moveaxis(cum, 3, 2).reshape(B * nc * H, 1, Q)
    dtf = jnp.moveaxis(dt, 3, 2).reshape(B * nc * H, 1, Q)

    kernel = functools.partial(_ssd_kernel, chunk=Q)
    y, st = pl.pallas_call(
        kernel,
        grid=(B * nc * H,),
        in_specs=[
            pl.BlockSpec((1, Q, P), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, Q, N), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, Q, N), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, 1, Q), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, 1, Q), lambda b: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, Q, P), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, N, P), lambda b: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * nc * H, Q, P), x.dtype),
            jax.ShapeDtypeStruct((B * nc * H, N, P), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(xf, bf, cf, cumf, dtf)
    y = jnp.moveaxis(y.reshape(B, nc, H, Q, P), 2, 3)
    st = st.reshape(B, nc, H, N, P)
    return y, st
