"""Pipeline parallelism: GPipe-style stage executor on a mesh axis.

Completes the parallelism menu (DP/FSDP/TP/EP/SP + PP). The scan-over-layers
layout makes PP natural: the stacked layer dim is sharded over a ``stage``
mesh axis, each stage runs its local layers, and activations hop stages via
``lax.ppermute`` inside ``jax.shard_map``. The schedule is the classic GPipe
fill/steady/drain loop over microbatches (bubble fraction
(S-1)/(S-1+M)); compute and the permute collective overlap across
iterations under XLA's async scheduling on TPU.

Used for depth-dominated models when a single stage's layers + optimizer
shard exceed HBM even under FSDP; validated bit-close against sequential
execution in tests/spmd_scripts (8-device subprocess).
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def gpipe_forward(stack_params: Any, x: jnp.ndarray, *,
                  body: Callable[[Any, jnp.ndarray], jnp.ndarray],
                  mesh, axis: str = "stage", n_micro: int):
    """Run ``body`` over a layer stack sharded on ``axis``.

    stack_params: pytree with leading layer dim L on every leaf, sharded on
        ``axis`` (L % n_stages == 0 — each stage owns L/n_stages layers).
    x: (B, ...) activations, replicated; B % n_micro == 0.
    body(layer_params, h) -> h applies ONE layer.

    Returns f(x) with layers applied in order, identical to the sequential
    loop (up to dtype round-off).
    """
    n_stages = mesh.shape[axis]
    B = x.shape[0]
    assert B % n_micro == 0, (B, n_micro)
    mb = B // n_micro

    def stage_program(p_local, x_rep):
        sid = jax.lax.axis_index(axis)
        micro = x_rep.reshape((n_micro, mb) + x_rep.shape[1:])

        def run_local(h):
            def step(c, pl):
                return body(pl, c), None
            out, _ = jax.lax.scan(step, h, p_local)
            return out

        ticks = n_micro + n_stages - 1
        carry = jnp.zeros_like(micro[0])
        acc = jnp.zeros_like(micro)
        for t in range(ticks):
            inject = micro[min(t, n_micro - 1)]
            h_in = jnp.where(sid == 0, inject, carry)
            h_out = run_local(h_in)
            # last stage banks finished microbatch (t - n_stages + 1)
            m = t - (n_stages - 1)
            if m >= 0:
                bank = jnp.where(sid == n_stages - 1, h_out,
                                 jnp.zeros_like(h_out))
                acc = acc.at[m].set(bank)
            carry = jax.lax.ppermute(
                h_out, axis,
                [(i, (i + 1) % n_stages) for i in range(n_stages)])
        # only the last stage holds real outputs; share with everyone
        acc = jax.lax.psum(acc, axis) / 1.0
        return acc.reshape(x_rep.shape)

    fn = jax.shard_map(stage_program, mesh=mesh, in_specs=(P(axis), P()),
                       out_specs=P(), check_vma=False)
    return fn(stack_params, x)


def pipeline_bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_stages - 1 + n_micro)
