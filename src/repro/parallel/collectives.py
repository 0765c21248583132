"""Distributed-optimization collectives: int8-compressed gradient sync.

Cross-pod gradient reduction rides the slow DCN links; compressing that hop
is the classic distributed-optimization trick. ``compressed_psum`` performs
an all-reduce over a mesh axis where the wire format is per-chunk-scaled
int8 (error-feedback optional at the call site): each shard all-gathers the
quantized operand (1 byte/elem + scales) and dequant-sums locally — 4x
fewer bytes on the wire than an fp32 ring all-reduce's 2x traversal.

Used inside ``jax.shard_map`` with ``axis_names={axis}`` (all other mesh
axes stay automatic), so XLA keeps handling data/model sharding while the
pod-axis collective is explicit.
"""
from __future__ import annotations

import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def quantize_int8(x: jnp.ndarray, block: int = 256
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-block symmetric int8 quantisation. Returns (q, scales)."""
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % block
    if pad:
        flat = jnp.pad(flat, (0, pad))
    blocks = flat.reshape(-1, block).astype(jnp.float32)
    scale = jnp.max(jnp.abs(blocks), axis=1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(blocks / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_int8(q: jnp.ndarray, scale: jnp.ndarray, shape: Tuple[int, ...],
                    dtype) -> jnp.ndarray:
    flat = (q.astype(jnp.float32) * scale).reshape(-1)
    n = 1
    for d in shape:
        n *= d
    return flat[:n].reshape(shape).astype(dtype)


def compressed_psum(x: jnp.ndarray, axis_name: str,
                    block: int = 256) -> jnp.ndarray:
    """int8-on-the-wire all-reduce over ``axis_name`` (inside shard_map)."""
    q, scale = quantize_int8(x, block)
    qg = jax.lax.all_gather(q, axis_name)          # int8 bytes on the wire
    sg = jax.lax.all_gather(scale, axis_name)
    total = jnp.sum(qg.astype(jnp.float32) * sg, axis=0)
    flat = total.reshape(-1)
    n = 1
    for d in x.shape:
        n *= d
    return flat[:n].reshape(x.shape).astype(x.dtype)


def compression_error_bound(x: jnp.ndarray, block: int = 256) -> float:
    """Worst-case per-element quantisation error: scale/2 per block."""
    q, scale = quantize_int8(x, block)
    return float(jnp.max(scale)) / 2.0


def make_compressed_grad_sync(mesh, axis: str = "pod", block: int = 256,
                              leaf_spec: P = None):
    """Returns grads -> grads *averaged* over ``axis`` with int8 wire format.

    ``leaf_spec`` describes the physical layout of each gradient leaf
    (default: sharded over ``axis`` on dim 0, replicated elsewhere); the
    compressed all-reduce runs over ``axis`` only.
    """
    spec = leaf_spec if leaf_spec is not None else P(axis)

    def sync_leaf(g):
        fn = jax.shard_map(
            lambda t: compressed_psum(t, axis, block) / mesh.shape[axis],
            mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False)
        return fn(g)

    def sync(grads):
        return jax.tree.map(sync_leaf, grads)

    return sync
