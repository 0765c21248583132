"""Random weights from a seed, made on the device in one jitted call.

The tree has the program's parameter layout (leaf names and shapes are read
from its schema); the values and their scales are this module's own, so the
reference and the program are handed the same arrays and neither makes
them. Every leaf name must be one this module knows: a new kind of leaf
fails here rather than being drawn at a guessed scale.
"""
from __future__ import annotations

import zlib
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

NORMS = ("ln1", "ln2", "final_ln", "q_norm", "k_norm")
BIASES = ("bq", "bk", "bv")
MATRICES = ("wq", "wk", "wv", "wo", "w_up", "w_gate", "w_down", "unembed")
EMBEDDINGS = ("tok",)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole seed, also one above 32 bits."""
    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def _leaf(key, path: Tuple[str, ...], shape, stacked: bool, dtype):
    name = path[-1]
    k = jax.random.fold_in(key, zlib.crc32("/".join(path).encode()) % (2**31))
    z = jax.random.normal(k, shape, jnp.float32)
    if name in NORMS:
        v = 1.0 + 0.1 * z
    elif name in BIASES:
        v = 0.1 * z
    elif name in EMBEDDINGS:
        v = z
    elif name in MATRICES:
        fan_in = shape[1] if stacked else shape[0]
        v = z / jnp.sqrt(jnp.float32(fan_in))
    else:
        raise KeyError(f"no rule for parameter leaf {'/'.join(path)!r}")
    return v.astype(dtype)


def make(shapes: Dict[str, Any], seed: int,
         dtype=jnp.bfloat16) -> Dict[str, Any]:
    """Weights for ``shapes`` (a tree of ``jax.ShapeDtypeStruct`` in the
    program's layout). Leaves under ``stack`` carry a leading layer axis."""

    def build(key):
        def walk(tree, path):
            if isinstance(tree, dict):
                return {k: walk(v, path + (k,)) for k, v in tree.items()}
            return _leaf(key, path, tree.shape, path[0] == "stack", dtype)
        return walk(shapes, ())

    return jax.jit(build)(seed_key(seed))
